"""Shared utilities: name scopes, activation capture, pytree helpers."""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Name scopes (flax-style paths, used to key calibration Grams and quantized
# layer parameter subtrees).  Each scope also enters ``jax.named_scope``, so
# the same names reach every traced op's metadata (``op_name`` in the HLO,
# ``tf_op`` in a profiler trace) with ``/`` in place of ``.``; the layer
# names are declared in ``repro.obs.names.SCOPES``.
# ---------------------------------------------------------------------------

_state = threading.local()


def _scope_stack() -> list[str]:
    if not hasattr(_state, "scopes"):
        _state.scopes = []
    return _state.scopes


@contextlib.contextmanager
def scope(name: str) -> Iterator[None]:
    name = str(name)
    _scope_stack().append(name)
    try:
        with jax.named_scope(name.replace(".", "/")):
            yield
    finally:
        _scope_stack().pop()


def current_scope() -> str:
    return ".".join(_scope_stack())


# ---------------------------------------------------------------------------
# Activation capture for calibration.  ``QLinear.apply`` calls
# ``record_activation(path, x)``; inside a ``capture_grams`` context with
# concrete (non-traced) values, the Gram matrix H += X^T X is accumulated in
# float32.  Under jit tracing, recording is a no-op.
# ---------------------------------------------------------------------------


def solver_precision():
    """Context (or decorator) that makes every f32 matmul traced inside
    it a full f32 product (``"highest"``).  A TPU otherwise runs an f32
    matmul as one bf16 pass, which perturbs a calibration Gram or a
    solver product by ~2^-8 relative: enough for the damped Cholesky of
    an ill-conditioned Gram to fail.  The CPU always multiplies in f32,
    so it sees no change."""
    return jax.default_matmul_precision("highest")


class GramStore:
    """Accumulates per-layer Gram matrices H = sum_batches X^T X (f32).

    ``keep_leading=True`` (MoE expert buffers shaped (E, C, D)) keeps the
    leading dim and accumulates one Gram per expert: H (E, D, D).
    ``groups`` (the dropless MoE layer's rows ``(N, D)``, sorted by expert
    with ``groups[e]`` rows for expert ``e``) gives the same ``(E, D, D)``,
    each expert's Gram from the rows routed to it."""

    def __init__(self) -> None:
        self.grams: dict[str, np.ndarray] = {}
        self.counts: dict[str, int] = {}

    def add(self, path: str, x: jax.Array, keep_leading: bool = False,
            groups: jax.Array | None = None) -> None:
        if groups is not None:
            x, keep_leading = _grouped_rows(x, groups), True
        if keep_leading:
            x3 = jnp.asarray(x, jnp.float32)
            x3 = x3.reshape(x3.shape[0], -1, x3.shape[-1])
            with solver_precision(), scope("gram"):
                h = jax.device_get(jnp.einsum("ecd,ecf->edf", x3, x3))
            cnt = x3.shape[1]
        else:
            x2 = jnp.asarray(x, jnp.float32).reshape(-1, x.shape[-1])
            with solver_precision(), scope("gram"):
                h = np.asarray(x2.T @ x2)
            cnt = x2.shape[0]
        if path in self.grams:
            self.grams[path] = self.grams[path] + h
            self.counts[path] += cnt
        else:
            self.grams[path] = np.array(h)
            self.counts[path] = cnt

    def gram(self, path: str) -> np.ndarray:
        return self.grams[path]

    def paths(self) -> list[str]:
        return sorted(self.grams)

    def merge(self, other: "GramStore") -> None:
        """Accumulate another store's sums into this one (path-wise).

        ``run_calibration`` accumulates each batch into a scratch store and
        merges it only after a finiteness check, so one bad batch cannot
        poison the whole run's Grams."""
        for path, h in other.grams.items():
            if path in self.grams:
                self.grams[path] = self.grams[path] + h
                self.counts[path] += other.counts[path]
            else:
                self.grams[path] = np.array(h)
                self.counts[path] = other.counts[path]

    def all_finite(self) -> bool:
        """True when every accumulated Gram is fully finite."""
        return all(np.isfinite(g).all() for g in self.grams.values())


def _grouped_rows(x: jax.Array, groups: jax.Array) -> jax.Array:
    """Rows ``x (N, D)`` sorted by group, ``groups[e]`` of them in group
    ``e`` (rows past the groups belong to none), laid out as ``(E, C, D)``
    with ``C`` the largest group, zero-padded."""
    sizes = np.asarray(groups)
    group = np.repeat(np.arange(len(sizes)), sizes)
    pos = np.arange(len(group)) - (np.cumsum(sizes) - sizes)[group]
    out = jnp.zeros((len(sizes), max(int(sizes.max()), 1), x.shape[-1]),
                    jnp.float32)
    return out.at[group, pos].set(jnp.asarray(x[:len(group)], jnp.float32))


def _capture_store() -> GramStore | None:
    return getattr(_state, "capture", None)


@contextlib.contextmanager
def capture_grams(store: GramStore) -> Iterator[GramStore]:
    prev = getattr(_state, "capture", None)
    _state.capture = store
    try:
        yield store
    finally:
        _state.capture = prev


def is_capturing() -> bool:
    return _capture_store() is not None


def record_activation(path: str, x: jax.Array, keep_leading: bool = False,
                      groups: jax.Array | None = None) -> None:
    store = _capture_store()
    if store is None:
        return
    if isinstance(x, jax.core.Tracer):  # under jit: capture is eager-only
        return
    store.add(path, x, keep_leading=keep_leading, groups=groups)


# ---------------------------------------------------------------------------
# Pytree helpers.
# ---------------------------------------------------------------------------


def tree_paths(tree: Any, prefix: str = "") -> dict[str, Any]:
    """Flatten a nested dict pytree to {dot.path: leaf}."""
    out: dict[str, Any] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            p = f"{prefix}.{k}" if prefix else str(k)
            out.update(tree_paths(v, p))
    else:
        out[prefix] = tree
    return out


def get_path(tree: Any, path: str) -> Any:
    node = tree
    for k in path.split("."):
        node = node[k]
    return node


def set_path(tree: dict, path: str, value: Any) -> None:
    keys = path.split(".")
    node = tree
    for k in keys[:-1]:
        node = node.setdefault(k, {})
    node[keys[-1]] = value


def tree_size_bytes(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(x.size * x.dtype.itemsize for x in leaves if hasattr(x, "size"))


def tree_param_count(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(x.shape)) for x in leaves if hasattr(x, "shape"))


def assert_finite(tree: Any, what: str = "tree") -> None:
    for path, leaf in tree_paths(tree).items():
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            if not bool(jnp.all(jnp.isfinite(leaf))):
                raise FloatingPointError(f"non-finite values in {what}:{path}")


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
