"""Batched layer-wise quantization engine: vmap across shape-bucketed layers.

The per-layer MagR→OPTQ→CLoQ stack (and the LoftQ/QLoRA/RTN baselines) is a
closed-form pipeline of traced JAX ops — nothing about it is inherently
sequential across *layers*.  Running it layer-by-layer from Python pays one
dispatch chain, one ``eigh``+``svd``, and one host sync per linear, so model
quantization wall-time scales with layer count instead of with hardware.

This module batches it:

1.  **Planner** (:func:`plan_buckets`): every quantization site — a 2-D
    linear, or one expert slice of a stacked ``(E, m, n)`` MoE weight — is a
    :class:`LayerTask`.  Tasks are grouped into buckets keyed by
    :class:`BucketSpec`: ``(m, n, method, bits, group_size, rank, split,
    block_size, …)``.  Each task's ``(method, qspec)`` comes from its
    resolved per-site spec (``LayerTask.site``, a
    :class:`repro.core.recipe.SiteSpec`) when quantization was planned from
    a :class:`~repro.core.recipe.QuantRecipe` — mixed-precision plans just
    produce more buckets — or from the legacy global pair.  Everything
    shape- or branch-like (OPTQ's sweep block via
    :func:`repro.core.optq.pick_block`, the MagR gate ``bits <= 4``) is
    resolved *here*, at plan time, so the traced core has no data-dependent
    Python branching.

2.  **Executor** (:func:`run_bucket` / :func:`quantize_layer_batch`): each
    bucket stacks its ``(W, H)`` pairs to ``(L, m, n)`` / ``(L, m, m)`` and
    runs a single ``jax.jit(jax.vmap(...))`` executable over the whole
    method stack — one trace, one dispatch, all layers of the bucket
    factorized in parallel.  Per-task PRNG keys are threaded through so
    random LoRA inits match the sequential path bit-for-bit.

3.  **Sharding** (:func:`run_bucket_sharded`): on a multi-device mesh the
    planner assigns each bucket ``n_shards`` column shards over the
    ``model`` axis (falling back to ``1`` = replicated only when ``n``
    doesn't divide the axis).  The bucket then runs as **one** ``shard_map``
    whose body vmaps the same per-layer core over the local
    ``(L, m, n_local)`` shard — sharding composed *inside* the vmapped
    bucket, so an L-layer bucket on D devices costs a single dispatch
    instead of L per-layer sharded dispatches.  The only communication is
    the Gram-trick psum: one ``(L, m, m)`` all-reduce per bucket for CLoQ,
    one per AltMin round for LoftQ (``loftq.svd_lowrank_topr``) — every
    method, LoftQ included, rides the fused sharded path.

4.  **Streaming** (:func:`quantize_layer_batch` with ``stream=True``):
    bucket execution is double-buffered — host stacking of bucket ``k+1``
    overlaps with device compute of bucket ``k`` via JAX's async dispatch,
    so the host-side gather never serializes with device math.

The sequential per-layer path in :mod:`repro.core.pipeline` remains as the
fallback and as the numerical-parity oracle (``tests/test_batched.py``,
``tests/test_batched_sharded.py``).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import TYPE_CHECKING, Callable

if TYPE_CHECKING:       # annotation only — no import cycle at runtime
    from repro.core.recipe import SiteSpec

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cloq import (cloq_init, cloq_init_sharded,
                             cloq_lowrank_local, gram_root, regularize_gram)
from repro.core.loftq import loftq_init, qlora_init
from repro.core.magr import magr_preprocess
from repro.core.optq import (optq_quantize_core, optq_quantize_sharded,
                             pick_block)
from repro.core.quantizer import (QuantConfig, dequantize_int, pack_codes,
                                  quantize_int)
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import names as obs_names
from repro.obs import trace as obs_trace
from repro.utils import solver_precision

Array = jax.Array

# methods whose base quantization consumes a calibration Gram
GRAM_METHODS = ("cloq", "gptq")

# methods the planner must keep replicated on a mesh.  Empty: every method's
# stack is column-local given the replicated Gram, with the two full-width
# SVDs (CLoQ's R dW, LoftQ's per-round W - Q) recovered exactly from column
# shards via the Gram trick (cloq.cloq_lowrank_local, loftq.svd_lowrank_topr).
_REPLICATED_METHODS: tuple[str, ...] = ()


def bucket_axis_size(mesh, axis: str = "model") -> int:
    """Size of the mesh's ``axis`` (``1`` when there is no mesh or the
    mesh doesn't carry the axis) — the candidate shard count the planner
    and the cost model both reason about.

    >>> bucket_axis_size(None)
    1
    """
    if mesh is None or axis not in getattr(mesh, "axis_names", ()):
        return 1
    return int(mesh.shape[axis])


def bucket_shards(n: int, method: str, mesh=None,
                  axis: str = "model") -> int:
    """Column-shard count the planner assigns a bucket: the ``axis`` size of
    ``mesh`` when ``n`` divides it (and the method is not forced replicated
    — currently none is), else ``1`` (replicated fallback).

    This is the *divisibility gate* only; with a cost model the planner
    further re-decides each bucket's path from predicted time
    (:func:`apply_cost_model`), and may keep a divisible bucket replicated
    when its collectives would dominate.

    >>> bucket_shards(48, "cloq", mesh=None)
    1
    """
    k = bucket_axis_size(mesh, axis)
    if k <= 1 or method in _REPLICATED_METHODS or n % k != 0:
        return 1
    return k


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Static signature of one vmapped executable.  Hashable: used both as
    the bucket key and as the jit static argument."""
    m: int
    n: int
    method: str
    bits: int
    group_size: int | None
    rank: int
    split: str
    block_size: int          # OPTQ sweep block, already a divisor of m
    act_order: bool
    lambda_frac: float
    magr: bool               # MagR gate (bits <= 4), resolved at plan time
    magr_iters: int
    has_gram: bool
    n_shards: int = 1        # column shards over the model axis (1 = local)
    # execution path the planner chose for the bucket: "replicated" (one
    # local jit(vmap) dispatch), "sharded" (one shard_map(vmap) dispatch,
    # n_shards > 1), or "sequential" (L per-layer dispatches — picked only
    # by the cost model's memory gate).  Recorded in the serialized bucket
    # manifest so restore and the health requeue replay the same decision.
    exec_path: str = "replicated"


@dataclasses.dataclass
class LayerTask:
    """One quantization site: a 2-D weight (possibly one expert slice of a
    stacked MoE weight) plus its Gram and PRNG key.

    ``site`` (a :class:`repro.core.recipe.SiteSpec`) carries the task's
    *resolved* ``(method, qspec)`` when quantization was planned from a
    :class:`~repro.core.recipe.QuantRecipe`; tasks without one fall back to
    the global pair passed to :func:`plan_buckets` /
    :func:`quantize_layer_batch`.  Mixing specs across tasks is free — the
    planner keys buckets by the full static signature, so each distinct
    resolved spec becomes its own bucket."""
    path: str                # lin path in the param tree
    expert: int | None       # index into the stacked (E, m, n) weight
    W: Array                 # (m, n)
    H: Array | np.ndarray | None   # (m, m) calibration Gram
    key: Array               # per-task PRNG key
    site: "SiteSpec | None" = None   # resolved per-site spec (optional)


def task_site(t: LayerTask, qspec=None, method: str | None = None):
    """A task's effective ``(qspec, method)``: its resolved
    :class:`~repro.core.recipe.SiteSpec` when present, else the global
    fallback pair."""
    if t.site is not None:
        return t.site.qspec, t.site.method
    if qspec is None or method is None:
        raise ValueError(
            f"task {t.path!r} carries no resolved SiteSpec and no global "
            "(qspec, method) fallback was given")
    return qspec, method


def make_spec(m: int, n: int, qspec, method: str, has_gram: bool,
              base: QuantConfig | None = None, *, mesh=None,
              axis: str = "model", for_eval: bool = False) -> BucketSpec:
    """Resolve all static/branching decisions for one (shape, method).

    With ``mesh``, the bucket's column-shard count over ``axis`` is also
    resolved here (see :func:`bucket_shards`), so the executor's choice of
    :func:`run_bucket` vs :func:`run_bucket_sharded` is a pure plan-time
    lookup.

    ``for_eval`` marks a *sensitivity-sweep* bucket
    (:func:`evaluate_layer_batch`): the calibration Gram is then routed
    into the bucket whenever one exists — every candidate's proxy error
    ``tr(E^T H E)`` is weighted by the same calibration data, even for
    methods whose quantization itself is data-free."""
    base = base or QuantConfig(bits=qspec.bits, group_size=qspec.group_size)
    k = bucket_shards(n, method, mesh, axis)
    return BucketSpec(
        m=m, n=n, method=method, bits=qspec.bits,
        group_size=qspec.group_size, rank=qspec.rank, split=qspec.split,
        block_size=pick_block(m, base.block_size),
        act_order=base.act_order, lambda_frac=base.lambda_frac,
        magr=(method == "cloq" and qspec.bits <= 4),
        magr_iters=base.magr_iters,
        has_gram=has_gram and (for_eval or method in GRAM_METHODS),
        n_shards=k, exec_path="sharded" if k > 1 else "replicated")


def magr_alpha(H: Array, m: int) -> Array:
    """MagR regularization strength ``0.001 * tr(H) / m`` — a traced scalar
    (no host sync), shared by every engine path so they all gate and weight
    MagR identically."""
    return 0.001 * jnp.trace(H) / m


def spec_qcfg(spec: BucketSpec) -> QuantConfig:
    """Expand a plan-time :class:`BucketSpec` into the :class:`QuantConfig`
    the traced cores consume (single source of truth for the mapping)."""
    return QuantConfig(bits=spec.bits, group_size=spec.group_size,
                       block_size=spec.block_size, act_order=spec.act_order,
                       lambda_frac=spec.lambda_frac)


def quantize_single(W: Array, H: Array | None, key: Array,
                    spec: BucketSpec, axis: str | None = None) -> dict:
    """Traced single-layer core (host-sync free): the leaf dict of
    :func:`quantize_single_deq` (see there for the full contract)."""
    return quantize_single_deq(W, H, key, spec, axis)[0]


@solver_precision()     # f32 solver products on every backend
def quantize_single_deq(W: Array, H: Array | None, key: Array,
                        spec: BucketSpec,
                        axis: str | None = None) -> tuple[dict, Array]:
    """Traced single-layer core (host-sync free).  Mirrors the sequential
    ``pipeline._quantize_one`` but with every static decision pre-resolved
    in ``spec`` — safe under ``jax.vmap``.  Returns ``(leaves, Qd)`` where
    ``Qd`` is the dequantized base — the quantity the sensitivity sweep
    (:func:`eval_single`) measures the residual against without a second
    unpack round-trip.

    Args:
        W:    (m, n_local) weight — the full layer when ``axis`` is None, or
              one column shard inside a ``shard_map`` body.
        H:    (m, m) calibration Gram, always replicated (full); ``None``
              for data-free methods.
        key:  (2,) PRNG key, replicated across shards so random LoRA inits
              agree on every device.
        spec: static bucket signature (shapes, method, grid, gates).
        axis: mesh axis name when running as the shard-local body of
              :func:`run_bucket_sharded`; selects the Gram-trick solves
              over the dense SVDs (CLoQ: ``cloq_lowrank_local``, one psum;
              LoftQ: ``svd_lowrank_topr``, one psum per AltMin round).  All
              other ops are per-column and need no communication.

    Returns a dict of leaves; column-dimension leaves (``qcodes``,
    ``scales``, ``zeros``, ``absmax``, ``lora_b``) cover only the local
    columns when sharded, ``lora_a`` is replicated."""
    qcfg = spec_qcfg(spec)
    W = jnp.asarray(W, jnp.float32)
    m, n = spec.m, W.shape[1]          # n is shard-local under shard_map
    if spec.method == "cloq":
        H = jnp.asarray(H, jnp.float32)
        if spec.magr:
            Wp = magr_preprocess(W, H, alpha=magr_alpha(H, m),
                                 iters=spec.magr_iters)
        else:
            Wp = W
        Qd, Qc, s, z = optq_quantize_core(Wp, H, qcfg)
        # spec.lambda_frac regularizes BOTH the OPTQ damping (via qcfg) and
        # the CLoQ Gram root, so the health ladder's re-damp rung reaches
        # every Cholesky/eigh in the stack
        Hreg = regularize_gram(H, spec.lambda_frac)
        if axis is None:
            A, B = cloq_init(Hreg, W - Qd, spec.rank, spec.split)
        else:
            R, Rinv = gram_root(Hreg)
            A, B = cloq_lowrank_local(R, Rinv, W - Qd, spec.rank,
                                      spec.split, axis)
        return {"qcodes": pack_codes(Qc, spec.bits), "scales": s, "zeros": z,
                "lora_a": A, "lora_b": B}, Qd
    if spec.method == "gptq":
        Qd, Qc, s, z = optq_quantize_core(W, jnp.asarray(H, jnp.float32),
                                          qcfg)
        A = jax.random.normal(key, (m, spec.rank), jnp.float32) / np.sqrt(m)
        B = jnp.zeros((n, spec.rank), jnp.float32)
        return {"qcodes": pack_codes(Qc, spec.bits), "scales": s, "zeros": z,
                "lora_a": A, "lora_b": B}, Qd
    if spec.method == "loftq":
        Qd, A, B, qstate = loftq_init(W, qcfg, spec.rank, iters=5, axis=axis)
        codes, s, z = qstate
        return {"qcodes": pack_codes(codes, spec.bits), "scales": s,
                "zeros": z, "lora_a": A, "lora_b": B}, Qd
    if spec.method == "qlora":
        Qd, A, B, qstate = qlora_init(W, qcfg, spec.rank, key)
        codes, absmax = qstate
        return {"qcodes": pack_codes(codes, 4), "absmax": absmax,
                "lora_a": A, "lora_b": B}, Qd
    if spec.method == "rtn":
        codes, s, z = quantize_int(W, spec.bits, spec.group_size)
        Qd = dequantize_int(codes, s, z, spec.group_size)
        A = jax.random.normal(key, (m, spec.rank), jnp.float32) / np.sqrt(m)
        B = jnp.zeros((n, spec.rank), jnp.float32)
        return {"qcodes": pack_codes(codes, spec.bits), "scales": s,
                "zeros": z, "lora_a": A, "lora_b": B}, Qd
    raise ValueError(f"unknown method {spec.method}")


def eval_single(W: Array, H: Array | None, key: Array, spec: BucketSpec,
                axis: str | None = None) -> Array:
    """Traced single-candidate *sensitivity* core: the calibration-weighted
    proxy error of quantizing this site with ``spec``,

        err = tr(E^T H E),    E = W - Q - A B^T

    (PAPER.md §3's layer-wise discrepancy ``||X E||_F^2`` written through
    the Gram ``H = X^T X`` — no calibration activations materialized).
    Falls back to the unweighted ``||E||_F^2`` when the bucket carries no
    Gram.  Runs the very same quantization stack as
    :func:`quantize_single_deq`, so the error ranks exactly what the
    engine would produce.  Under ``shard_map`` (``axis`` given) the
    per-column contributions ``e_j^T H e_j`` are shard-local given the
    replicated Gram; one scalar psum recovers the total."""
    leaves, Qd = quantize_single_deq(W, H, key, spec, axis)
    W = jnp.asarray(W, jnp.float32)
    E = W - Qd - leaves["lora_a"] @ leaves["lora_b"].T
    if spec.has_gram:
        err = jnp.einsum("ij,ik,kj->", E, jnp.asarray(H, jnp.float32), E)
    else:
        err = jnp.sum(E * E)
    if axis is not None:
        err = jax.lax.psum(err, axis)
    return err


@partial(jax.jit, static_argnames=("spec",))
def run_bucket(Ws: Array, Hs: Array | None, keys: Array,
               spec: BucketSpec) -> dict:
    """One compiled executable per bucket signature: vmap of
    :func:`quantize_single` over stacked layers.

    Args:
        Ws:   (L, m, n) stacked weights of the bucket.
        Hs:   (L, m, m) stacked calibration Grams, or ``None`` for methods
              that don't consume one.
        keys: (L, 2) per-task PRNG keys (split in path order by the driver
              so random LoRA inits match the sequential engine).
        spec: static bucket signature (jit static argument).

    Returns a dict of stacked leaves (leading dim ``L``).  Runs entirely on
    the local device; for the multi-device variant see
    :func:`run_bucket_sharded`."""
    if Hs is None:
        return jax.vmap(
            lambda W, k: quantize_single(W, None, k, spec))(Ws, keys)
    return jax.vmap(
        lambda W, H, k: quantize_single(W, H, k, spec))(Ws, Hs, keys)


def bucket_fn(spec: BucketSpec):
    """The (untraced) bucket program of :func:`run_bucket` as a plain
    function — what the persisted compile cache lowers, serializes, and
    reloads (:class:`repro.core.compile_cache.CompileCache`).  Positional
    signature: ``(Ws, Hs, keys)`` when the spec carries a Gram, else
    ``(Ws, keys)``."""
    if spec.has_gram:
        def fn(Ws, Hs, keys):
            return jax.vmap(
                lambda W, H, k: quantize_single(W, H, k, spec))(Ws, Hs, keys)
    else:
        def fn(Ws, keys):
            return jax.vmap(
                lambda W, k: quantize_single(W, None, k, spec))(Ws, keys)
    return fn


@partial(jax.jit, static_argnames=("spec",))
def _run_single(W: Array, H: Array | None, key: Array,
                spec: BucketSpec) -> dict:
    return quantize_single(W, H, key, spec)


def run_bucket_sequential(Ws: Array, Hs: Array | None, keys: Array,
                          spec: BucketSpec) -> dict:
    """Per-layer execution of one bucket: ``L`` dispatches of the jitted
    single-layer core, outputs stacked to :func:`run_bucket`'s layout.

    The cost model picks this path only through its memory gate — a
    bucket whose stacked ``(L, m, n)`` working set exceeds the calibrated
    budget would not fit if vmapped, so it trades ``L`` dispatch overheads
    for peak memory ``1/L`` of the fused path.  ``Ws``/``Hs`` may be
    per-layer lists (:func:`_stage_bucket` stacks nothing for this path),
    and at most two layers are on the device at once: each dispatch waits
    for the layer before the previous one."""
    outs: list[dict] = []
    for j in range(len(Ws)):
        if len(outs) >= 2:
            jax.block_until_ready(outs[-2])
        outs.append(_run_single(Ws[j], None if Hs is None else Hs[j],
                                keys[j], requeue_spec(spec)))
    return {k: jnp.stack([o[k] for o in outs]) for k in outs[0]}


@partial(jax.jit, static_argnames=("spec",))
def run_bucket_eval(Ws: Array, Hs: Array | None, keys: Array,
                    spec: BucketSpec) -> Array:
    """Sensitivity-sweep analog of :func:`run_bucket`: one compiled
    executable per ``(shape, candidate-spec)`` slab, vmapping
    :func:`eval_single` over the stacked layers.  Returns the ``(L,)``
    proxy errors — the whole candidate evaluation for a bucket costs one
    trace and one dispatch, never a per-candidate Python loop."""
    if Hs is None:
        return jax.vmap(
            lambda W, k: eval_single(W, None, k, spec))(Ws, keys)
    return jax.vmap(
        lambda W, H, k: eval_single(W, H, k, spec))(Ws, Hs, keys)


@lru_cache(maxsize=64)
def _sharded_eval_executable(spec: BucketSpec, mesh, axis: str):
    """Compiled shard_map(vmap(eval_single)) for one (spec, mesh) pair —
    the sweep's distributed path: each device quantizes + scores its
    column shard, one scalar-per-layer psum totals the proxy errors."""
    from jax.sharding import PartitionSpec as P

    if spec.has_gram:
        def local(Ws_l, Hs_l, keys_l):
            return jax.vmap(lambda W, H, k: eval_single(
                W, H, k, spec, axis=axis))(Ws_l, Hs_l, keys_l)
        in_specs = (P(None, None, axis), P(None, None, None), P(None, None))
    else:
        def local(Ws_l, keys_l):
            return jax.vmap(lambda W, k: eval_single(
                W, None, k, spec, axis=axis))(Ws_l, keys_l)
        in_specs = (P(None, None, axis), P(None, None))

    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=P(None))
    return jax.jit(fn)


def run_bucket_eval_sharded(Ws: Array, Hs: Array | None, keys: Array,
                            spec: BucketSpec, mesh,
                            axis: str = "model") -> Array:
    """Distributed :func:`run_bucket_eval`: ``shard_map`` over ``axis``
    (same planner gate as :func:`run_bucket_sharded` — ``spec.n_shards >
    1`` only when ``n`` divides the axis).  Returns replicated ``(L,)``
    proxy errors."""
    fn = _sharded_eval_executable(spec, mesh, axis)
    if spec.has_gram:
        return fn(Ws, Hs, keys)
    return fn(Ws, keys)


def task_leaf_specs(method: str, axis: str | None = "model",
                    lead: int = 0) -> dict:
    """PartitionSpecs of ONE task's (unstacked) output leaves.

    Column-dimension leaves (``qcodes``/``scales``/``zeros``/``absmax``)
    shard their last dim over ``axis``; ``lora_b`` (n, r) shards its column
    dim; ``lora_a`` (m, r) is replicated — the Gram-trick psum (and the
    replicated PRNG key for the random-init baselines) makes it identical
    on every device.  ``axis=None`` yields the fully-replicated fallback
    layout; ``lead`` prepends that many unsharded dims (stacked MoE expert
    leaves in the param tree carry a leading ``E``).

    This is the layout source of truth: :func:`bucket_out_specs` stacks it
    with the bucket dim ``L``, and checkpoint restore rebuilds per-leaf
    shardings from a saved bucket manifest with it
    (:func:`repro.checkpoint.manager.manifest_shardings`)."""
    from jax.sharding import PartitionSpec as P
    pre = (None,) * lead
    col = P(*pre, None, axis)
    out = {"qcodes": col, "lora_a": P(*pre, None, None),
           "lora_b": P(*pre, axis, None)}
    if method == "qlora":
        out["absmax"] = col
    else:
        out["scales"] = col
        out["zeros"] = col
    return out


def bucket_out_specs(method: str, axis: str = "model"):
    """PartitionSpecs of one sharded bucket's output leaves: the per-task
    layout of :func:`task_leaf_specs` under an unsharded leading bucket
    dim ``L``."""
    from jax.sharding import PartitionSpec as P
    return {k: P(None, *sp)
            for k, sp in task_leaf_specs(method, axis).items()}


@lru_cache(maxsize=64)
def _sharded_executable(spec: BucketSpec, mesh, axis: str):
    """Compiled shard_map(vmap(quantize_single)) for one (spec, mesh) pair.

    Cached so repeated buckets with the same signature reuse the
    executable, mirroring ``run_bucket``'s jit cache.  Bounded so a
    long-lived process sweeping many distinct meshes doesn't pin compiled
    executables (and their Mesh references) forever."""
    from jax.sharding import PartitionSpec as P

    out_specs = bucket_out_specs(spec.method, axis)

    if spec.has_gram:
        def local(Ws_l, Hs_l, keys_l):
            return jax.vmap(lambda W, H, k: quantize_single(
                W, H, k, spec, axis=axis))(Ws_l, Hs_l, keys_l)
        in_specs = (P(None, None, axis), P(None, None, None), P(None, None))
    else:
        def local(Ws_l, keys_l):
            return jax.vmap(lambda W, k: quantize_single(
                W, None, k, spec, axis=axis))(Ws_l, keys_l)
        in_specs = (P(None, None, axis), P(None, None))

    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    return jax.jit(fn)


def run_bucket_sharded(Ws: Array, Hs: Array | None, keys: Array,
                       spec: BucketSpec, mesh, axis: str = "model") -> dict:
    """Distributed bucket executable: ``shard_map`` over the ``axis`` mesh
    axis whose body vmaps :func:`quantize_single` over the bucket's layers.

    Args:
        Ws:   (L, m, n) stacked weights; the column dim ``n`` must be
              divisible by ``mesh.shape[axis]`` (the planner guarantees
              this — ``spec.n_shards > 1`` only when it holds).
        Hs:   (L, m, m) stacked Grams (replicated to every device) or
              ``None``.
        keys: (L, 2) per-task PRNG keys, replicated.
        spec: static bucket signature with ``spec.n_shards > 1``.
        mesh: a ``jax.sharding.Mesh`` carrying ``axis``.
        axis: mesh axis name to column-shard over (default ``"model"``).

    Each device sweeps its ``(L, m, n/D)`` column shard of the whole
    MagR→OPTQ→CLoQ (or baseline) stack in one fused program; the only
    communication is CLoQ's ``(L, m, m)`` Gram psum.  Returns the same
    stacked leaf dict as :func:`run_bucket`, with column leaves sharded
    and ``lora_a`` replicated."""
    fn = _sharded_executable(spec, mesh, axis)
    if spec.has_gram:
        return fn(Ws, Hs, keys)
    return fn(Ws, keys)


def per_layer_sharded_dispatch(tasks: list[LayerTask], qspec, mesh,
                               axis: str = "model",
                               base: QuantConfig | None = None) -> list:
    """The pre-bucket status quo: one sharded OPTQ dispatch + one sharded
    CLoQ dispatch *per layer* (MagR replicated on the host side).

    Kept as the baseline that :func:`run_bucket_sharded` is measured
    against (``benchmarks/table10_init_cost.py`` ``sharded_rows``,
    ``examples/distributed_quantize.py``) — defined here, next to
    :func:`quantize_single`, so the MagR gate and alpha stay the single
    source of truth for both paths.  Returns per-task ``(A, B)`` pairs."""
    outs = []
    for t in tasks:
        m, n = t.W.shape
        spec = make_spec(m, n, qspec, "cloq", t.H is not None, base,
                         mesh=mesh, axis=axis)
        qcfg = spec_qcfg(spec)
        W = jnp.asarray(t.W, jnp.float32)
        H = jnp.asarray(t.H, jnp.float32)
        if spec.magr:
            W_q = magr_preprocess(W, H, alpha=magr_alpha(H, m),
                                  iters=spec.magr_iters)
        else:
            W_q = W
        Qd, _, _, _ = optq_quantize_sharded(W_q, H, qcfg, mesh, axis)
        A, B = cloq_init_sharded(regularize_gram(H), W - Qd, spec.rank,
                                 mesh, axis, spec.split)
        outs.append((A, B))
    return outs


def apply_cost_model(buckets: dict[BucketSpec, list[int]], cost_model, *,
                     mesh=None,
                     axis: str = "model") -> dict[BucketSpec, list[int]]:
    """Re-decide each planned bucket's execution path from predicted time.

    The divisibility-planned ``buckets`` (whose bucket *membership* is
    final — path choice never changes which tasks group together) are
    re-specced by ``cost_model.decide(spec, L, k)`` (see
    :class:`repro.core.costmodel.CostModel`): each bucket picks
    replicated / sharded / sequential from the calibrated
    flops/bytes/collective estimate, now that the bucket size ``L`` is
    known.  Insertion order is preserved.  ``cost_model=None`` is the
    identity (legacy divisibility-only planning)."""
    if cost_model is None:
        return buckets
    k = bucket_axis_size(mesh, axis)
    out: dict[BucketSpec, list[int]] = {}
    for spec, idxs in buckets.items():
        k_eff = 1 if spec.method in _REPLICATED_METHODS else k
        path, shards = cost_model.decide(spec, len(idxs), k_eff)
        spec = dataclasses.replace(spec, exec_path=path, n_shards=shards)
        out.setdefault(spec, []).extend(idxs)
    return out


def requeue_spec(spec: BucketSpec) -> BucketSpec:
    """The spec a *fresh single-slice, meshless plan* would produce for
    this bucket — what the health ladder requeues a failing slice under
    (``health.heal_task``), so a healed site's spec (and its manifest /
    journal entry) matches re-planning that site alone: unsharded, one
    replicated dispatch, every other static decision unchanged.

    >>> s = BucketSpec(m=8, n=8, method="rtn", bits=4, group_size=None,
    ...                rank=2, split="paper", block_size=8, act_order=False,
    ...                lambda_frac=0.01, magr=False, magr_iters=1,
    ...                has_gram=False, n_shards=4, exec_path="sharded")
    >>> requeue_spec(s).n_shards, requeue_spec(s).exec_path
    (1, 'replicated')
    """
    return dataclasses.replace(spec, n_shards=1, exec_path="replicated")


def plan_buckets(tasks: list[LayerTask], qspec=None, method: str | None = None,
                 base: QuantConfig | None = None, *, mesh=None,
                 axis: str = "model", for_eval: bool = False,
                 cost_model=None) -> dict[BucketSpec, list[int]]:
    """Group task indices by executable signature (insertion-ordered).

    Args:
        tasks:  flattened quantization sites (see :class:`LayerTask`).
                Tasks carrying a resolved ``site``
                (:class:`repro.core.recipe.SiteSpec`) bucket by their own
                spec — one run may mix methods, bit-widths, and ranks.
        qspec:  fallback ``repro.models.modules.QSpec`` for tasks without a
                resolved site (the legacy global pair).
        method: fallback init method name (``cloq``/``gptq``/``loftq``/
                ``qlora``/``rtn``) for tasks without a resolved site.
        base:   optional :class:`QuantConfig` overriding sweep defaults.
        mesh:   optional ``jax.sharding.Mesh``; buckets whose column count
                divides ``mesh.shape[axis]`` get ``n_shards > 1`` and run
                via :func:`run_bucket_sharded`; the rest fall back to the
                replicated :func:`run_bucket`.
        axis:   mesh axis name for column sharding.
        for_eval: plan *sensitivity-sweep* buckets
                (:func:`evaluate_layer_batch`): route each task's Gram into
                its bucket whenever present so every candidate's proxy
                error is calibration-weighted (see :func:`make_spec`).
        cost_model: optional :class:`repro.core.costmodel.CostModel`.
                When given, each bucket's execution path (replicated /
                sharded / sequential) is chosen from predicted time
                instead of divisibility alone (:func:`apply_cost_model`);
                ``None`` keeps the legacy divisibility-only behavior.

    Returns an insertion-ordered ``{BucketSpec: [task indices]}``."""
    buckets: dict[BucketSpec, list[int]] = {}
    for i, t in enumerate(tasks):
        t_qspec, t_method = task_site(t, qspec, method)
        m, n = t.W.shape
        has_gram = t.H is not None
        if t_method in GRAM_METHODS and not has_gram:
            raise ValueError(
                f"method {t_method!r} needs a calibration Gram for {t.path}"
                f"{'' if t.expert is None else f'[expert {t.expert}]'}")
        spec = make_spec(m, n, t_qspec, t_method, has_gram, base,
                         mesh=mesh, axis=axis, for_eval=for_eval)
        buckets.setdefault(spec, []).append(i)
    return apply_cost_model(buckets, cost_model, mesh=mesh, axis=axis)


def plan_manifest(tasks: list[LayerTask],
                  buckets: dict[BucketSpec, list[int]],
                  axis: str = "model") -> dict:
    """Serialize one planner run to a JSON-able **bucket manifest**: every
    bucket's static spec (shard count included) plus the task -> bucket
    assignment with each task's param-tree path and expert index.

    Saved alongside checkpoints (``checkpoint.manager.save_tree(...,
    manifest=...)``) so a resharded restore can rebuild per-bucket
    shardings directly from the file — no model config, no planner
    (:func:`repro.checkpoint.manager.manifest_shardings`)."""
    return {
        "version": 1,
        "axis": axis,
        "buckets": [
            {"spec": dataclasses.asdict(spec),
             "tasks": [{"path": tasks[i].path, "expert": tasks[i].expert}
                       for i in idxs]}
            for spec, idxs in buckets.items()],
    }


def _stage_bucket(tasks: list[LayerTask], idxs: list[int],
                  spec: BucketSpec):
    """Host-side staging of one bucket: stack (W, H, key) to device arrays.

    This is the host work the streaming executor overlaps with device
    compute of the previous bucket.  A bucket the memory gate sent down
    the sequential path stacks nothing: its layers go to the device one
    at a time (:func:`run_bucket_sequential`)."""
    keys = jnp.stack([tasks[i].key for i in idxs])
    if spec.exec_path == "sequential":
        return ([tasks[i].W for i in idxs],
                [tasks[i].H for i in idxs] if spec.has_gram else None, keys)
    Ws = jnp.stack([jnp.asarray(tasks[i].W, jnp.float32) for i in idxs])
    Hs = None
    if spec.has_gram:
        Hs = jnp.stack([jnp.asarray(tasks[i].H, jnp.float32)
                        for i in idxs])
    return Ws, Hs, keys


def quantize_layer_batch(tasks: list[LayerTask], qspec=None,
                         method: str | None = None,
                         base: QuantConfig | None = None,
                         progress: Callable[[str], None] | None = None,
                         *, mesh=None, axis: str = "model",
                         stream: bool = True, policy=None, report=None,
                         journal=None,
                         should_stop: Callable[[], bool] | None = None,
                         cost_model=None, compile_cache=None
                         ) -> list[dict | None]:
    """Quantize all ``tasks`` bucket-by-bucket.

    The model-level batched engine entry point
    (``pipeline.quantize_model(engine="batched")`` drives it).

    Args:
        tasks:    flattened quantization sites, one per (layer | expert),
                  each optionally carrying its resolved ``site`` spec
                  (mixed-precision recipes; see :func:`plan_buckets`).
        qspec:    fallback ``QSpec`` (bits/group_size/rank/split) for tasks
                  without a resolved site.
        method:   fallback init method (see module docstring).
        base:     optional ``QuantConfig`` overriding sweep defaults.
        progress: optional callback, called once per *bucket* with a
                  structured ``[bucket] key=value`` plan-composition line
                  (:func:`repro.obs.log.format_event`: spec, shape, layer
                  count, execution path, cache tallies from the metrics
                  registry) so long mixed runs are observable.
        mesh:     optional ``jax.sharding.Mesh``: buckets run column-sharded
                  over ``axis`` where the planner allows (see
                  :func:`plan_buckets`); ``None`` = single-device.
        axis:     mesh axis name (default ``"model"``).
        stream:   double-buffered bucket streaming (default on): bucket
                  ``k``'s executable is dispatched asynchronously and the
                  host immediately stages bucket ``k+1``'s stacked arrays
                  while the device computes.  ``stream=False`` serializes
                  (block on each bucket before staging the next) — same
                  results, used as the ordering oracle in tests.
        policy:   optional :class:`repro.core.health.HealthPolicy`.  When
                  enabled, every finished bucket is checked by one fused
                  ``jit(vmap)`` health pass (:func:`repro.core.health.
                  check_bucket`) and failing slices are requeued through
                  the sequential oracle under the degradation ladder
                  (:func:`repro.core.health.heal_task`); healed-to-dense
                  slices yield ``None`` results.
        report:   optional :class:`repro.core.health.HealthReport`
                  collecting ladder outcomes and run events (one is
                  created internally if ``policy`` is set without one).
        journal:  optional :class:`repro.checkpoint.manager.QuantJournal`.
                  Each completed (checked, healed) bucket is committed
                  synchronously — leaves + spec/task fingerprint + health
                  records — before the next bucket's results land, and
                  buckets whose valid journal entry already exists are
                  skipped entirely on restart (their committed leaves are
                  returned bit-identical).
        should_stop: optional zero-arg callable polled at every bucket
                  boundary (after the journal commit); returning True
                  raises :class:`repro.core.health.QuantPreempted` — the
                  clean SIGTERM path of ``launch/train.py``.
        cost_model: optional :class:`repro.core.costmodel.CostModel` (or
                  anything its ``coerce`` accepts): bucket execution paths
                  are chosen from predicted time instead of divisibility
                  (see :func:`plan_buckets`).
        compile_cache: optional
                  :class:`repro.core.compile_cache.CompileCache` (or a
                  directory path): replicated buckets run through
                  persisted AOT executables keyed on the plan fingerprint
                  — the second process start deserializes instead of
                  retracing, with hits/misses surfaced in the progress
                  line.

    Returns one leaf dict per task, in task order (same leaves as the
    sequential path); entries are ``None`` for slices the health ladder
    degraded to dense."""
    from repro.core import faults, health
    from repro.core.compile_cache import CompileCache, canonical_digest
    from repro.core.costmodel import CostModel

    cost_model = CostModel.coerce(cost_model)
    cache = CompileCache.coerce(compile_cache)
    with obs_trace.span("quant.plan", tasks=len(tasks)) as sp:
        buckets = plan_buckets(tasks, qspec, method, base, mesh=mesh,
                               axis=axis, cost_model=cost_model)
        sp.set(buckets=len(buckets))
    scope = (canonical_digest(plan_manifest(tasks, buckets, axis))
             if cache is not None else None)
    results: list[dict | None] = [None] * len(tasks)
    items = list(buckets.items())
    guarded = policy is not None and policy.enabled
    if guarded and report is None:
        report = health.HealthReport()

    # journal resume: collect buckets whose committed entry matches this
    # plan (spec + task list fingerprint); stale entries are recomputed
    loaded: dict[int, list] = {}
    if journal is not None:
        for b, (spec, idxs) in enumerate(items):
            task_ids = [[tasks[i].path, tasks[i].expert] for i in idxs]
            entry = journal.load_bucket(b, dataclasses.asdict(spec),
                                        task_ids)
            if entry is None:
                continue
            loaded[b] = entry[0]
            obs_metrics.counter(obs_names.JOURNAL_RESTORED).inc()
            obs_metrics.counter(obs_names.JOURNAL_SKIPPED_TASKS).inc(
                len(idxs))
            if report is not None:
                report.records.update(entry[1])
                report.event(f"bucket {b} restored from journal "
                             f"({len(idxs)} slices skipped)")

    def dispatch(b: int, staged) -> tuple[list[int], dict]:
        spec, idxs = items[b]
        Ws, Hs, keys = staged
        path = "sharded" if spec.n_shards > 1 else spec.exec_path
        cache_fields: dict = {}
        if spec.n_shards > 1:
            out = run_bucket_sharded(Ws, Hs, keys, spec, mesh, axis)
        elif spec.exec_path == "sequential":
            out = run_bucket_sequential(Ws, Hs, keys, spec)
        elif cache is not None:
            args = (Ws, Hs, keys) if spec.has_gram else (Ws, keys)
            out, hit = cache.call(
                "bucket", {"scope": scope, "spec": dataclasses.asdict(spec),
                           "L": len(idxs)}, bucket_fn(spec), args)
            # cache tallies come from the metrics registry (the
            # CompileCache mirrors every hit/miss into it)
            reg = obs_metrics.get_registry()
            cache_fields = {
                "cache": "hit" if hit else "miss",
                "hits": reg.counter(obs_names.CACHE_HITS).value,
                "misses": reg.counter(obs_names.CACHE_MISSES).value}
        else:
            out = run_bucket(Ws, Hs, keys, spec)
        obs_metrics.counter(obs_names.QUANT_BUCKETS).inc()
        obs_metrics.counter(obs_names.QUANT_TASKS).inc(len(idxs))
        obs_metrics.counter(obs_names.QUANT_PATH + path).inc()
        if progress:
            g = "col" if spec.group_size is None else spec.group_size
            progress(obs_log.format_event(
                "bucket", i=b,
                spec=f"{spec.method}/{spec.bits}b/g{g}/r{spec.rank}",
                shape=f"{spec.m}x{spec.n}", layers=len(idxs),
                path=path, shards=spec.n_shards, **cache_fields))
        return idxs, out

    def stage(b: int):
        spec_b, idxs_b = items[b]
        with obs_trace.span("bucket.stage", bucket=b, layers=len(idxs_b)):
            return _stage_bucket(tasks, idxs_b, spec_b)

    staged = None
    for b in range(len(items)):
        spec, idxs = items[b]
        if b in loaded:
            staged = None                        # prefetch was for bucket b
            if progress:
                progress(obs_log.format_event(
                    "bucket", i=b, restored="journal", layers=len(idxs)))
            for j, i in enumerate(idxs):
                results[i] = loaded[b][j]
            continue
        if staged is None:
            staged = stage(b)
        cur = staged
        with obs_trace.span("bucket.execute", bucket=b,
                            path=("sharded" if spec.n_shards > 1
                                  else spec.exec_path),
                            shards=spec.n_shards,
                            layers=len(idxs)):
            idxs, out = dispatch(b, cur)         # async dispatch
        staged = None
        if stream and b + 1 < len(items) and (b + 1) not in loaded:
            # double-buffer: stage bucket b+1 on the host while the device
            # computes bucket b
            staged = stage(b + 1)
        elif not stream:
            jax.block_until_ready(out)           # serialize (oracle mode)
        for j, i in enumerate(idxs):
            results[i] = {k: v[j] for k, v in out.items()}
        if guarded:
            with obs_trace.span("bucket.health_check", bucket=b,
                                layers=len(idxs)):
                ok = health.check_bucket(cur[0], out, spec, policy)
            report.checked += len(idxs)
            obs_metrics.counter(obs_names.HEALTH_CHECKED).inc(len(idxs))
            for j, i in enumerate(idxs):
                if not ok[j]:
                    t = tasks[i]
                    results[i] = health.heal_task(t.W, t.H, t.key, spec,
                                                  policy, report, t.path,
                                                  t.expert)
        if journal is not None:
            # synchronous commit point of the streamed bucket: the journal
            # entry is only visible once fully written (atomic save_tree)
            hrecs = {}
            if report is not None:
                for i in idxs:
                    sk = health.HealthReport.site_key(tasks[i].path,
                                                      tasks[i].expert)
                    if sk in report.records:
                        hrecs[sk] = report.records[sk]
            journal.commit_bucket(
                b, dataclasses.asdict(spec),
                [[tasks[i].path, tasks[i].expert] for i in idxs],
                [results[i] for i in idxs], health_records=hrecs)
        faults.maybe_kill("kill_between_buckets", b)
        if should_stop is not None and should_stop():
            raise health.QuantPreempted(b)
    return results


def evaluate_layer_batch(tasks: list[LayerTask],
                         base: QuantConfig | None = None,
                         progress: Callable[[str], None] | None = None,
                         *, mesh=None, axis: str = "model",
                         stream: bool = True) -> list[float]:
    """Proxy error ``tr(E^T H E)`` of every task, bucket-by-bucket — the
    execution engine of the bit-allocation sensitivity sweep
    (:mod:`repro.core.allocate`).

    Tasks carry their *candidate* :class:`~repro.core.recipe.SiteSpec` in
    ``LayerTask.site``; the planner (``for_eval=True``) groups them into
    ``(shape, candidate-spec)`` slabs, each evaluated by ONE
    ``jit(vmap)`` executable (:func:`run_bucket_eval`) — so sweeping a
    C-candidate grid over an N-site model dispatches per *bucket*, not per
    ``site x candidate``.  With ``mesh``, divisible buckets ride the
    sharded Gram-trick path (:func:`run_bucket_eval_sharded`); streaming
    double-buffers host staging exactly like :func:`quantize_layer_batch`.

    Returns one Python float per task, in task order."""
    buckets = plan_buckets(tasks, base=base, mesh=mesh, axis=axis,
                           for_eval=True)
    results: list[float | None] = [None] * len(tasks)
    items = list(buckets.items())
    pending: list[tuple[list[int], Array]] = []

    def dispatch(b: int, staged):
        spec, idxs = items[b]
        Ws, Hs, keys = staged
        if progress:
            g = "col" if spec.group_size is None else spec.group_size
            progress(obs_log.format_event(
                "sweep", i=b,
                spec=f"{spec.method}/{spec.bits}b/g{g}/r{spec.rank}",
                shape=f"{spec.m}x{spec.n}", candidates=len(idxs),
                path=("sharded" if spec.n_shards > 1 else "replicated"),
                shards=spec.n_shards))
        if spec.n_shards > 1:
            out = run_bucket_eval_sharded(Ws, Hs, keys, spec, mesh, axis)
        else:
            out = run_bucket_eval(Ws, Hs, keys, spec)
        return idxs, out

    staged = None
    for b in range(len(items)):
        if staged is None:
            staged = _stage_bucket(tasks, items[b][1], items[b][0])
        with obs_trace.span("sweep.execute", bucket=b,
                            candidates=len(items[b][1])):
            idxs, out = dispatch(b, staged)      # async dispatch
        staged = None
        if stream and b + 1 < len(items):
            staged = _stage_bucket(tasks, items[b + 1][1], items[b + 1][0])
        elif not stream:
            jax.block_until_ready(out)
        # defer the host sync: float() would serialize with the device
        pending.append((idxs, out))
    for idxs, out in pending:
        errs = np.asarray(out)
        for j, i in enumerate(idxs):
            results[i] = float(errs[j])
    return results
