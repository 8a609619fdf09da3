"""Persisted compile cache: AOT bucket/decode executables across processes.

CLoQ-style quantization is a one-shot compile-heavy pass: every distinct
:class:`~repro.core.batched.BucketSpec` is one ``jit(vmap)`` executable,
and a mixed-precision recipe means N of them — all retraced and recompiled
on *every* process start (serve cold-start, train restart, each benchmark
rep).  This module persists the compiled executables to disk so the second
process start deserializes instead of retracing.

Format: ``jax.experimental.serialize_executable`` — a pickled
``(payload, in_tree, out_tree)`` triple wrapping XLA's own serialized
executable.  ``deserialize_and_load`` returns a ready
``jax.stages.Compiled`` (no trace, no XLA compile — true AOT).  Entries
that fail to load (truncated file, different XLA build, hand-edited bytes)
are treated as **corrupt**: one warning, the entry is deleted, and the
function recompiles — the cache can never make a run incorrect, only
faster.

Key layout (sha1 over canonical JSON): ``kind`` (``"bucket"`` /
``"decode"``), the caller's ``parts`` (bucket spec + layer count +
manifest hash; serve config + site set), the flattened input
shape/dtype signature, plus the environment fingerprint — jax version,
backend, device count.  Any of these changing is a **miss by
construction**: a new manifest, a jax upgrade, or a different device
topology never replays a stale executable.

Portability gate: on the **cpu** backend, executables containing
``custom-call`` ops (the LAPACK eigh/SVD/Cholesky in the CLoQ/LoftQ
math) bind process-local function pointers — a deserialized copy
crashes at run time (verified: both ``serialize_executable`` and a
StableHLO ``jax.export`` round-trip segfault on ``lapack_*_ffi``
targets).  Those executables are never written to disk; they stay in
the in-process memo and are counted as ``unportable``.  Custom-call-free
programs (RTN/QLoRA buckets, the serve decode step) persist normally,
and non-cpu backends persist unconditionally (name-registered custom
calls there survive the supported AOT path).

>>> canonical_digest({"b": 1, "a": 2}) == canonical_digest({"a": 2, "b": 1})
True
>>> len(canonical_digest({"a": 2})) == 40
True
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import warnings
from typing import Any, Callable

import jax

from repro.obs import metrics as obs_metrics
from repro.obs import names as obs_names

_FORMAT = "xc1"          # serialize_executable triple, pickled

_OBS_COUNTERS = {
    "hits": obs_names.CACHE_HITS,
    "misses": obs_names.CACHE_MISSES,
    "corrupt": obs_names.CACHE_CORRUPT,
    "unportable": obs_names.CACHE_UNPORTABLE,
}


def canonical_digest(obj) -> str:
    """sha1 hex digest of an object's canonical (sorted-key) JSON form —
    the cache-key and manifest-hash primitive."""
    blob = json.dumps(obj, sort_keys=True, default=str,
                      separators=(",", ":"))
    return hashlib.sha1(blob.encode()).hexdigest()


def _signature(args) -> list:
    leaves, treedef = jax.tree.flatten(args)
    return [[list(x.shape), str(x.dtype)] for x in leaves] + [str(treedef)]


def _abstract(args):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)


class CompileCache:
    """Disk-backed executable cache with hit/miss/corrupt counters.

    One instance per process/run; the directory is shared across
    processes.  ``get`` is the whole API: look up (or compile and
    persist) the executable for ``fn`` at ``args``'s shapes.  Counters
    (``hits``/``misses``/``corrupt``) are surfaced in the bucket progress
    line and asserted by the cold-start tests."""

    def __init__(self, directory: str, *, jax_version: str | None = None,
                 backend: str | None = None):
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.jax_version = jax_version or jax.__version__
        self.backend = backend or jax.default_backend()
        self.hits = 0
        self.misses = 0
        self.corrupt = 0
        self.unportable = 0
        self._mem: dict[str, Any] = {}

    def _tally(self, event: str) -> None:
        """Bump the per-instance counter and its registry mirror."""
        setattr(self, event, getattr(self, event) + 1)
        obs_metrics.counter(_OBS_COUNTERS[event]).inc()

    @classmethod
    def coerce(cls, obj) -> "CompileCache | None":
        """Accept a CompileCache, a directory path, or ``None``."""
        if obj is None or isinstance(obj, cls):
            return obj
        if isinstance(obj, (str, os.PathLike)):
            return cls(os.fspath(obj))
        raise TypeError(
            f"cannot coerce {type(obj).__name__} to CompileCache")

    def key(self, kind: str, parts: dict, args,
            donate_argnums: tuple = ()) -> str:
        return canonical_digest({
            "kind": kind, "parts": parts, "sig": _signature(args),
            "donate": list(donate_argnums),
            "jax": self.jax_version, "backend": self.backend,
            "devices": jax.device_count()})

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, f"{key}.bin")

    def get(self, kind: str, parts: dict, fn: Callable, args: tuple,
            donate_argnums: tuple = ()) -> tuple[Any, bool]:
        """Return ``(executable, hit)`` for ``fn`` specialized to
        ``args``'s shapes/dtypes.  The executable is called exactly like
        ``jax.jit(fn, donate_argnums=donate_argnums)`` at those shapes;
        the donation is part of the key and of the persisted executable."""
        key = self.key(kind, parts, args, donate_argnums)
        if key in self._mem:
            self._tally("hits")
            return self._mem[key], True
        path = self._path(key)
        if os.path.exists(path):
            try:
                with open(path, "rb") as f:
                    tag, payload, in_tree, out_tree = pickle.load(f)
                if tag != _FORMAT:
                    raise ValueError(f"unknown cache format {tag!r}")
                from jax.experimental import serialize_executable as se
                compiled = se.deserialize_and_load(payload, in_tree,
                                                   out_tree)
                self._tally("hits")
                self._mem[key] = compiled
                return compiled, True
            except KeyboardInterrupt:
                raise
            except Exception as e:          # corrupt entry: warn + rebuild
                self._tally("corrupt")
                warnings.warn(
                    f"corrupt compile-cache entry {key[:12]} "
                    f"({type(e).__name__}: {e}); recompiling",
                    RuntimeWarning, stacklevel=2)
                try:
                    os.remove(path)
                except OSError:
                    pass
        compiled = jax.jit(fn, donate_argnums=donate_argnums).lower(
            *_abstract(args)).compile()
        self._tally("misses")
        self._mem[key] = compiled
        if not self._portable(compiled):
            self._tally("unportable")
            return compiled, False
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            fd, tmp = tempfile.mkstemp(dir=self.directory, suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                pickle.dump((_FORMAT, payload, in_tree, out_tree), f)
            os.replace(tmp, path)
        except KeyboardInterrupt:
            raise
        except Exception as e:              # persist failure is non-fatal
            warnings.warn(
                f"could not persist compile-cache entry {key[:12]} "
                f"({type(e).__name__}: {e}); executable stays in-process",
                RuntimeWarning, stacklevel=2)
        return compiled, False

    def _portable(self, compiled) -> bool:
        """Whether ``compiled`` survives a process boundary.  On cpu,
        custom-call targets (LAPACK FFI) are process-local function
        pointers — see the module docstring; everything else persists."""
        if self.backend != "cpu":
            return True
        try:
            hlo = compiled.as_text()
        except Exception:
            return False
        return "custom_call_target=" not in hlo

    def call(self, kind: str, parts: dict, fn: Callable,
             args: tuple) -> tuple[Any, bool]:
        """``get`` + invoke: returns ``(fn(*args), hit)``."""
        compiled, hit = self.get(kind, parts, fn, args)
        return compiled(*args), hit

    def summary(self) -> str:
        s = f"cache hits={self.hits} misses={self.misses}"
        if self.corrupt:
            s += f" corrupt={self.corrupt}"
        if self.unportable:
            s += f" unportable={self.unportable}"
        return s


class PersistedFunction:
    """A ``jax.jit``-shaped wrapper whose executables persist across
    processes: each distinct input shape signature resolves through the
    :class:`CompileCache` (``serve.engine`` wraps its decode step in one
    when the engine is given a cache)."""

    def __init__(self, cache: CompileCache, kind: str, parts: dict,
                 fn: Callable, donate_argnums: tuple = ()):
        self.cache = cache
        self.kind = kind
        self.parts = parts
        self.fn = fn
        self.donate_argnums = tuple(donate_argnums)

    def __call__(self, *args):
        compiled, _ = self.cache.get(self.kind, self.parts, self.fn, args,
                                     self.donate_argnums)
        return compiled(*args)
