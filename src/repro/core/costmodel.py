"""Flops/bytes/collective cost model for the bucket planner.

The planner historically picked a bucket's execution path by divisibility
alone (``n % k == 0`` => shard), which is a live performance bug: at toy
widths the sharded LoftQ bucket is ~2x *slower* than replicated because
its per-AltMin-round ``(L, m, m)`` psum dominates the saved compute
(``results/table10_init_cost.json`` ``loftq_sharded_row``).  This module
predicts wall time for each candidate path instead:

* **replicated** — one fused ``jit(vmap)`` dispatch on the local device,
* **sharded**    — one ``shard_map(vmap)`` dispatch over ``k`` devices:
  compute and memory traffic divide by ``k``, but the method's Gram-trick
  collectives (CLoQ: 1 psum/bucket, LoftQ: 1 psum per AltMin round) are
  added back,
* **sequential** — ``L`` per-layer dispatches; never faster under this
  model's linear terms, but selected when the stacked bucket working set
  exceeds the calibrated memory budget (the vmapped stack would thrash).

Inputs come from two places:

1. A one-time **per-host microbenchmark** (:func:`calibrate`), cached to
   disk (``REPRO_COSTCAL`` or ``~/.cache/repro/``): matmul throughput,
   streaming memory bandwidth, per-dispatch overhead, and psum
   latency/bandwidth.
2. **XLA's own FLOP/byte counts** for the bucket's traced program, via
   ``jit(...).lower(...).cost_analysis()`` — the same plumbing
   ``launch/dryrun.py`` reports per-step costs with
   (:func:`normalize_cost_analysis` is shared by both) — with a closed-form
   analytic estimate as fallback when XLA declines to count.

Decisions are **deterministic given a calibration file**: no timing runs
at plan time, so CI plans with a fake calibration table and gets
reproducible buckets.  Only a finite memory budget (a device that
reports its limit) adds inputs: each bucket's compiled footprint and
the bytes the device holds when the bucket is planned.

>>> cal = CostCalibration(flops_per_s=1e9, bytes_per_s=1e9,
...                       dispatch_s=1e-3, psum_latency_s=5e-3,
...                       psum_bytes_per_s=1e8, shard_efficiency=2.0)
>>> model = CostModel(cal, layer_costs=lambda s: (8.0 * s.m * s.m * s.n,
...                                               4.0 * s.m * s.n))
>>> model.decide_geometry("loftq", m=64, n=64, L=16, k=2)[0]
'replicated'
>>> model.decide_geometry("cloq", m=2048, n=2048, L=16, k=2)[0]
'sharded'
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
from typing import Callable

import jax

# execution paths a bucket can take (BucketSpec.exec_path values)
EXEC_PATHS = ("replicated", "sharded", "sequential")

# Gram-trick all-reduces per bucket when sharded: CLoQ does one (L, m, m)
# psum inside cloq_lowrank_local; LoftQ does one per AltMin round
# (loftq.svd_lowrank_topr, iters=5).  Everything else is column-local.
PSUM_ROUNDS = {"cloq": 1, "loftq": 5}

CAL_ENV = "REPRO_COSTCAL"


def normalize_cost_analysis(cost) -> dict:
    """Normalize ``cost_analysis()`` output to one flat dict.

    ``lowered.cost_analysis()`` returns a dict; ``compiled.cost_analysis()``
    returns a list of per-computation dicts on some backends/versions, or
    ``None`` when the backend declines.  This is the single shared shim —
    ``launch/dryrun.py`` reports through it and :class:`CostModel` reads
    FLOP/byte counts through it.

    >>> normalize_cost_analysis([{"flops": 2.0}])
    {'flops': 2.0}
    >>> normalize_cost_analysis(None)
    {}
    """
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost) if cost else {}


@dataclasses.dataclass(frozen=True)
class CostCalibration:
    """Per-host machine constants the planner's cost model consumes.

    Produced by :func:`calibrate` (measured once, cached to disk) or
    loaded from a JSON file — tests write fake tables so decisions are
    deterministic with no timing in CI."""
    flops_per_s: float            # dense matmul throughput
    bytes_per_s: float            # streaming memory bandwidth
    dispatch_s: float             # fixed per-dispatch overhead
    psum_latency_s: float         # fixed latency of one all-reduce
    psum_bytes_per_s: float       # all-reduce payload bandwidth
    # measured aggregate speedup of a column-sharded matmul over the same
    # matmul on one device: ~k on real k-chip hardware, ~1 on fake devices
    # sharing one host's cores (sharding then buys nothing but collectives)
    shard_efficiency: float = 1.0
    memory_budget_bytes: float = math.inf   # stacked-bucket working set cap
    backend: str = "cpu"
    jax_version: str = ""
    n_devices: int = 1
    source: str = "default"       # "measured" | "file" | "default"

    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        payload = dataclasses.asdict(self)
        # JSON has no inf; encode the unbounded budget as null
        if math.isinf(payload["memory_budget_bytes"]):
            payload["memory_budget_bytes"] = None
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(
            os.path.abspath(path)), suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path: str) -> "CostCalibration":
        with open(path) as f:
            payload = json.load(f)
        if payload.get("memory_budget_bytes") is None:
            payload["memory_budget_bytes"] = math.inf
        known = {f.name for f in dataclasses.fields(cls)}
        payload = {k: v for k, v in payload.items() if k in known}
        payload["source"] = "file"
        return cls(**payload)


def default_calibration_path() -> str:
    """Disk location of the one-time calibration: ``$REPRO_COSTCAL`` when
    set, else a per-(backend, jax-version) file under ``~/.cache/repro``."""
    env = os.environ.get(CAL_ENV)
    if env:
        return env
    cache = os.environ.get("XDG_CACHE_HOME",
                           os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(cache, "repro",
                        f"costcal-{jax.default_backend()}-"
                        f"{jax.__version__}.json")


def load_calibration(path: str | None = None) -> CostCalibration | None:
    """Load a calibration file if one exists; ``None`` otherwise (callers
    then fall back to the divisibility-only planner)."""
    path = path or default_calibration_path()
    try:
        return CostCalibration.load(path)
    except (FileNotFoundError, json.JSONDecodeError, TypeError, ValueError):
        return None


def _best_of(thunk, reps: int = 3) -> float:
    """Best wall time of ``thunk()`` over ``reps`` runs.  The thunk owns
    device synchronisation — callers pass closures that end in
    ``jax.block_until_ready`` so the delta measures compute, not
    dispatch."""
    import time
    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        thunk()
        best = min(best, time.perf_counter() - t0)
    return best


def device_memory_budget() -> float:
    """Bytes the first local device may hold (``bytes_limit`` of its
    ``memory_stats()``), or ``inf`` where the backend reports no limit
    (the CPU).  The budget the memory gate of :meth:`CostModel.decide`
    holds a stacked bucket to."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return float(limit) if limit else math.inf


def device_bytes_in_use() -> float:
    """Bytes the first local device holds now (0 where the backend
    reports none): arrays that stay resident while a bucket runs, such as
    the model being quantized."""
    stats = jax.devices()[0].memory_stats() or {}
    return float(stats.get("bytes_in_use", 0))


def calibrate(mesh=None, *, path: str | None = None,
              force: bool = False) -> CostCalibration:
    """One-time per-host microbenchmark; cached to ``path`` (default
    :func:`default_calibration_path`) so every later process loads the
    table instead of re-timing.

    Measures: dense matmul throughput, streaming memory bandwidth,
    per-dispatch overhead, and (when ``mesh`` spans >1 device) psum
    latency + bandwidth solved from two payload sizes.  The memory budget
    is the device's own limit (:func:`device_memory_budget`).  Wall cost is a
    few hundred ms; ``force=True`` re-measures."""
    import jax.numpy as jnp

    path = path or default_calibration_path()
    if not force:
        cal = load_calibration(path)
        if cal is not None:
            return cal

    key, wkey = jax.random.split(jax.random.PRNGKey(0))
    # matmul throughput
    a = jax.random.normal(key, (1024, 1024), jnp.float32)
    mm = jax.jit(lambda x: x @ x)
    jax.block_until_ready(mm(a))
    t_mm = _best_of(lambda: jax.block_until_ready(mm(a)))
    flops_per_s = 2 * 1024 ** 3 / max(t_mm, 1e-9)
    # streaming bandwidth (read + write one 64 MiB buffer)
    big = jnp.zeros((16 * 1024 * 1024,), jnp.float32)
    st = jax.jit(lambda x: x + 1.0)
    jax.block_until_ready(st(big))
    t_st = _best_of(lambda: jax.block_until_ready(st(big)))
    bytes_per_s = 2 * big.size * 4 / max(t_st, 1e-9)
    # per-dispatch overhead (tiny op, fully dispatch-bound)
    tiny = jnp.zeros((1,), jnp.float32)
    jax.block_until_ready(st(tiny))
    dispatch_s = _best_of(lambda: jax.block_until_ready(st(tiny)), reps=5)

    psum_latency_s = dispatch_s
    psum_bytes_per_s = bytes_per_s
    shard_efficiency = 1.0
    n_devices = 1
    if mesh is not None and math.prod(mesh.shape.values()) > 1:
        from jax.sharding import PartitionSpec as P

        axis = mesh.axis_names[0]
        n_devices = math.prod(mesh.shape.values())

        def timed_psum(side: int) -> float:
            x = jnp.zeros((side, side), jnp.float32)
            fn = jax.jit(jax.shard_map(
                lambda v: jax.lax.psum(v, axis), mesh=mesh,
                in_specs=P(None, None), out_specs=P(None, None)))
            jax.block_until_ready(fn(x))
            return _best_of(lambda: jax.block_until_ready(fn(x)))

        t_small, small = timed_psum(64), 64 * 64 * 4
        t_large, large = timed_psum(1024), 1024 * 1024 * 4
        psum_latency_s = max(t_small - small * (t_large - t_small)
                             / max(large - small, 1), 1e-9)
        psum_bytes_per_s = max((large - small)
                               / max(t_large - t_small, 1e-9), 1.0)

        # aggregate speedup of column-sharding a matmul over this mesh:
        # ~k when the shards are real chips, ~1 when they share one host
        w = jax.random.normal(wkey, (1024, 2048), jnp.float32)
        sh = jax.jit(jax.shard_map(lambda v: v @ v.T @ v, mesh=mesh,
                                   in_specs=P(None, axis),
                                   out_specs=P(None, axis)))
        rep = jax.jit(lambda v: v @ v.T @ v)
        jax.block_until_ready(sh(w))
        jax.block_until_ready(rep(w))
        t_sh = _best_of(lambda: jax.block_until_ready(sh(w)))
        t_rep = _best_of(lambda: jax.block_until_ready(rep(w)))
        shard_efficiency = min(max(t_rep / max(t_sh, 1e-9), 1e-2),
                               float(n_devices))

    cal = CostCalibration(
        flops_per_s=flops_per_s, bytes_per_s=bytes_per_s,
        dispatch_s=dispatch_s, psum_latency_s=psum_latency_s,
        psum_bytes_per_s=psum_bytes_per_s,
        shard_efficiency=shard_efficiency,
        memory_budget_bytes=device_memory_budget(),
        backend=jax.default_backend(), jax_version=jax.__version__,
        n_devices=n_devices, source="measured")
    try:
        cal.save(path)
    except OSError:
        pass                      # read-only cache dir: stay in-memory
    return cal


def analytic_layer_costs(method: str, m: int, n: int, rank: int,
                         has_gram: bool) -> tuple[float, float]:
    """Closed-form per-layer FLOP/byte estimate — the fallback when XLA's
    ``cost_analysis()`` declines to count (e.g. unlowered custom calls).
    Deliberately coarse: the OPTQ column sweep is ~``m^2 n`` MACs, the
    eigh/SVD factorizations ~``m^3``, LoRA products ~``m n r``."""
    flops = 8.0 * m * m * n + 30.0 * m ** 3 + 6.0 * m * n * rank
    bytes_ = 4.0 * (3 * m * n + (2 * m * m if has_gram else 0)
                    + 2 * (m + n) * rank)
    return flops, bytes_


def _layer_args(spec, L: int | None = None):
    """Abstract ``(W, H or None, key)`` of one layer of ``spec``, or of
    ``L`` stacked layers."""
    import jax.numpy as jnp
    lead = () if L is None else (L,)
    W = jax.ShapeDtypeStruct(lead + (spec.m, spec.n), jnp.float32)
    H = (jax.ShapeDtypeStruct(lead + (spec.m, spec.m), jnp.float32)
         if spec.has_gram else None)
    return W, H, jax.ShapeDtypeStruct(lead + (2,), jnp.uint32)


def compiled_bucket_footprint(spec, L: int) -> float | None:
    """Device bytes the fused ``L``-layer bucket of ``spec`` holds at
    once: the arguments, outputs and temporaries of its stacked program,
    compiled for the default backend (``memory_analysis()``).  It is the
    very program :func:`~repro.core.batched.run_bucket` runs if the
    bucket fuses, so a persistent compile cache serves that second
    compile.  ``inf`` where the compiler finds the program does not fit
    the device; ``None`` for a geometry-only spec (nothing to lower) or a
    backend that reports no analysis."""
    from repro.core.batched import BucketSpec, requeue_spec, run_bucket

    if not isinstance(spec, BucketSpec):
        return None
    Ws, Hs, keys = _layer_args(spec, L)
    try:
        compiled = run_bucket.lower(Ws, Hs, keys,
                                    spec=requeue_spec(spec)).compile()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" in str(e):
            return math.inf
        raise
    mem = compiled.memory_analysis()
    if mem is None:
        return None
    return float(mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def xla_layer_costs(spec) -> tuple[float, float]:
    """Per-layer FLOP/byte counts from XLA's lowered ``cost_analysis()``
    of the bucket's actual traced core (no compile, no execution) — the
    same counter ``launch/dryrun.py`` reports, read through
    :func:`normalize_cost_analysis`."""
    from repro.core.batched import quantize_single

    W, H, key = _layer_args(spec)
    if spec.has_gram:
        lowered = jax.jit(
            lambda w, h, k: quantize_single(w, h, k, spec)).lower(W, H, key)
    else:
        lowered = jax.jit(
            lambda w, k: quantize_single(w, None, k, spec)).lower(W, key)
    cost = normalize_cost_analysis(lowered.cost_analysis())
    flops = float(cost.get("flops", 0.0) or 0.0)
    bytes_ = float(cost.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0:
        return analytic_layer_costs(spec.method, spec.m, spec.n,
                                    spec.rank, spec.has_gram)
    if bytes_ <= 0.0:
        bytes_ = analytic_layer_costs(spec.method, spec.m, spec.n,
                                      spec.rank, spec.has_gram)[1]
    return flops, bytes_


class CostModel:
    """Predicted-time path chooser for one bucket.

    ``layer_costs`` maps a :class:`~repro.core.batched.BucketSpec`-like
    object (needs ``.m .n .method .rank .has_gram``) to per-layer
    ``(flops, bytes)``; defaults to :func:`xla_layer_costs` with the
    analytic fallback.  Under a finite memory budget the gate also reads
    :meth:`bucket_footprint`.  All decisions are pure arithmetic over the
    calibration table — no timing, deterministic."""

    def __init__(self, calibration: CostCalibration, *,
                 layer_costs: Callable | None = None):
        self.calibration = calibration
        self._layer_costs = layer_costs or xla_layer_costs
        self._cost_cache: dict = {}
        self._footprint_cache: dict = {}

    @classmethod
    def coerce(cls, obj) -> "CostModel | None":
        """Accept a CostModel, a CostCalibration, a calibration-file path,
        or ``None`` (=> no cost model, divisibility-only planner)."""
        if obj is None or isinstance(obj, cls):
            return obj
        if isinstance(obj, CostCalibration):
            return cls(obj)
        if isinstance(obj, str):
            cal = load_calibration(obj)
            if cal is None:
                raise FileNotFoundError(
                    f"no cost calibration at {obj!r} — run "
                    "repro.core.costmodel.calibrate(path=...) once")
            return cls(cal)
        raise TypeError(f"cannot coerce {type(obj).__name__} to CostModel")

    @staticmethod
    def _key(spec) -> tuple:
        return (spec.method, spec.m, spec.n, spec.rank, spec.has_gram,
                getattr(spec, "bits", None), getattr(spec, "group_size", None))

    def layer_costs(self, spec) -> tuple[float, float]:
        k = self._key(spec)
        if k not in self._cost_cache:
            self._cost_cache[k] = self._layer_costs(spec)
        return self._cost_cache[k]

    def bucket_footprint(self, spec, L: int) -> float:
        """:func:`compiled_bucket_footprint`, or ``L`` times the layer's
        ``bytes`` where that gives ``None``."""
        k = self._key(spec) + (L,)
        if k not in self._footprint_cache:
            fp = compiled_bucket_footprint(spec, L)
            self._footprint_cache[k] = (L * self.layer_costs(spec)[1]
                                        if fp is None else fp)
        return self._footprint_cache[k]

    def path_times(self, spec, L: int, k: int) -> dict:
        """Predicted seconds per candidate path for an ``L``-layer bucket
        on a ``k``-device axis.  ``sharded`` is present only when the
        planner's divisibility gate allows it (``k > 1`` and ``n % k ==
        0``).

        The sharded estimate evaluates the layer cost **at the shard
        width** ``n / k`` rather than dividing the full cost by ``k`` —
        the m-dimension work (``eigh``, Gram root, the per-shard
        Gram-trick factorizations) is replicated on every shard and does
        not divide, which is exactly why small-width sharding loses."""
        cal = self.calibration
        f, by = self.layer_costs(spec)
        compute = L * f / cal.flops_per_s + L * by / cal.bytes_per_s
        times = {"replicated": compute + cal.dispatch_s,
                 "sequential": compute + L * cal.dispatch_s}
        if k > 1 and spec.n % k == 0:
            local = dataclasses.replace(spec, n=spec.n // k)
            f_l, by_l = self.layer_costs(local)
            # each shard's device rate: flops_per_s scaled by the measured
            # shard efficiency spread over k shards (on fake same-host
            # devices efficiency ~ 1, so k shards run at 1/k speed each)
            rate = max(cal.shard_efficiency, 1e-3) / k
            local_compute = (L * f_l / (cal.flops_per_s * rate)
                             + L * by_l / (cal.bytes_per_s * rate))
            rounds = PSUM_ROUNDS.get(spec.method, 0)
            psum_payload = rounds * L * spec.m * spec.m * 4.0
            times["sharded"] = (local_compute + cal.dispatch_s
                                + rounds * cal.psum_latency_s
                                + psum_payload / cal.psum_bytes_per_s)
        return times

    def decide(self, spec, L: int, k: int) -> tuple[str, int]:
        """Choose ``(exec_path, n_shards)`` for one bucket from predicted
        time.  The stacked working set (:meth:`bucket_footprint`) is
        gated first against the calibration's memory budget less what the
        device already holds — a bucket that cannot hold ``L`` stacked
        layers runs sequentially regardless of predicted speed."""
        budget = self.calibration.memory_budget_bytes
        if math.isfinite(budget) and self.bucket_footprint(spec, L) > (
                budget - device_bytes_in_use()):
            return "sequential", 1
        times = self.path_times(spec, L, k)
        best = min(EXEC_PATHS, key=lambda p: times.get(p, math.inf))
        return best, (k if best == "sharded" else 1)

    def decide_geometry(self, method: str, *, m: int, n: int, L: int,
                        k: int, rank: int = 16,
                        has_gram: bool | None = None) -> tuple[str, int]:
        """:meth:`decide` from raw geometry (no BucketSpec needed) — the
        entry point manifest restore uses, and the doctest surface."""
        geo = _Geometry(m=m, n=n, method=method, rank=rank,
                        has_gram=(method in ("cloq", "gptq")
                                  if has_gram is None else has_gram))
        return self.decide(geo, L, k)

    def explain(self, spec, L: int, k: int) -> str:
        times = self.path_times(spec, L, k)
        parts = ", ".join(f"{p}={times[p] * 1e3:.2f}ms"
                          for p in EXEC_PATHS if p in times)
        path, shards = self.decide(spec, L, k)
        return (f"{spec.method} {spec.m}x{spec.n} x{L} on k={k}: {parts} "
                f"-> {path}" + (f" x{shards}" if shards > 1 else ""))


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """Minimal spec-shaped record for :meth:`CostModel.decide_geometry`
    (keeps the cost model importable without the planner)."""
    m: int
    n: int
    method: str
    rank: int
    has_gram: bool
    bits: int | None = None
    group_size: int | None = None
