"""Table 10 analog: initialization wall-time, LoftQ vs CLoQ (vs distributed
CLoQ path), at realistic layer dims.  No backprop in either — the paper's
cost claim is SVD-count, which we measure directly.

Extended with the batched quantization engine (``repro.core.batched``): for
a bucket of N same-shape layers — the MoE-expert / attention-projection
regime where shape-bucketing actually fires — the per-layer sequential
engine (a Python loop of ``pipeline._quantize_one`` over the MagR→OPTQ→CLoQ
stack) is timed against one ``jit(vmap)`` dispatch over the stacked bucket
(``batched_s``).  Wall-times are best-of-``REPS`` to tame shared-machine
noise; the ``speedup`` column is what ``quantize_model`` gains on models
whose linears bucket well.  Large single layers amortize poorly on a
serial-BLAS host — those go to the sharded path instead (DESIGN.md §3).

The ``sharded_rows`` section measures the *distributed* batched engine: on
a multi-device mesh (a subprocess with fake CPU devices here), a bucket of
N layers run as ONE fused shard_map(vmap) program
(``run_bucket_sharded``) vs the per-layer sharded status quo (a Python
loop of ``optq_quantize_sharded`` + ``cloq_init_sharded`` dispatches).
``loftq_sharded_row`` exercises the calibrated cost-model planner
(``repro.core.costmodel``) on its historical misprediction — the toy-width
LoftQ bucket that divisibility planning sharded at a 2.3x slowdown — and
reports the chosen path's time against the worst path's.
``cold_start_row`` measures the persisted compile cache
(``repro.core.compile_cache``): the first quantize call of a fresh
process against an empty vs populated cache directory."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS, FAST
from repro.core.batched import LayerTask, plan_buckets, quantize_layer_batch
from repro.core.cloq import cloq_init, regularize_gram
from repro.core.loftq import loftq_init
from repro.core.magr import magr_preprocess
from repro.core.optq import optq_quantize
from repro.core.pipeline import _quantize_one
from repro.core.quantizer import QuantConfig
from repro.core.recipe import QuantRecipe, SiteRule
from repro.models.modules import QSpec

REPS = 3               # best-of reps for the engine comparison

# (m, n, layers-per-bucket): the many-same-shape-layers regime
BUCKETS = [(64, 64, 16), (128, 128, 16)] if FAST else \
    [(64, 64, 16), (128, 128, 16), (256, 256, 8)]


def _cloq_stack(W, H, qcfg, rank):
    Wp = magr_preprocess(W, H, alpha=0.001 * jnp.trace(H) / W.shape[0])
    Qd, _, _, _ = optq_quantize(Wp, H, qcfg)
    return cloq_init(regularize_gram(H), W - Qd, rank)


def _best_of(f, reps=REPS) -> float:
    ts = []
    for _ in range(reps):
        t0 = time.time()
        f()
        ts.append(time.time() - t0)
    return min(ts)


def _bucket_row(m: int, n: int, n_layers: int, qspec: QSpec, rng) -> dict:
    Ws = [jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
          for _ in range(n_layers)]
    Hs = []
    for _ in range(n_layers):
        X = rng.normal(size=(1024, m)).astype(np.float32)
        Hs.append(jnp.asarray(X.T @ X))
    keys = jax.random.split(jax.random.PRNGKey(0), n_layers)
    tasks = [LayerTask(f"l{i}", None, Wi, Hi, ki)
             for i, (Wi, Hi, ki) in enumerate(zip(Ws, Hs, keys))]

    def seq():
        for t in tasks:
            out = _quantize_one(t.W, t.H, qspec, "cloq", t.key)
        jax.block_until_ready(out["lora_a"])

    def bat():
        outs = quantize_layer_batch(tasks, qspec, "cloq")
        jax.block_until_ready(outs[-1]["lora_a"])

    seq()
    bat()          # compile both executables before timing
    t_seq, t_bat = _best_of(seq), _best_of(bat)
    return {"m": m, "n": n, "n_layers": n_layers,
            "sequential_s": round(t_seq, 3), "batched_s": round(t_bat, 3),
            "speedup": round(t_seq / t_bat, 2)}


def _health_guard_row(rng, m: int = 256, n: int = 256,
                      n_layers: int = 8) -> dict:
    """Health-guard overhead on a clean bucket: the per-bucket check is one
    ``jit(vmap)`` finiteness + RTN-roundtrip pass — O(m n) per slice against
    the sweep's O(m^2 n) — so a healthy run should pay well under 5% for
    the guarantee that a bad Gram degrades instead of shipping NaNs.
    Measured at a realistic width (the relative cost only shrinks as m
    grows) with extra reps: single-shot timings on this 2-core host swing
    more than the quantity being measured."""
    from repro.core.health import HealthPolicy, HealthReport

    qspec = QSpec(bits=2, group_size=64, rank=16)
    Ws = [jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
          for _ in range(n_layers)]
    Hs = []
    for _ in range(n_layers):
        X = rng.normal(size=(1024, m)).astype(np.float32)
        Hs.append(jnp.asarray(X.T @ X))
    keys = jax.random.split(jax.random.PRNGKey(0), n_layers)
    tasks = [LayerTask(f"l{i}", None, Wi, Hi, ki)
             for i, (Wi, Hi, ki) in enumerate(zip(Ws, Hs, keys))]

    def unguarded():
        outs = quantize_layer_batch(tasks, qspec, "cloq")
        jax.block_until_ready(outs[-1]["lora_a"])

    def guarded():
        outs = quantize_layer_batch(tasks, qspec, "cloq",
                                    policy=HealthPolicy(),
                                    report=HealthReport())
        jax.block_until_ready(outs[-1]["lora_a"])

    unguarded()
    guarded()      # compile both (incl. the check executable) before timing
    t_off, t_on = _best_of(unguarded, reps=5), _best_of(guarded, reps=5)
    return {"m": m, "n": n, "n_layers": n_layers,
            "unguarded_s": round(t_off, 3), "guarded_s": round(t_on, 3),
            "overhead_pct": round((t_on - t_off) / t_off * 100, 2)}


def _obs_overhead_row(rng, m: int = 256, n: int = 256,
                      n_layers: int = 8) -> dict:
    """Observability overhead on a quantize bucket: the same
    ``quantize_layer_batch`` call with the span tracer disabled (the
    default — every ``obs.trace.span`` returns the shared no-op span)
    vs enabled (every span recorded and entering its profiler
    annotation).
    ``check_bench.py`` gates ``overhead_pct`` — tracing must stay cheap
    enough to leave on for any diagnostic run.  ``noop_span_ns`` is the
    per-call cost of a disabled span, the price every instrumented
    callsite pays in ordinary (untraced) runs."""
    from repro.obs import trace as obs_trace

    qspec = QSpec(bits=2, group_size=64, rank=16)
    Ws = [jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
          for _ in range(n_layers)]
    Hs = []
    for _ in range(n_layers):
        X = rng.normal(size=(1024, m)).astype(np.float32)
        Hs.append(jnp.asarray(X.T @ X))
    keys = jax.random.split(jax.random.PRNGKey(0), n_layers)
    tasks = [LayerTask(f"l{i}", None, Wi, Hi, ki)
             for i, (Wi, Hi, ki) in enumerate(zip(Ws, Hs, keys))]

    def quant():
        outs = quantize_layer_batch(tasks, qspec, "cloq")
        jax.block_until_ready(outs[-1]["lora_a"])

    quant()                                # compile before timing
    obs_trace.disable()
    t_off = _best_of(quant, reps=5)
    obs_trace.enable()
    try:
        t_on = _best_of(quant, reps=5)
    finally:
        obs_trace.disable()

    # per-call cost of a disabled span (amortized over a tight loop)
    reps = 20_000
    t0 = time.perf_counter()
    for _ in range(reps):
        with obs_trace.span("noop"):
            pass
    noop_ns = (time.perf_counter() - t0) / reps * 1e9
    return {"m": m, "n": n, "n_layers": n_layers,
            "untraced_s": round(t_off, 3), "traced_s": round(t_on, 3),
            "overhead_pct": round((t_on - t_off) / t_off * 100, 2),
            "noop_span_ns": round(noop_ns, 1)}


def _mixed_recipe_row(rng, n_layers: int = 8) -> dict:
    """Heterogeneous-plan cost: one QuantRecipe resolving 2-bit/r16 CLoQ
    MLP sites next to 4-bit/r8 CLoQ attention sites, executed as two
    buckets by the same batched engine vs the per-site sequential loop.
    Tracks that mixed plans cost bucket-engine time, not per-layer time."""
    recipe = QuantRecipe(
        rules=(SiteRule("*.mlp.*", bits=2, rank=16),
               SiteRule("*.attn.*", bits=4, rank=8)),
        method="cloq", qspec=QSpec(bits=4, group_size=64, rank=8))
    paths = ([f"blocks.{i}.mlp.up" for i in range(n_layers)] +
             [f"blocks.{i}.attn.q" for i in range(n_layers)])
    sites = recipe.resolve(paths)
    dims = {"mlp": (64, 128), "attn": (64, 64)}
    keys = jax.random.split(jax.random.PRNGKey(0), len(paths))
    tasks = []
    for p, k in zip(paths, keys):
        m, n = dims["mlp" if ".mlp." in p else "attn"]
        W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
        X = rng.normal(size=(1024, m)).astype(np.float32)
        tasks.append(LayerTask(p, None, W, jnp.asarray(X.T @ X), k,
                               site=sites[p]))
    n_buckets = len(plan_buckets(tasks))

    def seq():
        for t in tasks:
            out = _quantize_one(t.W, t.H, t.site.qspec, t.site.method, t.key)
        jax.block_until_ready(out["lora_a"])

    def mixed():
        outs = quantize_layer_batch(tasks)
        jax.block_until_ready(outs[-1]["lora_a"])

    seq()
    mixed()        # compile both before timing
    t_seq, t_mix = _best_of(seq), _best_of(mixed)
    return {"n_layers": len(tasks), "n_buckets": n_buckets,
            "rules": ["mlp: cloq/2b/r16 64x128", "attn: cloq/4b/r8 64x64"],
            "sequential_s": round(t_seq, 3), "mixed_batched_s": round(t_mix, 3),
            "speedup": round(t_seq / t_mix, 2)}


def _auto_alloc_row(rng, n_layers: int = 8) -> dict:
    """Bit-allocation sweep cost + plan quality.

    Wall-clock: the vmapped sensitivity sweep (one fused eval bucket per
    ``(shape x candidate)`` slab, ``batched.evaluate_layer_batch``) vs the
    per-candidate sequential loop (one ``_quantize_one`` + proxy-error
    computation per site x candidate).  Quality: total proxy error of the
    auto-allocated plan vs the uniform-bit plan at the SAME byte budget
    (budget = the uniform plan's exact bytes)."""
    from repro.core.allocate import (budget_curve, default_grid, emit_recipe,
                                     group_sites, site_bytes, solve_budget,
                                     sweep_sensitivity)
    from repro.core.batched import evaluate_layer_batch
    from repro.core.quantizer import dequantize_int, unpack_codes
    from repro.core.recipe import SiteSpec

    base = QSpec(bits=4, group_size=16, rank=8)
    grid = default_grid(bits=(2, 3, 4), methods=("cloq",), ranks=(0, 8))
    dims = {"mlp": (64, 128), "attn": (64, 64)}
    paths = ([f"blocks.{i}.mlp.up" for i in range(n_layers)] +
             [f"blocks.{i}.attn.q" for i in range(n_layers)])
    keys = jax.random.split(jax.random.PRNGKey(0), len(paths))
    tasks, meta = [], {}
    for p, k in zip(paths, keys):
        m, n = dims["mlp" if ".mlp." in p else "attn"]
        W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
        X = rng.normal(size=(1024, m)).astype(np.float32)
        tasks.append(LayerTask(p, None, W, jnp.asarray(X.T @ X), k))
        meta[p] = (m, n, 1, 1)

    def groups():
        return group_sites(meta, ("blocks",))

    def vmapped():
        return sweep_sensitivity(tasks, groups(), grid, base, jnp.float32)

    def per_candidate():
        errs = []
        for t in tasks:
            for method, bits, rank in grid:
                q = QSpec(bits=bits, group_size=16, rank=rank, method=method)
                out = _quantize_one(t.W, t.H, q, method, t.key)
                codes = unpack_codes(out["qcodes"], bits, t.W.shape[0])
                Qd = dequantize_int(codes, out["scales"], out["zeros"], 16)
                E = t.W - Qd - out["lora_a"] @ out["lora_b"].T
                errs.append(jnp.einsum("ij,ik,kj->", E, t.H, E))
        jax.block_until_ready(errs[-1])
        return errs

    swept = vmapped()
    per_candidate()                # compile both before timing
    t_vmap, t_seq = _best_of(vmapped), _best_of(per_candidate)

    # plan quality at equal budget: uniform INT3/r8 vs the auto allocation
    uni = SiteSpec("cloq", QSpec(bits=3, group_size=16, rank=8))
    budget = sum(len(g.paths) * site_bytes(g.m, g.n, uni, jnp.float32)
                 for g in swept)
    uni_err = sum(
        e for t, e in zip(
            tasks, evaluate_layer_batch(
                [LayerTask(t.path, None, t.W, t.H, t.key, site=uni)
                 for t in tasks])))
    choice = solve_budget(swept, budget)
    auto_bytes = sum(g.bytes_[c] for g, c in zip(swept, choice))
    auto_err = sum(g.errors[c] for g, c in zip(swept, choice))
    recipe = emit_recipe(swept, choice, base)
    return {"n_sites": len(tasks), "n_candidates": len(grid),
            "sequential_sweep_s": round(t_seq, 3),
            "vmapped_sweep_s": round(t_vmap, 3),
            "speedup": round(t_seq / t_vmap, 2),
            "budget_bytes": budget,
            "uniform_int3_err": round(float(uni_err), 3),
            "auto_bytes": auto_bytes,
            "auto_err": round(float(auto_err), 3),
            "auto_beats_uniform": bool(auto_err < uni_err),
            "n_rules": len(recipe.rules),
            "curve_points": len(budget_curve(swept))}


# Distributed-engine comparison, run in a subprocess so we control the fake
# device count regardless of how the parent process initialized jax.
_SHARDED_SNIPPET = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from repro.core.batched import (LayerTask, per_layer_sharded_dispatch,
                                plan_buckets, quantize_layer_batch)
from repro.launch.mesh import make_model_mesh
from repro.models.modules import QSpec

m, n, L, reps = {m}, {n}, {L}, {reps}
rng = np.random.default_rng(0)
mesh = make_model_mesh()
qspec = QSpec(bits=2, group_size=64, rank=16)
Ws = [jnp.asarray(rng.normal(size=(m, n)), jnp.float32) for _ in range(L)]
Hs = []
for _ in range(L):
    X = rng.normal(size=(1024, m)).astype(np.float32)
    Hs.append(jnp.asarray(X.T @ X))
keys = jax.random.split(jax.random.PRNGKey(0), L)
tasks = [LayerTask(f"l{{i}}", None, Wi, Hi, ki)
         for i, (Wi, Hi, ki) in enumerate(zip(Ws, Hs, keys))]
spec = next(iter(plan_buckets(tasks, qspec, "cloq", mesh=mesh)))

def per_layer():
    outs = per_layer_sharded_dispatch(tasks, qspec, mesh)
    jax.block_until_ready(outs[-1][0])

def fused():
    outs = quantize_layer_batch(tasks, qspec, "cloq", mesh=mesh)
    jax.block_until_ready(outs[-1]["lora_a"])

per_layer(); fused()                       # compile before timing
def best(f):
    ts = []
    for _ in range(reps):
        t0 = time.time(); f(); ts.append(time.time() - t0)
    return min(ts)
t_layer, t_fused = best(per_layer), best(fused)
print("RESULT " + json.dumps({{
    "m": m, "n": n, "n_layers": L, "n_devices": len(jax.devices()),
    "n_shards": spec.n_shards,
    "per_layer_sharded_s": round(t_layer, 3),
    "sharded_batched_s": round(t_fused, 3),
    "speedup": round(t_layer / t_fused, 2)}}))
"""


# LoftQ at toy widths is the planner's historical soft spot: divisibility
# said "shard", reality said "replicate" (speedup 0.43x in the pinned
# baseline).  The cost-model planner calibrates this host, predicts both
# paths, and picks the cheaper one — so the row now times BOTH paths and
# reports chosen vs worst: ``speedup >= 1.0`` iff the model chose right,
# which tests/test_perf_levers.py gates on.
_LOFTQ_SHARDED_SNIPPET = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from repro.core.batched import LayerTask, plan_buckets, quantize_layer_batch
from repro.core.costmodel import CostModel, calibrate
from repro.launch.mesh import make_model_mesh
from repro.models.modules import QSpec

m, n, L, reps = {m}, {n}, {L}, {reps}
rng = np.random.default_rng(0)
mesh = make_model_mesh()
cal = calibrate(mesh, path="/tmp/repro_costcal_bench.json", force=True)
cm = CostModel(cal)
qspec = QSpec(bits=2, group_size=64, rank=16)
Ws = [jnp.asarray(rng.normal(size=(m, n)), jnp.float32) for _ in range(L)]
keys = jax.random.split(jax.random.PRNGKey(0), L)
tasks = [LayerTask(f"l{{i}}", None, Wi, None, ki)
         for i, (Wi, ki) in enumerate(zip(Ws, keys))]
spec = next(iter(plan_buckets(tasks, qspec, "loftq", mesh=mesh,
                              cost_model=cm)))

def replicated():
    outs = quantize_layer_batch(tasks, qspec, "loftq")
    jax.block_until_ready(outs[-1]["lora_a"])

def sharded():
    outs = quantize_layer_batch(tasks, qspec, "loftq", mesh=mesh)
    jax.block_until_ready(outs[-1]["lora_a"])

replicated(); sharded()                    # compile before timing
def best(f):
    ts = []
    for _ in range(reps):
        t0 = time.time(); f(); ts.append(time.time() - t0)
    return min(ts)
t_rep, t_shard = best(replicated), best(sharded)
times = {{"replicated": t_rep, "sharded": t_shard}}
chosen = "sharded" if spec.n_shards > 1 else "replicated"
worst = max(times, key=times.get)
print("RESULT " + json.dumps({{
    "method": "loftq", "m": m, "n": n, "n_layers": L,
    "n_devices": len(jax.devices()), "n_shards": spec.n_shards,
    "chosen_path": chosen,
    "replicated_batched_s": round(t_rep, 3),
    "sharded_batched_s": round(t_shard, 3),
    "chosen_s": round(times[chosen], 3),
    "worst_s": round(times[worst], 3),
    "speedup": round(times[worst] / times[chosen], 3)}}))
"""


# Cold-start cost of the persisted compile cache: the FIRST quantize call
# of a fresh process — trace + XLA compile against an empty cache dir, one
# disk deserialize against a populated one.  rtn is the bucket whose
# executable is custom-call-free, the kind that persists on every backend
# including this cpu host (cloq/loftq executables carry LAPACK custom
# calls and persist only on accelerator backends — repro.core.compile_cache
# keeps them in-process here, correctly).
_COLDSTART_SNIPPET = """
import json, os, time
import jax, jax.numpy as jnp, numpy as np
from repro.core.batched import LayerTask, quantize_layer_batch
from repro.core.compile_cache import CompileCache
from repro.models.modules import QSpec

m, n, L = {m}, {n}, {L}
rng = np.random.default_rng(0)
qspec = QSpec(bits=4, group_size=64, rank=16, method="rtn")
Ws = [jnp.asarray(rng.normal(size=(m, n)), jnp.float32) for _ in range(L)]
keys = jax.random.split(jax.random.PRNGKey(0), L)
tasks = [LayerTask(f"l{{i}}", None, Wi, None, ki)
         for i, (Wi, ki) in enumerate(zip(Ws, keys))]
cache = CompileCache(os.environ["REPRO_BENCH_CACHE"])
jax.block_until_ready(Ws[-1])
t0 = time.time()
outs = quantize_layer_batch(tasks, qspec, "rtn", compile_cache=cache)
jax.block_until_ready(jax.tree.leaves(outs[-1])[0])
t = time.time() - t0
print("RESULT " + json.dumps({{
    "first_call_s": round(t, 3), "hits": cache.hits,
    "misses": cache.misses}}))
"""


def _run_cpu_child(code: str, env: dict) -> dict:
    """Run a benchmark snippet in a fresh process on the CPU and return
    its ``RESULT`` line.  The child sets ``JAX_PLATFORMS=cpu`` so it never
    competes with this process for an accelerator; a failing child
    raises."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(env, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(src) + os.pathsep +
               env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"benchmark child failed (exit "
                           f"{proc.returncode}):\n{proc.stderr[-4000:]}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def _cold_start_row(m: int = 512, n: int = 512, n_layers: int = 8) -> dict:
    """Run the cold-start snippet in two fresh subprocesses sharing one
    cache directory: run 1 populates it (miss), run 2 deserializes
    (hit)."""
    import tempfile
    code = textwrap.dedent(_COLDSTART_SNIPPET).format(m=m, n=n, L=n_layers)
    with tempfile.TemporaryDirectory() as d:
        env = dict(os.environ, REPRO_BENCH_CACHE=d)
        cold, warm = [_run_cpu_child(code, env) for _ in range(2)]
    return {"method": "rtn", "m": m, "n": n, "n_layers": n_layers,
            "cold_first_call_s": cold["first_call_s"],
            "warm_first_call_s": warm["first_call_s"],
            "cold_misses": cold["misses"], "warm_hits": warm["hits"],
            "speedup": round(cold["first_call_s"] /
                             max(warm["first_call_s"], 1e-9), 2)}


def _sharded_bucket_row(m: int, n: int, n_layers: int,
                        n_devices: int = 2,
                        snippet: str = _SHARDED_SNIPPET) -> dict:
    """Time one fused sharded bucket vs its status-quo baseline in a
    fresh subprocess with ``n_devices`` fake CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{n_devices}").strip()
    code = textwrap.dedent(snippet).format(m=m, n=n, L=n_layers,
                                           reps=REPS)
    return _run_cpu_child(code, env)


def run() -> dict:
    rng = np.random.default_rng(0)
    dims = [(512, 512), (1024, 1024)] if FAST else \
        [(512, 512), (1024, 1024), (2048, 2048)]
    rows = []
    for (m, n) in dims:
        W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
        X = jnp.asarray(rng.normal(size=(2048, m)), jnp.float32)
        H = X.T @ X
        qcfg = QuantConfig(bits=2, group_size=64)

        t0 = time.time()
        Ql, Al, Bl, _ = loftq_init(W, qcfg, 64, iters=5)
        jax.block_until_ready(Al)
        t_loftq = time.time() - t0

        t0 = time.time()
        A, B = _cloq_stack(W, H, qcfg, 64)
        jax.block_until_ready(A)
        t_cloq = time.time() - t0

        rows.append({"m": m, "n": n, "loftq_s": round(t_loftq, 3),
                     "cloq_s": round(t_cloq, 3),
                     "ratio": round(t_cloq / t_loftq, 2)})
        print(f"  {m}x{n}: loftq={t_loftq:.2f}s cloq={t_cloq:.2f}s",
              flush=True)

    qspec = QSpec(bits=2, group_size=64, rank=16)
    batched_rows = []
    for (m, n, n_layers) in BUCKETS:
        row = _bucket_row(m, n, n_layers, qspec, rng)
        batched_rows.append(row)
        print(f"  bucket {m}x{n} x{n_layers}: seq={row['sequential_s']}s "
              f"batched={row['batched_s']}s ({row['speedup']}x)", flush=True)

    sharded_rows = []
    for (m, n, n_layers) in ([(64, 64, 16)] if FAST else
                             [(64, 64, 16), (128, 128, 16)]):
        row = _sharded_bucket_row(m, n, n_layers)
        sharded_rows.append(row)
        print(f"  sharded bucket {m}x{n} x{n_layers} "
              f"({row['n_devices']} dev): "
              f"per-layer={row['per_layer_sharded_s']}s "
              f"fused={row['sharded_batched_s']}s "
              f"({row['speedup']}x)", flush=True)

    hg = _health_guard_row(rng)
    print(f"  health guard {hg['m']}x{hg['n']} x{hg['n_layers']}: "
          f"off={hg['unguarded_s']}s on={hg['guarded_s']}s "
          f"({hg['overhead_pct']}% overhead)", flush=True)

    ob = _obs_overhead_row(rng)
    print(f"  obs tracing {ob['m']}x{ob['n']} x{ob['n_layers']}: "
          f"off={ob['untraced_s']}s on={ob['traced_s']}s "
          f"({ob['overhead_pct']}% overhead, "
          f"noop span {ob['noop_span_ns']}ns)", flush=True)

    mixed = _mixed_recipe_row(rng)
    print(f"  mixed recipe ({mixed['n_buckets']} buckets, "
          f"{mixed['n_layers']} sites): seq={mixed['sequential_s']}s "
          f"mixed={mixed['mixed_batched_s']}s ({mixed['speedup']}x)",
          flush=True)

    auto = _auto_alloc_row(rng)
    print(f"  auto alloc ({auto['n_sites']} sites x "
          f"{auto['n_candidates']} candidates): "
          f"seq={auto['sequential_sweep_s']}s "
          f"vmapped={auto['vmapped_sweep_s']}s ({auto['speedup']}x); "
          f"uniform-int3 err={auto['uniform_int3_err']} vs "
          f"auto err={auto['auto_err']} at {auto['budget_bytes']} B",
          flush=True)

    lq = _sharded_bucket_row(64, 64, 16, snippet=_LOFTQ_SHARDED_SNIPPET)
    print(f"  loftq planner bucket 64x64 x16 ({lq['n_devices']} dev): "
          f"replicated={lq['replicated_batched_s']}s "
          f"sharded={lq['sharded_batched_s']}s -> "
          f"chose {lq['chosen_path']} ({lq['speedup']}x vs worst)",
          flush=True)

    cs = _cold_start_row()
    print(f"  cold start rtn {cs['m']}x{cs['n']} x{cs['n_layers']}: "
          f"cold={cs['cold_first_call_s']}s "
          f"warm={cs['warm_first_call_s']}s ({cs['speedup']}x, "
          f"warm hits={cs['warm_hits']})", flush=True)

    out = {"rows": rows,
           "batched_rows": batched_rows,
           "batched_speedup_best": max(r["speedup"] for r in batched_rows),
           "sharded_rows": sharded_rows,
           "health_guard_row": hg,
           "obs_overhead_row": ob,
           "mixed_recipe_row": mixed,
           "auto_alloc_row": auto,
           "loftq_sharded_row": lq,
           "cold_start_row": cs,
           "note": ("paper Table 10: comparable runtimes; CLoQ trades "
                    "LoftQ's 5 SVD iterations for OPTQ+2 SVDs.  batched_s: "
                    "one jit(vmap) dispatch over a bucket of same-shape "
                    "layers vs the sequential per-layer engine loop "
                    f"(best of {REPS}).  sharded_rows: the distributed "
                    "engine — one fused shard_map(vmap) program per bucket "
                    "vs per-layer sharded dispatches, on fake CPU devices "
                    "in a subprocess.  loftq_sharded_row: the calibrated "
                    "cost-model planner choosing replicated vs sharded; "
                    "speedup is chosen-path vs worst-path (>= 1.0 means it "
                    "chose right).  cold_start_row: first quantize call of "
                    "a fresh process, empty vs populated compile cache")}
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "table10_init_cost.json"), "w") as f:
        json.dump(out, f, indent=1)

    # metrics snapshot for check_bench counter floors.  The cold-start
    # runs happen in subprocesses whose registries die with them, so
    # their cache tallies are mirrored into this process's registry.
    from repro.obs import metrics as obs_metrics
    from repro.obs import names as obs_names
    obs_metrics.counter(obs_names.CACHE_HITS).inc(cs["warm_hits"])
    obs_metrics.counter(obs_names.CACHE_MISSES).inc(cs["cold_misses"])
    obs_metrics.save(os.path.join(RESULTS, "metrics-table10.json"))
    return out


if __name__ == "__main__":
    print(json.dumps(run(), indent=1))
