"""Cost-model bucket planner + persisted compile cache.

The planner half runs with FAKE calibration tables (deterministic, no
timing in CI): the historical misprediction — toy-width LoftQ sharded at a
slowdown — must route replicated, large buckets must still shard, and the
decision must be a pure function of the calibration file.  The cache half
asserts the cold-start contract: a second process (here: a second
``CompileCache`` instance or a real subprocess) hits the persisted entry,
any fingerprint change is a miss by construction, a corrupt entry recovers
with one warning, and process-local (LAPACK custom-call) executables are
never persisted on cpu — the crash class that motivated the gate.
"""
from __future__ import annotations

import dataclasses
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched import (LayerTask, plan_buckets, plan_manifest,
                                requeue_spec)
from repro.core.compile_cache import CompileCache, PersistedFunction
from repro.core.costmodel import (CostCalibration, CostModel,
                                  load_calibration)
from repro.launch.mesh import make_model_mesh
from repro.models.modules import QSpec
from tests.util import run_with_devices

# Fake per-host table: 1 GFLOP/s, 1 GB/s, 1 ms dispatch, slow psums,
# shard_efficiency 2.0 = two real chips (not fake same-host devices).
FAKE = dict(flops_per_s=1e9, bytes_per_s=1e9, dispatch_s=1e-3,
            psum_latency_s=5e-3, psum_bytes_per_s=1e8,
            shard_efficiency=2.0)

def _model(**over) -> CostModel:
    cal = CostCalibration(**{**FAKE, **over})
    return CostModel(cal, layer_costs=lambda s: (8.0 * s.m * s.m * s.n,
                                                 4.0 * s.m * s.n))


def _toy_tasks(m: int, n: int, L: int, with_gram: bool = True):
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(0), L)
    tasks = []
    for i in range(L):
        W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
        H = None
        if with_gram:
            X = rng.normal(size=(4 * m, m)).astype(np.float32)
            H = jnp.asarray(X.T @ X)
        tasks.append(LayerTask(f"blocks.{i}.attn.q", None, W, H, keys[i]))
    return tasks


# -- planner decisions (fake calibration, no timing) ------------------------

def test_toy_loftq_routes_replicated():
    """The fixed misprediction: psum rounds dominate at toy widths."""
    path, shards = _model().decide_geometry("loftq", m=64, n=64, L=16, k=2)
    assert (path, shards) == ("replicated", 1)


def test_large_bucket_still_shards():
    path, shards = _model().decide_geometry("cloq", m=2048, n=2048,
                                            L=16, k=2)
    assert (path, shards) == ("sharded", 2)


def test_memory_gate_forces_sequential():
    cm = _model(memory_budget_bytes=1024.0)
    path, shards = cm.decide_geometry("cloq", m=256, n=256, L=64, k=2)
    assert (path, shards) == ("sequential", 1)


def test_memory_gate_reads_compiled_footprint():
    """Under a finite budget the gate holds the fused bucket's compiled
    footprint (arguments + outputs + temporaries of the stacked program)
    to it, not the layers' traffic."""
    from repro.core.costmodel import compiled_bucket_footprint
    tasks = _toy_tasks(32, 48, 1)
    spec = next(iter(plan_buckets(tasks, QSpec(bits=4, group_size=16,
                                               rank=4), "cloq")))
    L = 8
    fp = compiled_bucket_footprint(spec, L)
    assert fp >= 4.0 * L * (32 * 48 + 32 * 32)      # W and H at least
    assert compiled_bucket_footprint(_geo("cloq", 32, 48), L) is None
    for budget, path in ((fp - 1.0, "sequential"), (fp + 1.0, "replicated")):
        cm = CostModel(CostCalibration(**FAKE, memory_budget_bytes=budget),
                       layer_costs=lambda s: (1.0, 1e30))
        assert cm.decide(spec, L, 1) == (path, 1)


def test_bucket_the_compiler_cannot_fit_is_infinite(monkeypatch):
    """A TPU compiler refuses a stacked program larger than HBM: that
    bucket's footprint is infinite, so the gate runs it sequentially."""
    import repro.core.batched as batched
    from repro.core.costmodel import compiled_bucket_footprint

    class _Refused:
        def lower(self, *a, **k):
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")
    monkeypatch.setattr(batched, "run_bucket", _Refused())
    spec = next(iter(plan_buckets(_toy_tasks(32, 48, 1),
                                  QSpec(bits=4, group_size=16, rank=4),
                                  "cloq")))
    assert compiled_bucket_footprint(spec, 8) == float("inf")
    cm = CostModel(CostCalibration(**FAKE, memory_budget_bytes=1e30),
                   layer_costs=lambda s: (1.0, 1.0))
    assert cm.decide(spec, 8, 1) == ("sequential", 1)


def test_indivisible_width_never_shards():
    # n % k != 0: the sharded path must not even be a candidate
    times = _model().path_times(_geo("cloq", 2048, 2047), L=16, k=2)
    assert "sharded" not in times


def _geo(method, m, n, rank=16):
    from repro.core.costmodel import _Geometry
    return _Geometry(m=m, n=n, method=method, rank=rank,
                     has_gram=method in ("cloq", "gptq"))


def test_decisions_deterministic_from_file(tmp_path):
    """Plan-time decisions are a pure function of the calibration file."""
    cal = CostCalibration(**FAKE)
    p = str(tmp_path / "cal.json")
    cal.save(p)
    grid = [("loftq", 64, 64, 16), ("loftq", 1024, 1024, 16),
            ("cloq", 64, 64, 8), ("cloq", 2048, 2048, 16),
            ("rtn", 512, 512, 4)]
    runs = []
    for _ in range(2):
        cm = CostModel.coerce(p)
        cm._layer_costs = lambda s: (8.0 * s.m * s.m * s.n, 4.0 * s.m * s.n)
        assert cm.calibration.source == "file"
        runs.append([cm.decide_geometry(meth, m=m, n=n, L=L, k=2)
                     for meth, m, n, L in grid])
    assert runs[0] == runs[1]


def test_load_calibration_missing_or_corrupt(tmp_path):
    assert load_calibration(str(tmp_path / "nope.json")) is None
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert load_calibration(str(bad)) is None


def test_plan_buckets_meshless_with_cost_model():
    """No mesh => k=1: the cost model can only pick replicated/sequential,
    and toy buckets pick replicated."""
    tasks = _toy_tasks(16, 16, 4)
    qspec = QSpec(bits=2, group_size=16, rank=4)
    buckets = plan_buckets(tasks, qspec, "cloq", cost_model=_model())
    (spec, idxs), = buckets.items()
    assert spec.exec_path == "replicated"
    assert spec.n_shards == 1
    assert len(idxs) == 4


@pytest.mark.multidevice
def test_plan_buckets_cost_model_on_mesh():
    """On a 2-device mesh the cost model routes the toy LoftQ bucket
    replicated (the fix) and a large LoftQ bucket sharded — decisions made
    at plan time, deterministic, no timing."""
    run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.core.batched import LayerTask, plan_buckets
        from repro.core.costmodel import CostCalibration, CostModel
        from repro.models.modules import QSpec

        cal = CostCalibration(flops_per_s=1e9, bytes_per_s=1e9,
                              dispatch_s=1e-3, psum_latency_s=5e-3,
                              psum_bytes_per_s=1e8, shard_efficiency=2.0)
        cm = CostModel(cal, layer_costs=lambda s: (8.0 * s.m * s.m * s.n,
                                                   4.0 * s.m * s.n))
        from repro.launch.mesh import make_model_mesh
        mesh = make_model_mesh(2)
        qspec = QSpec(bits=2, group_size=64, rank=16)

        def plan(m, n, L):
            W = jnp.zeros((m, n), jnp.float32)
            keys = jax.random.split(jax.random.PRNGKey(0), L)
            tasks = [LayerTask(f"l{i}", None, W, None, keys[i])
                     for i in range(L)]
            spec = next(iter(plan_buckets(tasks, qspec, "loftq", mesh=mesh,
                                          cost_model=cm)))
            return spec.exec_path, spec.n_shards

        assert plan(64, 64, 16) == ("replicated", 1), plan(64, 64, 16)
        assert plan(1024, 1024, 16) == ("sharded", 2), plan(1024, 1024, 16)
        print("OK")
    """, n_devices=2)


def test_requeue_spec_matches_fresh_single_plan():
    """The health ladder's requeue must land on the same spec a fresh
    meshless plan of that site alone would produce."""
    tasks = _toy_tasks(16, 16, 1)
    qspec = QSpec(bits=2, group_size=16, rank=4)
    fresh = next(iter(plan_buckets(tasks[:1], qspec, "cloq")))
    sharded = dataclasses.replace(fresh, n_shards=2, exec_path="sharded")
    assert requeue_spec(sharded) == fresh
    sequential = dataclasses.replace(fresh, exec_path="sequential")
    assert requeue_spec(sequential) == fresh


# -- manifest round-trip + divergence warning -------------------------------

def _manifest(m=16, n=16, L=4):
    tasks = _toy_tasks(m, n, L)
    qspec = QSpec(bits=2, group_size=16, rank=4)
    buckets = plan_buckets(tasks, qspec, "cloq")
    return plan_manifest(tasks, buckets)


def test_manifest_divergence_single_warning():
    """A manifest whose save-time layout cannot be reproduced on the
    restore mesh re-resolves with exactly ONE legible warning."""
    from repro.checkpoint.manager import manifest_shardings

    manifest = _manifest()
    # pretend it was saved sharded x2 on a bigger mesh
    for b in manifest["buckets"]:
        b["spec"]["n_shards"] = 2
        b["spec"]["exec_path"] = "sharded"
    mesh = make_model_mesh(1)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        shardings = manifest_shardings(manifest, mesh)
    relayout = [w for w in rec if "restore-time bucket layout" in
                str(w.message)]
    assert len(relayout) == 1
    assert "saved sharded x2 -> restored replicated x1" in \
        str(relayout[0].message)
    assert shardings       # every task leaf got a NamedSharding


def test_manifest_same_layout_no_warning():
    from repro.checkpoint.manager import manifest_shardings

    manifest = _manifest()
    mesh = make_model_mesh(1)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        manifest_shardings(manifest, mesh)
    assert not [w for w in rec if "restore-time" in str(w.message)]


def test_manifest_cost_model_replay():
    """Restore through the SAME cost model the planner used => no
    divergence; through a different decision rule => one warning."""
    from repro.checkpoint.manager import manifest_shardings

    tasks = _toy_tasks(16, 16, 4)
    qspec = QSpec(bits=2, group_size=16, rank=4)
    cm = _model()
    buckets = plan_buckets(tasks, qspec, "cloq", cost_model=cm)
    manifest = plan_manifest(tasks, buckets)
    mesh = make_model_mesh(1)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        manifest_shardings(manifest, mesh, cost_model=cm)
    assert not [w for w in rec if "restore-time" in str(w.message)]
    # a cost model with a tiny memory budget re-decides to sequential
    with pytest.warns(RuntimeWarning, match="restore-time bucket layout"):
        manifest_shardings(manifest, mesh,
                           cost_model=_model(memory_budget_bytes=1.0))


@pytest.mark.multidevice
def test_manifest_roundtrip_other_device_count():
    """A checkpoint manifest planned on 1 device restores onto a 4-device
    mesh: shard counts re-resolve against the new mesh and the layout
    change is reported once."""
    run_with_devices("""
        import warnings
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.batched import LayerTask, plan_buckets, plan_manifest
        from repro.checkpoint.manager import manifest_shardings
        from repro.models.modules import QSpec

        rng = np.random.default_rng(0)
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        tasks = []
        for i in range(4):
            W = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
            X = rng.normal(size=(64, 16)).astype(np.float32)
            tasks.append(LayerTask(f"blocks.{i}.attn.q", None, W,
                                   jnp.asarray(X.T @ X), keys[i]))
        qspec = QSpec(bits=2, group_size=16, rank=4)
        manifest = plan_manifest(tasks, plan_buckets(tasks, qspec, "cloq"))
        assert all(b["spec"]["n_shards"] == 1 for b in manifest["buckets"])

        from repro.launch.mesh import make_model_mesh
        mesh = make_model_mesh(4)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            shardings = manifest_shardings(manifest, mesh)
        relayout = [w for w in rec
                    if "restore-time bucket layout" in str(w.message)]
        assert len(relayout) == 1, [str(w.message) for w in rec]
        assert "x4" in str(relayout[0].message)
        assert shardings
        print("OK")
    """, n_devices=4)


# -- persisted compile cache ------------------------------------------------

def _double(x):
    return x * 2.0 + 1.0


def test_second_instance_hits(tmp_path):
    x = jnp.arange(8.0)
    c1 = CompileCache(str(tmp_path))
    out1, hit1 = c1.call("t", {"scope": "a"}, _double, (x,))
    assert not hit1 and c1.misses == 1
    # a fresh instance on the same directory = a second process start
    c2 = CompileCache(str(tmp_path))
    out2, hit2 = c2.call("t", {"scope": "a"}, _double, (x,))
    assert hit2 and c2.hits == 1 and c2.misses == 0
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


@pytest.mark.multidevice
def test_second_process_hits(tmp_path):
    """The real cold-start contract: a separate PROCESS deserializes the
    persisted executable instead of recompiling."""
    code = f"""
        import jax.numpy as jnp
        from repro.core.compile_cache import CompileCache
        cache = CompileCache(r"{tmp_path}")
        out, hit = cache.call("t", {{"scope": "a"}},
                              lambda x: x * 2.0 + 1.0, (jnp.arange(8.0),))
        print("SUMMARY", cache.summary(), "hit", hit, float(out.sum()))
    """
    first = run_with_devices(code, n_devices=1).stdout
    second = run_with_devices(code, n_devices=1).stdout
    assert "hits=0 misses=1" in first and "hit False" in first
    assert "hits=1 misses=0" in second and "hit True" in second


def test_miss_on_parts_change(tmp_path):
    x = jnp.arange(4.0)
    c = CompileCache(str(tmp_path))
    c.call("t", {"scope": "a"}, _double, (x,))
    _, hit = c.call("t", {"scope": "b"}, _double, (x,))
    assert not hit and c.misses == 2


def test_miss_on_jax_version_change(tmp_path):
    x = jnp.arange(4.0)
    CompileCache(str(tmp_path)).call("t", {}, _double, (x,))
    c2 = CompileCache(str(tmp_path), jax_version="0.0.other")
    _, hit = c2.call("t", {}, _double, (x,))
    assert not hit and c2.misses == 1


def test_miss_on_shape_change(tmp_path):
    c = CompileCache(str(tmp_path))
    c.call("t", {}, _double, (jnp.arange(4.0),))
    _, hit = c.call("t", {}, _double, (jnp.arange(8.0),))
    assert not hit and c.misses == 2


def test_corrupt_entry_warns_and_recovers(tmp_path):
    x = jnp.arange(8.0)
    c1 = CompileCache(str(tmp_path))
    c1.call("t", {}, _double, (x,))
    key = c1.key("t", {}, (x,))
    path = os.path.join(str(tmp_path), f"{key}.bin")
    assert os.path.exists(path)
    with open(path, "wb") as f:
        f.write(b"garbage, hand-edited bytes")
    c2 = CompileCache(str(tmp_path))
    with pytest.warns(RuntimeWarning, match="corrupt compile-cache entry"):
        out, hit = c2.call("t", {}, _double, (x,))
    assert not hit and c2.corrupt == 1 and c2.misses == 1
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 2 + 1)
    # the rebuilt entry is valid again
    c3 = CompileCache(str(tmp_path))
    _, hit3 = c3.call("t", {}, _double, (x,))
    assert hit3


def test_unportable_executable_not_persisted(tmp_path):
    """LAPACK custom-call executables bind process-local pointers on cpu —
    a deserialized copy segfaults — so the cache must keep them
    in-process.  Regression for the crash class, asserted structurally:
    nothing lands on disk and a fresh instance recompiles."""
    x = jnp.eye(8) * 2.0 + 0.1

    def f(x):
        return jnp.linalg.eigh(x)[0].sum()

    c1 = CompileCache(str(tmp_path))
    out, hit = c1.call("t", {}, f, (x,))
    assert not hit and c1.unportable == 1
    assert "unportable=1" in c1.summary()
    assert not [p for p in os.listdir(str(tmp_path))
                if p.endswith(".bin")]
    c2 = CompileCache(str(tmp_path))
    _, hit2 = c2.call("t", {}, f, (x,))
    assert not hit2 and c2.misses == 1          # recompiles, never crashes


def test_persisted_function_specializes_per_shape(tmp_path):
    cache = CompileCache(str(tmp_path))
    pf = PersistedFunction(cache, "t", {"scope": "a"}, _double)
    pf(jnp.arange(4.0))
    pf(jnp.arange(8.0))
    pf(jnp.arange(4.0))
    assert cache.misses == 2 and cache.hits == 1


def test_bucket_cache_counters_in_progress_line(tmp_path):
    """quantize_layer_batch(compile_cache=...) surfaces hit/miss counts in
    the bucket progress line, and a second cache instance hits (rtn's
    executable is custom-call-free => persistable even on cpu)."""
    from repro.core.batched import quantize_layer_batch

    tasks = _toy_tasks(16, 16, 4, with_gram=False)
    qspec = QSpec(bits=4, group_size=16, rank=4, method="rtn")
    msgs1: list[str] = []
    c1 = CompileCache(str(tmp_path))
    out1 = quantize_layer_batch(tasks, qspec, "rtn", progress=msgs1.append,
                                compile_cache=c1)
    assert any("cache=miss" in m for m in msgs1), msgs1
    assert c1.misses == 1

    msgs2: list[str] = []
    c2 = CompileCache(str(tmp_path))
    out2 = quantize_layer_batch(tasks, qspec, "rtn", progress=msgs2.append,
                                compile_cache=c2)
    assert any("cache=hit" in m for m in msgs2), msgs2
    assert c2.hits == 1 and c2.misses == 0
    for a, b in zip(out1, out2):
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))


def test_cached_bucket_matches_uncached():
    """The cache can never change results: cached and uncached runs of the
    same bucket are bit-identical (same executable semantics)."""
    import tempfile

    from repro.core.batched import quantize_layer_batch

    tasks = _toy_tasks(16, 16, 3, with_gram=False)
    qspec = QSpec(bits=4, group_size=16, rank=4, method="qlora")
    plain = quantize_layer_batch(tasks, qspec, "qlora")
    with tempfile.TemporaryDirectory() as d:
        cached = quantize_layer_batch(tasks, qspec, "qlora",
                                      compile_cache=CompileCache(d))
    for a, b in zip(plain, cached):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))
