"""The observability layer (repro.obs): spans, metrics, structured logs.

What gets proven:

* **disabled = free** — with the tracer off (the default), instrumented
  code paths return the shared no-op span, quantization results are
  bitwise identical to a traced run, and the per-callsite cost is
  sub-microsecond-ish (generously bounded for shared-CI noise);
* **spans round-trip** — nesting, attributes and error tagging survive
  chrome-trace export (the file Perfetto loads), with parent intervals
  enclosing child intervals;
* **histograms** — le-edge semantics at the edges, overflow slot,
  edge-list validation;
* **determinism** — two identical runs (including fault-injected ones
  that exercise the health ladder) produce identical counter snapshots,
  and the health events show up as both counters and trace events;
* **the name contract** — every emitted metric is declared in
  repro.obs.names, whose registry matches the committed
  tools/obs_metric_names.json.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import names as obs_names
from repro.obs import trace as obs_trace

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test starts with the module tracer off and a clean slate."""
    obs_trace.disable()
    obs_trace.get_tracer().clear()
    obs_metrics.reset()
    yield
    obs_trace.disable()
    obs_trace.get_tracer().clear()
    obs_metrics.reset()


def _tasks(n_layers=3, m=32, n=32, seed=0):
    from repro.core.batched import LayerTask
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(0), n_layers)
    tasks = []
    for i in range(n_layers):
        W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
        X = rng.normal(size=(128, m)).astype(np.float32)
        tasks.append(LayerTask(f"l{i}", None, W, jnp.asarray(X.T @ X),
                               keys[i]))
    return tasks


# --- disabled tracer is a no-op --------------------------------------------


def test_disabled_span_is_shared_singleton():
    assert not obs_trace.is_enabled()
    s1 = obs_trace.span("a", x=1)
    s2 = obs_trace.span("b")
    assert s1 is s2
    with s1 as sp:
        assert sp.set(anything=True) is sp
    assert obs_trace.get_tracer().events() == []


def test_disabled_tracer_results_bitwise_identical():
    """Tracing (every span recorded and annotated for the profiler) must
    not perturb quantization numerics in any way."""
    from repro.core.batched import quantize_layer_batch
    from repro.models.modules import QSpec

    qspec = QSpec(bits=4, group_size=16, rank=4)
    off = quantize_layer_batch(_tasks(), qspec, "cloq")
    obs_trace.enable()
    on = quantize_layer_batch(_tasks(), qspec, "cloq")
    obs_trace.disable()
    assert len(off) == len(on)
    for o, t in zip(off, on):
        assert set(o) == set(t)
        for k in o:
            np.testing.assert_array_equal(np.asarray(o[k]),
                                          np.asarray(t[k]), err_msg=k)
    # and the traced run actually recorded the engine spans
    names = {e["name"] for e in obs_trace.get_tracer().events()}
    assert "quant.plan" in names and "bucket.execute" in names


def test_disabled_span_overhead_near_zero():
    """The price of an instrumented callsite with tracing off: one call
    + one bool check.  Bounded generously for noisy shared hosts — the
    point is catching an accidental allocation/lock on the fast path,
    not microbenchmark precision."""
    reps = 50_000
    t0 = time.perf_counter()
    for _ in range(reps):
        with obs_trace.span("noop", a=1):
            pass
    per_call = (time.perf_counter() - t0) / reps
    assert per_call < 20e-6, f"disabled span costs {per_call * 1e9:.0f}ns"


# --- span recording + chrome-trace export ----------------------------------


def test_span_nesting_attrs_roundtrip(tmp_path):
    tr = obs_trace.Tracer()
    tr.enabled = True
    with tr.span("outer", bucket=0) as outer:
        with tr.span("inner", layers=3) as inner:
            inner.set(path="replicated")
        outer.set(ok=True)
    tr.instant("marker", note="here")
    out = tmp_path / "trace.json"
    tr.export(out)

    payload = json.loads(out.read_text())
    assert sorted(payload) == ["displayTimeUnit", "traceEvents"]
    evs = payload["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    # process metadata for Perfetto's track naming
    assert by_name["process_name"]["ph"] == "M"
    inner, outer = by_name["inner"], by_name["outer"]
    assert inner["ph"] == outer["ph"] == "X"
    assert inner["args"] == {"layers": 3, "path": "replicated"}
    assert outer["args"] == {"bucket": 0, "ok": True}
    # the parent interval encloses the child interval
    assert outer["ts"] <= inner["ts"]
    assert (inner["ts"] + inner["dur"]) <= (outer["ts"] + outer["dur"]
                                            + 1e-3)
    assert by_name["marker"]["ph"] == "i"
    # every event carries the common chrome-trace keys
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)


def test_span_records_error_attr():
    tr = obs_trace.Tracer()
    tr.enabled = True
    with pytest.raises(ValueError):
        with tr.span("will_fail"):
            raise ValueError("boom")
    (ev,) = tr.events()
    assert ev["args"]["error"] == "ValueError"


def test_traced_decorator():
    tr = obs_trace.get_tracer()
    calls = []

    @obs_trace.traced("my.step", kind="test")
    def fn(x):
        calls.append(x)
        return x * 2

    assert fn(3) == 6                        # disabled: plain call
    assert tr.events() == []
    obs_trace.enable()
    assert fn(4) == 8
    obs_trace.disable()
    (ev,) = tr.events()
    assert ev["name"] == "my.step" and ev["args"] == {"kind": "test"}
    assert calls == [3, 4]


# --- spans on the profiler's clock -----------------------------------------


def _profiled(tmp_path, enabled: bool):
    """Open ``obs.probe`` around a device op inside a profiler trace on the
    CPU; returns the span object and the trace's host events by name."""
    from jax.profiler import ProfileData
    if enabled:
        obs_trace.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("obs.probe", k=1) as sp:
            jnp.arange(8.0).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, []).append(ev)
    return sp, host


@pytest.mark.parametrize("enabled", [True, False], ids=["on", "off"])
def test_span_lands_in_a_profiler_trace_only_when_enabled(tmp_path, enabled):
    sp, host = _profiled(tmp_path, enabled)
    if enabled:
        (ev,) = host["obs.probe"]
        assert {k: v for k, v in ev.stats} == {"k": 1}
        (rec,) = obs_trace.get_tracer().events()
        # the same interval, to the tracer's and the profiler's clocks
        assert ev.duration_ns == pytest.approx(rec["dur"] * 1e3, rel=0.5)
    else:
        assert sp is obs_trace.span("other")        # the shared no-op
        assert "obs.probe" not in host
        assert obs_trace.get_tracer().events() == []


def test_complete_records_a_phase_that_already_ended():
    obs_trace.complete("serve.prefill", 1.0, 2.5, rid=3)   # off: nothing
    assert obs_trace.get_tracer().events() == []
    obs_trace.enable()
    obs_trace.complete("serve.prefill", 1.0, 2.5, rid=3)
    (ev,) = obs_trace.get_tracer().events()
    assert ev["ph"] == "X" and ev["args"] == {"rid": 3}
    assert ev["dur"] == pytest.approx(1.5e6)


def _op_name_scopes(compiled_text: str) -> set:
    """Every name-stack component of the compiled HLO's ``op_name``s, with
    the transforms JAX writes around a name (``jvp(attn)``) taken off."""
    import re
    out = set()
    for op_name in re.findall(r'op_name="([^"]*)"', compiled_text):
        for part in op_name.split("/"):
            while re.fullmatch(r"\w+\((.*)\)", part):
                part = re.fullmatch(r"\w+\((.*)\)", part).group(1)
            out.add(part)
    return out


def _tiny_quantized():
    from repro.core.pipeline import quantize_model
    from repro.core.recipe import QuantRecipe
    from repro.models.modules import QSpec
    from repro.models.transformer import ModelConfig, init_params

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      vocab=64, n_heads=4, n_kv_heads=2, d_ff=64,
                      dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = [{"tokens": np.random.default_rng(0).integers(1, 64, (2, 16))}]
    return quantize_model(params, cfg, calib, recipe=QuantRecipe.single(
        "cloq", QSpec(bits=4, group_size=16, rank=4)))[:2]


def _serve_step_text(qp, qcfg) -> str:
    from repro.serve import AdapterRegistry, ServeEngine, adapters_from_tree
    reg = AdapterRegistry.from_model(qp, capacity=2)
    reg.register("t0", adapters_from_tree(qp))
    eng = ServeEngine(qp, qcfg, reg, page_size=4, max_len=16,
                      bucket_capacity=2)
    B, rank = eng.bucket_capacity, reg.slot_of("t0")[0]
    z = jnp.zeros((B,), jnp.int32)
    return eng._exec.lower(
        eng._base, reg.stacks(rank), z, eng._k_pool, eng._v_pool,
        jnp.zeros((B, eng._maxp), jnp.int32), z,
        jnp.zeros((B, 1), jnp.int32)).compile().as_text()


def _train_step_text(qp, qcfg) -> str:
    from repro.launch.steps import build_state, make_train_step
    from repro.models.parallel import LOCAL
    from repro.optim import OptConfig
    ocfg = OptConfig(lr=1e-2, trainable="lora", total_steps=2)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    step = jax.jit(make_train_step(qcfg, ocfg, LOCAL))
    return step.lower(build_state(qp, ocfg), batch).compile().as_text()


LINEAR_SCOPES = {"q", "k", "v", "o", "gate", "up", "down", "dequant", "lora"}
STEP_SCOPES = {
    "serve": {"embed", "attn", "mlp", "lm_head", "kv_cache", "lora_gather",
              *LINEAR_SCOPES},
    "train": {"embed", "attn", "mlp", "lm_head", *LINEAR_SCOPES},
}


@pytest.mark.parametrize("step", sorted(STEP_SCOPES))
def test_layer_scopes_reach_the_compiled_op_names(step):
    """The names ``repro.utils.scope`` enters are in the op metadata the
    profiler reports as ``tf_op`` (the serve step on the XLA dequant
    path here; the chip runs the kernels instead)."""
    want = STEP_SCOPES[step]
    assert want <= set(obs_names.SCOPES)
    qp, qcfg = _tiny_quantized()
    text = {"serve": _serve_step_text,
            "train": _train_step_text}[step](qp, qcfg)
    missing = want - _op_name_scopes(text)
    assert not missing, f"scopes absent from the {step} step: {missing}"


# --- metrics ----------------------------------------------------------------


def test_histogram_edge_semantics():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("lat", edges=(0.1, 1.0, 10.0))
    for x in (0.05, 0.1, 0.100001, 1.0, 10.0, 10.1, 1e9):
        h.observe(x)
    # le edges: x == edge lands in that edge's bucket
    assert h.counts == [2, 2, 1, 2]
    assert h.count == 7
    snap = reg.snapshot()["histograms"]["lat"]
    assert snap["counts"] == [2, 2, 1, 2]
    assert snap["edges"] == [0.1, 1.0, 10.0]


def test_histogram_rejects_bad_edges():
    reg = obs_metrics.MetricsRegistry()
    for bad in ((), (1.0, 1.0), (2.0, 1.0)):
        with pytest.raises(ValueError):
            reg.histogram(f"bad{len(bad)}", edges=bad)
    with pytest.raises(ValueError):
        reg.histogram("undeclared.name")     # no edges, not in names.py


def test_snapshot_sorted_and_deterministic(tmp_path):
    def emit(reg):
        reg.counter("z.last").inc(2)
        reg.counter("a.first").inc()
        reg.gauge("mid").set(0.5)
        reg.histogram("h", edges=(1.0,)).observe(0.2)

    r1, r2 = obs_metrics.MetricsRegistry(), obs_metrics.MetricsRegistry()
    emit(r1)
    emit(r2)
    assert r1.snapshot() == r2.snapshot()
    assert list(r1.snapshot()["counters"]) == ["a.first", "z.last"]
    p = tmp_path / "m.json"
    r1.save(p)
    assert json.loads(p.read_text()) == r1.snapshot()


# --- fault-injected runs: counters + spans together -------------------------


def _quant_once():
    from repro.core import faults
    from repro.core.health import HealthReport
    from repro.core.pipeline import quantize_model
    from repro.core.recipe import QuantRecipe
    from repro.data import DataConfig, TokenStream
    from repro.models.modules import QSpec
    from repro.models.transformer import ModelConfig, init_params

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      vocab=128, n_heads=4, n_kv_heads=2, d_ff=64,
                      dtype=jnp.float32, scan_layers=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    stream = TokenStream(DataConfig(vocab=128, seq_len=32, global_batch=2))
    calib = [stream.next_batch() for _ in range(2)]
    recipe = QuantRecipe.single(
        "cloq", QSpec(bits=4, group_size=16, rank=4, method="cloq"))
    report = HealthReport()
    with faults.inject("gram_nan", match="blocks.0.attn.q"):
        quantize_model(params, cfg, calib, recipe=recipe,
                       engine="batched", report=report)
    return report


@pytest.mark.fault
def test_fault_run_counters_deterministic_and_health_visible():
    obs_trace.enable()
    _quant_once()
    obs_trace.disable()
    first = obs_metrics.snapshot()
    events = obs_trace.get_tracer().events()

    obs_metrics.reset()
    obs_trace.get_tracer().clear()
    _quant_once()                            # tracer off this time
    second = obs_metrics.snapshot()

    # identical event streams -> identical counters, traced or not
    assert first["counters"] == second["counters"]
    # the injected NaN gram walked the ladder: counted AND traced
    c = first["counters"]
    assert c[obs_names.HEALTH_PREFIX + "recovered_identity_gram"] >= 1
    assert c[obs_names.HEALTH_CHECKED] >= 1
    assert c[obs_names.QUANT_BUCKETS] >= 1
    names = [e["name"] for e in events]
    assert "health.heal" in names
    assert "health.recovered_identity_gram" in names
    assert "quant.model" in names and "quant.calibrate" in names


# --- the committed name contract -------------------------------------------


def test_registry_matches_committed_json():
    committed = json.loads(
        open(os.path.join(REPO, "tools", "obs_metric_names.json")).read())
    committed.pop("comment", None)
    live = obs_names.registry_dict()
    assert committed == json.loads(json.dumps(live)), (
        "repro.obs.names drifted from tools/obs_metric_names.json — "
        "run: python tools/check_obs.py --update-registry")


def test_emitted_serve_metrics_are_declared():
    """Everything the serve engine emits must be a declared name (the
    check_obs snapshot validation relies on it)."""
    for n in (obs_names.SERVE_SUBMITTED, obs_names.SERVE_ADMITTED,
              obs_names.SERVE_FINISHED, obs_names.SERVE_TOKENS,
              obs_names.SERVE_STEPS):
        assert n in obs_names.COUNTERS
    for n in (obs_names.SERVE_TTFT, obs_names.SERVE_TOKEN_LATENCY,
              obs_names.SERVE_QUEUE_WAIT, obs_names.SERVE_KV_OCCUPANCY):
        assert n in obs_names.HISTOGRAMS
    assert obs_names.SERVE_KV_PAGES_IN_USE in obs_names.GAUGES


# --- structured log lines ---------------------------------------------------


def test_log_format_event():
    line = obs_log.format_event("bucket", i=3, spec="cloq/4b/g16/r8",
                                s=0.123456)
    assert line == "[bucket] i=3 spec=cloq/4b/g16/r8 s=0.1235"
    assert obs_log.format_event("done", "all good") == "[done] all good"


def test_log_sink_swap_and_level():
    got = []
    obs_log.set_sink(got.append)
    try:
        obs_log.set_level("warn")
        obs_log.info("quiet", x=1)
        obs_log.warn("loud", x=2)
        assert got == ["[loud] x=2"]
    finally:
        obs_log.set_sink(None)
        obs_log.set_level("info")


# --- session wiring ---------------------------------------------------------


def test_session_exports_trace_and_metrics(tmp_path):
    from repro import obs
    tpath, mpath = tmp_path / "t.json", tmp_path / "m.json"
    with obs.session(tpath, mpath):
        assert obs_trace.is_enabled()
        with obs_trace.span("work", step=1):
            obs_metrics.counter(obs_names.TRAIN_STEPS).inc()
    assert not obs_trace.is_enabled()
    trace = json.loads(tpath.read_text())
    assert any(e["name"] == "work" for e in trace["traceEvents"])
    snap = json.loads(mpath.read_text())
    assert snap["counters"][obs_names.TRAIN_STEPS] == 1


def test_session_exports_on_exception(tmp_path):
    from repro import obs
    tpath = tmp_path / "t.json"
    with pytest.raises(RuntimeError):
        with obs.session(tpath, None):
            with obs_trace.span("doomed"):
                pass
            raise RuntimeError("crash")
    assert any(e["name"] == "doomed"
               for e in json.loads(tpath.read_text())["traceEvents"])
