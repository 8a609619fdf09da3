"""The program's spans and layer scopes in a profiler trace: the xplane
reader, the tables built from them, and the per-layer metrics that read
those tables."""
import dataclasses
import gzip
import os
import shutil

import bench_tiny  # noqa: F401
import pytest

from bench import harness, program_trace, trace_reduce, xplane


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


DATA = os.path.join(os.path.dirname(__file__), "data")
# two engine steps of the serve cell at 2 of its 28 layers, traced on one
# TPU v5 lite: before the program's spans and scopes reached the trace,
# and with them
RECORDED = os.path.join(DATA, "serve-2layers.v5e.xplane.pb.gz")
RECORDED_SCOPES = os.path.join(DATA, "serve-2layers-scopes.v5e.xplane.pb.gz")


def _unzip(gz: str, directory) -> str:
    path = os.path.join(directory, "serve.xplane.pb")
    with gzip.open(gz) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def recorded_path(tmp_path_factory):
    return _unzip(RECORDED, tmp_path_factory.mktemp("trace"))


@pytest.fixture(scope="module")
def recorded_scopes_path(tmp_path_factory):
    return _unzip(RECORDED_SCOPES, tmp_path_factory.mktemp("scopes"))


def _program_planes():
    ms = 1e6
    dev0 = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev("fusion.1", 0, 2 * ms), Ev("fusion.2", 5 * ms, 3 * ms),
        Ev("fusion.3", 9 * ms, 1 * ms)])])
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.engine_step", 0, 10 * ms),
        Ev("serve.step", 0, 9.5 * ms),
        Ev("serve.prepare", 1 * ms, 2.5 * ms),
        Ev("serve.upload", 3.5 * ms, 1 * ms),
        Ev("serve.commit", 8 * ms, 1.5 * ms),
        Ev("$builtins isinstance", 2 * ms, 0.1 * ms)])])
    return [host, dev0]


def test_program_gaps_take_the_innermost_program_span_at_their_middle():
    gaps, _ = program_trace.span_tables(_program_planes())
    # chip 0 idles over [2, 5] (middle 3.5: upload opens there, and is
    # the shorter) and [8, 9] (middle 8.5: commit)
    assert gaps == {"serve.upload": pytest.approx(0.003),
                    "serve.commit": pytest.approx(0.001)}
    # the benchmark's own labels are unchanged by the program's spans
    red = trace_reduce.reduce_planes(_program_planes(), 0.010)
    assert red.gaps == {"bench.engine_step": pytest.approx(0.004)}


def test_span_idle_is_chip0_idle_inside_each_program_span():
    _, idle = program_trace.span_tables(_program_planes())
    # serve.step [0, 9.5] holds idle [2, 5] and [8, 9]
    assert idle["serve.step"] == [1, pytest.approx(0.004)]
    # serve.prepare [1, 3.5] holds idle [2, 3.5]; commit [8, 9.5] [8, 9]
    assert idle["serve.prepare"] == [1, pytest.approx(0.0015)]
    assert idle["serve.commit"] == [1, pytest.approx(0.001)]
    assert "bench.engine_step" not in idle


def test_span_tables_are_empty_without_a_device_plane():
    host, _dev = _program_planes()
    assert program_trace.span_tables([host]) == ({}, {})


@pytest.mark.parametrize("tf_op,want", [
    ("jit(step_fn)/while/body/attn/q/dequant/and:",
     ["step_fn", "while", "body", "attn", "q", "dequant", "and:"]),
    ("jit(train_step)/transpose(jvp(lm_head))/dot_general",
     ["train_step", "lm_head", "dot_general"]),
    ("jit(train_step)/jvp()/while/body/rematted_computation/attn/mul",
     ["train_step", "", "while", "body", "rematted_computation", "attn",
      "mul"]),
])
def test_components_take_transforms_off_scope_names(tf_op, want):
    assert xplane.components(tf_op) == want


def test_scope_seconds_key_ops_by_metadata_id_and_skip_enclosing_ops():
    X = xplane
    names = {1: "%fusion.9 = bf16[8]{0} fusion(%a)",
             2: "%fusion.9 = bf16[8]{0} fusion(%b)",
             3: "%while.1 = (s32[]) while(%t)"}
    stats = {1: {X.TF_OP: "jit(step_fn)/kv_cache/gather:",
                 X.CATEGORY: "loop fusion"},
             2: {X.TF_OP: "jit(step_fn)/jit(_take)/lora_gather/gather:",
                 X.CATEGORY: "loop fusion"},
             3: {X.TF_OP: "jit(step_fn)/while:", X.CATEGORY: "while"}}
    events = [X.Event(1, 0, 2e6), X.Event(2, 2e6, 1e6), X.Event(1, 3e6, 2e6),
              X.Event(3, 0, 9e6)]
    planes = [X.Plane("/device:TPU:0", [X.Line("XLA Ops", events)], names,
                      stats),
              X.Plane("/host:CPU", [], {}, {})]
    got = program_trace.scope_seconds(planes)
    assert got["kv_cache"] == pytest.approx(0.004)      # two calls of id 1
    assert got["lora_gather"] == pytest.approx(0.001)   # same name, id 2
    assert got["step_fn"] == pytest.approx(0.005)       # the while left out
    assert "gather:" not in got and "while" not in got


@pytest.mark.parametrize("op_prefix,tf_op", [
    ("%dequant_matmul.57 ",
     "jit(step_fn)/while/body/closed_call/jit(dequant_matmul)/pallas_call:"),
    ("%fusion.131 ", "jit(step_fn)/jit(_take)/gather:"),
])
def test_reader_returns_tf_op_of_ops_in_a_recorded_trace(recorded_path,
                                                         op_prefix, tf_op):
    (dev,) = xplane.read(recorded_path, trace_reduce.DEVICE_PREFIX,
                         trace_reduce.OPS_LINE)
    assert dev.name == "/device:TPU:0"
    assert [ln.name for ln in dev.lines] == [trace_reduce.OPS_LINE]
    ids = [m for m, n in dev.names.items() if n.startswith(op_prefix)]
    assert ids and {dev.stats[m][xplane.TF_OP] for m in ids} == {tf_op}
    # every op event points at an entry of the plane's metadata
    assert all(ev.metadata_id in dev.names for ev in dev.lines[0].events)


def test_current_reads_the_newest_trace_once(tmp_path, recorded_path,
                                             monkeypatch):
    assert program_trace.current(str(tmp_path)) is None
    shutil.copy(recorded_path, tmp_path / "a.xplane.pb")
    got = program_trace.current(str(tmp_path))
    assert got is program_trace.current(str(tmp_path))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    assert program_trace.current() is got


NEW_READERS = ("serve.prefill_p95_ms", "serve.step_idle_ms",
               "serve.kv_cache_ms", "serve.adapter_gather_ms",
               "train.attn_ms", "train.dequant_ms")


class _Spans:
    """The program's tracer as the readers see it: its recorded events,
    in its chrome-trace form (microseconds)."""

    def __init__(self):
        self.recorded = []

    def events(self):
        return list(self.recorded)

    def add(self, name: str, seconds: float, **args):
        self.recorded.append({"name": name, "ph": "X", "ts": 0.0,
                              "dur": seconds * 1e6, "args": args})


@pytest.fixture
def fresh_tracer(monkeypatch):
    """An empty tracer in place of the program's module tracer."""
    from repro.obs import trace
    spans = _Spans()
    monkeypatch.setattr(trace, "get_tracer", lambda: spans)
    return spans


def _run(job, **kw):
    return {"job": job, "steps": [[(16, [1])]] * 2,
            "queue_waits_ms": [0.1] * 20, **kw}


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_read_nothing_from_a_program_without_spans(
        metric, recorded_path, fresh_tracer, tmp_path, monkeypatch):
    """A program without the spans and scopes (the parent of the change
    that added them) gives nothing to read, and the reader says so."""
    shutil.copy(recorded_path, tmp_path / "parent.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    read = harness.layer_reader(metric)
    assert read(_run(metric.split(".")[0])) is None


@pytest.mark.parametrize("metric,want", [
    ("serve.step_idle_ms", 4.0), ("serve.kv_cache_ms", 1.5),
    ("serve.adapter_gather_ms", 0.25), ("train.attn_ms", 5.0),
    ("train.dequant_ms", 0.5), ("serve.prefill_p95_ms", 19.0)])
def test_new_readers_scale_to_one_step(metric, want, fresh_tracer,
                                       monkeypatch):
    gaps, idle = program_trace.span_tables(_program_planes())
    tables = program_trace.ProgramTrace(
        gaps, idle, {"kv_cache": 0.003, "lora_gather": 0.0005,
                     "attn": 0.010, "dequant": 0.001})
    monkeypatch.setattr(program_trace, "current", lambda: tables)
    # 25 admitted requests, 20 of them due in the window: those take
    # 1..20 ms from admission to first token, the rest far longer
    for rid in range(25):
        fresh_tracer.add("serve.queue", 0.0, rid=rid)
        fresh_tracer.add("serve.prefill",
                         (rid + 1) * 1e-3 if rid < 20 else 9.0, rid=rid)
    got = harness.layer_reader(metric)(_run(metric.split(".")[0]))
    assert got == pytest.approx(want)


@pytest.fixture(scope="module")
def recorded_scopes(recorded_scopes_path):
    from jax.profiler import ProfileData
    red = trace_reduce.reduce_planes(
        ProfileData.from_file(recorded_scopes_path).planes, 1.0)
    return red, program_trace.read_file(recorded_scopes_path)


@pytest.mark.parametrize("scope", ["kv_cache", "lora_gather", "attn", "mlp",
                                   "lm_head", "embed"])
def test_a_recorded_v5e_trace_attributes_device_time_to_scopes(
        recorded_scopes, scope):
    red, tables = recorded_scopes
    secs = tables.scope_seconds.get(scope, 0.0)
    assert 0 < secs < red.busy_s


def test_a_recorded_v5e_trace_holds_the_programs_step_spans(recorded_scopes):
    red, tables = recorded_scopes
    steps, idle = tables.span_idle["serve.step"]
    assert steps == 2 and 0 < idle < 1.0
    # each step runs both rank buckets: a decode, an upload, a dispatch
    # and a sync each
    for child in ("serve.decode", "serve.upload", "serve.dispatch",
                  "serve.sync", "serve.prepare"):
        assert tables.span_idle[child][0] == 4
    assert set(tables.program_gaps) <= {*tables.span_idle,
                                        program_trace.OTHER}
    # the kernels' names and call counts are those of the first recording
    assert red.kernel("dequant")[1] == 56 and red.kernel("flash")[1] == 8
