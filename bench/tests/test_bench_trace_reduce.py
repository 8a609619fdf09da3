"""The reduction from a profiler trace to busy time, op times and
labelled idle gaps."""
import dataclasses
import gzip
import importlib.util
import os
import shutil

import bench_tiny  # noqa: F401
import pytest

from bench import harness, trace_reduce


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


def _planes():
    ms = 1e6
    dev0 = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step", 0, 10 * ms)]),
        Line("XLA Ops", [Ev("fusion.1", 0, 2 * ms), Ev("dequant_k", 1 * ms,
                                                      2 * ms),
                         Ev("fusion.1", 6 * ms, 1 * ms),
                         Ev("dequant_k", 9 * ms, 1 * ms)])])
    dev1 = Plane("/device:TPU:1", [
        Line("XLA Ops", [Ev("fusion.1", 0, 4 * ms)])])
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.engine_step", 0, 8 * ms), Ev("bench.submit", 7 * ms,
                                              0.5 * ms),
        Ev("other", 3 * ms, 1 * ms)])])
    return [host, dev1, dev0]


def test_busy_is_the_union_of_ops_averaged_over_chips():
    red = trace_reduce.reduce_planes(_planes(), 0.010)
    # chip 0: [0, 3] + [6, 7] + [9, 10] = 5 ms; chip 1: 4 ms
    assert red.n_chips == 2
    assert red.busy_s == pytest.approx(0.0045)
    assert red.op_seconds["dequant_k"] == pytest.approx(0.003)
    assert red.kernel("dequant") == (pytest.approx(0.0015), 1)


def test_gaps_take_the_innermost_bench_span_open_at_their_middle():
    red = trace_reduce.reduce_planes(_planes(), 0.010)
    # chip 0 idles over [3, 6] and [7, 9]; at their middles (4.5 and 8)
    # engine_step is open, and "other" is not one of the benchmark's spans
    assert red.gaps["bench.engine_step"] == pytest.approx(0.003 + 0.002)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(0.007)]
    assert len(bd["idle_gaps"]) <= trace_reduce.TOP


# two engine steps of the serve cell at 2 of its 28 layers, traced on one
# TPU v5 lite with a `bench.engine_step` span around each
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "serve-2layers.v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "serve.xplane.pb"
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    from jax.profiler import ProfileData
    return trace_reduce.reduce_planes(ProfileData.from_file(str(path)).planes,
                                      1.0)


def test_a_recorded_v5e_trace_has_one_busy_chip(recorded):
    assert recorded.n_chips == 1
    assert 0 < recorded.busy_s < 1.0
    assert recorded.gaps and set(recorded.gaps) <= {"bench.engine_step",
                                                    "bench.submit",
                                                    "host:other"}


@pytest.mark.parametrize("metric", ["dequant_matmul_roofline",
                                    "flash_attention_roofline"])
def test_roofline_readers_find_their_kernel_in_a_recorded_trace(recorded,
                                                                metric):
    path = os.path.join(harness.BENCH, "layers", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"needle_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    secs, calls = recorded.kernel(mod.KERNEL)
    # 2 steps x 2 rank buckets x 2 layers: 7 linears a layer for the
    # matmul kernel, one attention a layer; the ops that consume a
    # kernel's output name it among their operands and are not counted
    assert calls == {"dequant_matmul_roofline": 56,
                     "flash_attention_roofline": 8}[metric]
    assert 0 < secs < recorded.busy_s
