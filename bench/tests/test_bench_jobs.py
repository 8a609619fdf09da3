"""Each job driver end to end on the CPU at a smoke size: the harness
prints its result line, names the CPU as the device, and prints no
device metric."""
import io
import json
import time

import bench_tiny
import pytest

from bench import harness

SPEC = harness.spec()
DEVICE_SOURCES = ("device_trace",)


@pytest.fixture(autouse=True)
def keep_cache_config():
    """``harness.run`` points JAX's compile cache at the checkout; put the
    process's setting back for the tests that run after these."""
    import jax
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.fixture(autouse=True)
def smoke_limits(monkeypatch):
    bench_tiny.patch_limits(monkeypatch)


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_job_runs_end_to_end_on_cpu(wl, trace, monkeypatch):
    bench_tiny.patch_traffic(monkeypatch)
    out = io.StringIO()
    result = harness.run(wl["name"], 2**31 + 11, 1.0, trace,
                         t_start=time.perf_counter(), allow_cpu=True,
                         overrides=bench_tiny.config_for(wl["name"]),
                         out=out)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == result
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    names = set(result["metrics"])
    if trace:
        device = {m["name"] for m in SPEC["per_layer"]
                  if m["source"] in DEVICE_SOURCES or "mfu" in m["name"]}
        assert not names & device
    else:
        want = {m["name"] for m in SPEC["end_to_end"]
                if harness.applies(m, wl["name"])}
        assert names == want
        assert all(v["value"] > 0 for v in result["metrics"].values())
