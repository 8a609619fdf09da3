"""The correctness check fails where the timed path is broken underneath,
and its control (the reference in float8, in the program's place) comes
out not correct.  The fault tests drive a whole run through the harness,
past its look for a chip, and see ``correct`` come out false."""
import io
import time

import bench_tiny
import pytest

from bench import faults, harness
from bench.jobs import serve, train
from bench.reference import dense

SERVE = "qwen3-1.7b.serve.multitenant"
TRAIN = "qwen3-1.7b.finetune.packed"


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


def _run(workload, seed, monkeypatch):
    bench_tiny.patch_traffic(monkeypatch)
    return harness.run(workload, seed, 1.0, False,
                       t_start=time.perf_counter(), allow_cpu=True,
                       overrides=bench_tiny.config_for(workload),
                       out=io.StringIO())


def _plant_serve(monkeypatch, name):
    setup = serve.Job.setup

    def broken(self):
        setup(self)
        faults.SERVE[name](self)
    monkeypatch.setattr(serve.Job, "setup", broken)


def test_serve_altered_token_fails_the_check(monkeypatch):
    _plant_serve(monkeypatch, "altered_token")
    result = _run(SERVE, 21, monkeypatch)
    assert result["correct"] is False
    c = result["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault", ["wrong_slot", "zero_adapters"])
def test_serve_adapter_gather_fault_fails_the_check(fault, monkeypatch):
    _plant_serve(monkeypatch, fault)
    result = _run(SERVE, 23, monkeypatch)
    assert result["correct"] is False
    c = result["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]


def _serve(seed):
    job = serve.Job(bench_tiny.cell(SERVE, seed))
    job.setup()
    job.window(harness.Tracer(False))
    job.release()
    return job


def test_serve_control_reads_above_the_program():
    job = _serve(22)
    control = job.check(control=True)
    assert not harness.passed(control)
    g = job.gap_readings
    assert g["program"] <= serve.GAP_LIMIT < g["control"]


def _train(seed, fault=None):
    job = train.Job(bench_tiny.cell(TRAIN, seed))
    job.fault = fault
    job.setup()
    job.release()
    return job


def _failed(readings):
    return [k for k, v in train.LIMITS.items() if readings[k] > v]


def test_train_sound_run_passes():
    job = _train(31)
    assert harness.passed(job.check())


def test_train_state_left_unchanged_fails_the_check():
    job = _train(32)
    ref = train.reference_run(job, dense.EXACT)
    got = train.program_run(job)
    got["after"] = {k: ref["p0"][k].copy() for k in got["after"]}
    assert "update_norm_rel" in _failed(train.compare(got, ref))


def test_train_half_batch_fails_the_check(monkeypatch):
    monkeypatch.setattr(train.Job, "fault",
                        staticmethod(faults.train_half_batch))
    result = _run(TRAIN, 33, monkeypatch)
    assert result["correct"] is False


def test_train_control_reads_above_the_program():
    job = _train(34)
    assert harness.passed(job.check())
    assert not harness.passed(job.check(control=True))
