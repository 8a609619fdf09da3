"""The correctness check fails where the timed path is broken underneath,
and its control (the reference in float8, in the program's place) comes
out not correct.  The fault tests drive a whole run through the harness,
past its look for a chip, and see ``correct`` come out false."""
import io
import time

import bench_tiny
import pytest

from bench import harness
from bench.jobs import common

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


@pytest.fixture
def serve(monkeypatch):
    """The serve cell's job module, held to the smoke size."""
    bench_tiny.patch(monkeypatch, SERVE)
    return bench_tiny.job_module(SERVE)


@pytest.fixture
def train(monkeypatch):
    """The fine-tune cell's job module, held to the smoke size."""
    bench_tiny.patch(monkeypatch, TRAIN)
    return bench_tiny.job_module(TRAIN)


def _run(workload, seed):
    return harness.run(workload, seed, 1.0, False,
                       t_start=time.perf_counter(), allow_cpu=True,
                       overrides=bench_tiny.config_for(workload),
                       out=io.StringIO())


def _plant(monkeypatch, mod, name):
    monkeypatch.setattr(mod.Job, "fault", staticmethod(mod.FAULTS[name]))


def test_serve_altered_token_fails_the_check(serve, monkeypatch):
    _plant(monkeypatch, serve, "altered_token")
    result = _run(SERVE, 21)
    assert result["correct"] is False
    c = result["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault", ["wrong_slot", "zero_adapters"])
def test_serve_adapter_gather_fault_fails_the_check(fault, serve,
                                                    monkeypatch):
    _plant(monkeypatch, serve, fault)
    result = _run(SERVE, 23)
    assert result["correct"] is False
    c = result["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]


def _serve(mod, seed):
    job = mod.Job(bench_tiny.cell(SERVE, seed))
    job.setup()
    job.window(harness.Tracer(False))
    job.release()
    return job


def test_serve_control_reads_above_the_program(serve):
    job = _serve(serve, 22)
    control = job.check(control=True)
    assert not harness.passed(control)
    g = job.gap_readings
    assert g["program"] <= serve.LIMITS["served_logit_gap"] < g["control"]


def _train(mod, seed):
    job = mod.Job(bench_tiny.cell(TRAIN, seed))
    job.setup()
    job.release()
    return job


def _failed(mod, readings):
    return [k for k, v in mod.LIMITS.items() if readings[k] > v]


def test_train_sound_run_passes(train):
    job = _train(train, 31)
    assert harness.passed(job.check())


def test_train_state_left_unchanged_fails_the_check(train):
    job = _train(train, 32)
    ref = train.reference_run(job, common.reference(job.cell.config).EXACT)
    got = train.program_run(job)
    got["after"] = {k: ref["p0"][k].copy() for k in got["after"]}
    assert "update_norm_rel" in _failed(train, train.compare(got, ref))


def test_train_half_batch_fails_the_check(train, monkeypatch):
    _plant(monkeypatch, train, "half_batch")
    result = _run(TRAIN, 33)
    assert result["correct"] is False


def test_train_control_reads_above_the_program(train):
    job = _train(train, 34)
    assert harness.passed(job.check())
    assert not harness.passed(job.check(control=True))
