"""A cell of a new configuration, traffic mix, job, reference and smoke
size runs from files alone: the harness finds each by its name in
``BENCHMARK.json``, and nothing of the benchmark's code names it."""
import io
import json
import os
import shutil
import sys
import textwrap
import time

import bench_tiny
import pytest

from bench import harness

WL = "tiny-dense.forward.greedy"
# the checkout's own reference module, a copy of the dense one
REF = "plain"

# a job the test defines: the program's forward over rows drawn from the
# seed, timed in the window, against the reference the configuration names
JOB = '''\
    """The program's forward against the reference's greedy choice."""
    import time

    import numpy as np

    from bench import weights
    from bench.jobs import common

    LIMITS = {"forward_logit_gap": 1.0}
    FAULTS = {"shifted_token": lambda job: setattr(job, "shift", 1)}


    class Job:
        fault = None

        def __init__(self, cell):
            self.cell = cell
            self.rows = int(cell.traffic["rows"])
            self.seq = int(cell.traffic["seq_len"])
            self.shift = 0

        def setup(self):
            import jax
            import jax.numpy as jnp
            from repro.models.transformer import forward
            cfg = common.model_config(self.cell.config)
            self.params, _ = common.packed_base(cfg, self.cell.config,
                                                self.cell.seed, rank=0)
            self.tokens = jax.random.randint(
                jax.random.PRNGKey(self.cell.seed), (self.rows, self.seq),
                0, cfg.vocab)
            self.fwd = jax.jit(lambda p, t: jnp.argmax(
                forward(p, cfg, {"tokens": t})[0][..., :cfg.vocab], -1))
            self.fwd(self.params, self.tokens).block_until_ready()
            if self.fault:
                self.fault(self)

        def window(self, tracer):
            self.n, t0 = 0, time.perf_counter()
            tracer.start()
            while self.n == 0 or time.perf_counter() - t0 < self.cell.seconds:
                with tracer.span("bench.forward"):
                    out = self.fwd(self.params, self.tokens)
                out.block_until_ready()
                self.n += 1
            tracer.stop()
            self.window_s = time.perf_counter() - t0
            self.served = np.asarray(out) + self.shift

        def counts(self):
            return self.n, 0

        def end_to_end(self):
            return {"forward_rows_per_s": self.n * self.rows / self.window_s}

        def reading(self, red, peaks):
            return {"job": "forward", "step_ms": 1e3 * self.window_s / self.n}

        def release(self):
            del self.params

        def check(self):
            import jax.numpy as jnp
            ref = common.reference(self.cell.config)
            dims = ref.Dims.of(self.cell.config)
            key = weights.seed_key(self.cell.seed)
            x = ref.embedding(key, dims)[self.tokens]
            for layer in range(dims.n_layers):
                x = ref.block(ref.EXACT, dims, x,
                              ref.base_layer(key, dims, layer), {})
            logits = ref.EXACT.einsum("ntd,dv->ntv", ref.final_norm(x),
                                      ref.head(key, dims))
            served = jnp.take_along_axis(
                logits, jnp.asarray(self.served)[..., None] % dims.vocab,
                -1)[..., 0]
            self.gap = float(jnp.max(jnp.max(logits, -1) - served))
            return [{"name": "forward_logit_gap", "value": self.gap,
                     "limit": LIMITS["forward_logit_gap"]}]
'''

READER = '''\
    """One forward of the window, in ms."""


    def read(run):
        return run["step_ms"] if run["job"] == "forward" else None
'''


def _write(root, rel, content):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(content, str):
            f.write(textwrap.dedent(content))
        else:
            json.dump(content, f, indent=1)


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A checkout in ``tmp_path`` that holds one new cell as files: its
    ``BENCHMARK.json``, configuration, reference, mix, job, per-layer
    reader and smoke size.  The harness reads it as it reads its own."""
    import jax
    root = str(tmp_path)
    config = dict(harness.config_file("qwen3-1.7b"), name="tiny-dense",
                  reference=REF)
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 10,
        "configs": [{"name": "tiny-dense", "source": config["source"],
                     "file": "bench/configs/tiny-dense.json", "reduced": [],
                     "why": "a second configuration naming its reference"}],
        "workloads": [{"name": WL, "config": "tiny-dense",
                       "traffic": "forward.greedy", "chips": 1,
                       "why": "a job, mix and smoke size as files only"}],
        "end_to_end": [
            {"name": "forward_rows_per_s", "unit": "rows/s",
             "better": "higher", "bound": 0.01, "source": "host_clock",
             "workloads": [WL]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "forward.step_ms", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "models/forward",
             "moves": "forward_rows_per_s", "workloads": [WL]}],
    }
    _write(root, "BENCHMARK.json", spec)
    _write(root, "bench/configs/tiny-dense.json", config)
    _write(root, "bench/traffic/forward.greedy.json",
           {"job": "forward", "why": "greedy forward of rows from the seed",
            "rows": 64, "seq_len": 128})
    _write(root, "bench/jobs/forward.py", JOB)
    with open(os.path.join(harness.BENCH, "reference", "dense.py")) as f:
        _write(root, f"bench/reference/{REF}.py", f.read())
    _write(root, "bench/layers/forward.step_ms.py", READER)
    _write(root, f"bench/tests/tiny/{WL}.json", {
        "why": "two narrow layers and short rows",
        "config": {"n_layers": 2, "d_model": 128, "n_heads": 2,
                   "n_kv_heads": 1, "head_dim": 64, "d_ff": 256,
                   "vocab": 512, "vocab_pad_multiple": 256},
        "traffic": {"rows": 2, "seq_len": 16},
        "limits": {"forward_logit_gap": 0.05}})
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", os.path.join(root, "bench"))
    monkeypatch.setattr(harness, "TRACE_DIR",
                        os.path.join(root, ".bench_trace"))
    # the checkout's own directory first, as bench/run.py puts it
    monkeypatch.syspath_prepend(root)
    prev = jax.config.jax_compilation_cache_dir
    yield root
    jax.config.update("jax_compilation_cache_dir", prev)
    sys.modules.pop("bench.jobs.forward", None)
    sys.modules.pop(f"bench.reference.{REF}", None)
    shutil.rmtree(os.path.join(root, ".bench_trace"), ignore_errors=True)


def _run(seed, trace=False):
    out = io.StringIO()
    result = harness.run(WL, seed, 0.3, trace, t_start=time.perf_counter(),
                         allow_cpu=True, overrides=bench_tiny.config_for(WL),
                         out=out)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == result
    return result


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_new_cell_runs_from_files_alone(checkout, trace, monkeypatch):
    bench_tiny.patch(monkeypatch, WL)
    job = bench_tiny.job_module(WL)
    assert job.__file__ == os.path.join(checkout, "bench", "jobs",
                                        "forward.py")
    assert job.LIMITS == {"forward_logit_gap": 0.05}
    from bench.jobs import common
    ref = common.reference(harness.config_file("tiny-dense"))
    assert ref.__file__ == os.path.join(checkout, "bench", "reference",
                                        f"{REF}.py")
    result = _run(2**31 + 3, trace)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    names = {"forward.step_ms"} if trace else {"forward_rows_per_s",
                                               "setup_s"}
    assert set(result["metrics"]) == names
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_new_cell_check_fails_a_fault_of_its_job(checkout, monkeypatch):
    bench_tiny.patch(monkeypatch, WL)
    job = bench_tiny.job_module(WL)
    monkeypatch.setattr(job.Job, "fault",
                        staticmethod(job.FAULTS["shifted_token"]))
    result = _run(2**31 + 4)
    assert result["correct"] is False
