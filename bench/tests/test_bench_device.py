"""The benchmark refuses a machine without the chips its cell asks for."""
import os
import subprocess
import sys

import bench_tiny
import pytest

from bench import harness


def test_check_device_refuses_the_cpu():
    with pytest.raises(harness.NoDevice):
        harness.check_device(1)


def test_run_exits_nonzero_and_prints_no_result_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(bench_tiny.ROOT, "bench", "run.py"),
         "--workload", harness.spec()["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=bench_tiny.ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr
