"""A smoke size of the benchmark's cells for CPU tests: the published
configuration with its widths and depth cut down, and short traffic."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# serving runs the Pallas kernels in interpret mode, so it stays narrow,
# though wide enough, and with outputs long enough, that the float8
# control and a wrong tenant's adapter show in the served tokens;
# fine-tuning is wide enough that a leaf's norm averages the bf16
# rounding about as the cell's leaves do relative to its limits (the
# vocabulary differs from the width: the program's tied head is told
# from an untied one by its shape)
CONFIG = {
    "serve": {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 64, "d_ff": 512, "vocab": 1024,
              "vocab_pad_multiple": 256},
    "train": {"n_layers": 2, "d_model": 512, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 128, "d_ff": 1024, "vocab": 1024,
              "vocab_pad_multiple": 256, "train_batch": 2, "lora_rank": 16},
}

TRAFFIC = {
    "serve": {"engine": {"bucket_capacity": 4, "page_size": 8,
                         "max_len": 64},
              "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                         "min": 2, "max": 24},
              "output": {"dist": "uniform", "min": 8, "max": 16},
              "rate_per_s": 6.0, "block_s": 2.0},
    "train": {"seq_len": 256},
}


# the correctness limits at this size (the cell's own, set from readings
# on the chip at its size, are in the job modules); served gap at seeds
# 21-23: program 0.0-0.013, float8 0.14-0.18, a wrong slot 0.22-0.26, the
# rank-64 bucket zeroed 0.09-0.13; the fine-tune limits are those that
# sound runs pass and the float8 control and the faults fail here
SERVE_GAP_LIMIT = 0.1
TRAIN_LIMITS = {"loss_rel": 2e-4, "grad_norm_rel": 3e-3,
                "update_norm_rel": 1.5e-3}


def patch_limits(monkeypatch):
    """Hold the smoke size's readings to the smoke size's limits."""
    from bench.jobs import serve, train
    monkeypatch.setattr(serve, "GAP_LIMIT", SERVE_GAP_LIMIT)
    monkeypatch.setattr(train, "LIMITS", dict(TRAIN_LIMITS))


def patch_traffic(monkeypatch):
    """Make ``harness.traffic_file`` return the smoke size of each mix."""
    from bench import harness
    orig = harness.traffic_file

    def small(name):
        mix = orig(name)
        return dict(mix, **TRAFFIC[mix["job"]])
    monkeypatch.setattr(harness, "traffic_file", small)


def config_for(workload: str) -> dict:
    """The smoke-size overrides of ``workload``'s configuration."""
    from bench import harness
    wl = harness.find(harness.spec()["workloads"], workload, "workload")
    return CONFIG[harness.traffic_file(wl["traffic"])["job"]]


def cell(workload: str, seed: int, seconds: float = 1.0):
    """A ``harness.Cell`` of ``workload`` at the smoke size."""
    from bench import harness
    wl = harness.find(harness.spec()["workloads"], workload, "workload")
    mix = harness.traffic_file(wl["traffic"])
    mix = dict(mix, **TRAFFIC[mix["job"]])
    config = dict(harness.config_file(wl["config"]), **CONFIG[mix["job"]])
    return harness.Cell(workload, config, mix, seed, seconds, False)
