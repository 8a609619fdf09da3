"""A smoke size of the benchmark's cells for CPU tests.

Each cell has ``bench/tests/tiny/<workload>.json``: its ``config``
(keys of the configuration file replaced: widths and depth cut down),
its ``traffic`` (keys of the mix replaced: short traffic) and its
``limits`` (the correctness limits at this size, which replace the job
module's ``LIMITS``; the cell's own, set from readings on the chip at its
size, are in the job module), with a ``why``."""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def path(workload: str) -> str:
    """Where ``workload``'s smoke size lives, under the harness's
    ``bench`` directory."""
    from bench import harness
    return os.path.join(harness.BENCH, "tests", "tiny", f"{workload}.json")


def tiny(workload: str) -> dict:
    with open(path(workload)) as f:
        return json.load(f)


def _entry(workload: str) -> dict:
    from bench import harness
    return harness.find(harness.spec()["workloads"], workload, "workload")


def mix(workload: str) -> dict:
    """``workload``'s traffic mix at the smoke size."""
    from bench import harness
    return dict(harness.traffic_file(_entry(workload)["traffic"]),
                **tiny(workload)["traffic"])


def job_module(workload: str):
    """The module of ``workload``'s job, found by its traffic's ``job``."""
    from bench import harness
    return harness.job_module(mix(workload)["job"])


def patch(monkeypatch, workload: str) -> None:
    """Hold ``workload``'s job to the smoke size's limits, and make
    ``harness.traffic_file`` return the smoke size of its mix."""
    from bench import harness
    small = tiny(workload)
    traffic = _entry(workload)["traffic"]
    monkeypatch.setattr(job_module(workload), "LIMITS",
                        dict(small["limits"]))
    orig = harness.traffic_file

    def smoke(name):
        mix = orig(name)
        return dict(mix, **small["traffic"]) if name == traffic else mix
    monkeypatch.setattr(harness, "traffic_file", smoke)


def config_for(workload: str) -> dict:
    """The smoke-size overrides of ``workload``'s configuration."""
    return dict(tiny(workload)["config"])


def cell(workload: str, seed: int, seconds: float = 1.0):
    """A ``harness.Cell`` of ``workload`` at the smoke size."""
    from bench import harness
    config = dict(harness.config_file(_entry(workload)["config"]),
                  **config_for(workload))
    return harness.Cell(workload, config, mix(workload), seed, seconds,
                        False)
