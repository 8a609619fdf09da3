#!/usr/bin/env python
"""Benchmark-floor checker: the perf numbers in ``results/*.json`` must not
regress below their gated floors.

Pinned-row tests (``tests/test_perf_levers.py``) guard the *schema* of the
result files; this tool guards the *values*, so a refactor that silently
loses a speedup fails verification even when every test stays green:

* ``table10_init_cost.json -> loftq_sharded_row.speedup >= 1.0`` — the
  cost-model planner must keep choosing the faster execution path for its
  historical misprediction (chosen-vs-worst ratio, so < 1.0 means the
  planner picked the slower path again);
* ``table10_init_cost.json -> cold_start_row.speedup > 1.0`` — a warm
  persisted compile cache must keep beating a cold process start;
* ``serve_bench.json -> speedup >= 3.0`` — the continuous-batching serving
  engine must stay well ahead of the static-slot baseline;
* ``table10_init_cost.json -> obs_overhead_row.overhead_pct <= 20.0`` — a
  ceiling, not a floor: span tracing must stay cheap
  enough to leave on for any diagnostic run;
* ``metrics-*.json`` counter floors — the benchmark runs must actually
  exercise what they claim (warm compile-cache hits, finished serve
  requests), asserted on the ``repro.obs`` metrics snapshots the
  benchmarks persist alongside their result tables.

Wired into the verify skill (`.claude/skills/verify/SKILL.md`):

    python tools/check_bench.py

A MISSING result file is reported but non-fatal (benchmarks are
regenerated on demand, not checked into every environment); a
present-but-regressed value fails.  Exit codes follow
:mod:`tools.checklib`: 0 clean, 1 floor violation, 2 usage error.
"""
from __future__ import annotations

import json
import operator
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools import checklib  # noqa: E402

RESULTS = REPO / "results"

_OPS = {">=": operator.ge, ">": operator.gt, "<=": operator.le,
        "<": operator.lt}

# (file, dotted key path, bound, op) — op is the comparison the measured
# value must satisfy against the bound (">=" floor, "<=" ceiling, ...)
FLOORS = [
    ("table10_init_cost.json", "loftq_sharded_row.speedup", 1.0, ">="),
    ("table10_init_cost.json", "cold_start_row.speedup", 1.0, ">"),
    ("table10_init_cost.json", "obs_overhead_row.overhead_pct",
     20.0, "<="),
    ("serve_bench.json", "speedup", 3.0, ">="),
    # metrics-snapshot counters: the runs must have exercised the paths
    ("metrics-table10.json", "counters.compile_cache.hits", 0.0, ">"),
    ("metrics-table10.json", "counters.quant.buckets", 0.0, ">"),
    ("metrics-serve_bench.json",
     "counters.serve.requests_finished", 0.0, ">"),
    ("metrics-serve_bench.json", "counters.serve.tokens", 0.0, ">"),
]


def _lookup(obj, dotted: str):
    """Resolve ``dotted`` greedily: metric names contain dots, so at each
    level prefer the longest prefix that is a key of the current dict."""
    while dotted:
        if not isinstance(obj, dict):
            raise KeyError(dotted)
        if dotted in obj:
            return obj[dotted]
        parts = dotted.split(".")
        for i in range(len(parts) - 1, 0, -1):
            head = ".".join(parts[:i])
            if head in obj:
                obj, dotted = obj[head], ".".join(parts[i:])
                break
        else:
            raise KeyError(dotted)
    return obj


def _floor_check(fname: str, key: str, bound: float,
                 op: str) -> checklib.Check:
    name = f"{fname}:{key}"
    cmp = _OPS[op]

    def check() -> checklib.CheckResult:
        path = RESULTS / fname
        if not path.exists():
            return checklib.CheckResult(name, skipped=True,
                                        detail="not generated")
        try:
            value = float(_lookup(json.loads(path.read_text()), key))
        except (KeyError, TypeError, ValueError) as e:
            return checklib.CheckResult(
                name, errors=[f"cannot read {key!r} ({e!r})"])
        if not cmp(value, bound):
            return checklib.CheckResult(
                name, errors=[f"{key} = {value} violates bound "
                              f"{op} {bound}"])
        return checklib.CheckResult(name,
                                    detail=f"{value} ({op} {bound})")
    check.__name__ = name
    return check


def main(argv=None) -> int:
    checklib.make_parser("check_bench.py",
                         "perf floors over results/*.json").parse_args(argv)
    return checklib.run_checks(
        "bench", [_floor_check(*f) for f in FLOORS])


if __name__ == "__main__":
    sys.exit(main())
