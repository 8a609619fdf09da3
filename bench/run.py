"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
a slice of the window with the profiler and reports its per-layer metrics,
the device's busy time and a breakdown.  The last stdout line is one JSON
object; each number the correctness check compared is printed beside its
limit as the last lines of stderr and under ``checks`` in that object.
Exits non-zero, with no result, unless JAX's first device is a TPU and it
sees the chips the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_start=T_START)
    except harness.NoDevice as e:
        print(e.code, file=sys.stderr, flush=True)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
