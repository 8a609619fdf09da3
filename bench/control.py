"""Read the correctness numbers of sound runs, of the control and of
planted faults, over many seeds in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30 \\
        [--faults wrong_slot,altered_token]

The cell's job (``bench/jobs/<job>.py``, found by the traffic file's
``job``) reads, for each seed, ``Job.control(cell, faults)``: serving
serves the cell for ``--seconds`` at its own load and replays the sampled
requests through the reference in float32 (the program's reading) and in
float8 (the control: the gap of the token float8 puts first), then
serves again with each named fault of the job's ``FAULTS`` planted under
the engine; training runs the program's checked steps, the reference in
float32 and in float8 in the program's place, and the program again with
each named fault planted in its feed.

Each line is one JSON object, with each reading's verdict under the
job's ``LIMITS``.  The benchmark's own runs never run this: it is how their
limits were set (PERF.md gives the readings).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--faults", default="",
                    help="comma-separated names from the job's FAULTS")
    args = ap.parse_args(argv)
    from bench import harness
    bench = harness.spec()
    wl = harness.find(bench["workloads"], args.workload, "workload")
    harness.check_device(wl["chips"])
    from repro.launch.jax_cache import enable_compilation_cache
    enable_compilation_cache()
    fault_names = [f for f in args.faults.split(",") if f]
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(args.workload, harness.config_file(wl["config"]),
                            harness.traffic_file(wl["traffic"]), seed,
                            args.seconds, False)
        t0 = time.perf_counter()
        job = harness.job_class(cell.traffic["job"])
        for got in job.control(cell, fault_names):
            print(json.dumps({"seed": seed, **got,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
