"""Read the correctness numbers of sound runs, of the control and of
planted faults, over many seeds in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30 \\
        [--faults wrong_slot,altered_token]

Serving: for each seed the cell is set up and served for ``--seconds`` at
its own load; then the reference replays the sampled requests in float32
(the program's reading) and in float8 (the control: the gap of the token
float8 puts first).  With ``--faults`` each seed is served again with each
named fault of ``bench/faults.py`` planted under the engine.

Training: for each seed the program runs its checked steps; the reference
runs them in float32, and in float8 as the control in the program's place.
With ``--faults`` the program runs again with each named fault planted in
its feed.  A step that returns its state unchanged reads 1 on the change
of the adapters by construction and needs no run.

Each line is one JSON object, with each reading's verdict under the
cell's limits.  The benchmark's own runs never run this: it is how their
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


def _values(checks: list) -> dict:
    return {c["name"]: c["value"] for c in checks}


def serve_readings(cell, fault: str | None) -> dict:
    from bench import faults, harness
    from bench.jobs.serve import Job
    job = Job(cell)
    job.setup()
    if fault:
        faults.SERVE[fault](job)
    job.window(harness.Tracer(False))
    job.release()
    if fault:
        checks = job.check()
        return {"fault": fault, **_values(checks),
                "correct": harness.passed(checks)}
    ctrl = job.check(control=True)
    prog = [dict(c, value=job.gap_readings["program"])
            if c["name"] == "served_logit_gap" else c for c in ctrl]
    return {"program": _values(prog), "program_correct": harness.passed(prog),
            "control": _values(ctrl), "control_correct": harness.passed(ctrl),
            "finished": len(job.finished)}


def train_readings(cell, fault_names: list) -> dict:
    from bench import faults, harness
    from bench.jobs import train
    job = train.Job(cell)
    job.setup()
    job.release()
    prog = job.check()
    out = {"program": job.readings, "program_correct": harness.passed(prog)}
    ctrl = job.check(control=True)
    out.update(control=job.readings, control_correct=harness.passed(ctrl))
    for name in fault_names:
        broken = train.Job(cell)
        broken.fault = faults.TRAIN[name]
        broken.reference = job.reference
        broken.setup()
        broken.release()
        checks = broken.check()
        out[name] = dict(broken.readings, correct=harness.passed(checks))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--faults", default="",
                    help="comma-separated names from bench/faults.py")
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
        if cell.traffic["job"] == "serve":
            runs = [serve_readings(cell, f) for f in [None, *fault_names]]
        else:
            runs = [train_readings(cell, fault_names)]
        for got in runs:
            print(json.dumps({"seed": seed, **got,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
