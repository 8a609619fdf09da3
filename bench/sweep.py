"""Sweep a serving cell's arrival rate on the chip, to find its knee.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 2,3,4

One process sets the cell up once, then runs the cell's window at each
rate in turn (draining the engine between rates) and prints one line per
rate: offered and completed requests per second, the queue at the middle
and at the end of the window, and the tails.  The knee is the highest rate
whose queue does not grow through the window.  The benchmark's own runs
never sweep: a cell's rate is fixed in its traffic file.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    from bench import harness
    from bench import traffic as gen
    from bench.jobs.serve import Job

    bench = harness.spec()
    wl = harness.find(bench["workloads"], args.workload, "workload")
    harness.check_device(wl["chips"])
    from repro.launch.jax_cache import enable_compilation_cache
    enable_compilation_cache()
    cell = harness.Cell(args.workload, harness.config_file(wl["config"]),
                        harness.traffic_file(wl["traffic"]), args.seed,
                        args.seconds, False)
    job = Job(cell)
    job.setup()
    print(f"setup: seconds={time.perf_counter() - T_START:.3f}", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        job.requests = gen.serve_requests(mix, args.seed, args.seconds + 60,
                                          job.cfg.vocab)
        steps0 = job.engine.steps
        t0 = time.perf_counter()
        job.window(harness.Tracer(False))
        e2e = job.end_to_end()
        done = len(job.finished)
        print(json.dumps({
            "rate_per_s": rate, "due": job.n_window,
            "finished_per_s": done / args.seconds,
            "queue_half_end": job.queue_len, "engine_steps": job.engine.steps - steps0,
            "seconds_incl_drain": time.perf_counter() - t0, **e2e}),
            flush=True)
        job.engine.run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
