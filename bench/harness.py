"""The benchmark's driver: one cell, one seed, one process.

``BENCHMARK.json`` names the cell; everything that belongs to one
configuration, traffic mix or per-layer metric lives in a file of its own
that is found here by name:

* ``bench/configs/<config>.json``  the model configuration as it is run;
* ``bench/traffic/<traffic>.json`` the mix, whose ``job`` names the window
  driver ``bench/jobs/<job>.py``;
* ``bench/reference/<reference>.py`` the plain reference that the
  configuration's ``reference`` key names (``dense`` where it names none);
* ``bench/layers/<metric>.py``     one reader per per-layer metric;
* ``bench/tests/tiny/<workload>.json`` the cell's smoke size for the tests.

A run sets up (weights from the seed, warm-up of every shape the cell
uses), measures for ``--seconds``, reads its metrics, frees the program's
state, and then checks what the timed path produced against the plain
reference.  Its last stdout line is one JSON object.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
# profiler output of a ``--trace 1`` run, inside the checkout (gitignored)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoDevice(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def config_file(name: str) -> dict:
    return load_json(os.path.join(BENCH, "configs", f"{name}.json"))


def traffic_file(name: str) -> dict:
    return load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def job_module(job: str):
    """``bench/jobs/<job>.py``: its ``Job`` drives the window, its
    ``LIMITS`` hold the correctness limits and its ``FAULTS`` the faults
    planted under the timed path."""
    return importlib.import_module(f"bench.jobs.{job}")


def job_class(job: str):
    return job_module(job).Job


def layer_reader(metric: str):
    """``read(run) -> float | None`` of ``bench/layers/<metric>.py``."""
    path = os.path.join(BENCH, "layers", f"{metric}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_layer_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def check_device(chips: int, allow_cpu: bool = False) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu" and not allow_cpu:
        raise NoDevice(f"no TPU: JAX's first device is {dev['platform']}; "
                       "the benchmark never falls back to the CPU")
    if dev["count"] < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                       f"{dev['count']}")
    return dev


class CompileCount:
    """Backend compiles seen through jax.monitoring."""

    def __init__(self) -> None:
        import jax
        self.n, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes() -> int | None:
    import jax
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.local_devices()]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


@dataclasses.dataclass
class Cell:
    """What a job is given: the cell's entries and files, and the run's
    seed, window and trace flag."""
    workload: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool


class Tracer:
    """The profiler around a slice of the window that the job chooses,
    and the benchmark's own host spans around each call into the
    program."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.t0 = self.t1 = None

    def start(self) -> None:
        if not self.enabled or self.t0 is not None:
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR)
        jax.profiler.start_trace(TRACE_DIR)
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.enabled or self.t0 is None or self.t1 is not None:
            return
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def active(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def span(self, name: str, **kw):
        if not self.active:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name, **kw)


def passed(checks: list) -> bool:
    """Whether every number compared lies within its limit."""
    return all(c["value"] <= c["limit"] for c in checks)


def _device_json(dev: dict) -> dict:
    return {"platform": dev["platform"], "kind": dev["kind"],
            "count": dev["count"], "memory_peak_bytes": peak_bytes()}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, allow_cpu: bool = False, overrides: dict | None = None,
        out=sys.stdout) -> dict:
    """Run one cell and return its result object (also printed as the
    last line of ``out``).  ``allow_cpu`` and ``overrides`` (keys of the
    configuration file replaced, for a smoke size) exist for the tests
    and are never set by the command."""
    bench = spec()
    wl = find(bench["workloads"], workload, "workload")
    dev = check_device(wl["chips"], allow_cpu)
    from repro.launch.jax_cache import enable_compilation_cache
    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    compiles = CompileCount()

    config = dict(config_file(wl["config"]), **(overrides or {}))
    cell = Cell(workload, config, traffic_file(wl["traffic"]), seed, seconds,
                trace)
    job = job_class(cell.traffic["job"])(cell)
    job.setup()
    setup_s = time.perf_counter() - t_start
    n0, c0 = compiles.n, compiles.seconds
    print(f"setup: seconds={setup_s:.3f} compiles={compiles.n} "
          f"compile_seconds={compiles.seconds:.3f} "
          f"persistent_cache_hits={compiles.cache_hits}", flush=True)

    tracer = Tracer(trace)
    job.window(tracer)
    tracer.stop()
    print(f"window: compiles={compiles.n - n0} "
          f"compile_seconds={compiles.seconds - c0:.3f}", flush=True)
    device = _device_json(dev)

    breakdown = None
    if trace:
        from bench import trace_reduce
        red = trace_reduce.reduce_dir(TRACE_DIR, tracer.t1 - tracer.t0)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        reading = job.reading(red, peaks(dev["kind"]) if not allow_cpu
                              else None)
        metrics = {}
        for m in bench["per_layer"]:
            if applies(m, workload):
                value = layer_reader(m["name"])(reading)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = job.end_to_end()
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"] if applies(m, workload)}

    job.release()
    checks = job.check()
    correct = passed(checks)
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    attempted, failed = job.counts()
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    print(json.dumps(result), file=out, flush=True)
    return result
