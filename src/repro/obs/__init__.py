"""repro.obs — unified tracing, metrics and structured logging.

Three zero-dependency pieces (``docs/architecture.md`` §16):

* :mod:`repro.obs.trace` — context-manager/decorator spans exported as
  chrome-trace JSON (open at https://ui.perfetto.dev); disabled by
  default, in which case every ``span()`` returns a shared no-op.
* :mod:`repro.obs.metrics` — counters / gauges / fixed-bucket
  histograms with a deterministic JSON snapshot, names pinned by
  :mod:`repro.obs.names` and ``tools/obs_metric_names.json``.
* :mod:`repro.obs.log` — ``[event] key=value`` structured progress
  lines with a swappable sink.

Launchers wire the lot through :func:`session`:

>>> from repro import obs
>>> with obs.session():                    # no outputs requested
...     with obs.trace.span("noop"):       # no-op: tracer stays off
...         obs.metrics.counter("quant.buckets").inc()
>>> obs.metrics.counter("quant.buckets").value >= 1
True
"""
from __future__ import annotations

import contextlib

from repro.obs import log, metrics, names, trace  # noqa: F401


def default_metrics_path(tool: str) -> str:
    """Where a launcher drops its snapshot when only ``--trace-out``
    was given (the ``results/metrics-*.json`` convention)."""
    return f"results/metrics-{tool}.json"


@contextlib.contextmanager
def session(trace_out=None, metrics_out=None):
    """Enable tracing when ``trace_out`` is set, and on exit (even an
    exceptional one) export the trace and/or metrics snapshot."""
    if trace_out:
        trace.enable()
    try:
        yield
    finally:
        if trace_out:
            trace.export(trace_out)
            trace.disable()
        if metrics_out:
            metrics.save(metrics_out)
