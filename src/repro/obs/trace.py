"""Span tracer on the profiler's clock, with chrome-trace (Perfetto) export.

Spans are context managers (or the :func:`traced` decorator) recording
wall-clock intervals with attributes, process id and thread id.  The
module-level tracer is **disabled by default** and every ``span()`` call
then returns a shared no-op singleton — one function call plus a bool
check, nothing allocated and nothing imported, so instrumented hot paths
cost effectively nothing when tracing is off (gated by
``obs_overhead_row`` in table10).

While the tracer is enabled, each span also enters a
``jax.profiler.TraceAnnotation`` of its name and attributes, so when a
profiler trace is being taken the span lands on its host plane, on the
same clock as the device's ops.  JAX dispatch is asynchronous: a span
that closes right after a jitted call has measured *dispatch*; the device
trace beside it shows when the work ran.  Phases that cross calls (a
request's queueing, prefill, generation) are recorded with
:meth:`Tracer.complete` from timestamps the caller took with
``time.perf_counter``; they stay in the span list only.

Export is the chrome-trace JSON array format (``{"traceEvents": [...]}``
with ``"X"`` complete events, microsecond timestamps) — load the file at
https://ui.perfetto.dev or ``chrome://tracing``.

>>> tr = Tracer()
>>> tr.enabled = True
>>> with tr.span("bucket.execute", bucket=0) as sp:
...     sp = sp.set(path="sharded")
>>> ev = tr.events()[0]
>>> ev["name"], ev["ph"], ev["args"]
('bucket.execute', 'X', {'bucket': 0, 'path': 'sharded'})
>>> sorted(tr.to_dict())
['displayTimeUnit', 'traceEvents']
>>> tr.enabled = False
>>> tr.span("ignored") is tr.span("also-ignored")   # shared no-op
True
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time


class _NullSpan:
    """Shared do-nothing span returned while tracing is disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()


def _jsonable(v):
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    return str(v)


def _annotation(name: str, args: dict):
    """The profiler's host-side marker for one span (recorded only while a
    profiler trace is being taken)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, **args)


class Span:
    """One live span; record happens at ``__exit__``."""
    __slots__ = ("_tracer", "name", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0.0
        self._ann = None

    def set(self, **attrs) -> "Span":
        """Attach attributes mid-span (shown under *args* in Perfetto)."""
        self.args.update(attrs)
        return self

    def __enter__(self) -> "Span":
        self._ann = _annotation(self.name, self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        self._tracer._record(self.name, self._t0, t1, self.args)
        return False


class Tracer:
    """Collects span events; thread-safe; one per process is plenty."""

    def __init__(self):
        self.enabled = False
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._origin = time.perf_counter()

    # -- recording ----------------------------------------------------

    def span(self, name: str, **args):
        if not self.enabled:
            return _NULL_SPAN
        return Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event (``ph: "i"``)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t",
              "pid": os.getpid(), "tid": threading.get_ident(),
              "ts": (time.perf_counter() - self._origin) * 1e6}
        if args:
            ev["args"] = {k: _jsonable(v) for k, v in args.items()}
        with self._lock:
            self._events.append(ev)

    def complete(self, name: str, t0: float, t1: float, **args) -> None:
        """Record a span that already ended, from ``time.perf_counter``
        stamps ``t0`` and ``t1`` (a phase that crossed calls)."""
        if not self.enabled:
            return
        self._record(name, t0, t1, args)

    def _record(self, name: str, t0: float, t1: float,
                args: dict) -> None:
        ev = {"name": name, "ph": "X",
              "pid": os.getpid(), "tid": threading.get_ident(),
              "ts": (t0 - self._origin) * 1e6,
              "dur": max(0.0, (t1 - t0) * 1e6)}
        if args:
            ev["args"] = {k: _jsonable(v) for k, v in args.items()}
        with self._lock:
            self._events.append(ev)

    # -- export -------------------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def to_dict(self) -> dict:
        evs = self.events()
        pids = sorted({e["pid"] for e in evs})
        meta = [{"name": "process_name", "ph": "M", "pid": pid,
                 "tid": 0, "args": {"name": "repro"}} for pid in pids]
        return {"traceEvents":
                meta + sorted(evs, key=lambda e: e["ts"]),
                "displayTimeUnit": "ms"}

    def export(self, path) -> None:
        """Write chrome-trace JSON to ``path`` (dirs created)."""
        path = os.fspath(path)
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1)


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def is_enabled() -> bool:
    return _TRACER.enabled


def enable() -> None:
    """Turn the module tracer on."""
    _TRACER.enabled = True


def disable() -> None:
    _TRACER.enabled = False


def span(name: str, **args):
    """Open a span on the module tracer (no-op singleton when off)."""
    if not _TRACER.enabled:        # fast path: no kwargs dict consumers
        return _NULL_SPAN
    return Span(_TRACER, name, args)


def instant(name: str, **args) -> None:
    _TRACER.instant(name, **args)


def complete(name: str, t0: float, t1: float, **args) -> None:
    """A phase that already ended, on the module tracer (see
    :meth:`Tracer.complete`)."""
    _TRACER.complete(name, t0, t1, **args)


def export(path) -> None:
    _TRACER.export(path)


def traced(name: str | None = None, **attrs):
    """Decorator form: ``@traced("quant.calibrate")``."""
    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not _TRACER.enabled:
                return fn(*a, **kw)
            with Span(_TRACER, label, dict(attrs)):
                return fn(*a, **kw)
        return wrapper
    return deco
