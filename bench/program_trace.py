"""The program's own spans and layer scopes in a ``--trace 1`` run's
profiler trace, for the per-layer metrics that read them.

The program's spans (``repro.obs``) enter a ``jax.profiler.TraceAnnotation``
while its tracer is on, so they sit on the trace's host planes beside the
benchmark's ``bench.*`` spans, on the device ops' clock.  Its layer scopes
(``repro.utils.scope``, a ``jax.named_scope``) land in each device op's
name stack, the ``tf_op`` stat of the op's metadata, which
:mod:`bench.xplane` reads.  From those:

* ``program_gaps``: each chip-0 idle gap labelled by the innermost program
  span open at its midpoint, or ``host:other``;
* ``span_idle``: for each program span name, how many such spans the trace
  holds and chip 0's idle time inside them;
* ``scope_seconds``: the device time per chip of the ops under each
  component of their name stack.

A program that records no spans or scopes gives empty tables, and the
metrics that read them report nothing.  :mod:`bench.trace_reduce` is left
as it is: its busy time, op times and ``bench.*`` gap labels are what the
accepted metrics read.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import os

from bench import trace_reduce, xplane

# the program's span families (docs/architecture.md, the span taxonomy)
PROGRAM_PREFIXES = ("serve.", "train.", "quant.", "bucket.", "sweep.",
                    "health.", "ckpt.", "journal.", "dryrun.")
OTHER = "host:other"


@dataclasses.dataclass
class ProgramTrace:
    program_gaps: dict       # program span -> idle seconds, chip 0
    span_idle: dict          # span name -> [spans, chip-0 idle seconds]
    scope_seconds: dict      # name-stack component -> device s per chip


def _innermost(spans: list, t: float) -> str:
    """Name of the shortest of ``spans`` open at ``t``, or ``OTHER``."""
    inner = [s for s in spans if s[1] <= t <= s[2]]
    return min(inner, key=lambda s: s[2] - s[1])[0] if inner else OTHER


def _busy_in(iv: list, starts: list, a: float, b: float) -> float:
    """Length of the union ``iv`` (sorted, disjoint; ``starts`` its left
    ends) that falls inside ``[a, b]``."""
    got = 0.0
    for i in range(max(bisect.bisect_right(starts, a) - 1, 0), len(iv)):
        lo, hi = iv[i]
        if lo >= b:
            break
        got += max(0.0, min(hi, b) - max(lo, a))
    return got


def span_tables(planes) -> tuple[dict, dict]:
    """``(program_gaps, span_idle)`` of ``planes``, given as
    :func:`bench.trace_reduce.reduce_planes` takes them."""
    chips, program = [], []
    for plane in planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            lines = list(plane.lines)
            ops = [ln for ln in lines
                   if ln.name == trace_reduce.OPS_LINE] or lines
            chips.append((plane.name, [(a, b) for ln in ops
                                       for _, a, b in
                                       trace_reduce._events(ln)]))
        else:
            for ln in plane.lines:
                program += [ev for ev in trace_reduce._events(ln)
                            if ev[0].startswith(PROGRAM_PREFIXES)]
    if not chips:
        return {}, {}
    chips.sort(key=lambda d: int(d[0][len(trace_reduce.DEVICE_PREFIX):]
                                 or 0))
    iv = trace_reduce._union(chips[0][1])
    gaps: dict = collections.defaultdict(float)
    idle: dict = {}
    for (_, b0), (a1, _) in zip(iv, iv[1:]):
        gaps[_innermost(program, (b0 + a1) / 2)] += (a1 - b0) * 1e-9
    starts = [a for a, _ in iv]
    for name, a, b in program:
        n_idle = idle.setdefault(name, [0, 0.0])
        n_idle[0] += 1
        n_idle[1] += (b - a - _busy_in(iv, starts, a, b)) * 1e-9
    return dict(gaps), idle


def scope_seconds(planes) -> dict:
    """Device seconds per chip of the ops under each component of their
    name stack (``tf_op``); ``planes`` as :func:`bench.xplane.read` gives
    them.  Ops that enclose others (``while``, ``call``, ``conditional``)
    are left out: their time is their body's, counted there."""
    per: dict = collections.defaultdict(float)
    n = 0
    for plane in planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        n += 1
        by_id: dict = collections.defaultdict(float)
        for ln in plane.lines:
            for ev in ln.events:
                by_id[ev.metadata_id] += ev.duration_ns * 1e-9
        for mid, secs in by_id.items():
            stats = plane.stats.get(mid, {})
            tf_op = stats.get(xplane.TF_OP)
            if not tf_op or stats.get(xplane.CATEGORY) in xplane.ENCLOSING:
                continue
            for part in set(xplane.components(tf_op)[:-1]):
                per[part] += secs
    return {k: v / n for k, v in per.items()} if n else {}


def read_file(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    gaps, idle = span_tables(ProfileData.from_file(path).planes)
    scopes = scope_seconds(xplane.read(path, trace_reduce.DEVICE_PREFIX,
                                       trace_reduce.OPS_LINE))
    return ProgramTrace(gaps, idle, scopes)


_CACHE: dict = {}


def current(directory: str | None = None) -> ProgramTrace | None:
    """The tables of the newest trace under ``directory`` (the harness's
    trace directory by default), read once per file; None where there is
    no trace."""
    if directory is None:
        from bench import harness
        directory = harness.TRACE_DIR
    try:
        path = trace_reduce.trace_file(directory)
    except FileNotFoundError:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = read_file(path)
    return _CACHE[key]
