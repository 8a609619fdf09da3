"""From a profiler trace (``*.xplane.pb``) to device busy time, time per
device operation and the idle gaps, labelled by the benchmark's host spans.

Device planes are those named ``/device:TPU:<n>``.  Their operations are
the events of the line named ``XLA Ops`` (every line, where a plane has no
such line).  Busy time is the union of those events' intervals, averaged
over the chips in the trace.  An idle gap is a stretch of chip 0 between
two busy intervals; it is labelled by the innermost ``bench.*`` host span
(``jax.profiler.TraceAnnotation``) open at its midpoint, or ``host:other``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."
TOP = 10


@dataclasses.dataclass
class Reduction:
    busy_s: float                      # mean over chips
    window_s: float                    # traced window, host clock
    n_chips: int
    op_seconds: dict                   # op name -> seconds, summed on chips
    op_counts: dict                    # op name -> events
    gaps: dict                         # host label -> idle seconds, chip 0

    def kernel(self, needle: str) -> tuple[float, int]:
        """Seconds and events of every op whose own name holds ``needle``,
        per chip.  A v5e trace names an op by its HLO text, ``%name =
        shape op(operands)``, and an op that consumes a kernel's output
        names the kernel among its operands, so only the text before
        `` = `` is matched."""
        own = lambda n: needle in n.split(" = ", 1)[0]  # noqa: E731
        secs = sum(s for n, s in self.op_seconds.items() if own(n))
        cnt = sum(c for n, c in self.op_counts.items() if own(n))
        return secs / max(self.n_chips, 1), cnt // max(self.n_chips, 1)

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def reduce_planes(planes, window_s: float) -> Reduction:
    """``planes``: objects with ``name`` and ``lines``; each line has a
    ``name`` and ``events`` with ``name``, ``start_ns`` and
    ``duration_ns`` (``jax.profiler.ProfileData`` gives them)."""
    devices, spans = [], []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices.append((plane.name, [ev for ln in ops
                                         for ev in _events(ln)]))
        else:
            for ln in plane.lines:
                spans += [ev for ev in _events(ln)
                          if ev[0].startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: int(d[0][len(DEVICE_PREFIX):] or 0))
    op_s: dict = collections.defaultdict(float)
    op_n: dict = collections.defaultdict(int)
    busy = []
    for _, evs in devices:
        for name, a, b in evs:
            op_s[name] += (b - a) * 1e-9
            op_n[name] += 1
        busy.append(sum(b - a for a, b in _union([(a, b)
                                                  for _, a, b in evs])))
    gaps: dict = collections.defaultdict(float)
    if devices:
        iv = _union([(a, b) for _, a, b in devices[0][1]])
        for (_, b0), (a1, _) in zip(iv, iv[1:]):
            mid = (b0 + a1) / 2
            inner = [s for s in spans if s[1] <= mid <= s[2]]
            label = (min(inner, key=lambda s: s[2] - s[1])[0] if inner
                     else "host:other")
            gaps[label] += (a1 - b0) * 1e-9
    n = len(devices)
    return Reduction(busy_s=(sum(busy) / n * 1e-9) if n else 0.0,
                     window_s=window_s, n_chips=n, op_seconds=dict(op_s),
                     op_counts=dict(op_n), gaps=dict(gaps))


def trace_file(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return files[-1]


def reduce_dir(directory: str, window_s: float) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(trace_file(directory)).planes,
                         window_s)
