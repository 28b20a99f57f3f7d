"""Reduce a profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read.

Device operations are the events on the ``XLA Ops`` line of each
``/device:...`` plane.  Host events are every event on the ``/host:CPU``
plane's thread lines.  The traced window is the span of the host event the
benchmark opens around its measured window (``WINDOW_EVENT``), so device and
host times share the profiler's own clock.

* busy: the union of device-op intervals inside the window, per device,
  averaged over devices; idle share = 1 - busy / window.
* op time and calls by name: the device ops that lie wholly inside the
  window.
* idle gaps: the complement of the busy union, each named by the host
  events that overlap it most (jit dispatches, transfers, the harness's
  own waits), so a gap says what the host was doing while the chip idled.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_EVENT = "bench_window"
DEVICE_OP_LINE = "XLA Ops"

Interval = Tuple[float, float]        # (start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    """Flat events of one trace: per-device op events and host events,
    each (name, start_ns, end_ns)."""
    device_ops: Dict[str, List[Tuple[str, float, float]]]
    host: List[Tuple[str, float, float]]

    def window(self) -> Optional[Interval]:
        spans = [(s, e) for name, s, e in self.host if name == WINDOW_EVENT]
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)


def from_profile(profile) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``."""
    device_ops: Dict[str, list] = {}
    host: list = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events)
    return Trace(device_ops=device_ops, host=host)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(max(files,
                                                  key=os.path.getmtime)))


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Sorted, disjoint union of ``intervals`` clipped to [lo, hi]."""
    out: List[list] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of a sorted disjoint union within [lo, hi]."""
    out, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # averaged over devices
    op_seconds: Dict[str, float]        # summed over devices
    op_calls: Dict[str, int]            # events inside the window
    gaps: List[Tuple[str, float]]       # longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def matching(self, pattern: str) -> Tuple[float, int]:
        """(seconds, calls) of the ops whose name contains ``pattern``."""
        names = [n for n in self.op_seconds if pattern in n]
        return (sum(self.op_seconds[n] for n in names),
                sum(self.op_calls[n] for n in names))


def reduce(trace: Trace, *, top_gaps: int = 10) -> Optional[Reduction]:
    """None when the trace holds no window or no device op."""
    window = trace.window()
    if window is None or not trace.device_ops:
        return None
    lo, hi = window
    busy_total = 0.0
    ops: Dict[str, float] = collections.defaultdict(float)
    calls: Dict[str, int] = collections.defaultdict(int)
    all_gaps: List[Interval] = []
    for events in trace.device_ops.values():
        busy = union([(s, e) for _, s, e in events], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for name, s, e in events:
            if lo <= s and e <= hi:     # whole calls only: time and count
                ops[name] += e - s      # stay paired for per-call bytes
                calls[name] += 1
        all_gaps.extend(gaps(busy, lo, hi))
    n_dev = len(trace.device_ops)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top_gaps]
    named = [(_host_activity(trace.host, g, lo), (g[1] - g[0]) * 1e-9)
             for g in longest]
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=busy_total / n_dev * 1e-9,
                     op_seconds={k: v * 1e-9 for k, v in ops.items()},
                     op_calls=dict(calls), gaps=named)


def _host_activity(host, gap: Interval, lo: float, top: int = 2) -> str:
    """The host events that overlap ``gap`` most, as one label that also
    gives the gap's start in seconds after the window opened."""
    cover: Dict[str, float] = collections.defaultdict(float)
    for name, s, e in host:
        if name == WINDOW_EVENT:
            continue
        d = _overlap((s, e), gap)
        if d > 0:
            cover[name] += d
    span = gap[1] - gap[0]
    best = sorted(cover.items(), key=lambda kv: -kv[1])[:top]
    what = " + ".join(f"{name} ({d / span:.0%})" for name, d in best)
    return f"+{(gap[0] - lo) * 1e-9:.3f}s: {what or 'no host event'}"


def op_kind(name: str) -> str:
    """An op's kind from its trace name, the HLO text of the instruction:
    the instruction name without its numeric suffix, and the fusion kind
    when there is one (``%fusion.2763 = ... kind=kOutput ...`` ->
    ``fusion kOutput``).  The layers of the unrolled model are separate
    instructions of one kind, so kinds, not instructions, add up."""
    head = name.split(" = ")[0].lstrip("%")
    kind = re.sub(r"(\.\d+)+$", "", head)
    fusion = re.search(r"kind=(k\w+)", name)
    return f"{kind} {fusion.group(1)}" if fusion else kind


def top_ops(red: Reduction, n: int = 10) -> List[List]:
    """The op kinds that took the most device time, longest first."""
    kinds: Dict[str, float] = collections.defaultdict(float)
    for name, s in red.op_seconds.items():
        kinds[op_kind(name)] += s
    return [[k, s] for k, s in
            sorted(kinds.items(), key=lambda kv: -kv[1])[:n]]
