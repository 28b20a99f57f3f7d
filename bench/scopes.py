"""Named-scope and program-span readings of the traced window.

The program names its device work with ``jax.named_scope`` (``dit/attn``,
``dit/mlp``, ``parataa/denoise``, ``parataa/anderson``, ...), which the
compiler keeps in each HLO instruction's ``op_name`` metadata, and it puts
its host spans (``stepwise.poll``, ``stepwise.harvest``, ``loop.idle``,
...) on the profiler's host plane, on the device trace's clock.  This
module reduces one trace to:

* ``scope_seconds``: device seconds per scope prefix, over the ops that lie
  wholly inside the window (as ``trace_reduce`` counts op time), summed
  over devices.  An op's scope path runs from the first ``parataa`` or
  ``dit`` component of its ``op_name`` through the plain lower-case
  components after it, without the op's own name: ``jit(program)/vmap()/
  while/body/closed_call/parataa/denoise/dit/attn/exp`` counts under
  ``parataa``, ``parataa/denoise``, ``parataa/denoise/dit`` and
  ``parataa/denoise/dit/attn``.
* ``idle_under``: per host event name, the seconds of the window in which
  every device is idle while the host is inside an event of that name.

Where the names are: a TPU trace's ``XLA Ops`` event is named by its HLO
instruction's text without metadata, and its stats hold no ``op_name``.
The trace does hold each program's optimized HLO (``Hlo Proto`` on the
``/host:metadata`` plane, one per program id), and each op event's
metadata gives its program id and instruction name, so the op_name is
looked up there.  ``jax.profiler.ProfileData`` exposes neither, so the
file is read here with a small protobuf wire-format decoder (XSpace,
XPlane, XLine, XEvent, XEventMetadata, XStat; HloProto down to each
instruction's name and ``metadata.op_name``).

The harness passes its readers ``trace_reduce``'s reduction alone, and
keeps the trace file in a temporary directory until they have read, so
``for_window`` finds that file again under the temporary root and takes
it only if its window is the reduction's own.  A trace without the
program's scopes or spans reads empty maps, and the readers ``None``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import tempfile
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import trace_reduce
from trace_reduce import Interval

ROOTS = ("parataa", "dit")
_PLAIN = re.compile(r"[a-z]+")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")
HLO_PROTO_STAT = "Hlo Proto"
PROGRAM_ID_STAT = "program_id"


@dataclasses.dataclass
class ScopedTrace:
    """Per-device ops as (scope path, start_ns, end_ns), and host events
    as (name, start_ns, end_ns)."""
    device_ops: Dict[str, List[Tuple[str, float, float]]]
    host: List[Tuple[str, float, float]]

    def window(self) -> Optional[Interval]:
        return trace_reduce.Trace({}, self.host).window()


@dataclasses.dataclass
class ScopeReduction:
    window_s: float
    busy_s: float                       # averaged over devices
    devices: int
    scope_seconds: Dict[str, float]     # per scope prefix
    idle_under: Dict[str, float]        # per host event name

    def ending(self, scope: str) -> float:
        """Seconds under every scope prefix that ends in ``scope`` (``dit/
        attn`` takes ``parataa/denoise/dit/attn`` and a bare ``dit/attn``),
        each op once."""
        return sum(s for k, s in self.scope_seconds.items()
                   if k == scope or k.endswith("/" + scope))


def scope_path(op_name: str) -> str:
    """The named-scope path of one op: '' when it has none."""
    parts = op_name.split("/")[:-1]
    for i, part in enumerate(parts):
        if part in ROOTS:
            path = [part]
            for nxt in parts[i + 1:]:
                if not _PLAIN.fullmatch(nxt):
                    break
                path.append(nxt)
            return "/".join(path)
    return ""


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varint and fixed
    fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            byte = buf[i]
            i += 1
            key |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                break
        kind = key & 7
        if kind == 0 or kind == 2:
            value = shift = 0
            while True:
                byte = buf[i]
                i += 1
                value |= (byte & 0x7F) << shift
                shift += 7
                if byte < 0x80:
                    break
            if kind == 2:
                value, i = buf[i:i + value], i + value
        elif kind == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported wire type {kind}")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_values(plane, field: int) -> Iterator[memoryview]:
    """The values of a ``map<int64, Message>`` field."""
    for f, entry in _fields(plane):
        if f == field:
            for g, value in _fields(entry):
                if g == 2:
                    yield value


def _varints(buf) -> List[int]:
    """A packed repeated varint field."""
    out, value, shift = [], 0, 0
    for byte in bytes(buf):
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            out.append(value)
            value = shift = 0
    return out


def _op_names(hlo_proto) -> Dict[str, str]:
    """Instruction name -> ``metadata.op_name`` of one HloProto.  A fusion
    takes its root's metadata, which may come from an op outside every
    scope that the compiler folded in (the weight slices of the step
    program read ``closed_call/broadcast_in_dim``, from batching the
    solver's ``cond``); such a fusion takes the first scoped op_name among
    its fused instructions instead."""
    insts = []                                  # (name, op_name, calls)
    by_comp: Dict[int, List[str]] = {}
    for f, module in _fields(hlo_proto):
        if f != 1:                                  # HloProto.hlo_module
            continue
        for g, comp in _fields(module):
            if g != 3:                              # .computations
                continue
            comp_id, names = None, []
            for h, v in _fields(comp):
                if h == 5:                          # .id
                    comp_id = v
                elif h == 2:                        # .instructions
                    name, op_name, fusion, calls = "", "", False, []
                    for k, w in _fields(v):
                        if k == 1:                  # .name
                            name = _text(w)
                        elif k == 2:                # .opcode
                            fusion = bytes(w) == b"fusion"
                        elif k == 7:                # .metadata
                            for m, x in _fields(w):
                                if m == 2:          # OpMetadata.op_name
                                    op_name = _text(x)
                        elif k == 38:               # .called_computation_ids
                            calls = _varints(w) if fusion else []
                    insts.append((name, op_name, calls))
                    names.append(op_name)
            by_comp[comp_id] = names
    out = {}
    for name, op_name, calls in insts:
        if calls and not scope_path(op_name):
            op_name = next((n for c in calls for n in by_comp.get(c, ())
                            if scope_path(n)), op_name)
        if name and op_name:
            out[name] = op_name
    return out


def _plane(buf):
    """(name, lines, {metadata id: (name, display name, stats)})."""
    name, lines, stat_names = "", [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
    for value in _map_values(buf, 5):               # XStatMetadata
        d = dict(_fields(value))
        stat_names[d.get(1, 0)] = _text(d.get(2, b""))
    meta = {}
    for value in _map_values(buf, 4):               # XEventMetadata
        mid, mname, display, stats = 0, "", "", {}
        for f, v in _fields(value):
            if f == 1:
                mid = v
            elif f == 2:
                mname = _text(v)
            elif f == 4:
                display = _text(v)
            elif f == 5:                            # XStat
                d = dict(_fields(v))
                key = stat_names.get(d.get(1), "")
                if key in (HLO_PROTO_STAT, PROGRAM_ID_STAT):
                    stats[key] = d.get(6, d.get(3, d.get(4)))
        meta[mid] = (mname, display, stats)
    return name, lines, meta


def _line(line) -> Tuple[str, int]:
    """A line's name and its timestamp in ns."""
    name, ts = "", 0
    for f, v in _fields(line):
        if f == 2:
            name = _text(v)
        elif f == 3:
            ts = v
    return name, ts


def _events(line, ts: int, meta) -> Iterator[Tuple[tuple, float, float]]:
    """A line's events as (their metadata, start_ns, end_ns)."""
    for f, ev in _fields(line):
        if f != 4:
            continue
        mid = off = dur = 0
        for g, v in _fields(ev):
            if g == 1:
                mid = v
            elif g == 2:
                off = v
            elif g == 3:
                dur = v
        start = ts + off / 1000
        yield meta.get(mid, ("", "", {})), start, start + dur / 1000


def read_xspace(path: str) -> ScopedTrace:
    """Device ops with their scope paths, and host events, from one
    ``.xplane.pb``."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes = [_plane(v) for f, v in _fields(data) if f == 1]
    scoped: Dict[int, Dict[str, str]] = {}      # program id -> inst -> path
    for name, _, meta in planes:
        if name == "/host:metadata":
            for mname, _, stats in meta.values():
                m = _PROGRAM_ID.search(mname)
                if m and HLO_PROTO_STAT in stats:
                    scoped[int(m.group(1))] = {
                        inst: scope_path(op_name) for inst, op_name in
                        _op_names(stats[HLO_PROTO_STAT]).items()}
    device_ops: Dict[str, list] = {}
    host: list = []
    for name, lines, meta in planes:
        for line in lines:
            lname, ts = _line(line)
            if name.startswith("/device:") and \
                    lname == trace_reduce.DEVICE_OP_LINE:
                device_ops.setdefault(name, []).extend(
                    (scoped.get(st.get(PROGRAM_ID_STAT), {}).get(inst, ""),
                     s, e) for (_, inst, st), s, e in _events(line, ts, meta))
            elif name == "/host:CPU":
                host.extend((m[0], s, e) for m, s, e in _events(line, ts,
                                                                meta))
    return ScopedTrace(device_ops=device_ops, host=host)


def _overlap(spans: Sequence[Interval], gaps: Sequence[Interval]) -> float:
    """Total overlap of two sorted disjoint interval lists, by bisection
    into ``gaps`` (the long one)."""
    starts = [s for s, _ in gaps]
    ends = [e for _, e in gaps]
    cum = [0.0]
    for s, e in gaps:
        cum.append(cum[-1] + (e - s))
    total = 0.0
    for s, e in spans:
        i = bisect.bisect_right(ends, s)        # first gap ending after s
        j = bisect.bisect_left(starts, e)       # first gap starting at e
        if i < j:
            total += (cum[j] - cum[i] - max(0.0, s - starts[i])
                      - max(0.0, ends[j - 1] - e))
    return total


def reduce(trace: ScopedTrace) -> Optional[ScopeReduction]:
    """None when the trace holds no window or no device op."""
    window = trace.window()
    if window is None or not trace.device_ops:
        return None
    lo, hi = window
    busy_total = 0.0
    by_path: Dict[str, float] = collections.defaultdict(float)
    every: list = []
    for events in trace.device_ops.values():
        busy = trace_reduce.union([(s, e) for _, s, e in events], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        every.extend(busy)
        for path, s, e in events:
            if lo <= s and e <= hi:
                by_path[path] += e - s
    scopes: Dict[str, float] = collections.defaultdict(float)
    for path, ns in by_path.items():
        parts = path.split("/") if path else []
        for k in range(1, len(parts) + 1):
            scopes["/".join(parts[:k])] += ns
    # idle on every device: the complement of the union over devices
    all_idle = trace_reduce.gaps(trace_reduce.union(every, lo, hi), lo, hi)
    by_name: Dict[str, list] = collections.defaultdict(list)
    for name, s, e in trace.host:
        by_name[name].append((s, e))
    idle = {name: _overlap(trace_reduce.union(spans, lo, hi), all_idle)
            * 1e-9 for name, spans in by_name.items()}
    n_dev = len(trace.device_ops)
    return ScopeReduction(window_s=(hi - lo) * 1e-9,
                          busy_s=busy_total / n_dev * 1e-9, devices=n_dev,
                          scope_seconds={k: v * 1e-9
                                         for k, v in scopes.items()},
                          idle_under=idle)


_cache: Dict[str, Optional[ScopeReduction]] = {}


def for_window(red) -> Optional[ScopeReduction]:
    """The scope reduction of the trace ``red`` (a ``trace_reduce``
    reduction) was made from: of the three newest ``.xplane.pb`` files
    under a directory of the temporary root, the one whose window lasts
    ``red.window_s``, to the microsecond."""
    if red is None:
        return None
    files = glob.glob(os.path.join(tempfile.gettempdir(), "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    for path in sorted(files, key=os.path.getmtime, reverse=True)[:3]:
        if path not in _cache:
            _cache[path] = reduce(read_xspace(path))
        found = _cache[path]
        if found is not None and abs(found.window_s - red.window_s) < 1e-6:
            return found
    return None


# ---------------------------------------------------------------------------
# the arithmetic of the readers (``metrics/<name>.py``)
# ---------------------------------------------------------------------------


def attn_share(ctx):
    """Device time under ``dit/attn`` (q/k/v/o projections, scores,
    softmax, context), in % of busy time."""
    red = for_window(ctx["trace"])
    if red is None or red.busy_s <= 0:
        return None
    seconds = red.ending("dit/attn")
    if not seconds:
        return None
    return 100.0 * seconds / (red.busy_s * red.devices)


def solver_share(ctx):
    """Device time under ``parataa`` outside ``parataa/denoise`` (the
    residuals, the Anderson round and its kernels, the pins), in % of busy
    time."""
    red = for_window(ctx["trace"])
    if red is None or red.busy_s <= 0 or ctx["taa"] is None:
        return None
    solver = red.scope_seconds.get("parataa")
    if not solver:
        return None
    denoise = red.scope_seconds.get("parataa/denoise", 0.0)
    return 100.0 * (solver - denoise) / (red.busy_s * red.devices)


FETCH_SPANS = ("stepwise.poll", "stepwise.harvest")


def idle_in_fetch(ctx):
    """Share of the window, in %, in which every device idles while the
    host is in the round's blocking fetches (``stepwise.poll``, the
    summary; ``stepwise.harvest``, the gather and trajectory fetch)."""
    red = for_window(ctx["trace"])
    if red is None or not any(n in red.idle_under for n in FETCH_SPANS):
        return None
    idle = sum(red.idle_under.get(n, 0.0) for n in FETCH_SPANS)
    return 100.0 * idle / red.window_s
