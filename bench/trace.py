"""Reduce a profiler trace to device busy time, op times and idle gaps.

``jax.profiler.trace`` writes an ``.xplane.pb``; ``jax.profiler.
ProfileData`` reads it.  Each TPU chip is a plane ``/device:TPU:<i>``
whose ``XLA Ops`` line holds one event per operation executed, with its
start and duration in nanoseconds on the clock the host's planes share.
The benchmark's own host spans (``jax.profiler.TraceAnnotation`` with a
name starting ``bench.``) are events on the ``/host:CPU`` plane.

* busy: the union of a device's op intervals inside the window (ops
  overlap on a chip, so their durations are never summed), averaged
  over the devices;
* op seconds: device self time by op inside the window, averaged over
  the devices (an op that holds others, as a ``while`` holds its body,
  counts only the time its children leave uncovered); an op is named by
  its HLO name, opcode and result shape, ``fusion.17 (fusion
  f32[1048576])``;
* idle gaps: stretches of the window in which no device runs an op,
  each named by the benchmark span that covers most of it.

The window is the ``bench.window`` span; a trace without one is an error.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "outside any bench span"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP_GAPS = 10
# opcodes of the collectives, each also with its async -start / -done pair
COLLECTIVE_OPCODES = ("collective-permute", "all-reduce", "all-gather",
                      "reduce-scatter", "all-to-all")


@dataclasses.dataclass
class Trace:
    """Intervals (seconds) read from one trace: ops per device, host spans."""

    device_ops: dict[int, list[tuple[str, float, float]]]
    spans: list[tuple[str, float, float]]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                          # mean over devices
    devices: int
    op_seconds: dict[str, float]           # mean over devices, by op name
    idle_gaps: list[tuple[str, float]]     # longest first


def op_name(text: str) -> str:
    """``fusion.17 (fusion f32[1048576])`` from the HLO text of an op
    (``%fusion.17 = f32[1048576]{0:T(1024)} fusion(...), ...``); a name
    that is not HLO text stays as it is."""
    name, sep, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not sep:
        return name
    if rest.startswith("("):                 # tuple-shaped: opcode only
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = re.sub(r"\{[^}]*\}", "", shape)
    opcode = rest.partition("(")[0]
    return f"{name} ({opcode} {shape})".replace(" )", ")")


def opcode(op: str) -> str:
    """The opcode of an op named by ``op_name``: ``all-reduce`` of
    ``psum.44 (all-reduce f32[4])``; empty for a name without one."""
    return op.partition(" (")[2].split(" ")[0].rstrip(")")


def is_collective(op: str) -> bool:
    """Whether an op named by ``op_name`` moves data between chips.  By
    opcode, not by name: XLA names a ``psum`` all-reduce ``psum.44``."""
    return opcode(op).startswith(COLLECTIVE_OPCODES)


def self_times(events) -> list[tuple[str, float]]:
    """``(name, self seconds)`` of each ``(name, start, end)`` event: its
    length less that of the events nested directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    child = [0.0] * len(events)
    stack: list[int] = []
    for i in order:
        _, a, b = events[i]
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][2]:
            child[stack[-1]] += b - a
        stack.append(i)
    return [(name, (b - a) - child[i])
            for i, (name, a, b) in enumerate(events)]


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals) -> float:
    return sum(b - a for a, b in merge(intervals))


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """Stretches of ``[lo, hi]`` that no interval of ``busy`` covers."""
    out, t = [], lo
    for a, b in merge(clip(busy, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap, spans) -> str:
    """Name of the span that overlaps ``gap`` the most."""
    lo, hi = gap
    best, best_overlap = NO_SPAN, 0.0
    for name, a, b in spans:
        overlap = min(b, hi) - max(a, lo)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def summarize(trace: Trace) -> TraceSummary:
    """Busy time, op times and the ``TOP_GAPS`` longest idle gaps of
    ``trace`` inside its ``bench.window`` span."""
    marks = [(a, b) for name, a, b in trace.spans if name == WINDOW_SPAN]
    if not marks:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    ndev = len(trace.device_ops)
    busy, by_op, every = 0.0, {}, []
    for evs in trace.device_ops.values():
        inside = [(name, max(a, lo), min(b, hi)) for name, a, b in evs
                  if b > lo and a < hi]
        busy += length([(a, b) for _, a, b in inside])
        every += [(a, b) for _, a, b in inside]
        for name, s in self_times(inside):
            by_op[name] = by_op.get(name, 0.0) + s
    spans = [s for s in trace.spans if s[0] != WINDOW_SPAN]
    idle = sorted(gaps(every, lo, hi), key=lambda g: g[0] - g[1])[:TOP_GAPS]
    return TraceSummary(
        window_s=hi - lo,
        busy_s=busy / ndev if ndev else 0.0,
        devices=ndev,
        op_seconds={k: v / ndev for k, v in by_op.items()} if ndev else {},
        idle_gaps=[(attribute(g, spans), g[1] - g[0]) for g in idle])


def read_xplane(path) -> Trace:
    """Device ops and ``bench.`` host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device_ops: dict[int, list] = {}
    spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = device_ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(op_name(e.name), e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(device_ops={d: evs for d, evs in device_ops.items() if evs},
                 spans=spans)


def find_xplane(log_dir) -> Path:
    """The single ``.xplane.pb`` that one ``jax.profiler.trace`` wrote."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]
