"""The program's host spans in a profiler trace: what the host was doing
while the devices idled.

The program marks the phases of its host path with spans named
``repro.<layer>.<phase>`` (``src/repro/spans.py``); the benchmark spans
its own calls ``bench.window`` and ``bench.request``.  Both are events on
the ``/host:CPU`` plane, on the clock the device planes share.
``trace.read_xplane`` keeps the ``bench.`` spans alone, and
``trace.attribute`` names a gap by the span that overlaps it most, which
among nested spans is always the outermost.  This module reads both
prefixes, one list of spans per host thread line, and adds:

* span seconds: the self time of each span name inside ``bench.window``
  (a span's length less that of the spans nested directly inside it on
  its line), summed by name;
* idle gaps as ``trace.summarize`` finds them, each named by the span
  with the largest self overlap with the gap: its overlap less the part
  that spans nested directly inside it cover.  Without nested spans this
  is ``trace.attribute``'s name;
* the shares the two layers' metrics would read (``host_share_solve``,
  ``rebalance_share_partition``).

The harness deletes its trace once ``trace.summarize`` has read it, so
none of this reaches a result line yet.  To read a cell's spans on the
chip:

    python bench/spans.py --workload <cell> --seed <n> --seconds <s> [--keep <dir>]

sets the cell up and warms it as ``run.py`` does, traces the window with
the profiler options of ``run.py --trace 1``, and prints one JSON line:
the cell's end-to-end metrics with tracing on (``setup_s`` aside), busy
and window seconds, span seconds, the two shares and the longest idle
gaps by name.  ``--keep`` copies the ``.xplane.pb`` there.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as tr  # noqa: E402

PREFIXES = ("bench.", "repro.")
SERVE = "repro.serve."
SERVE_ROOT = "repro.serve.solve"
SERVE_WAIT = "repro.serve.wait"
PARTITION_ROOT = "repro.partition"
REBALANCE = "repro.kmeans.rebalance"


@dataclasses.dataclass
class SpanSummary:
    window_s: float
    busy_s: float                          # mean over devices
    span_seconds: dict[str, float]         # self time inside the window
    idle_gaps: list[tuple[str, float]]     # longest first


def read_spans(path) -> list[list[tuple[str, float, float]]]:
    """``(name, start, end)`` of the ``bench.`` and ``repro.`` spans of one
    ``.xplane.pb``, one list per host thread line; a name is cut at its
    first ``#``."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            spans = [(e.name.partition("#")[0], e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                     for e in line.events if e.name.startswith(PREFIXES)]
            if spans:
                lines.append(spans)
    return lines


def window(lines) -> tuple[float, float]:
    marks = [(a, b) for spans in lines for name, a, b in spans
             if name == tr.WINDOW_SPAN]
    if not marks:
        raise ValueError(f"the trace holds no {tr.WINDOW_SPAN!r} span")
    return min(a for a, _ in marks), max(b for _, b in marks)


def span_seconds(lines, lo: float, hi: float) -> dict[str, float]:
    """Self seconds of each span name inside ``[lo, hi]``, by line with
    ``trace.self_times``, summed by name."""
    out: dict[str, float] = {}
    for spans in lines:
        inside = [(name, max(a, lo), min(b, hi)) for name, a, b in spans
                  if b > lo and a < hi]
        for name, s in tr.self_times(inside):
            out[name] = out.get(name, 0.0) + s
    return out


def _parents(spans) -> list[int]:
    """Index of the span each span is nested directly inside, or -1 (the
    walk of ``trace.self_times``)."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1],
                                                     -spans[i][2]))
    parent = [-1] * len(spans)
    stack: list[int] = []
    for i in order:
        _, a, b = spans[i]
        while stack and spans[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= spans[stack[-1]][2]:
            parent[i] = stack[-1]
        stack.append(i)
    return parent


def attribute(gap, lines) -> str:
    """Name of the span with the largest self overlap with ``gap``, the
    ``bench.window`` span aside; ``trace.NO_SPAN`` where none overlaps."""
    lo, hi = gap
    best, best_overlap = tr.NO_SPAN, 0.0
    for spans in lines:
        spans = [s for s in spans if s[0] != tr.WINDOW_SPAN]
        own = [max(0.0, min(b, hi) - max(a, lo)) for _, a, b in spans]
        for i, p in enumerate(_parents(spans)):
            if p >= 0:
                own[p] -= max(0.0, min(spans[i][2], hi)
                              - max(spans[i][1], lo))
        for (name, _, _), overlap in zip(spans, own):
            if overlap > best_overlap:
                best, best_overlap = name, overlap
    return best


def summarize(trace: tr.Trace, lines) -> SpanSummary:
    """Window, busy time, span seconds and the ``trace.TOP_GAPS`` longest
    idle gaps of a trace read by ``trace.read_xplane`` and
    ``read_spans``."""
    lo, hi = window(lines)
    every = [(max(a, lo), min(b, hi)) for evs in trace.device_ops.values()
             for _, a, b in evs if b > lo and a < hi]
    idle = sorted(tr.gaps(every, lo, hi), key=lambda g: g[0] - g[1])
    return SpanSummary(
        window_s=hi - lo, busy_s=tr.summarize(trace).busy_s,
        span_seconds=span_seconds(lines, lo, hi),
        idle_gaps=[(attribute(g, lines), g[1] - g[0])
                   for g in idle[:tr.TOP_GAPS]])


def host_share_solve(s: SpanSummary) -> float | None:
    """Self time of the service's host phases (every ``repro.serve.``
    span but ``serve.wait``) over the window; ``None`` without a
    ``repro.serve.solve`` span."""
    if SERVE_ROOT not in s.span_seconds:
        return None
    return sum(v for name, v in s.span_seconds.items()
               if name.startswith(SERVE) and name != SERVE_WAIT) / s.window_s


def rebalance_share_partition(s: SpanSummary) -> float | None:
    """Self time of ``repro.kmeans.rebalance`` over the window; ``None``
    without a ``repro.partition`` span."""
    if PARTITION_ROOT not in s.span_seconds:
        return None
    return s.span_seconds.get(REBALANCE, 0.0) / s.window_s


def traced_window(workload: str, seed: int, seconds: float,
                  keep: Path | None = None, *, accelerator: bool = True,
                  overrides: dict | None = None) -> dict:
    """Set up and warm ``workload`` as ``harness.run_cell`` does, then
    trace one closed-loop window; the line ``main`` prints.
    ``accelerator`` and ``overrides`` are ``run_cell``'s, for the CPU
    tests."""
    import jax

    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    cell = harness.load_cell(ROOT, workload, overrides)
    devices = harness.devices_for(cell.chips, accelerator)
    use_compile_cache()
    kind = harness.load_module(ROOT / "bench" / "kinds"
                               / f"{cell.traffic['kind']}.py")
    inputs = harness.load_inputs(ROOT, cell.config)
    system = kind.setup(cell.config, cell.traffic, inputs, devices, seed)
    system.warm()
    log_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            window_s, records, _ = harness.closed_loop(
                system, seconds, 0, seed, traced=True)
        finally:
            jax.profiler.stop_trace()
        path = tr.find_xplane(log_dir)
        s = summarize(tr.read_xplane(path), read_spans(path))
        if keep is not None:
            keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, keep / f"{workload}.{seed}.xplane.pb")
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    run = harness.Run(cell=cell, setup_s=0.0, window_s=window_s,
                      requests=records, counters={}, trace=None, sizes={},
                      device_kind=devices[0].device_kind,
                      chips=len(devices))
    e2e = {m["name"]: harness.load_module(
        ROOT / "bench" / "metrics" / f"{m['name']}.py").read(run)
        for m in cell.end_to_end if m["name"] != "setup_s"}
    return {"workload": workload, "seed": seed,
            "device": {"kind": devices[0].device_kind,
                       "count": len(devices)},
            "attempted": len(records),
            "failed": sum(r["failed"] for r in records),
            "end_to_end": e2e, "window_s": s.window_s, "busy_s": s.busy_s,
            "host_share.solve": host_share_solve(s),
            "rebalance_share.partition": rebalance_share_partition(s),
            "span_seconds": dict(sorted(s.span_seconds.items(),
                                        key=lambda kv: -kv[1])),
            "idle_gaps": [[name, g] for name, g in s.idle_gaps]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=Path, default=None,
                    help="directory to copy the .xplane.pb into")
    args = ap.parse_args(argv)
    print(json.dumps(traced_window(args.workload, args.seed, args.seconds,
                                   args.keep)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
