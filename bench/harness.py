"""Run one cell of ``BENCHMARK.json``: set up, warm up, measure, check.

Everything a cell needs is found by name:

* ``BENCHMARK.json`` names the cell's configuration and traffic mix;
* the configuration is the file the ``configs`` entry names; it names
  its input generator, ``inputs/<generator>.py``, with the generator's
  parameters and seed (the run's ``--seed`` draws the requests, so that
  every seed runs against the same deployment), and the limits of the
  numbers that decide ``correct``;
* the traffic mix is ``traffic/<traffic>.json``; its ``kind`` names the
  module that builds the system under test, makes the requests from the
  seed and checks the answers, ``kinds/<kind>.py``;
* every metric is ``metrics/<metric name>.py``, whose ``read(run)``
  returns the number or ``None`` where it finds nothing to read.

A later configuration, traffic mix or metric is a new file and a new
entry in ``BENCHMARK.json``; no file here needs an edit for it.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One cell, with its configuration and traffic mix as read from
    their files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


@dataclasses.dataclass
class Run:
    """What the metric readers read: one record per request of the
    window, the system's counters over the window, the reduced trace."""

    cell: Cell
    setup_s: float
    window_s: float
    requests: list[dict]
    counters: dict
    trace: object | None          # trace.TraceSummary of a traced run
    sizes: dict                   # of the system's input, e.g. n and nnz
    device_kind: str
    chips: int


def load_module(path: Path):
    """Import a file by its path (names may hold dots, as metrics do)."""
    name = "bench._by_path." + hashlib.blake2b(
        str(path.resolve()).encode(), digest_size=8).hexdigest()
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def _merged(base: dict, over: dict | None) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merged(out.get(k, {}), v) if isinstance(v, dict) else v
    return out


def load_cell(root: Path, workload: str, overrides: dict | None = None
              ) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``.  ``overrides``
    (``{"config": {...}, "traffic": {...}}``) are merged into the two
    files as read; the CPU tests use them to shrink a cell."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    overrides = overrides or {}
    config = _merged(json.loads((root / conf["file"]).read_text()),
                     overrides.get("config"))
    traffic = _merged(json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        overrides.get("traffic"))
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in spec["per_layer"] if workload in m["workloads"]]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def devices_for(chips: int, accelerator: bool = True):
    """The first ``chips`` devices; without ``accelerator`` (CPU tests)
    whatever JAX has."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if accelerator and platform != "tpu":
        raise NoAccelerator(f"needs a TPU, but JAX found platform "
                            f"{platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)} {platform!r} devices")
    return devices[:chips]


def load_inputs(root: Path, config: dict) -> dict:
    """The configuration's inputs, from the cache under ``bench/.cache``
    where an earlier run made them.  The key holds the generator's
    parameters (its own seed among them: a deployment's mesh is one fixed
    draw, as DIMACS10's file is) and a hash of its source, so an edited
    generator never reads a stale file."""
    gen = root / "bench" / "inputs" / f"{config['generator']}.py"
    params = config["generator_params"]
    h = hashlib.blake2b(gen.read_bytes(), digest_size=8)
    h.update(json.dumps(params, sort_keys=True).encode())
    path = (root / "bench" / ".cache"
            / f"{config['generator']}-{h.hexdigest()}.npz")
    if path.exists():
        with np.load(path) as f:
            return {k: f[k] for k in f.files}
    arrays = load_module(gen).make(**params)
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".npz")
    with os.fdopen(fd, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)
    return arrays


class _CompileCounter:
    """Counts traces and backend compiles while ``on`` (none may happen
    inside the measured window)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def close(self):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listen)

    def _listen(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.count += 1


def closed_loop(system, seconds: float, sample: int, seed: int,
                traced: bool = False):
    """One client: send the next request when the last answer is on the
    host, until ``seconds`` have passed; the request in flight at the
    deadline finishes and counts.  Returns ``(window_s, records,
    kept)``: a record per request, and a sample of ``sample`` (request,
    answer) pairs drawn from the seed (reservoir), for the check."""
    import jax.profiler

    span = jax.profiler.TraceAnnotation if traced else (
        lambda name: nullcontext())
    rng = np.random.default_rng([seed, 1])
    records, kept = [], []
    with span("bench.window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        for i, req in enumerate(system.requests()):
            start = time.perf_counter()
            if start >= deadline:
                break
            with span("bench.request"):
                try:
                    answer = system.serve(req)
                except Exception as e:      # counted as failed, run goes on
                    print(f"request {i} failed: {e!r}", file=sys.stderr)
                    answer = None
            end = time.perf_counter()
            records.append({"start": start - t0, "end": end - t0,
                            "units": system.units(req),
                            "trips": None if answer is None
                            else system.trips(answer),
                            "failed": answer is None})
            if answer is not None:
                if len(kept) < sample:
                    kept.append((req, answer))
                else:
                    j = int(rng.integers(0, i + 1))
                    if j < sample:
                        kept[j] = (req, answer)
        window_s = (records[-1]["end"] if records
                    else time.perf_counter() - t0)
    return window_s, records, kept


def _memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, accelerator: bool = True,
             overrides: dict | None = None, control: bool = False,
             t_start: float | None = None) -> dict:
    """Run one cell and return its result line (see ``run.py``).

    ``control`` puts the configuration's control (the reference one
    precision below, or a guarantee broken) in the program's place; only
    the control's own script and tests set it."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(root, workload, overrides)
    devices = devices_for(cell.chips, accelerator)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # cache every program, however quickly it compiled, so that a run
    # after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = load_module(root / "bench" / "kinds" / f"{cell.traffic['kind']}.py")
    inputs = load_inputs(root, cell.config)
    system = kind.setup(cell.config, cell.traffic, inputs, devices, seed,
                        control=control)
    n = system.sizes()["n"]
    if n != cell.config["n"]:
        raise ValueError(f"the inputs have n = {n}, the configuration "
                         f"states n = {cell.config['n']}")
    system.warm()
    counter = _CompileCounter()
    before = system.counters()
    setup_s = time.perf_counter() - t_start

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # device ops and bench spans only
        jax.profiler.start_trace(log_dir, profiler_options=options)
    counter.on = True
    try:
        window_s, records, kept = closed_loop(
            system, seconds, int(cell.traffic["check_sample"]), seed,
            traced=trace)
    finally:
        counter.close()
        if trace:
            jax.profiler.stop_trace()
    after = system.counters()
    counters = {k: after[k] - before.get(k, 0) for k in after}
    memory_peak = _memory_peak(devices)
    sizes = system.sizes()
    system.release()
    del system

    summary = None
    if trace:
        from bench import trace as tr

        try:
            t = tr.read_xplane(tr.find_xplane(log_dir))
            if t.device_ops:
                summary = tr.summarize(t)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    checks = kind.check(cell.config, cell.traffic, inputs, kept)
    failed = sum(r["failed"] for r in records)
    correct = (failed == 0 and bool(kept)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    run = Run(cell=cell, setup_s=setup_s, window_s=window_s,
              requests=records, counters=counters, trace=summary, sizes=sizes,
              device_kind=devices[0].device_kind, chips=len(devices))
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module(root / "bench" / "metrics"
                            / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ops = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ops[:10]],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    result["diagnostics"] = {
        "compiles_in_window": counter.count, "window_s": window_s,
        "setup_s": setup_s, "checked": len(kept),
        "latency_s": [r["end"] - r["start"] for r in records],
        "trips": [r["trips"] for r in records]}
    result["checks"] = checks
    return result
