"""The span reader of ``bench/spans.py``: on spans made by hand, on the
two recorded TPU traces (which hold ``bench.`` spans only, so it must
agree with ``trace.summarize`` there), and end to end on a tiny cell on
the CPU, as ``python bench/spans.py`` runs it on the chip."""
from pathlib import Path

import pytest

from bench import spans as sp
from bench import trace as tr

DATA = Path(__file__).parent / "data"

# one request on one host line: the service's phases nested in
# repro.serve.solve, nested in bench.request, inside bench.window
LINE = [("bench.window", 0.0, 10.0), ("bench.request", 0.5, 9.0),
        ("repro.serve.solve", 1.0, 8.5), ("repro.serve.pad", 2.0, 4.0),
        ("repro.serve.wait", 4.2, 5.8), ("repro.serve.gather", 6.0, 8.0)]
DEVICE = {0: [("f", 1.0, 2.0), ("g", 4.5, 6.0)]}


def test_a_gap_is_named_by_the_innermost_span():
    trace = tr.Trace(device_ops=DEVICE, spans=LINE)
    s = sp.summarize(trace, [LINE])
    # idle: [0, 1], [2, 4.5], [6, 10]
    assert s.idle_gaps == [("repro.serve.gather", 4.0),
                           ("repro.serve.pad", 2.5),
                           ("bench.request", 1.0)]
    # trace.py names each by the outermost span that covers it
    assert [name for name, _ in tr.summarize(trace).idle_gaps] == [
        "bench.request"] * 3


def test_a_gap_outside_every_span_but_the_window():
    line = [("bench.window", 0.0, 3.0), ("repro.serve.pad", 0.0, 0.5)]
    s = sp.summarize(tr.Trace(device_ops={0: [("f", 1.0, 2.0)]},
                              spans=line[:1]), [line])
    # idle: [0, 1], half of it in the pad, and [2, 3]
    assert s.idle_gaps == [("repro.serve.pad", 1.0), (tr.NO_SPAN, 1.0)]


def test_span_seconds_are_self_times_inside_the_window():
    assert sp.span_seconds([LINE], 0.0, 10.0) == pytest.approx(
        dict(tr.self_times(LINE)))
    assert sp.span_seconds([LINE], 0.0, 10.0) == pytest.approx({
        "bench.window": 1.5, "bench.request": 1.0,
        "repro.serve.solve": 1.9, "repro.serve.pad": 2.0,
        "repro.serve.wait": 1.6, "repro.serve.gather": 2.0})
    # a name on two lines sums; a span is cut at the window's ends
    other = [("repro.serve.pad", 9.0, 12.0)]
    assert sp.span_seconds([LINE, other], 0.0, 10.0)[
        "repro.serve.pad"] == pytest.approx(3.0)


def test_shares_read_the_program_spans():
    s = sp.summarize(tr.Trace(device_ops=DEVICE, spans=LINE), [LINE])
    # solve 1.9 + pad 2.0 + gather 2.0 of host work; the wait is not
    assert sp.host_share_solve(s) == pytest.approx(5.9 / 10.0)
    assert sp.rebalance_share_partition(s) is None
    line = [("bench.window", 0.0, 10.0), ("repro.partition", 1.0, 9.0),
            ("repro.kmeans.loop", 2.0, 3.0),
            ("repro.kmeans.rebalance", 3.0, 8.5)]
    s = sp.summarize(tr.Trace(device_ops=DEVICE, spans=line), [line])
    assert sp.rebalance_share_partition(s) == pytest.approx(0.55)
    assert sp.host_share_solve(s) is None
    # a partition that never rebalanced reads 0, not None
    s = sp.summarize(tr.Trace(device_ops=DEVICE, spans=line[:3]),
                     [line[:3]])
    assert sp.rebalance_share_partition(s) == 0.0


# trace.summarize's values on the recorded traces: window, busy, op
# seconds summed, and the idle gaps (name, seconds), longest first
RECORDED = {
    "sample.xplane.pb": (0.103508412, 3.432600000000119e-05, [
        ("bench.client", 0.05090649900000001),
        ("bench.client", 0.050591866000000006),
        ("bench.client", 0.0019757159999999885),
        ("bench.client", 1.9999999989472883e-09),
        ("bench.client", 9.999999994736442e-10),
        ("bench.client", 9.999999994736442e-10),
        ("bench.client", 9.999999994736442e-10)]),
    "sample_x4.xplane.pb": (0.105818108, 9.0523499999981e-05, [
        ("bench.client", 0.05192548700000002),
        ("bench.client", 0.051521928999999994),
        ("bench.request", 0.0022776090000000138),
        ("bench.client", 1.9999999989472883e-09),
        ("bench.client", 1.9999999989472883e-09),
        ("bench.client", 1.0000000272292198e-09)]),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_traces_without_program_spans_read_as_before(name):
    window_s, busy_s, gaps = RECORDED[name]
    trace = tr.read_xplane(DATA / name)
    lines = sp.read_spans(DATA / name)
    base = tr.summarize(trace)
    s = sp.summarize(trace, lines)
    assert base.window_s == s.window_s == pytest.approx(window_s, abs=0)
    assert base.busy_s == s.busy_s == pytest.approx(busy_s)
    assert sum(base.op_seconds.values()) == pytest.approx(busy_s)
    assert base.idle_gaps == s.idle_gaps
    assert [n for n, _ in s.idle_gaps] == [n for n, _ in gaps]
    assert [g for _, g in s.idle_gaps] == pytest.approx(
        [g for _, g in gaps], abs=1e-12)
    assert set(s.span_seconds) == {"bench.window", "bench.request",
                                   "bench.client"}
    assert sp.host_share_solve(s) is None
    assert sp.rebalance_share_partition(s) is None


def test_a_tiny_cell_traced_on_the_cpu(monkeypatch):
    """``traced_window`` on a tiny ``solve_1col``: the service's spans are
    read, and every request is answered."""
    import repro.launch.compile_cache as cc

    from bench.tests.cpu_cell import TINY

    monkeypatch.setattr(cc, "use_compile_cache", lambda: None)
    workload = "delaunay_n20.solve_1col"
    line = sp.traced_window(workload, 2 ** 33 + 7, 0.5, accelerator=False,
                            overrides=TINY[workload])
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["end_to_end"]) == {"cols_per_s", "solve_p95_s"}
    assert {"repro.serve.solve", "repro.serve.admit", "repro.serve.pad",
            "repro.serve.scatter", "repro.serve.dispatch",
            "repro.serve.wait", "repro.serve.gather"} <= set(
                line["span_seconds"])
    assert 0 < line["host_share.solve"] < 1
    assert line["rebalance_share.partition"] is None
