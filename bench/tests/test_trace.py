"""The trace reduction of ``bench/trace.py``: on intervals made by hand,
and on a small trace recorded on a TPU v5e chip by ``record_trace.py``
(three ``bench.request`` spans, each running a jitted loop, separated by
``bench.client`` spans that sleep 50 ms with the device idle), and on one
recorded on four v5e chips whose loop exchanges halos and sums dots."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness
from bench import trace as tr

SAMPLE = Path(__file__).parent / "data" / "sample.xplane.pb"
SAMPLE_X4 = Path(__file__).parent / "data" / "sample_x4.xplane.pb"


def collective_share(summary):
    metric = harness.load_module(harness.ROOT / "bench" / "metrics"
                                 / "collective_share.solve.py")
    return metric.read(SimpleNamespace(trace=summary))


def test_busy_is_the_union_of_overlapping_ops():
    assert tr.merge([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == [(0, 3), (5, 6)]
    assert tr.length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.gaps([(1, 2), (1.5, 3), (4, 5)], 0, 6) == [(0, 1), (3, 4),
                                                       (5, 6)]


def test_summary_averages_devices_and_names_gaps():
    t = tr.Trace(
        device_ops={0: [("fusion.1", 1.0, 3.0),
                        ("psum.2 (all-reduce f32[4])", 2.0, 4.0)],
                    1: [("fusion.1", 1.0, 2.0), ("fusion.3", 6.0, 7.0)]},
        spans=[("bench.window", 0.0, 10.0), ("bench.request", 0.5, 4.5),
               ("bench.client", 4.5, 9.0)])
    s = tr.summarize(t)
    assert s.window_s == 10.0 and s.devices == 2
    assert s.busy_s == pytest.approx((3.0 + 2.0) / 2)     # unions: 3 and 2
    assert s.op_seconds["fusion.1"] == pytest.approx((2.0 + 1.0) / 2)
    assert sum(v for op, v in s.op_seconds.items()
               if tr.is_collective(op)) == pytest.approx(1.0)
    # idle on every device: [0, 1], [4, 6], [7, 10]
    assert s.idle_gaps == [("bench.client", 3.0), ("bench.client", 2.0),
                           ("bench.request", 1.0)]


def test_an_op_holding_others_counts_its_own_time_only():
    events = [("while", 0.0, 10.0), ("body.1", 1.0, 3.0),
              ("body.2", 4.0, 6.0), ("inner", 4.5, 5.0), ("after", 11.0, 12.0)]
    assert dict(tr.self_times(events)) == {
        "while": 6.0, "body.1": 2.0, "body.2": 1.5, "inner": 0.5,
        "after": 1.0}


def test_op_names_from_hlo_text():
    assert tr.op_name("%fusion.17 = f32[1048576]{0:T(1024)S(1)} fusion("
                      "s32[7339966]{0:T(1024)} %bitcast.43), kind=kCustom"
                      ) == "fusion.17 (fusion f32[1048576])"
    assert tr.op_name("%while.2 = (f32[8,1]{0,1:T(1,128)}, s32[1]{0}) "
                      "while((f32[8,1]{0,1}, s32[1]{0}) %tuple.29)"
                      ) == "while.2 (while)"
    # collectives by opcode, as the TPU compiler names them
    psum = tr.op_name("%psum.44 = f32[4]{0:T(128)S(1)} all-reduce(%m.2), "
                      "channel_id=1, replica_groups={{0,1,2,3}}")
    assert psum == "psum.44 (all-reduce f32[4])" and tr.is_collective(psum)
    assert tr.is_collective(tr.op_name(
        "%collective-permute-done.1 = f32[64,4]{1,0:T(8,128)S(1)} "
        "collective-permute-done(%collective-permute-start.1)"))
    assert not tr.is_collective(tr.op_name(
        "%pad_maximum_fusion.3 = f32[1216,4]{1,0:T(8,128)S(1)} fusion("
        "%collective-permute-done, %get-tuple-element.417), kind=kLoop"))
    assert not tr.is_collective("all-reduce.2")


def test_gap_outside_every_span():
    t = tr.Trace(device_ops={0: [("f", 1.0, 2.0)]},
                 spans=[("bench.window", 0.0, 3.0)])
    assert tr.summarize(t).idle_gaps[0] == (tr.NO_SPAN, 1.0)


def test_a_trace_without_the_window_span_is_refused():
    t = tr.Trace(device_ops={0: [("f", 1.0, 2.0)]},
                 spans=[("bench.request", 0.0, 3.0)])
    with pytest.raises(ValueError, match="bench.window"):
        tr.summarize(t)


def test_recorded_tpu_trace():
    t = tr.read_xplane(SAMPLE)
    assert list(t.device_ops) == [0]
    names = {name for name, _, _ in t.spans}
    assert names == {"bench.window", "bench.request", "bench.client"}
    s = tr.summarize(t)
    window = [b - a for name, a, b in t.spans if name == "bench.window"]
    assert s.window_s == pytest.approx(window[0])
    assert 0 < s.busy_s < s.window_s
    # the two 50 ms sleeps are the longest idle stretches, and the host
    # was in bench.client during each
    (n1, g1), (n2, g2) = s.idle_gaps[:2]
    assert n1 == n2 == "bench.client"
    assert 0.05 <= g2 <= g1 < 0.1
    # self times tile the busy time: the loop's body ops sit inside it
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s)
    assert any(name.startswith("while") for name in s.op_seconds)
    assert collective_share(s) == 0.0           # one chip: no collective


def test_recorded_four_chip_tpu_trace_counts_its_collectives():
    t = tr.read_xplane(SAMPLE_X4)
    assert sorted(t.device_ops) == [0, 1, 2, 3]
    s = tr.summarize(t)
    assert s.devices == 4 and 0 < s.busy_s < s.window_s
    coll = {op: v for op, v in s.op_seconds.items() if tr.is_collective(op)}
    assert {tr.opcode(op) for op in coll} >= {
        "all-reduce", "collective-permute-start", "collective-permute-done"}
    # the psum is named for the primitive, not for its opcode
    assert any(op.startswith("psum") for op in coll)
    share = collective_share(s)
    assert share == pytest.approx(sum(coll.values()) / s.busy_s)
    assert 0.5 < share < 1      # the loop does little but exchange


def test_collectives_the_tpu_compiler_emits_are_told_by_opcode():
    """The recorder's four-chip loop, compiled for a described v5e 2x2
    (no chip): its ppermute and psum come out as
    ``collective-permute-start``/``-done`` and an all-reduce named
    ``psum...``, and each is counted as a collective."""
    import os

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.tests import record_trace

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = Mesh(topo.devices[:4], ("pu",))
    x = jax.ShapeDtypeStruct((4 * 4096,), jnp.float32,
                             sharding=NamedSharding(mesh, P("pu")))
    hlo = record_trace.exchange_program(mesh).lower(x).compile().as_text()
    ops = [tr.op_name(line) for line in
           (raw.strip().removeprefix("ROOT ") for raw in hlo.splitlines())
           if line.startswith("%") and " = " in line]
    counted = {tr.opcode(op) for op in ops if tr.is_collective(op)}
    assert counted >= {"collective-permute-start", "collective-permute-done",
                       "all-reduce"}
    assert any(op.startswith("psum") and tr.is_collective(op) for op in ops)
