"""The benchmark's own copies against the program's originals, and the
plain references on hand-made inputs."""
import numpy as np
import pytest

from bench import reference, sequence
from bench.kinds import partition as kind


@pytest.mark.parametrize("family", ["topo1", "topo2"])
@pytest.mark.parametrize("divisor", [12, 6])
@pytest.mark.parametrize("row", [0, 2, 4])
def test_pu_sets_and_waterfill_match_the_program(family, divisor, row):
    from repro.core import Topology, scale_to_load, target_block_sizes
    from repro.core.topology import TABLE_III_FAST_SPECS

    n, k = 1_000_000, 24
    fs, fm = TABLE_III_FAST_SPECS[row]
    req = kind.pu_set(family, k, divisor, (fs, fm), n, 1.2)
    topo = scale_to_load(getattr(Topology, family)(
        k, fast_fraction=1 / divisor, fast_speed=fs, fast_memory=fm), n)
    assert np.allclose(req.speeds, topo.speeds)
    assert np.allclose(req.memories, topo.memories)
    assert np.allclose(reference.waterfill(n, req.speeds, req.memories),
                       target_block_sizes(n, topo), rtol=1e-12)


def test_waterfill_saturates_the_fast_pu():
    tw = reference.waterfill(100, [4.0, 1.0, 1.0], [30.0, 100.0, 100.0])
    assert np.allclose(tw, [30.0, 35.0, 35.0])


def test_sfc_partition_meets_the_sizes_and_cut_counts_edges():
    rng = np.random.default_rng(0)
    coords = rng.random((1000, 2)).astype(np.float32)
    part = reference.sfc_partition(coords, [100.4, 499.6, 400.0])
    assert np.bincount(part).tolist() == [100, 500, 400]
    # a path 0-1-2-3 split in the middle cuts one edge
    indptr = np.array([0, 1, 3, 5, 6])
    indices = np.array([1, 0, 2, 1, 3, 2])
    assert reference.edge_cut(indptr, indices, np.array([0, 0, 1, 1])) == 1


def test_residuals_in_float64():
    indptr, indices = np.array([0, 1, 2]), np.array([0, 1])
    data = np.array([2.0, 4.0], np.float32)
    b = np.array([[2.0], [4.0]])
    assert reference.residuals(indptr, indices, data, b,
                               np.array([[1.0], [1.0]])) == 0.0
    assert reference.residuals(indptr, indices, data, b,
                               np.array([[1.0], [0.0]])) == pytest.approx(
        4 / np.sqrt(20))


def test_balanced_sequence_keeps_every_prefix_near_the_mix():
    mix = {1: 0.35, 2: 0.2, 3: 0.15, 4: 0.15, 6: 0.1, 8: 0.05}
    seq = sequence.balanced(mix, 20)
    assert sorted(seq) == sorted([1] * 7 + [2] * 4 + [3] * 3 + [4] * 3
                                 + [6] * 2 + [8])
    for i in range(1, 21):
        for w, p in mix.items():
            assert abs(seq[:i].count(w) - p * i) < 1


def test_partition_traffic_sends_every_pu_set_of_the_paper_once():
    import itertools
    import json

    from bench.harness import ROOT

    traffic = json.loads((ROOT / "bench" / "traffic"
                          / "partition_topo.json").read_text())
    sent = [(p["family"], p["fast_divisor"], tuple(p["fast"]))
            for p in traffic["pu_sets"]]
    from repro.core.topology import TABLE_III_FAST_SPECS

    paper = itertools.product(["topo1", "topo2"], [12, 6],
                              [tuple(r) for r in TABLE_III_FAST_SPECS])
    assert sorted(sent) == sorted(paper)
