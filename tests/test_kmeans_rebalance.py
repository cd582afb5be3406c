"""geoKM's exact-size pass: ``_exact_rebalance`` decides the walk's moves a
target at a time and must give the labels of the plain walk
(``_exact_rebalance_reference``) bit for bit, ties included, on both
regret paths (the squared-distance table, and the distances computed per
round above ``D2_TABLE_MAX``)."""
import numpy as np
import pytest

from repro.core import balanced_kmeans as bkm


def _instance(seed: int):
    """Random points, centers, labels and targets: n 20-2000, k 2-12,
    2-D or 3-D; every third on a coarse integer grid, so regrets tie."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 2001))
    k = int(rng.integers(2, 13))
    dim = int(rng.integers(2, 4))
    if seed % 3 == 0:
        coords = rng.integers(0, 4, (n, dim)).astype(np.float32)
        centers = rng.integers(0, 4, (k, dim)).astype(np.float32)
    else:
        coords = rng.random((n, dim), dtype=np.float32)
        centers = rng.random((k, dim), dtype=np.float32)
    part = rng.integers(0, k, n).astype(np.int32)
    tw = rng.dirichlet(np.ones(k)) * n
    return coords, centers, part, tw


@pytest.mark.parametrize("table", [True, False], ids=["table", "no_table"])
@pytest.mark.parametrize("seed", range(24))
def test_exact_rebalance_equals_the_walk(seed, table, monkeypatch):
    if not table:
        monkeypatch.setattr(bkm, "D2_TABLE_MAX", 0)
    coords, centers, part, tw = _instance(seed)
    want = bkm._exact_rebalance_reference(coords, centers, part.copy(), tw)
    got, stats = bkm._exact_rebalance(coords, centers, part.copy(), tw)
    np.testing.assert_array_equal(got, want)
    assert stats["moved"] == int((got != part).sum())
    assert stats["rounds"] <= stats["passes"]


def test_balanced_labels_take_no_round():
    coords, centers, part, _ = _instance(1)
    tw = np.bincount(part, minlength=len(centers)).astype(np.float64)
    got, stats = bkm._exact_rebalance(coords, centers, part.copy(), tw)
    np.testing.assert_array_equal(got, part)
    np.testing.assert_array_equal(
        bkm._exact_rebalance_reference(coords, centers, part.copy(), tw), part)
    assert stats == {"rounds": 0, "passes": 0, "moved": 0}


def _walk(regret: np.ndarray, left: np.ndarray, n_move: int) -> np.ndarray:
    """One round of the plain walk over a (targets, rows) ``regret``."""
    left = left.copy()
    dest = np.full(regret.shape[1], -1)
    moved = 0
    for f in np.argsort(regret.T, axis=None, kind="stable"):
        if moved >= n_move:
            break
        v, t = np.unravel_index(f, regret.T.shape)
        if left[t] > 0 and dest[v] < 0:
            dest[v] = t
            left[t] -= 1
            moved += 1
    return dest


@pytest.mark.parametrize("absorbed", [True, False],
                         ids=["absorbed", "targets_fill_first"])
@pytest.mark.parametrize("seed", range(4))
def test_greedy_moves_equal_one_round_of_the_walk(seed, absorbed):
    """A round on its own, also where the targets' room cannot take all
    ``n_move`` rows (``_exact_rebalance`` never asks for that: the most
    overloaded block's surplus is at most the total deficit)."""
    rng = np.random.default_rng(seed)
    u, m = int(rng.integers(1, 8)), int(rng.integers(10, 400))
    if seed % 2:    # ties, and zeros of both signs, which tie too
        regret = (rng.integers(-3, 4, (u, m)).astype(np.float32)
                  * rng.choice(np.float32([-1, 1]), (u, m)))
    else:
        regret = rng.standard_normal((u, m)).astype(np.float32)
    left = rng.integers(1, max(2, m // u), u)
    room = int(left.sum())
    n_move = (int(rng.integers(1, min(room, m) + 1)) if absorbed
              else room + int(rng.integers(1, 5)))
    dest, passes = bkm._greedy_moves(regret, left, n_move)
    np.testing.assert_array_equal(dest, _walk(regret, left, n_move))
    assert (dest >= 0).sum() == min(room, n_move, m)
    assert 1 <= passes <= u + 1


def test_greedy_moves_tie_signed_zeros():
    """-0.0 and 0.0 compare equal, so the lower row goes first."""
    regret = np.float32([[0.0, -0.0, 1.0]])
    dest, _ = bkm._greedy_moves(regret, np.array([1]), 1)
    np.testing.assert_array_equal(dest, [0, -1, -1])
    np.testing.assert_array_equal(dest, _walk(regret, np.array([1]), 1))


def test_rebalance_span_records_its_stats(tmp_path):
    """``kmeans.rebalance`` carries ``rounds``, ``passes`` and ``moved``;
    ``moved`` is the labels the pass changed.  One k-means trip leaves
    the sizes off their targets, so the pass has work."""
    from bench.tests.test_program_spans import record
    from repro.sparse.generators import rdg

    g = rdg(600, seed=3)
    tw = np.array([250.0, 120.0, 130.0, 100.0])
    kw = dict(iters=1, price_steps=1)
    loop = bkm.partition_balanced_kmeans(g, tw, exact=False, **kw)
    out = {}

    def run():
        out["part"] = bkm.partition_balanced_kmeans(g, tw, **kw)

    events = record(tmp_path, run)
    (stats,) = [e[3] for e in events if e[0] == "repro.kmeans.rebalance"]
    assert set(stats) == {"rounds", "passes", "moved"}
    assert stats["moved"] == int((out["part"] != loop).sum()) > 0
    assert 1 <= stats["rounds"] <= stats["passes"]
    np.testing.assert_array_equal(np.bincount(out["part"], minlength=4),
                                  tw.astype(np.int64))
