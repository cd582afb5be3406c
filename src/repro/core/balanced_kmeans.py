"""Balanced k-means geometric partitioner (geoKM) — von Looz et al. ICPP'18,
used by the paper as Geographer's phase-1, extended here with heterogeneous
target block weights (Algorithm 1 output) and a hierarchical mode (Sec. V).

Method.  Minimize sum of squared point-center distances subject to per-block
target sizes tw_i.  We use the *influence* formulation: each center carries a
multiplicative price gamma_i; points choose argmin_i gamma_i * dist(x, c_i)^2.
Loads above target raise the price, loads below lower it — a tatonnement that
converges to blocks of the requested sizes with compact shapes.

Implementation is JAX-native and jit-compiled: the hot loop is an (n, k)
distance computation (a matmul on the MXU — see kernels/pdist.py for the
Pallas version), a segment-sum for loads/centroids, and a price update.
Fixed trip count via lax.fori_loop keeps it a single XLA program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..sparse.graph import Graph
from ..spans import span
from .geometry import morton_codes, weighted_split_assignment
from ..kernels import ops as kops


def _init_centers(coords: np.ndarray, tw: np.ndarray) -> np.ndarray:
    """SFC seeding: slice the Morton order at cumulative target weights and
    take each chunk's centroid (Geographer's initialization)."""
    codes = np.asarray(morton_codes(jnp.asarray(coords)))
    order = np.argsort(codes, kind="stable")
    part = weighted_split_assignment(order, tw)
    k = len(tw)
    sums = np.zeros((k, coords.shape[1]), dtype=np.float64)
    np.add.at(sums, part, coords)
    counts = np.maximum(np.bincount(part, minlength=k), 1)
    return (sums / counts[:, None]).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("iters", "price_steps",
                                             "use_pallas"))
def _bkm_loop(coords, centers, tw, iters: int, price_steps: int,
              price_lr: float = 0.18, use_pallas: bool = False):
    """The jit'd optimization loop.

    Per outer iteration: `price_steps` rounds of price adjustment under fixed
    centers (cheap: reuse the distance matrix), then one centroid update.
    Returns (part, centers, prices).
    """
    n = coords.shape[0]
    k = centers.shape[0]
    tw_frac = tw / jnp.sum(tw)

    def assign(dist2, log_price):
        eff = dist2 + log_price[None, :]      # log-domain multiplicative price
        return jnp.argmin(eff, axis=1)

    def outer(it, state):
        centers, log_price = state
        if use_pallas:
            dist2 = kops.pairwise_sqdist(coords, centers)
        else:
            dist2 = (jnp.sum(coords * coords, axis=1, keepdims=True)
                     - 2.0 * coords @ centers.T
                     + jnp.sum(centers * centers, axis=1)[None, :])
        # normalize so prices act on comparable scales
        dist2 = dist2 / (jnp.mean(dist2) + 1e-12)

        def price_round(_, lp):
            part = assign(dist2, lp)
            load = jnp.zeros(k).at[part].add(1.0)
            load_frac = load / n
            # raise price where overloaded, lower where underloaded
            lp = lp + price_lr * jnp.log((load_frac + 1e-6)
                                         / (tw_frac + 1e-6))
            return lp - jnp.mean(lp)

        log_price = jax.lax.fori_loop(0, price_steps, price_round, log_price)
        part = assign(dist2, log_price)
        one_hot_sums = jnp.zeros((k, coords.shape[1])).at[part].add(coords)
        counts = jnp.zeros(k).at[part].add(1.0)
        new_centers = one_hot_sums / jnp.maximum(counts, 1.0)[:, None]
        # keep empty centers where they were
        new_centers = jnp.where(counts[:, None] > 0, new_centers, centers)
        return new_centers, log_price

    centers, log_price = jax.lax.fori_loop(
        0, iters, outer, (centers, jnp.zeros(k, coords.dtype)))
    if use_pallas:
        dist2 = kops.pairwise_sqdist(coords, centers)
    else:
        dist2 = (jnp.sum(coords * coords, axis=1, keepdims=True)
                 - 2.0 * coords @ centers.T
                 + jnp.sum(centers * centers, axis=1)[None, :])
    dist2 = dist2 / (jnp.mean(dist2) + 1e-12)
    part = assign(dist2, log_price)
    return part, centers, log_price


def _exact_rebalance(coords: np.ndarray, centers: np.ndarray,
                     part: np.ndarray, tw: np.ndarray) -> np.ndarray:
    """Post-pass: enforce sizes exactly (floor(tw) sum-preserving) by moving
    the cheapest vertices out of overloaded blocks to the nearest underloaded
    block.  Keeps compactness: candidates are those with the smallest
    (d_target^2 - d_own^2) regret."""
    k = len(tw)
    want = np.round(tw).astype(np.int64)
    want[np.argmax(want)] += len(part) - want.sum()  # fix rounding drift
    d2 = ((coords[:, None, :] - centers[None, :, :]) ** 2).sum(-1) \
        if len(coords) * k <= 5_000_000 else None
    for _ in range(4 * k):
        sizes = np.bincount(part, minlength=k)
        over = np.nonzero(sizes > want)[0]
        under = np.nonzero(sizes < want)[0]
        if len(over) == 0:
            break
        b = over[np.argmax(sizes[over] - want[over])]
        members = np.nonzero(part == b)[0]
        if d2 is not None:
            regret = d2[members][:, under] - d2[members][:, b][:, None]
        else:
            dm = coords[members]
            d_own = ((dm - centers[b]) ** 2).sum(-1)
            d_tgt = ((dm[:, None, :] - centers[under][None]) ** 2).sum(-1)
            regret = d_tgt - d_own[:, None]
        flat = np.argsort(regret, axis=None, kind="stable")
        n_move = int(sizes[b] - want[b])
        moved = 0
        deficit = (want - sizes).clip(min=0)
        for f in flat:
            if moved >= n_move:
                break
            vi, uj = np.unravel_index(f, regret.shape)
            tgt = under[uj]
            if deficit[tgt] > 0 and part[members[vi]] == b:
                part[members[vi]] = tgt
                deficit[tgt] -= 1
                moved += 1
    return part


def partition_balanced_kmeans(g: Graph, tw: np.ndarray, seed: int = 0,
                              iters: int = 30, price_steps: int = 12,
                              exact: bool = True,
                              use_pallas: bool = False) -> np.ndarray:
    """geoKM: balanced k-means with heterogeneous target weights.  Its
    phases are the host spans ``kmeans.seed`` (Morton seeding on the
    host), ``kmeans.loop`` (the device loop until its labels are on the
    host) and ``kmeans.rebalance`` (the host's exact-size pass)."""
    assert g.coords is not None, "balanced k-means needs coordinates"
    tw = np.asarray(tw, dtype=np.float64)
    coords = np.asarray(g.coords, dtype=np.float32)
    with span("kmeans.seed"):
        centers0 = _init_centers(coords, tw)
    with span("kmeans.loop"):
        part, centers, _ = _bkm_loop(
            jnp.asarray(coords), jnp.asarray(centers0),
            jnp.asarray(tw, dtype=jnp.float32), iters=iters,
            price_steps=price_steps, use_pallas=use_pallas)
        part = np.asarray(part, dtype=np.int32).copy()
    if exact:
        with span("kmeans.rebalance"):
            part = _exact_rebalance(coords, np.asarray(centers), part, tw)
    return part


def partition_hierarchical_kmeans(g: Graph, tw: np.ndarray,
                                  fanouts: tuple[int, ...], seed: int = 0,
                                  **kw) -> np.ndarray:
    """Hierarchical balanced k-means (Sec. V): partition level-by-level along
    the topology tree so border-sharing blocks land on nearby PUs.

    At level i, each current block is split into fanouts[i+1] children whose
    target weights are the sums of the leaf tw's under each child.
    """
    assert g.coords is not None
    tw = np.asarray(tw, dtype=np.float64)
    k = len(tw)
    assert int(np.prod(fanouts)) == k
    part = np.zeros(g.n, dtype=np.int64)   # block id at current level
    leaf_lo = {0: 0}
    leaf_hi = {0: k}
    for level, fan in enumerate(fanouts):
        new_part = np.zeros_like(part)
        new_lo, new_hi = {}, {}
        for blk in np.unique(part):
            lo, hi = leaf_lo[blk], leaf_hi[blk]
            per_child = (hi - lo) // fan
            child_tw = np.array([tw[lo + c * per_child:
                                    lo + (c + 1) * per_child].sum()
                                 for c in range(fan)])
            mask = part == blk
            ids = np.nonzero(mask)[0]
            sub = Graph(indptr=np.array([0, 0]), indices=np.zeros(0, np.int32),
                        weights=np.zeros(0, np.float32),
                        coords=g.coords[ids])
            sub.indptr = np.zeros(len(ids) + 1, dtype=np.int64)  # coords-only
            # scale child tw to the actual number of points in this block
            scale = len(ids) / max(child_tw.sum(), 1e-9)
            sub_part = partition_balanced_kmeans(sub, child_tw * scale,
                                                 seed=seed, **kw)
            for c in range(fan):
                cid = blk * fan + c
                new_part[ids[sub_part == c]] = cid
                new_lo[cid] = lo + c * per_child
                new_hi[cid] = lo + (c + 1) * per_child
        part, leaf_lo, leaf_hi = new_part, new_lo, new_hi
    # final: blocks are already leaf-indexed (level order == leaf order)
    out = np.zeros(g.n, dtype=np.int32)
    for blk in np.unique(part):
        out[part == blk] = leaf_lo[blk]
    return out
