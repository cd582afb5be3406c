"""Partition traffic: heterogeneous PU sets against one resident mesh.

Each request is one PU set from the paper's simulated systems (Sec. VI):
TOPO1 (a fast set F and slow PUs of speed 1, memory 2) or TOPO2 (F, and
two slow sets of equal size, S2 like TOPO1's slow PUs and S1 of memory 2
with speed / memory half that of F), |F| = k/12 or k/6, the fast (speed,
memory) a row of the paper's Table III, and memories scaled so that they
hold ``headroom`` times the load.  The program answers it as a user of
``repro.core`` would: Algorithm-1 targets (``target_block_sizes``), then
``partition`` with the configuration's method.

The number of PUs, k, is the configuration's ``pus``.  Traffic
parameters: ``pu_sets`` (each a ``family``, a ``fast_divisor`` d with
|F| = k / d, and the ``fast`` (speed, memory) of a Table III row),
``headroom``, ``check_sample``.  Every seed sends the same sequence of PU
sets (``pu_sets`` in order, cycled) against the configuration's one mesh;
the seed is the partitioner's seed.  Warm-up answers the first PU set
once: there is one compiled shape, since k is fixed.

Checked, for a sample of the answers, against the reference's own
Algorithm 1 (``reference.waterfill``):

* ``imbalance``: the largest block over its target, minus 1; the limit
  is the configuration's epsilon (the paper's 3%);
* ``cut_ratio``: the edges the partition cuts over those cut by a plain
  Morton-order split at the same targets (``reference.sfc_partition``).
"""
from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from bench import reference


@dataclasses.dataclass
class Request:
    name: str
    speeds: np.ndarray
    memories: np.ndarray


def pu_set(family: str, k: int, divisor: int, fast: tuple[float, float],
           n: int, headroom: float) -> Request:
    """TOPO1 / TOPO2 with |F| = k / ``divisor`` and memories scaled to
    ``headroom * n``."""
    fs, fm = fast
    n_fast = max(1, int(round(k / divisor)))
    speeds, mems = [fs] * n_fast, [fm] * n_fast
    if family == "topo1":
        speeds += [1.0] * (k - n_fast)
        mems += [2.0] * (k - n_fast)
    elif family == "topo2":
        n_s1 = (k - n_fast) // 2
        speeds += [fs / fm] * n_s1 + [1.0] * (k - n_fast - n_s1)
        mems += [2.0] * (k - n_fast)
    else:
        raise ValueError(f"unknown PU family {family!r}")
    mems = np.asarray(mems) * (headroom * n / sum(mems))
    return Request(name=f"{family}/F={n_fast}/fast={fs:g},{fm:g}",
                   speeds=np.asarray(speeds, np.float64), memories=mems)


def make_requests(config: dict, traffic: dict, n: int) -> list[Request]:
    """The traffic's PU sets, in its order, at the configuration's k."""
    return [pu_set(p["family"], int(config["pus"]), int(p["fast_divisor"]),
                   tuple(p["fast"]), n, traffic["headroom"])
            for p in traffic["pu_sets"]]


def graph(inputs: dict):
    from repro.sparse.graph import Graph

    return Graph(indptr=inputs["indptr"], indices=inputs["indices"],
                 weights=np.ones(len(inputs["indices"]), np.float32),
                 coords=inputs["coords"])


class Partitioner:
    """``repro.core``: Algorithm 1, then ``partition``."""

    def __init__(self, config, traffic, inputs, devices, seed):
        self.g = graph(inputs)
        self.n = self.g.n
        self.method = config["partition"]["method"]
        self.eps = config["partition"]["eps"]
        self.seed = int(np.random.default_rng([seed, 3]).integers(2 ** 31))
        self.pool = make_requests(config, traffic, self.n)

    def requests(self):
        return itertools.cycle(self.pool)

    def warm(self):
        self.serve(self.pool[0])

    def serve(self, req: Request) -> np.ndarray:
        from repro.core import PU, Topology, partition, target_block_sizes

        topo = Topology(tuple(PU(float(s), float(m), f"pu{i}") for i, (s, m)
                              in enumerate(zip(req.speeds, req.memories))))
        part, _ = partition(self.g, topo, method=self.method,
                            tw=target_block_sizes(self.n, topo),
                            seed=self.seed, eps=self.eps)
        return np.asarray(part)

    @staticmethod
    def units(req: Request) -> int:
        return 1

    @staticmethod
    def trips(answer) -> None:
        return None

    def counters(self) -> dict:
        return {}

    def sizes(self) -> dict:
        return {"n": self.n, "nnz": len(self.g.indices)}

    def release(self):
        self.g = None


class Control(Partitioner):
    """Neither the targets nor the geometry: vertices in an order drawn
    from the seed, split into k equal blocks."""

    def __init__(self, config, traffic, inputs, devices, seed):
        super().__init__(config, traffic, inputs, devices, seed)
        self.rng = np.random.default_rng([seed, 4])

    def warm(self):
        pass

    def serve(self, req: Request) -> np.ndarray:
        k = len(req.speeds)
        part = np.empty(self.n, np.int32)
        part[self.rng.permutation(self.n)] = np.arange(self.n) * k // self.n
        return part


def setup(config, traffic, inputs, devices, seed, control=False):
    return (Control if control else Partitioner)(config, traffic, inputs,
                                                 devices, seed)


def check(config, traffic, inputs, kept) -> dict:
    n = len(inputs["indptr"]) - 1
    imbalance = cut_ratio = 0.0
    for req, part in kept:
        k = len(req.speeds)
        part = np.asarray(part)
        if part.shape != (n,) or part.min() < 0 or part.max() >= k:
            imbalance = cut_ratio = np.inf
            continue
        tw = reference.waterfill(n, req.speeds, req.memories)
        sizes = np.bincount(part, minlength=k)
        imbalance = max(imbalance, float(np.max(sizes / tw)) - 1.0)
        ref = reference.sfc_partition(inputs["coords"], tw)
        cut_ratio = max(cut_ratio, reference.edge_cut(
            inputs["indptr"], inputs["indices"], part)
            / reference.edge_cut(inputs["indptr"], inputs["indices"], ref))
    limits = config["limits"]
    return {"imbalance": {"value": imbalance,
                          "limit": limits["imbalance"]},
            "cut_ratio": {"value": cut_ratio,
                          "limit": limits["cut_ratio"]}}
