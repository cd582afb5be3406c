"""Roofline-term extraction from compiled dry-run artifacts.

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / ICI_bw

with the per-chip peaks of ``launch.mesh.PEAKS`` for the chip's
``device_kind``.

cost_analysis() of the SPMD-partitioned executable reports the *per-device*
program, so dividing by per-chip peaks gives the same number as the global
formulation (global = per_device * chips; chips cancel).

collective_bytes is NOT in cost_analysis: we parse the post-SPMD HLO and sum
the output-tensor sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute.  all-reduce counts x2 (it moves the data
twice: reduce-scatter + all-gather on a ring).
"""
from __future__ import annotations

import re
from typing import Any

import numpy as np

from .mesh import TARGET_KIND, peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %ag = bf16[2,1024,128]{2,1,0} all-gather(...)
#        ROOT %t = (f32[8]{0}, f32[8]{0}) tuple(...)
_OP_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]))\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", )

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.-]+)\s*=\s*"
    r"((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]))\S*\s+"
    r"([\w-]+)\(([^)]*)", re.M)

_OPERAND_RE = re.compile(r"%([\w.-]+)")


def collective_bytes(hlo_text: str,
                     resolve_promotion: bool = True) -> dict[str, int]:
    """Sum output bytes of each collective kind (skipping -done duplicates).

    resolve_promotion: the CPU backend's float-normalization pass promotes
    every bf16 collective to f32 (convert -> collective -> convert back);
    on the TPU target these run in bf16.  When enabled, a collective whose
    payload is traced to a bf16 producer (operand is a convert / convert-
    fusion of a bf16 value, or the reducer is a '_promoted' clone) is
    counted at bf16 width — i.e. half its f32 wire size.
    """
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    dtype_of: dict[str, str] = {}
    kind_of: dict[str, tuple[str, list[str]]] = {}
    if resolve_promotion:
        for m in _DEF_RE.finditer(hlo_text):
            name, shape_str, opkind, ops = m.groups()
            dt = _SHAPE_RE.match(shape_str.lstrip("("))
            dtype_of[name] = dt.group(1) if dt else "?"
            kind_of[name] = (opkind, _OPERAND_RE.findall(ops or ""))

    def _payload_is_bf16(operand: str | None, line: str) -> bool:
        """True iff the wire payload is a promoted bf16 value.  Signatures:
        a '_promoted' cloned reducer, a convert-of-bf16 operand, or a
        convert/copy/bitcast fusion with a bf16 direct operand."""
        if "_promoted" in line:           # cloned bf16 reducer signature
            return True
        if operand is None:
            return False
        opkind, inner = kind_of.get(operand, ("", []))
        if opkind == "convert":
            return bool(inner) and dtype_of.get(inner[0]) == "bf16"
        if opkind == "fusion":
            return any(dtype_of.get(i) == "bf16" for i in inner)
        return False

    for m in _OP_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        # async pairs appear as -start/-done; the regex above strips the
        # suffix, but -done would double count.  Check the raw text window.
        tail = hlo_text[m.start():m.end()]
        if f"{kind}-done(" in tail:
            continue
        nbytes = _shape_bytes(shape_str)
        if resolve_promotion and "f32" in shape_str:
            line_end = hlo_text.find("\n", m.end())
            line = hlo_text[m.start():line_end]
            oper = re.search(r"\(%([\w.-]+)", line)
            if _payload_is_bf16(oper.group(1) if oper else None, line):
                nbytes //= 2
        out[kind] += nbytes
    return out


# Ops whose bytes are CPU-backend artifacts (bf16->f32 promotion inserts
# convert/copy pairs around every bf16 arithmetic op; TPU executes bf16
# natively) or that never touch HBM as standalone ops on TPU (layout
# bitcasts, broadcasts of scalars, tuple plumbing).
_STRUCTURAL_SKIP = frozenset((
    "parameter", "constant", "iota", "tuple", "get-tuple-element",
    "bitcast", "convert", "copy", "reduce-precision", "broadcast",
    "after-all", "partition-id",
))

_ENTRY_RE = re.compile(r"^ENTRY [^\{]*\{(.*?)^\}", re.M | re.S)
_SOP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*"
    r"((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]))\S*\s+([\w-]+)\(", re.M)


def structural_bytes(hlo_text: str,
                     s2_dim: int | None = None) -> tuple[float, float]:
    """TPU-adjusted HBM-traffic estimate: 2x the output bytes (write + read
    by consumer) of every entry-computation op that would exist on the TPU
    backend.  cost_analysis() on the CPU backend counts the f32-promotion
    converts the CPU inserts around every bf16 op — measured at >10x the
    real traffic for bf16 models — so the §Roofline memory term reports
    both the raw and this structural figure.

    Returns (total_bytes, s2_bytes): s2_bytes is the subtotal of ops whose
    shape contains the (S, S) attention-score pair — traffic the Pallas
    flash kernel (kernels/flash.py) keeps in VMEM on the TPU target.
    """
    m = _ENTRY_RE.search(hlo_text)
    body = m.group(1) if m else hlo_text
    total = 0
    s2 = 0
    for om in _SOP_RE.finditer(body):
        shape_str, kind = om.groups()
        if kind in _STRUCTURAL_SKIP:
            continue
        b = 2 * _shape_bytes(shape_str)
        total += b
        if s2_dim is not None:
            for _, dims in _SHAPE_RE.findall(shape_str):
                dd = [int(d) for d in dims.split(",") if d]
                if dd.count(s2_dim) >= 2:
                    s2 += b
                    break
    return float(total), float(s2)


def roofline_terms(flops: float, bytes_accessed: float,
                   coll: dict[str, int],
                   device_kind: str = TARGET_KIND) -> dict[str, Any]:
    """Three per-device roofline terms in seconds + the dominant one,
    against the peaks of one ``device_kind`` chip."""
    pk = peaks(device_kind)
    comm_bytes = sum(v * (2 if k == "all-reduce" else 1)
                     for k, v in coll.items())
    t_compute = flops / pk["peak_flops"]
    t_memory = bytes_accessed / pk["hbm_bw"]
    t_coll = comm_bytes / pk["ici_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    bound = max(t_compute, t_memory, t_coll)
    return {
        **terms,
        "dominant": dom,
        "collective_bytes": comm_bytes,
        "roofline_fraction": t_compute / bound if bound > 0 else 0.0,
        # fraction of the bound spent doing useful math: 1.0 = compute-bound
    }


def static_roofline(cost, device_kind: str = TARGET_KIND) -> dict[str, Any]:
    """Roofline terms from a static ``analysis.trace.TraceCost`` — the
    device-free counterpart of :func:`analyze_compiled`: no compilation,
    no HLO, just the jaxpr-counted per-CG-iteration FLOPs/bytes.

    ``TraceCost`` totals are global (summed over all devices); the
    roofline terms are per-device, so everything is divided by
    ``n_devices`` first.  ``cost.collectives()`` already uses the HLO
    collective names :func:`roofline_terms` expects (psum bytes arrive
    once and get the all-reduce x2 there).  Raises ``ValueError`` for a
    ``device_kind`` with no published peaks.
    """
    k = max(int(cost.n_devices), 1)
    coll = {name: b / k for name, b in cost.collectives().items()}
    out = roofline_terms(cost.flops_per_iter / k,
                         cost.hbm_bytes_per_iter / k, coll, device_kind)
    out["static_flops_per_iter"] = cost.flops_per_iter
    out["static_bytes_per_iter"] = cost.hbm_bytes_per_iter
    out["n_devices"] = k
    out["per_iteration"] = True
    return out


def modeled_makespan(g, part, anc=None, lams=None, speeds=None,
                     c_comp: float = 1.0) -> dict[str, Any]:
    """Partition-level modeled makespan (``core.costmodel``) — the
    machine-model counterpart of the jaxpr-counted :func:`static_roofline`:
    the roofline prices the *compiled program* (FLOPs/bytes/collective
    bytes of the padded SPMD executable), this prices the *partition*
    (per-PU Algorithm-1 compute + per-level deduplicated halo words).
    The two should rank partitions the same way — the padded program pays
    max block size as B and max per-level receive volume as S_lvl, which
    is exactly what the bottleneck model bounds.

    ``g`` is the adjacency :class:`repro.sparse.graph.Graph`; ``part`` a
    (n,) block array or a ``core.api.HierPartition`` (its ``anc``/
    ``lams`` are used unless overridden).  Returns the
    ``BottleneckCost.summary`` dict plus the summed-cut price under the
    same weights (``cut_price``) for side-by-side reporting.
    """
    from ..core.costmodel import BottleneckCost, CutCost

    if hasattr(part, "part"):              # HierPartition duck-type
        hp = part
        part = hp.part
        if anc is None:
            anc = hp.anc
        if lams is None:
            lams = hp.lams
    part = np.asarray(part)
    if anc is None:
        anc = np.zeros((0, int(part.max(initial=0)) + 1), dtype=np.int64)
    kw = dict(lams=None if lams is None else tuple(map(float, lams)),
              speeds=None if speeds is None else tuple(map(float, speeds)),
              c_comp=float(c_comp))
    out = BottleneckCost(**kw).summary(g, part, anc)
    out["cut_price"] = CutCost(**kw).price(g, part, np.atleast_2d(anc))
    return out


def analyze_compiled(lowered, compiled,
                     seq_len: int | None = None) -> dict[str, Any]:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):        # older jax returns [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    out = roofline_terms(flops, byts, coll)
    out["hlo_flops"] = flops
    out["hlo_bytes"] = byts
    out["collectives"] = coll
    out["collectives_raw_f32promoted"] = collective_bytes(
        hlo, resolve_promotion=False)
    sb, s2b = structural_bytes(hlo, s2_dim=seq_len)
    out["hlo_bytes_structural"] = sb
    out["hlo_bytes_attn_s2"] = s2b
    hbm_bw = peaks()["hbm_bw"]
    out["memory_s_structural"] = sb / hbm_bw
    out["memory_s_structural_flash"] = (sb - s2b) / hbm_bw
    try:
        mem = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    # memory_analysis() is optional on CPU/interpret backends: it raises
    # NotImplementedError/RuntimeError (XlaRuntimeError) where the backend
    # has no cost model, and AttributeError on executables that don't
    # expose it at all.  Anything else is a real bug and should surface.
    except (RuntimeError, NotImplementedError, AttributeError) as e:
        out["memory"] = {"error": str(e)}
    return out
