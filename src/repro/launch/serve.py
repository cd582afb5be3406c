"""Serving launcher: solver-as-a-service for sparse systems, plus the
token-serving scaffold (batched prefill + decode with a KV/state cache).

Solver serving (the paper's workload at traffic scale — many small/medium
CG solves against a pool of matrices, ROADMAP's solver-as-a-service item):

  PYTHONPATH=src python -m repro.launch.serve --solver --requests 64

:class:`SolverService` is the serving layer the bench and tests drive:

  * **operator cache** — LRU keyed by :func:`matrix_fingerprint` (shape +
    nnz + a blake2b content hash of indptr/indices/data), so repeat
    traffic skips ``build_plan`` / ``build_plan_tree`` / format
    conversion entirely and lands on the cached operator's jitted solve
    (the ``DistributedOperator._fused`` per-``(tol, max_iters,
    precondition)`` trace cache compounds with this: cache-hit requests
    re-enter an already-compiled program).
  * **bucketed admission** — each request's RHS batch is padded up to a
    size class from ``buckets`` (the MaxText ``offline_inference``
    pattern), so one compiled multi-RHS program per (matrix, class)
    serves every batch width in the class.  Padding columns are
    all-zero, and a zero column is *free* under the masked batched CG:
    ``||b||^2 = 0`` keeps it inactive from iteration 0.
  * **counters and spans** — :class:`ServeStats` tracks operator/bucket
    hits and misses, evictions, and real vs padded columns (padding
    waste).  Under a profiler session each solve records the host span
    ``repro.serve.solve`` (stats ``request``, ``width``, ``bucket``)
    holding one span per phase, in order: ``serve.admit`` (the operator
    cache; ``plan.build`` inside it on a miss, with the ``coo`` layout's
    ``groups`` and ``pad_share`` as stats), ``serve.pad``,
    ``serve.scatter`` (host layout and host-to-device copy),
    ``serve.dispatch`` (the call into the compiled program; long only
    when it traces or compiles), ``serve.wait`` (the device's CG) and
    ``serve.gather`` (device-to-host copy, host permutation, padding
    stripped).
  * **streaming updates** — :meth:`SolverService.update_matrix` applies an
    :class:`repro.sparse.replan.EdgeDelta` to a cached matrix: the plan is
    patched in O(delta) when it carries a replan cache, the old
    fingerprint is retired (no stale hits), and an optional
    :class:`repro.core.replan_policy.DriftPolicy` prices every update so
    a drifted partition triggers a full repartition with solver-state
    migration instead of unbounded quality decay.

Token serving (unchanged scaffold):

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --smoke \
      --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import hashlib
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from ..core.replan_policy import DriftDecision, DriftMonitor, DriftPolicy
from ..sparse import CooOperator, cg_solve, make_operator
from ..sparse.cg import CGResult
from ..sparse.graph import structure_graph
from ..sparse.replan import (EdgeDelta, apply_delta_csr, apply_edge_delta,
                             migrate_state)
from ..spans import span


# --------------------------------------------------------------------------
# Solver serving
# --------------------------------------------------------------------------

def matrix_fingerprint(indptr, indices, data) -> str:
    """Cache key for a CSR matrix: ``<n>:<nnz>:<blake2b>`` over the dtype,
    shape and bytes of all three arrays.  Content-hashed — two structurally
    identical matrices with different values never collide."""
    h = hashlib.blake2b(digest_size=16)
    for a in (indptr, indices, data):
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(np.int64(a.size).tobytes())
        h.update(a.tobytes())
    return f"{len(indptr) - 1}:{len(indices)}:{h.hexdigest()}"


@dataclasses.dataclass
class ServeStats:
    """Admission/cache counters, reported by the bench and asserted in
    tests.  ``padding_waste`` is the fraction of solved columns that were
    admission padding (cheap — padded columns converge in 0 iterations —
    but still traced/allocated work worth watching)."""

    operator_hits: int = 0
    operator_misses: int = 0
    operator_evictions: int = 0
    bucket_hits: int = 0            # (matrix, size-class) already warmed
    bucket_misses: int = 0          # first solve of the class: traces
    real_cols: int = 0
    padded_cols: int = 0
    solves: int = 0
    plan_patches: int = 0           # update_matrix served by O(delta) patch
    plan_rebuilds: int = 0          # update_matrix paid a full plan build
    drift_trips: int = 0            # rebuilds forced by the drift monitor

    @property
    def padding_waste(self) -> float:
        total = self.real_cols + self.padded_cols
        return self.padded_cols / total if total else 0.0


@dataclasses.dataclass
class UpdateResponse:
    """One served :meth:`SolverService.update_matrix`: the matrix moved to
    a new fingerprint, either by an O(delta) plan patch or by a full
    rebuild (drift trip / no replan cache)."""

    fingerprint: str                # fingerprint of the mutated matrix
    old_fingerprint: str
    patched: bool                   # True: O(delta) patch; False: rebuild
    repartitioned: bool             # rebuild used a fresh partition
    drift: DriftDecision | None     # None when no drift policy is set
    state: tuple | None             # migrated solver state (if passed in)


@dataclasses.dataclass
class SolveResponse:
    """One served solve: gathered solution plus per-column convergence
    info (padding columns already stripped)."""

    x: np.ndarray                   # (n,) or (n, nb)
    iters: np.ndarray               # () or (nb,) int
    residual: np.ndarray            # () or (nb,)
    fingerprint: str = ""
    bucket: int = 0
    cache_hit: bool = False         # operator came from the cache
    warm: bool = False              # (matrix, bucket) class already traced


class SolverService:
    """Multi-RHS CG serving over a pool of matrices (see module docstring).

    ``backend`` / ``op_kw`` go to :func:`repro.sparse.make_operator`
    verbatim (e.g. ``backend='dist_hier', part=..., k=8, mesh=...,
    pods=2``), so one service class fronts every SpMV backend; the
    solver parameters are fixed per service (one compiled program per
    operand shapes x size class; the matrix is an operand of the program,
    not a constant in it).  ``capacity`` bounds the operator cache
    (least-recently-used eviction drops the operator and its device
    arrays)."""

    def __init__(self, backend: str = "coo",
                 buckets: tuple[int, ...] = (1, 2, 4, 8, 16),
                 capacity: int = 8, tol: float = 1e-6,
                 max_iters: int = 500, precondition: str | None = None,
                 drift: DriftPolicy | None = None, repartition=None,
                 **op_kw):
        if not buckets or list(buckets) != sorted(set(buckets)):
            raise ValueError(f"buckets must be sorted unique size classes; "
                             f"got {buckets!r}")
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.backend = backend
        self.buckets = tuple(int(b) for b in buckets)
        self.capacity = capacity
        self.tol = tol
        self.max_iters = max_iters
        self.precondition = precondition
        self.op_kw = op_kw
        self.stats = ServeStats()
        self._ops: OrderedDict[str, object] = OrderedDict()
        self._warm: set[tuple[str, int]] = set()
        # one jitted batched CG for the operators without a fused .solve
        # (the single-device backends): the operator is an argument (a
        # pytree of its device arrays), so its matrix is never compiled
        # into the program, and one program serves every matrix and
        # request of the same shapes
        self._solve = jax.jit(functools.partial(
            cg_solve, tol=tol, max_iters=max_iters,
            precondition=precondition, batched=True))
        # (fingerprint, bucket) -> static price (trace audit + roofline)
        self._cost: dict[tuple[str, int], dict] = {}
        # streaming updates (update_matrix): host CSR per cached matrix,
        # drift monitor per matrix, per-matrix partition overrides from
        # drift-tripped repartitions
        self.drift = drift
        self.repartition = repartition
        self._csr: dict[str, tuple] = {}
        self._monitors: dict[str, DriftMonitor] = {}
        self._parts: dict[str, np.ndarray] = {}

    def bucket_for(self, nb: int) -> int:
        """Smallest admission class holding ``nb`` columns; oversize
        requests become their own exact-width class (served, but each
        distinct width traces its own program)."""
        for b in self.buckets:
            if nb <= b:
                return b
        return nb

    def operator_for(self, indptr, indices, data,
                     fingerprint: str | None = None):
        """``(fingerprint, operator, hit)`` with LRU admission: a cached
        matrix skips plan construction / format conversion entirely."""
        fp = fingerprint or matrix_fingerprint(indptr, indices, data)
        op = self._ops.get(fp)
        if op is not None:
            self._ops.move_to_end(fp)
            self.stats.operator_hits += 1
            return fp, op, True
        self.stats.operator_misses += 1
        with span("plan.build") as build:
            op = make_operator(indptr, indices, data, self.backend,
                               **self.op_kw)
            if isinstance(op, CooOperator):
                build.set_metadata(groups=op.groups,
                                   pad_share=op.pad_share)
        self._install(fp, op, (np.asarray(indptr), np.asarray(indices),
                               np.asarray(data)))
        return fp, op, False

    def _install(self, fp: str, op, csr: tuple) -> None:
        """Admit (fp, op) into the LRU, keeping the host CSR for
        :meth:`update_matrix`; evicts down to capacity."""
        self._ops[fp] = op
        self._csr[fp] = csr
        while len(self._ops) > self.capacity:
            old_fp, _ = self._ops.popitem(last=False)
            self._retire(old_fp)
            self.stats.operator_evictions += 1

    def _retire(self, fp: str) -> None:
        """Drop every per-matrix cache keyed by ``fp`` — warm size
        classes, static prices, host CSR, drift state."""
        self._warm = {w for w in self._warm if w[0] != fp}
        self._cost = {key: v for key, v in self._cost.items()
                      if key[0] != fp}
        self._csr.pop(fp, None)
        self._monitors.pop(fp, None)
        self._parts.pop(fp, None)

    def update_matrix(self, fingerprint: str, delta: EdgeDelta,
                      state=None) -> UpdateResponse:
        """Apply an :class:`EdgeDelta` to a cached matrix in place of a
        full re-admission: the operator moves to the mutated matrix's
        fingerprint via an O(delta) plan patch
        (:func:`repro.sparse.replan.apply_edge_delta`) when its plan
        carries a replan cache, and via a full rebuild otherwise.

        With a :class:`DriftPolicy` (``drift=`` at construction) every
        update is priced against the last full plan's baseline; a
        threshold trip forces a rebuild on a fresh partition from the
        ``repartition`` callable (``repartition(g) -> (n,) part``) and
        migrates ``state`` (a sequence of operator-space solver vectors)
        onto the new layout instead of restarting.  Trips without a
        ``repartition`` callable are recorded (``stats.drift_trips``,
        ``response.drift``) but still served by patching — the frozen
        partition is all there is.  A single-device operator rebuilt on
        the mutated matrix takes ``state`` to its own row order.

        The old fingerprint is fully retired: a subsequent solve against
        the *unmutated* matrix is an operator miss, never a stale hit.
        """
        csr = self._csr.get(fingerprint)
        if csr is None:
            raise KeyError(f"unknown or evicted fingerprint "
                           f"{fingerprint!r}")
        op = self._ops[fingerprint]
        indptr, indices, data = csr
        ip2, ix2, d2 = apply_delta_csr(indptr, indices, data, delta)
        new_fp = matrix_fingerprint(ip2, ix2, d2)
        plan = getattr(op, "plan", None)
        cache = getattr(plan, "_replan", None)

        decision = None
        monitor = self._monitors.pop(fingerprint, None)
        if self.drift is not None:
            if cache is not None:
                part, anc = cache.part, getattr(plan, "anc", None)
            else:
                part = self._parts.get(fingerprint,
                                       self.op_kw.get("part"))
                anc = None
            if part is not None:
                if monitor is None:
                    monitor = DriftMonitor(self.drift)
                    monitor.reset(structure_graph(indptr, indices, data),
                                  part, anc)
                g2 = structure_graph(ip2, ix2, d2)
                decision = monitor.observe(g2, part, anc)
                if decision.repartition:
                    self.stats.drift_trips += 1

        repartitioned = (decision is not None and decision.repartition
                         and self.repartition is not None)
        out_state = tuple(state) if state is not None else None
        if cache is not None and not repartitioned:
            new_plan = apply_edge_delta(plan, delta)
            new_op = dataclasses.replace(op, plan=new_plan)
            self.stats.plan_patches += 1
            patched = True
        else:
            kw = dict(self.op_kw)
            if fingerprint in self._parts:
                kw["part"] = self._parts[fingerprint]
            if repartitioned:
                kw["part"] = np.asarray(
                    self.repartition(structure_graph(ip2, ix2, d2)))
                self._parts[new_fp] = kw["part"]
            new_op = make_operator(ip2, ix2, d2, self.backend, **kw)
            self.stats.plan_rebuilds += 1
            patched = False
            new_plan = getattr(new_op, "plan", None)
            if out_state is not None and plan is not None \
                    and new_plan is not None:
                moved = migrate_state(plan, new_plan, *out_state)
                out_state = moved if isinstance(moved, tuple) else (moved,)
            elif out_state is not None and plan is None:
                # single-device operators order their rows by the matrix
                # (``coo`` by row length): back to its order, then on
                out_state = tuple(new_op.scatter(op.gather(s))
                                  for s in out_state)
            if monitor is not None:
                new_cache = getattr(new_plan, "_replan", None)
                monitor.reset(
                    structure_graph(ip2, ix2, d2),
                    new_cache.part if new_cache is not None
                    else kw.get("part"),
                    getattr(new_plan, "anc", None))

        self._ops.pop(fingerprint, None)
        self._retire(fingerprint)
        self._install(new_fp, new_op, (ip2, ix2, d2))
        if monitor is not None:
            self._monitors[new_fp] = monitor
        return UpdateResponse(fingerprint=new_fp,
                              old_fingerprint=fingerprint,
                              patched=patched, repartitioned=repartitioned,
                              drift=decision, state=out_state)

    def static_cost(self, indptr, indices, data, nb: int = 1,
                    fingerprint: str | None = None) -> dict:
        """Device-free price of serving a request of width ``nb``: admit
        it into its size class, resolve the operator through the cache,
        trace the solver on an abstract mesh (``analysis.trace``) and run
        the static roofline over the counted per-iteration cost.  No
        compilation, no devices — usable at admission time to pick a
        bucket or reject oversize work.  Cached per (matrix, bucket),
        evicted with the operator.

        When the service fronts a partitioned backend (``part=`` in
        ``op_kw``), the result also carries ``modeled`` — the
        partition-level cost-model summary (``roofline.modeled_makespan``:
        bottleneck makespan, critical PU, per-PU compute/comm split)
        next to the program-level trace price."""
        from ..analysis.trace import audit_operator
        from .roofline import modeled_makespan, static_roofline

        bucket = self.bucket_for(int(nb))
        fp, op, _ = self.operator_for(indptr, indices, data, fingerprint)
        cached = self._cost.get((fp, bucket))
        if cached is not None:
            return cached
        rep = audit_operator(op, nb=bucket if bucket > 1 else None,
                             tol=self.tol, max_iters=self.max_iters,
                             precondition=self.precondition,
                             subject=f"serve {self.backend} nb={bucket}")
        cost = rep.info.get("cost_cg") or rep.info.get("cost_matvec")
        out = {"fingerprint": fp, "bucket": bucket, "ok": rep.ok,
               "diagnostics": [str(d) for d in rep.diagnostics],
               "cost": cost, "roofline": static_roofline(cost)}
        part = self.op_kw.get("part")
        if part is not None:
            from ..sparse.graph import from_edges
            n = len(indptr) - 1
            src = np.repeat(np.arange(n), np.diff(np.asarray(indptr)))
            g = from_edges(n, src, np.asarray(indices), symmetrize=True)
            g.weights[:] = 1.0      # structure only: the matrix values
            # (e.g. negative Laplacian off-diagonals) are not link costs
            out["modeled"] = modeled_makespan(g, part)
        self._cost[(fp, bucket)] = out
        return out

    def solve(self, indptr, indices, data, b,
              fingerprint: str | None = None) -> SolveResponse:
        """Serve one request: admit ``b`` ((n,) or (n, nb)) into its size
        class, resolve the operator through the cache, run the batched
        masked CG, strip the padding columns.  Each phase is a host span
        under ``serve.solve`` (module docstring)."""
        b = np.asarray(b)
        single = b.ndim == 1
        bcols = b[:, None] if single else b
        nb = bcols.shape[1]
        bucket = self.bucket_for(nb)
        with span("serve.solve", request=self.stats.solves + 1, width=nb,
                  bucket=bucket):
            with span("serve.admit"):
                fp, op, hit = self.operator_for(indptr, indices, data,
                                                fingerprint)
            warm = (fp, bucket) in self._warm
            if warm:
                self.stats.bucket_hits += 1
            else:
                self.stats.bucket_misses += 1
                self._warm.add((fp, bucket))
            self.stats.real_cols += nb
            self.stats.padded_cols += bucket - nb
            self.stats.solves += 1
            with span("serve.pad"):
                if bucket > nb:
                    pad = np.zeros((bcols.shape[0], bucket - nb),
                                   bcols.dtype)
                    bcols = np.concatenate([bcols, pad], axis=1)
            res = self._run(op, bcols)
            with span("serve.gather"):
                x = op.gather(res.x)[:, :nb]
                iters = np.asarray(res.iters)[:nb]
                residual = np.asarray(res.residual)[:nb]
        if single:
            x, iters, residual = x[:, 0], iters[0], residual[0]
        return SolveResponse(x=x, iters=iters, residual=residual,
                             fingerprint=fp, bucket=bucket, cache_hit=hit,
                             warm=warm)

    def _run(self, op, bcols) -> CGResult:
        """Scatter ``bcols`` into operator space, run CG and wait for it:
        the fused distributed program where the operator has one (its own
        per-(tol, max_iters, precondition) trace cache), else the
        service's jitted batched CG with the operator as an argument."""
        with span("serve.scatter"):
            b = op.scatter(bcols)
        with span("serve.dispatch"):
            if hasattr(op, "fused_solver"):
                x, residual, iters = op.fused_solver(
                    self.tol, self.max_iters, self.precondition)(b)
                res = CGResult(x=x, iters=iters, residual=residual)
            else:
                res = self._solve(op, b)
        with span("serve.wait"):
            return jax.block_until_ready(res)


def _solver_traffic(args) -> None:
    """Synthetic traffic mix against a SolverService: a small pool of
    Laplacian systems, Zipf-ish repeat pattern, random batch widths.
    Prints solves/sec, latency percentiles and the cache counters."""
    from ..sparse.generators import grid
    from ..sparse.graph import laplacian_csr

    rng = np.random.default_rng(0)
    pool = []
    for i, side in enumerate((12, 16, 20, 24)[:args.pool]):
        g = grid((side, side))
        pool.append(laplacian_csr(g, shift=0.05 * (i + 1)))
    svc = SolverService(backend="coo", capacity=args.capacity,
                        tol=1e-6, max_iters=500)
    lat = []
    t_all = time.perf_counter()
    for r in range(args.requests):
        indptr, indices, data = pool[int(rng.zipf(1.5)) % len(pool)]
        nb = int(rng.integers(1, 9))
        b = rng.normal(size=(len(indptr) - 1, nb)).astype(np.float32)
        t0 = time.perf_counter()
        resp = svc.solve(indptr, indices, data, b)
        np.asarray(resp.x)
        lat.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t_all
    lat_ms = np.sort(np.array(lat)) * 1e3
    s = svc.stats
    print(f"requests={args.requests} solves/sec={args.requests / wall:.1f}")
    print(f"latency ms: p50={np.percentile(lat_ms, 50):.2f} "
          f"p95={np.percentile(lat_ms, 95):.2f} "
          f"max={lat_ms[-1]:.2f}")
    print(f"operator cache: hits={s.operator_hits} "
          f"misses={s.operator_misses} evictions={s.operator_evictions}")
    print(f"buckets: hits={s.bucket_hits} misses={s.bucket_misses} "
          f"padding_waste={s.padding_waste:.1%}")


# --------------------------------------------------------------------------
# Token serving (scaffold)
# --------------------------------------------------------------------------

def _token_serving(args) -> None:
    from ..configs.registry import get_config
    from ..models import encdec, transformer
    from ..models.steps import make_decode_step

    cfg = get_config(args.arch, smoke=args.smoke)
    mod = encdec if cfg.family == "audio" else transformer
    params, _ = mod.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    B = args.batch
    cache_len = args.prompt_len + max(args.gen, 1)
    prompts = rng.integers(0, cfg.vocab, size=(B, args.prompt_len),
                           dtype=np.int32)

    batch = {"tokens": jnp.asarray(prompts)}
    if cfg.family == "vlm":
        batch["img_embeds"] = jnp.asarray(rng.normal(scale=0.02, size=(
            B, cfg.n_img_tokens, cfg.d_model)).astype(np.float32))
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(rng.normal(scale=0.02, size=(
            B, cfg.n_frames, cfg.d_model)).astype(np.float32))

    if cfg.family == "audio":
        prefill = jax.jit(lambda p, b: encdec.prefill_forward(
            p, cfg, b["frames"], b["tokens"], cache_len=cache_len))
    elif cfg.family == "vlm":
        prefill = jax.jit(lambda p, b: transformer.prefill_forward(
            p, cfg, b["tokens"], cache_len=cache_len,
            img_embeds=b["img_embeds"]))
    else:
        prefill = jax.jit(lambda p, b: transformer.prefill_forward(
            p, cfg, b["tokens"], cache_len=cache_len))
    decode = jax.jit(make_decode_step(cfg))

    t0 = time.perf_counter()
    logits, cache = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    key = jax.random.PRNGKey(1)
    out = [prompts]
    t0 = time.perf_counter()
    for t in range(args.gen):
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(
            sub, logits[:, -1].astype(jnp.float32) / args.temperature,
            axis=-1).astype(jnp.int32)[:, None]
        tok = jnp.minimum(tok, cfg.vocab - 1)
        out.append(np.asarray(tok))
        logits, cache = decode(params, cache, tok,
                               jnp.int32(args.prompt_len + t))
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0
    gen = np.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={B} prompt={args.prompt_len} "
          f"gen={args.gen}")
    if args.gen:        # --gen 0 is prefill-only: no per-token rate exists
        print(f"prefill {t_prefill*1e3:.1f} ms; decode "
              f"{t_decode/args.gen*1e3:.2f} ms/token "
              f"({B*args.gen/t_decode:.1f} tok/s)")
    else:
        print(f"prefill {t_prefill*1e3:.1f} ms; decode skipped (--gen 0)")
    print("sample token ids:",
          gen[0, :args.prompt_len + min(args.gen, 8)].tolist())


def main():
    from ..configs.registry import ARCHS

    ap = argparse.ArgumentParser()
    ap.add_argument("--solver", action="store_true",
                    help="serve CG solves (synthetic traffic) instead of "
                         "tokens")
    ap.add_argument("--requests", type=int, default=32,
                    help="solver mode: synthetic requests to serve")
    ap.add_argument("--pool", type=int, default=3,
                    help="solver mode: distinct matrices in the pool")
    ap.add_argument("--capacity", type=int, default=8,
                    help="solver mode: operator-cache capacity")
    ap.add_argument("--arch", choices=ARCHS, default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()
    from .compile_cache import use_compile_cache
    use_compile_cache()
    if args.solver:
        _solver_traffic(args)
    else:
        _token_serving(args)


if __name__ == "__main__":
    main()
