"""Conjugate-gradient solver (Sec. VI-a: 'CG solver from LAMA ... applied to
systems derived from the graph's Laplacian') — JAX, lax.while_loop.

The operator is passed either as a bare matvec closure or as an
``operator.Operator`` (anything with ``matvec`` / ``dot``), so the same
solver drives the single-device row-group SpMV, the Pallas block-ELL
kernel, and the distributed shard_map SpMV — one solver, one benchmark
harness, every backend.

Multi-RHS batching (``batched=True``): ``b`` carries a trailing RHS-batch
axis (``(n, nb)`` single-device, ``(k, B, nb)`` distributed operator
space) and the loop runs all columns in one program with *per-column
convergence masks* — a finished column's alpha/beta are masked to zero,
so its x/r/p freeze while stragglers converge, and ``CGResult`` carries
per-column ``iters``/``residual``.  The total work is
``sum(iters)`` column-iterations, not ``nb * max(iters)``.

All epsilon guards are dtype-aware (``jnp.finfo(b.dtype)``): near-zero
alpha/beta denominators produce a zero step instead of an overflow (the
float32 failure mode of the old hard-coded ``1e-30``), and the ``tol2``
floor never demands a sub-denormal residual.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class CGResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray          # scalar, or (nb,) per column when batched
    residual: jnp.ndarray       # scalar, or (nb,) per column when batched


def jacobi_preconditioner(diag: jnp.ndarray) -> Callable:
    """M^-1 r = r / diag(A), with zero diagonal entries (padded ghost rows
    in the distributed layout) passed through as zero — ghost residuals are
    exactly zero, so this keeps them out of the Krylov space."""
    one = jnp.ones((), diag.dtype)
    safe = jnp.where(diag != 0, diag, one)
    inv = jnp.where(diag != 0, one / safe, 0)

    def apply(r):
        return r * inv

    return apply


def _safe_div(num, den):
    """num / den with a dtype-aware zero guard: a denominator at or below
    the smallest normal of its dtype yields a zero step instead of an
    overflow.  The old ``num / (den + 1e-30)`` was float64-centric — at
    float32 a denominator that underflows still divides by the 1e-30
    guard itself, so alpha could be off by orders of magnitude (or
    overflow to inf for large numerators)."""
    tiny = jnp.finfo(den.dtype).tiny
    ok = jnp.abs(den) > tiny
    return jnp.where(ok, num / jnp.where(ok, den, 1), 0)


def _tol2_floor(tol, b2):
    """Squared absolute tolerance ``tol^2 ||b||^2`` with dtype-aware
    floors: ``b2`` is floored to the smallest normal (a zero RHS converges
    immediately) and the product is floored to it too, so the stop test
    never demands a residual the dtype cannot even represent."""
    tiny = jnp.finfo(b2.dtype).tiny
    return jnp.maximum(tol * tol * jnp.maximum(b2, tiny), tiny)


def _resolve_operator(matvec, dot, precondition):
    """Unpack an Operator (matvec/dot/preconditioner resolution) — shared
    by the single-RHS and batched paths.  Returns
    ``(matvec, dot, precondition, batch_native)``."""
    batch_native = False
    if hasattr(matvec, "matvec"):
        op = matvec
        matvec = op.matvec
        dot = dot or getattr(op, "dot", None)
        batch_native = bool(getattr(op, "batch_native", False))
        if precondition == "jacobi":
            precondition = jacobi_preconditioner(op.diag())
        elif precondition == "block_jacobi":
            bj = getattr(op, "block_jacobi_preconditioner", None)
            if bj is None:
                raise ValueError(
                    "precondition='block_jacobi' needs an Operator with "
                    "per-PU blocks (DistributedOperator); "
                    f"{type(op).__name__} has none")
            precondition = bj()
    else:
        batch_native = bool(getattr(matvec, "batch_native", False))
    if isinstance(precondition, str):
        raise ValueError(f"precondition={precondition!r} needs an Operator "
                         "(jacobi: any backend with diag(); block_jacobi: "
                         "distributed backends); pass a callable M^-1 "
                         "instead")
    return matvec, dot, precondition, batch_native


def _cg_solve_batched(matvec, b, x0, tol, max_iters, dot, M,
                      batch_native) -> CGResult:
    """Multi-RHS CG: all columns advance in one loop; converged columns
    freeze (alpha/beta masked to zero) while stragglers iterate.

    ``matvec``/``M`` are single-column callables unless ``batch_native``
    (operators whose matvec carries the trailing batch axis through
    natively, e.g. the distributed halo schedules — vmap cannot cross
    their ppermute rounds on every supported JAX); ``dot`` is the
    single-column inner product and is vmapped over columns, so the
    distributed psum-reduced dot batches without modification.
    """
    nb = b.shape[-1]
    mv = matvec if batch_native else jax.vmap(matvec, in_axes=-1,
                                              out_axes=-1)
    dot = dot or (lambda u, v: jnp.vdot(u, v))
    dotb = jax.vmap(dot, in_axes=-1, out_axes=0)       # (..., nb) -> (nb,)
    Mb = None
    if M is not None:
        Mb = M if batch_native and getattr(M, "batch_native", False) \
            else jax.vmap(M, in_axes=-1, out_axes=-1)

    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - mv(x)
    tol2 = _tol2_floor(tol, dotb(b, b))                # (nb,)
    z = Mb(r) if Mb is not None else r
    p = z
    rz = dotb(r, z)
    rr = dotb(r, r)
    it = jnp.zeros((nb,), jnp.int32)

    def active(rr, it):
        return (rr > tol2) & (it < max_iters)

    def cond(state):
        _, _, _, _, rr, it = state
        return jnp.any(active(rr, it))

    def body(state):
        x, r, p, rz, rr, it = state
        act = active(rr, it)                           # (nb,) column masks
        ap = mv(p)
        # masked alpha: converged columns take a zero step, so their
        # x/r stay frozen while active columns advance (trailing-axis
        # broadcasting aligns the (nb,) scalars with (..., nb) vectors)
        alpha = jnp.where(act, _safe_div(rz, dotb(p, ap)), 0)
        x = x + alpha * p
        r = r - alpha * ap
        z = Mb(r) if Mb is not None else r
        rz_new = dotb(r, z)
        beta = jnp.where(act, _safe_div(rz_new, rz), 0)
        p = jnp.where(act, z + beta * p, p)
        rz = jnp.where(act, rz_new, rz)
        rr = jnp.where(act, dotb(r, r), rr)
        return x, r, p, rz, rr, it + act.astype(jnp.int32)

    x, r, p, rz, rr, it = jax.lax.while_loop(
        cond, body, (x, r, p, rz, rr, it))
    return CGResult(x=x, iters=it, residual=jnp.sqrt(rr))


def cg_solve(matvec: Callable[[jnp.ndarray], jnp.ndarray], b: jnp.ndarray,
             x0: jnp.ndarray | None = None, tol: float = 1e-6,
             max_iters: int = 500,
             dot: Callable | None = None,
             precondition: str | Callable | None = None,
             batched: bool = False) -> CGResult:
    """CG / preconditioned CG.  ``matvec`` is either a callable or an
    Operator (``matvec``/``dot`` attributes); ``dot`` may be overridden
    for distributed use (e.g. a psum-reduced local dot inside shard_map).

    ``precondition`` is ``None`` (plain CG), a callable ``z = M^-1(r)``,
    or a string — ``'jacobi'`` resolves through the Operator's ``diag()``
    (every backend carries its diagonal on-device) and ``'block_jacobi'``
    through the Operator's ``block_jacobi_preconditioner()`` (per-PU
    diagonal blocks; distributed backends only).  Convergence is always
    tested on the *unpreconditioned* residual ||r||^2 <= tol^2 ||b||^2, so
    preconditioning changes the iteration count, never the stop quality.

    ``batched=True`` treats the *last* axis of ``b`` as an RHS batch and
    runs the multi-RHS loop with per-column convergence masks (see module
    docstring); ``matvec``/``dot``/``precondition`` stay single-column —
    they are vmapped over the batch axis unless the operator declares
    ``batch_native`` (the distributed backends, whose schedules carry the
    batch axis through natively).
    """
    matvec, dot, precondition, batch_native = _resolve_operator(
        matvec, dot, precondition)
    if batched:
        return _cg_solve_batched(matvec, b, x0, tol, max_iters, dot,
                                 precondition, batch_native)
    dot = dot or (lambda u, v: jnp.vdot(u, v))
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - matvec(x)
    tol2 = _tol2_floor(tol, dot(b, b))

    if precondition is not None:
        M = precondition
        z = M(r)
        p = z
        rz = dot(r, z)
        rr = dot(r, r)

        def cond(state):
            return (state[4] > tol2) & (state[5] < max_iters)

        def body(state):
            x, r, p, rz, rr, it = state
            ap = matvec(p)
            alpha = _safe_div(rz, dot(p, ap))
            x = x + alpha * p
            r = r - alpha * ap
            z = M(r)
            rz_new = dot(r, z)
            p = z + _safe_div(rz_new, rz) * p
            return x, r, p, rz_new, dot(r, r), it + 1

        x, r, p, rz, rr, it = jax.lax.while_loop(
            cond, body, (x, r, p, rz, rr, jnp.zeros((), jnp.int32)))
        return CGResult(x=x, iters=it, residual=jnp.sqrt(rr))

    p = r
    rs = dot(r, r)

    def cond(state):
        _, _, _, rs, it = state
        return (rs > tol2) & (it < max_iters)

    def body(state):
        x, r, p, rs, it = state
        ap = matvec(p)
        alpha = _safe_div(rs, dot(p, ap))
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = dot(r, r)
        p = r + _safe_div(rs_new, rs) * p
        return x, r, p, rs_new, it + 1

    x, r, p, rs, it = jax.lax.while_loop(
        cond, body, (x, r, p, rs, jnp.zeros((), jnp.int32)))
    return CGResult(x=x, iters=it, residual=jnp.sqrt(rs))
