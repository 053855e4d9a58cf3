"""Preconditioned iterative solvers (JAX): GMRES(m), BiCGSTAB, CG.

These are the *consumers* of the ILU(k) preconditioner — the paper's point
is that preconditioning time dominates the solver as processors scale, so a
real system must include the solver to measure anything meaningful
(paper §I, §V-B).

Execution model: **device-resident**. Each solver compiles to a single
jitted computation — the Krylov iteration, the preconditioner application
(fused wavefront sweep, see ``repro.core.triangular.PrecondApply``),
the ELL SpMV (:func:`make_ell_matvec`), and for GMRES the restart logic
and the Givens-rotation least-squares solve all live inside one
``lax.while_loop``. There is exactly one dispatch per solve: no host
round-trips per iteration or per restart, no host ``lstsq``. Residual
histories are recorded into fixed-size device buffers carried through the
loop and trimmed on the host afterwards.

Multi-RHS: ``gmres_batched`` (or a 2-D ``b`` passed to ``solve_with_ilu``)
``vmap``s the same single-RHS engine over a stack of right-hand sides —
one dispatch for the whole batch, with per-lane freezing so already
converged systems stop updating (their iteration counts and histories stay
exact). The batched path shares the cached triangular plan; use it when
amortizing one factorization over many right-hand sides (the serving
scenario), not when RHS arrive one at a time.

All solvers take ``matvec`` (A·x) and ``precond`` (M^{-1}·x, identity if
None) as functions, run in float32, and report iteration counts + residual
history so tests/benches can reproduce the paper's "larger k => fewer
iterations" trade-off (Fig 5 discussion).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from .bitmath import barred, bitdot, bitnorm, hoisted_jit, lane_gather, masked_lane_sum
from .planner import COL_SENTINEL

def parse_batch_buckets(spec: str, source: str = "REPRO_BATCH_BUCKETS") -> tuple:
    """Parse and validate a comma-separated bucket spec.

    Buckets bound the set of compiled batch shapes, so a malformed spec
    must fail loudly at parse time — a silently-accepted ``0`` or ``-4``
    would only surface later as a bad pad target deep in a solve. Rules:
    every token an integer, every value positive, no duplicates, strictly
    ascending (the canonical form callers and ``bucket_batch`` assume).
    """
    toks = [t.strip() for t in str(spec).split(",") if t.strip()]
    if not toks:
        raise ValueError(f"{source}: empty bucket spec {spec!r} — expected "
                         "comma-separated positive integers, e.g. '1,2,4,8'")
    vals = []
    for t in toks:
        try:
            v = int(t)
        except ValueError:
            raise ValueError(
                f"{source}: bucket token {t!r} is not an integer "
                f"(full spec: {spec!r})") from None
        if v <= 0:
            raise ValueError(
                f"{source}: bucket sizes must be positive, got {v} "
                f"(full spec: {spec!r})")
        vals.append(v)
    if len(set(vals)) != len(vals):
        dupes = sorted({v for v in vals if vals.count(v) > 1})
        raise ValueError(
            f"{source}: duplicate bucket size(s) {dupes} (full spec: {spec!r})")
    if vals != sorted(vals):
        raise ValueError(
            f"{source}: bucket sizes must be ascending — got {vals}, "
            f"expected {sorted(vals)} (full spec: {spec!r})")
    return tuple(vals)


def batch_buckets():
    """RHS batch-size buckets for the serving path — ``REPRO_BATCH_BUCKETS``
    (comma-separated, positive, ascending) or the powers-of-two default.
    Bucketing keeps the number of compiled solver/precond shapes bounded: a
    ragged batch pads up to the nearest bucket (vmap lanes are independent,
    so zero padding never changes a real lane's bits) instead of minting a
    new executable per batch size. A malformed spec raises with the
    offending token — see :func:`parse_batch_buckets`."""
    import os

    return parse_batch_buckets(os.environ.get("REPRO_BATCH_BUCKETS", "1,2,4,8,16,32,64"))


def bucket_batch(nb: int, buckets=None) -> int:
    """Smallest bucket >= nb (nb itself when it exceeds every bucket)."""
    buckets = batch_buckets() if buckets is None else tuple(sorted(buckets))
    for w in buckets:
        if w >= nb:
            return w
    return nb


def _pad_rhs_batch(bs, tgt):
    if bs.shape[0] == tgt:
        return bs
    pad = jnp.zeros((tgt - bs.shape[0], bs.shape[1]), bs.dtype)
    return jnp.concatenate([bs, pad])


def _pad_tols(tol, tgt):
    """Pad a per-lane tol array to the bucket size. Padding lanes get 1.0 —
    their RHS is zero, so ``||b|| = 0`` stops them before any iteration
    regardless of tolerance; 1.0 just keeps the intent obvious."""
    tol_arr = np.asarray(tol, np.float32)
    if tol_arr.ndim == 0 or tol_arr.shape[0] == tgt:
        return tol
    return np.concatenate([tol_arr, np.ones(tgt - tol_arr.shape[0], np.float32)])


def _cached_engine(matvec, M, key, build):
    """Compiled-engine memo stored *on the matvec closure itself*: repeated
    solves with the same (matvec, precond) objects reuse one executable with
    zero retracing, and the engine (plus its captured device arrays) is
    garbage-collected with the closure — no module-level registry, so a
    stream of different matrices cannot grow device memory without bound."""
    try:
        store = matvec.__dict__.setdefault("_repro_engines", {})
    except AttributeError:  # exotic callable without __dict__: no caching
        return build()
    fn = store.get((M, key))
    if fn is None:
        fn = store[(M, key)] = build()
    return fn


# Termination verdict codes carried through the solver while-loops as an
# int32 lane state (0 = still running). Classification rides *outside* the
# iterate arithmetic — adding it changes no bits of x — and replaces the
# bare `(res > tolb) & (it < maxiter)` predicates so the serve layer can
# tell "hit the iteration budget" from "went NaN" from "flatlined".
VERDICT_RUNNING = 0
VERDICT_CONVERGED = 1
VERDICT_MAXITER = 2
VERDICT_STAGNATED = 3
VERDICT_BREAKDOWN = 4
VERDICT_DIVERGED = 5
VERDICTS = ("running", "converged", "maxiter", "stagnated", "breakdown", "diverged")

# stagnation = relative residual improvement below ε for `window`
# consecutive steps; divergence = residual blowing past `factor`·‖b‖.
# GMRES steps are whole restarts (few, substantial), so its window is short;
# CG/BiCGSTAB steps are single iterations with noisy residuals, so theirs is
# wide and the divergence bar higher (BiCGSTAB residuals legitimately spike).
_STAG_EPS = 1e-3
_GMRES_STALL_WINDOW = 5
_GMRES_DIV_FACTOR = 1e5
_KRYLOV_STALL_WINDOW = 25
_KRYLOV_DIV_FACTOR = 1e8


@dataclasses.dataclass
class SolveReport:
    """Per-lane termination report (the serve layer's retry policy keys on
    ``verdict``; ``shift``/``degraded`` are filled in by the solve entry
    points when the factorization came out of the breakdown ladder)."""

    verdict: str
    iterations: int
    residual: float
    converged: bool
    degraded: bool = False  # identity-precond fallback was active
    shift: float = 0.0      # diagonal shift α of the preconditioner's matrix


@dataclasses.dataclass
class SolveResult:
    x: np.ndarray
    iterations: int
    residual: float
    converged: bool
    history: np.ndarray  # residual norm per iteration (GMRES: per restart)
    verdict: str = ""
    report: SolveReport = None

    def __post_init__(self):
        if self.report is None:
            self.report = SolveReport(self.verdict, self.iterations,
                                      self.residual, self.converged)


def make_ell_matvec(cols: jnp.ndarray, vals: jnp.ndarray, n: int) -> Callable:
    """Row-major ELL SpMV, reduced through ``masked_lane_sum`` in lane
    order — bitwise equal to the sequential CSR row sum."""
    def matvec(x):
        xg = jnp.concatenate([x, jnp.zeros((1,), x.dtype)])
        gathered = lane_gather(xg, jnp.minimum(cols, n))
        return masked_lane_sum(cols, vals, gathered, COL_SENTINEL)[:n]
    return matvec


def _csr_to_ell_host(a, n_rows=None):
    """CSRMatrix -> host (cols, vals) sentinel-padded ELL arrays, with
    ``n_rows >= a.n`` all-sentinel padding rows (the one ELL scatter every
    matvec variant shares)."""
    n_rows = a.n if n_rows is None else n_rows
    lens = np.diff(a.indptr)
    W = max(int(lens.max(initial=0)), 1)
    cols = np.full((n_rows, W), COL_SENTINEL, np.int32)
    vals = np.zeros((n_rows, W), np.float32)
    row_of = np.repeat(np.arange(a.n), lens)
    pos = np.arange(a.nnz, dtype=np.int64) - a.indptr[row_of]
    cols[row_of, pos] = a.indices
    vals[row_of, pos] = a.data
    return cols, vals


def csr_to_ell_arrays(a):
    """CSRMatrix -> (cols, vals) sentinel-padded ELL arrays (vectorized)."""
    cols, vals = _csr_to_ell_host(a)
    return jnp.asarray(cols), jnp.asarray(vals)


def make_sharded_ell_matvec(a, mesh, axis: str = "band") -> Callable:
    """Row-block sharded ELL SpMV over a 1-D mesh (DESIGN.md §5).

    The ELL storage of A is split into D contiguous row blocks, each placed
    on its device; ``x`` is replicated (it is O(n) — the factors and the
    matrix are the memory hogs). Each device reduces its own rows through
    ``masked_lane_sum`` (the same lanes in the same order as
    :func:`make_ell_matvec`, so every output entry is bitwise identical to
    the single-device SpMV) and one ``all_gather`` of the (nb,) results —
    a copy — assembles the replicated output.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map

    d = int(mesh.devices.size)
    n = a.n
    nb = -(-n // d)
    cols, vals = _csr_to_ell_host(a, n_rows=d * nb)
    W = cols.shape[1]
    sh = NamedSharding(mesh, P(axis, None, None))
    cols_d = jax.device_put(cols.reshape(d, nb, W), sh)
    vals_d = jax.device_put(vals.reshape(d, nb, W), sh)

    def mv(c, v, x):
        xg = jnp.concatenate([x, jnp.zeros((1,), x.dtype)])
        gathered = lane_gather(xg, jnp.minimum(c[0], n))
        y = masked_lane_sum(c[0], v[0], gathered, COL_SENTINEL)  # (nb,)
        return jax.lax.all_gather(y, axis).reshape(-1)[:n]

    sm = shard_map(
        mv, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None, None), P(None)),
        out_specs=P(None), check_vma=False,
    )

    def matvec(x):
        return sm(cols_d, vals_d, x.astype(jnp.float32))

    return matvec


def _identity(x):
    return x


def _annotate_reports(res, fact):
    """Copy the factorization's ladder outcome (shift α, degraded flag) onto
    each lane's SolveReport — the serve layer reads these off the response
    instead of re-deriving them from the cache entry."""
    health = getattr(fact, "health", None)
    if health is not None and (health.shift != 0.0 or health.degraded):
        for r in res if isinstance(res, list) else (res,):
            r.report.shift = health.shift
            r.report.degraded = health.degraded
    return res


def _unpermute_results(res, ordering):
    """Map solve output(s) back to original row order — ``x`` is the only
    row-indexed field of a :class:`SolveResult` (pure gather, bitwise-
    neutral). Handles a single result or a multi-RHS result list."""
    for r in res if isinstance(res, list) else (res,):
        r.x = ordering.unpermute_vector(r.x)
    return res


def _trim_history(hist: np.ndarray, it: int, bnorm: float) -> np.ndarray:
    return np.asarray(hist)[:it] / max(bnorm, 1e-30)


# --------------------------------------------------------------------------
# CG (SPD systems — e.g. the Poisson benchmark)
# --------------------------------------------------------------------------
def _init_verdict(bnorm, tolb):
    """Lane verdict before the first iteration: a non-finite ‖b‖ is a
    breakdown on arrival (the quarantine trigger for poisoned requests); a
    ‖b‖ already within tolerance — notably the zero-RHS padding lanes of a
    bucketed batch — is converged at 0 iterations, exactly as the old
    ``res > tolb`` predicates behaved."""
    return jnp.where(
        ~jnp.isfinite(bnorm), jnp.int32(VERDICT_BREAKDOWN),
        jnp.where(bnorm <= tolb, jnp.int32(VERDICT_CONVERGED),
                  jnp.int32(VERDICT_RUNNING)))


def _classify(it, rnorm, stall, bnorm, tolb, window, div_factor, maxiter):
    """Post-step verdict. Later writes win, so the priority (low→high) is
    maxiter < stagnated < diverged < converged < breakdown: a lane that is
    simultaneously at its budget and within tolerance is converged, and a
    non-finite residual is a breakdown no matter what else holds."""
    v = jnp.where(it >= maxiter, jnp.int32(VERDICT_MAXITER),
                  jnp.int32(VERDICT_RUNNING))
    v = jnp.where(stall >= window, jnp.int32(VERDICT_STAGNATED), v)
    v = jnp.where(rnorm > div_factor * jnp.maximum(bnorm, 1e-30),
                  jnp.int32(VERDICT_DIVERGED), v)
    v = jnp.where(rnorm <= tolb, jnp.int32(VERDICT_CONVERGED), v)
    v = jnp.where(~jnp.isfinite(rnorm), jnp.int32(VERDICT_BREAKDOWN), v)
    return v


def _cg_core(matvec, M, b, tol, maxiter):
    bnorm = jnp.linalg.norm(b)
    tolb = tol * bnorm

    def body(carry):
        x, r, z, p, rz, it, _, hist, _v, stall, best = carry
        ap = matvec(p)
        alpha = rz / jnp.vdot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        z = M(r)
        rz_new = jnp.vdot(r, z)
        p = z + (rz_new / rz) * p
        rnorm = jnp.linalg.norm(r)
        hist = hist.at[it].set(rnorm)
        stall = jnp.where(rnorm < (1.0 - _STAG_EPS) * best, jnp.int32(0), stall + 1)
        best = jnp.minimum(best, rnorm)
        verdict = _classify(it + 1, rnorm, stall, bnorm, tolb,
                            _KRYLOV_STALL_WINDOW, _KRYLOV_DIV_FACTOR, maxiter)
        return x, r, z, p, rz_new, it + 1, rnorm, hist, verdict, stall, best

    def cond(carry):
        return carry[8] == VERDICT_RUNNING

    x0 = jnp.zeros_like(b)
    r0 = b
    z0 = M(r0)
    carry = (x0, r0, z0, z0, jnp.vdot(r0, z0), jnp.int32(0),
             jnp.linalg.norm(r0), jnp.zeros(maxiter, jnp.float32),
             _init_verdict(bnorm, tolb), jnp.int32(0), bnorm)
    x, r, *_, it, rnorm, hist, verdict, _s, _b = jax.lax.while_loop(cond, body, carry)
    return x, it, rnorm, bnorm, hist, verdict


def cg(matvec, b, precond=None, tol=1e-5, maxiter=500):
    M = precond or _identity
    b = jnp.asarray(b, jnp.float32)
    run = _cached_engine(matvec, M, ("cg", tol, maxiter), lambda: hoisted_jit(
        functools.partial(_cg_core, matvec, M, tol=tol, maxiter=maxiter), name="cg"))
    x, it, rnorm, bnorm, hist, verdict = run(b)
    rel = float(rnorm) / max(float(bnorm), 1e-30)
    return SolveResult(np.asarray(x), int(it), rel, rel <= tol * 1.01,
                       _trim_history(hist, int(it), float(bnorm)),
                       verdict=VERDICTS[int(verdict)])


# --------------------------------------------------------------------------
# BiCGSTAB (general nonsymmetric)
# --------------------------------------------------------------------------
def _bicgstab_core(matvec, M, b, tol, maxiter):
    bnorm = jnp.linalg.norm(b)
    tolb = tol * bnorm

    def body(carry):
        x, r, rhat, p, v, rho, alpha, omega, it, _, hist, _vd, stall, best = carry
        rho_new = jnp.vdot(rhat, r)
        beta = (rho_new / rho) * (alpha / omega)
        p = r + beta * (p - omega * v)
        phat = M(p)
        v = matvec(phat)
        alpha = rho_new / jnp.vdot(rhat, v)
        s = r - alpha * v
        shat = M(s)
        t = matvec(shat)
        omega = jnp.vdot(t, s) / jnp.vdot(t, t)
        x = x + alpha * phat + omega * shat
        r = s - omega * t
        rnorm = jnp.linalg.norm(r)
        hist = hist.at[it].set(rnorm)
        # a ρ/ω collapse (the classic BiCGSTAB breakdown) surfaces as a
        # non-finite rnorm one step later and classifies as BREAKDOWN —
        # strictly more informative than the old bare `isfinite` cut-out
        stall = jnp.where(rnorm < (1.0 - _STAG_EPS) * best, jnp.int32(0), stall + 1)
        best = jnp.minimum(best, rnorm)
        verdict = _classify(it + 1, rnorm, stall, bnorm, tolb,
                            _KRYLOV_STALL_WINDOW, _KRYLOV_DIV_FACTOR, maxiter)
        return (x, r, rhat, p, v, rho_new, alpha, omega, it + 1, rnorm, hist,
                verdict, stall, best)

    def cond(carry):
        return carry[11] == VERDICT_RUNNING

    x0 = jnp.zeros_like(b)
    r0 = b
    carry = (
        x0, r0, r0, jnp.zeros_like(b), jnp.zeros_like(b),
        jnp.float32(1), jnp.float32(1), jnp.float32(1), jnp.int32(0),
        jnp.linalg.norm(r0), jnp.zeros(maxiter, jnp.float32),
        _init_verdict(bnorm, tolb), jnp.int32(0), bnorm,
    )
    out = jax.lax.while_loop(cond, body, carry)
    x, *_, it, rnorm, hist, verdict, _s, _b = out
    return x, it, rnorm, bnorm, hist, verdict


def bicgstab(matvec, b, precond=None, tol=1e-5, maxiter=500):
    M = precond or _identity
    b = jnp.asarray(b, jnp.float32)
    run = _cached_engine(matvec, M, ("bicgstab", tol, maxiter), lambda: hoisted_jit(
        functools.partial(_bicgstab_core, matvec, M, tol=tol, maxiter=maxiter),
        name="bicgstab"))
    x, it, rnorm, bnorm, hist, verdict = run(b)
    rel = float(rnorm) / max(float(bnorm), 1e-30)
    return SolveResult(np.asarray(x), int(it), rel, rel <= tol * 1.01,
                       _trim_history(hist, int(it), float(bnorm)),
                       verdict=VERDICTS[int(verdict)])


# --------------------------------------------------------------------------
# Restarted GMRES(m), right-preconditioned, fully device-resident
# --------------------------------------------------------------------------
def _gmres_core(matvec, M, b, m, tol, maxiter):
    """One jitted computation: Arnoldi + Givens QR of the Hessenberg +
    restarts under a single ``lax.while_loop``.

    The big (n-sized) scan holds only the Arnoldi recurrence. The Givens QR
    runs as a second, m-sized scan over Hessenberg columns: it yields the
    least-squares residual ``|g[j+1]|`` after every inner step, from which
    the number of *useful* steps ``cnt`` is recovered, and the update is
    assembled from the first ``cnt`` columns only (the tail is masked out of
    the back-substitution) — identical to stopping mid-restart. No
    ``lstsq``, no host synchronization anywhere.

    Every reduction (dots, norms, the V·y combination) and every
    multiply-feeding-an-add goes through ``core.bitmath`` (pairwise-tree
    sums, barred products): XLA lowers ``jnp.vdot``/``jnp.sum`` and FMA
    contraction differently per fusion/batching context, so this is what
    makes a ``vmap``-batched lane produce exactly the bits of the same
    solve run alone — the batched-RHS bit-compat contract.

    The phases carry ``jax.named_scope`` names (``gmres.spmv``,
    ``gmres.precond``, ``gmres.orthogonalize``, ``gmres.qr``,
    ``gmres.update``), which reach each compiled op's ``op_name`` and so a
    profile's operations; names are metadata and change no bits.
    """
    n = b.shape[0]
    bnorm = bitnorm(b)
    tolb = tol * bnorm

    def inner(x0, r0, beta):
        V0 = jnp.zeros((m + 1, n), jnp.float32).at[0].set(r0 / jnp.maximum(beta, 1e-30))
        H0 = jnp.zeros((m + 1, m), jnp.float32)

        def arnoldi(carry, j):
            V, H = carry
            with jax.named_scope("gmres.precond"):
                z = M(V[j])
            with jax.named_scope("gmres.spmv"):
                w = matvec(z)

            # modified Gram-Schmidt
            def mgs(i, wh):
                w, h = wh
                hij = bitdot(V[i], w) * (i <= j)
                return w - barred(hij * V[i]), h.at[i].set(hij)

            with jax.named_scope("gmres.orthogonalize"):
                w, h = jax.lax.fori_loop(0, m + 1, mgs, (w, jnp.zeros(m + 1, jnp.float32)))
                hnext = bitnorm(w)
            V = V.at[j + 1].set(w / jnp.maximum(hnext, 1e-30))
            H = H.at[:, j].set(h.at[j + 1].set(hnext))
            return (V, H), None

        (V, H), _ = jax.lax.scan(arnoldi, (V0, H0), jnp.arange(m))

        # Givens QR over Hessenberg columns (m-sized data, cheap)
        g0 = jnp.zeros(m + 1, jnp.float32).at[0].set(beta)

        def qr_col(carry, inp):
            cs, sn, g = carry
            h, j = inp

            def rot(i, h):
                on = i < j
                hi = barred(cs[i] * h[i]) + barred(sn[i] * h[i + 1])
                hi1 = barred(-sn[i] * h[i]) + barred(cs[i] * h[i + 1])
                return (h.at[i].set(jnp.where(on, hi, h[i]))
                         .at[i + 1].set(jnp.where(on, hi1, h[i + 1])))

            h = jax.lax.fori_loop(0, m, rot, h)
            dsafe = jnp.maximum(jnp.sqrt(barred(h[j] * h[j]) + barred(h[j + 1] * h[j + 1])), 1e-30)
            c, s = h[j] / dsafe, h[j + 1] / dsafe
            hcol = h.at[j].set(barred(c * h[j]) + barred(s * h[j + 1])).at[j + 1].set(0.0)
            g = g.at[j + 1].set(-s * g[j]).at[j].set(c * g[j])
            return (cs.at[j].set(c), sn.at[j].set(s), g), (hcol[:m], jnp.abs(g[j + 1]))

        with jax.named_scope("gmres.qr"):
            (_cs, _sn, g), (r_cols, res_seq) = jax.lax.scan(
                qr_col, (jnp.zeros(m, jnp.float32), jnp.zeros(m, jnp.float32), g0),
                (H.T, jnp.arange(m)),
            )
        # useful steps: everything up to (and including) the first step that
        # cleared the tolerance; the masked tail contributes nothing below
        conv = res_seq <= tolb
        cnt = jnp.where(jnp.any(conv), jnp.argmax(conv) + 1, m).astype(jnp.int32)
        kmask = jnp.arange(m) < cnt
        R = r_cols.T * kmask  # zero masked columns; masked rows get unit diag
        g_eff = jnp.where(kmask, g[:m], 0.0)

        def backsub(jj, y):
            j = m - 1 - jj
            rj = R[j] * (jnp.arange(m) > j)
            num = g_eff[j] - bitdot(rj, y)
            den = jnp.where(kmask[j], R[j, j], 1.0)
            return y.at[j].set(num / den)

        # u = V[:m].T @ y as a fixed-order sequential combination (a matmul
        # reduces over m in a context-dependent order)
        def axpy(acc, vy):
            vj, yj = vy
            return acc + barred(yj * vj), None

        with jax.named_scope("gmres.update"):
            y = jax.lax.fori_loop(0, m, backsub, jnp.zeros(m, jnp.float32))
            u, _ = jax.lax.scan(axpy, jnp.zeros_like(r0), (V[:m], y))
        with jax.named_scope("gmres.precond"):
            du = M(u)
        return x0 + du, cnt

    def outer_cond(carry):
        return carry[6] == VERDICT_RUNNING

    def outer_body(carry):
        x, r, it, res, hist, tot, verdict, stall = carry
        active = verdict == VERDICT_RUNNING  # freezes terminated vmap lanes
        x2, cnt = inner(x, r, res)
        with jax.named_scope("gmres.spmv"):
            ax2 = matvec(x2)
        r2 = b - ax2
        rtrue = bitnorm(r2)
        # verdict/stall ride outside the iterate arithmetic: x2/r2/rtrue are
        # computed exactly as before, so classification changes no bits
        stall2 = jnp.where(rtrue < (1.0 - _STAG_EPS) * res, jnp.int32(0), stall + 1)
        v2 = _classify(it + 1, rtrue, stall2, bnorm, tolb,
                       _GMRES_STALL_WINDOW, _GMRES_DIV_FACTOR, maxiter)
        new = (x2, r2, it + 1, rtrue, hist.at[it].set(rtrue), tot + cnt, v2, stall2)
        return jax.tree_util.tree_map(lambda nw, old: jnp.where(active, nw, old), new, carry)

    init = (jnp.zeros_like(b), b, jnp.int32(0), bnorm,
            jnp.zeros(maxiter, jnp.float32), jnp.int32(0),
            _init_verdict(bnorm, tolb), jnp.int32(0))
    x, _r, it, res, hist, tot, verdict, _stall = jax.lax.while_loop(
        outer_cond, outer_body, init)
    # non-finite ‖b‖ must surface as a non-finite relative residual: with a
    # bare `bnorm > 0` a NaN b takes the 0.0 branch and the lane would
    # report converged — the exact poison the breakdown verdict exists for
    rel = jnp.where(bnorm > 0, res / jnp.maximum(bnorm, 1e-30),
                    jnp.where(jnp.isfinite(bnorm), 0.0, jnp.nan))
    return x, rel, it, tot, hist, bnorm, verdict


def gmres_engine(matvec, M, restart, tol, maxiter):
    """The compiled single-RHS GMRES engine over ``matvec`` and the
    preconditioner ``M``, cached on ``matvec``."""
    return _cached_engine(matvec, M, ("gmres", restart, tol, maxiter), lambda: hoisted_jit(
        functools.partial(_gmres_core, matvec, M, m=restart, tol=tol, maxiter=maxiter),
        name="gmres"))


def gmres(matvec, b, precond=None, restart=30, tol=1e-5, maxiter=20):
    """maxiter counts *outer* restarts. Solves A (M^{-1} u) = b, x = M^{-1} u.

    ``iterations`` reports the inner (Arnoldi) steps that did work;
    ``history`` holds the true relative residual after each restart.
    Compilation is cached on the identity of ``matvec``/``precond`` — reuse
    the same closures (e.g. a factorization's ``PrecondApply``) and repeated
    solves skip straight to the compiled engine."""
    M = precond or _identity
    b = jnp.asarray(b, jnp.float32)
    x, rel, it, tot, hist, bnorm, verdict = gmres_engine(matvec, M, restart, tol, maxiter)(b)
    rel = float(rel)
    return SolveResult(np.asarray(x), int(tot), rel, rel <= tol * 1.01,
                       _trim_history(hist, int(it), float(bnorm)),
                       verdict=VERDICTS[int(verdict)])


def gmres_batched(matvec, bs, precond=None, restart=30, tol=1e-5, maxiter=20) -> List[SolveResult]:
    """GMRES over a (batch, n) stack of right-hand sides in one dispatch.

    ``vmap`` of the single-RHS engine: every lane shares the cached
    triangular plan and SpMV arrays; converged lanes freeze (per-lane
    iteration counts and histories stay exact) while the rest continue.

    ``tol`` may be a scalar or a per-lane ``(batch,)`` array — the serving
    coalescer batches requests with *different* tolerances into one bucketed
    solve. Per-lane tolerances ride as a vmapped runtime argument, so one
    compiled engine serves every tolerance mix (no per-tol executables) and
    a lane's arithmetic is bitwise identical to the same solve run alone
    with its scalar tolerance: ``tol`` only feeds ``tol * ||b||`` (computed
    at runtime either way) and the stopping comparisons — never the
    iterate arithmetic."""
    M = precond or _identity
    bs = jnp.asarray(bs, jnp.float32)
    if bs.ndim != 2:
        raise ValueError(f"gmres_batched expects (batch, n), got shape {bs.shape}")
    tol_arr = np.asarray(tol, np.float32)
    if tol_arr.ndim == 0:
        key = ("gmres_batched", restart, tol, maxiter)
        run = _cached_engine(matvec, M, key, lambda: hoisted_jit(jax.vmap(
            functools.partial(_gmres_core, matvec, M, m=restart, tol=tol, maxiter=maxiter)),
            name="gmres_batched"))
        x, rel, it, tot, hist, bnorm, verdict = run(bs)
        tols = np.full(bs.shape[0], float(tol), np.float32)
    else:
        if tol_arr.shape != (bs.shape[0],):
            raise ValueError(
                f"gmres_batched: per-lane tol must have shape ({bs.shape[0]},) "
                f"matching the batch, got {tol_arr.shape}")
        key = ("gmres_batched_vtol", restart, maxiter)
        run = _cached_engine(matvec, M, key, lambda: hoisted_jit(jax.vmap(
            lambda b, t: _gmres_core(matvec, M, b, m=restart, tol=t, maxiter=maxiter)),
            name="gmres_batched"))
        x, rel, it, tot, hist, bnorm, verdict = run(bs, jnp.asarray(tol_arr))
        tols = tol_arr
    verdict = np.asarray(verdict)
    out = []
    for i in range(bs.shape[0]):
        r = float(rel[i])
        out.append(SolveResult(np.asarray(x[i]), int(tot[i]), r, r <= float(tols[i]) * 1.01,
                               _trim_history(hist[i], int(it[i]), float(bnorm[i])),
                               verdict=VERDICTS[int(verdict[i])]))
    return out


def solve_sharded(a, b, k=1, mesh=None, band_rows=32, rule="sum",
                  broadcast="psum", method="gmres", tol=1e-5, fact=None,
                  bucket=True, ordering=None, precond_method=None,
                  on_breakdown="raise", pivot_tol=None, **kw):
    """Distributed end-to-end solve: sharded TOP-ILU factorize + solve.

    The factorization stays device-resident (``ilu_sharded``), the
    preconditioner applies through the epoch-fused band-partitioned sweeps,
    and the SpMV runs row-block sharded — L/U and A are never re-replicated
    onto one device; only O(n) vectors are. The Krylov iteration itself is
    the same device-resident engine as the single-device path, so with
    identical matvec/precond outputs (both bitwise contracts) the iterates
    — and the solution — are bitwise identical to ``solve_with_ilu``.

    A 2-D ``b`` of shape (nb, n) routes through ``gmres_batched`` over the
    sharded matvec/precond and returns a list of results: the vmapped
    engine batches every sweep-epoch and SpMV collective over all
    right-hand sides (one exchange per epoch for the whole batch). With
    ``bucket=True`` (default) the batch is zero-padded up to the nearest
    ``batch_buckets()`` size, so serving traffic with ragged batch shapes
    reuses a bounded set of compiled engines; padded lanes are independent
    under vmap and are sliced off, leaving every real column bitwise equal
    to its per-column solve.

    Returns ``(SolveResult(s), ShardedILUFactorization)``. Factorization
    and matvec are memoized on the matrix, keyed by mesh devices (and the
    factorization config), like ``solve_with_ilu``'s caches; pass an
    already-built ``fact`` (a ``ShardedILUFactorization`` of the same
    matrix) to reuse it — and its cached precond — directly.

    ``ordering=`` solves the symmetrically permuted system (``"rcm"``,
    ``"fusion"`` — which targets this mesh's band ownership so sweep
    epochs fuse — an ``Ordering``, or a permutation array): ``A`` permutes
    once at plan time, ``b``/``x`` un/permute at this boundary (multi-RHS
    included), and the returned ``fact`` carries the permutation — a
    ``fact=`` round-trip without ``ordering=`` re-adopts it automatically.
    """
    from .api import ilu_sharded
    from .top_ilu import band_mesh

    # --- ordering boundary: solve the permuted system, then gather back ---
    # (a factorization built with an ordering carries it; adopting it here
    # keeps `fact=` reuse consistent instead of silently mixing row orders)
    caller_fact = fact is not None
    if ordering is None and caller_fact:
        ordering = getattr(fact, "ordering", None)
    if ordering is not None:
        from .ordering import make_ordering, permuted_system

        n_dev = int((fact.mesh if fact is not None else band_mesh(mesh)).devices.size)
        ord_ = make_ordering(a, ordering, n_devices=n_dev, band_rows=band_rows)
        if ord_ is not None:
            if caller_fact:
                # a caller-supplied fact must have been factored under this
                # exact permutation — anything else silently mixes row orders
                # (matvec on one system, preconditioner on another)
                fo = getattr(fact, "ordering", None)
                if fo is None or not np.array_equal(fo.perm, ord_.perm):
                    raise ValueError(
                        "solve_sharded: `fact` was factored under a "
                        f"different row ordering than ordering={ord_.name!r}"
                        " — pass the fact's own ordering (or none, to adopt"
                        " it), or refactor under the requested one")
            ap = permuted_system(a, ord_)
            # ordering="natural" stops the recursion from re-adopting the
            # ordering carried by `fact` — `ap` is already permuted
            res, fact = solve_sharded(
                ap, ord_.permute_vector(np.asarray(b, np.float32)), k=k,
                mesh=mesh, band_rows=band_rows, rule=rule, broadcast=broadcast,
                method=method, tol=tol, fact=fact, bucket=bucket,
                ordering="natural", precond_method=precond_method,
                on_breakdown=on_breakdown, pivot_tol=pivot_tol, **kw)
            if not caller_fact and fact is not None and fact.ordering is None:
                fact.ordering = ord_  # so `fact=` round-trips re-adopt it
            return _unpermute_results(res, ord_), fact

    if fact is not None:
        if mesh is not None and not np.array_equal(
            [d.id for d in mesh.devices.flat],
            [d.id for d in fact.mesh.devices.flat],
        ):
            raise ValueError(
                "solve_sharded: `fact` was factored on a different mesh than "
                "`mesh` — the SpMV and the preconditioner must share one mesh")
        mesh = fact.mesh
    else:
        mesh = band_mesh(mesh)
    mesh_key = tuple(dev.id for dev in mesh.devices.flat)
    cache = a.__dict__.setdefault("_solve_cache", {})
    mv_key = ("sharded_matvec", mesh_key)
    if mv_key not in cache:
        cache[mv_key] = make_sharded_ell_matvec(a, mesh)
    matvec = cache[mv_key]
    # precond_method=None defers to the factorization's own default
    # ("sweep" unless it was built with something else); "sweep"/"inverse"/
    # "auto" override per solve — engines for both methods cache on the fact
    precond = None
    if fact is not None:
        precond = fact.precond(broadcast=broadcast, method=precond_method)
    elif k is not None:
        f_key = ("sharded_fact", k, rule, band_rows, broadcast, mesh_key)
        if on_breakdown != "raise" or pivot_tol is not None:
            f_key = f_key + (on_breakdown, pivot_tol)
        if f_key not in cache:
            cache[f_key] = ilu_sharded(a, k, rule=rule, band_rows=band_rows,
                                       mesh=mesh, broadcast=broadcast,
                                       on_breakdown=on_breakdown,
                                       pivot_tol=pivot_tol)
        fact = cache[f_key]
        precond = fact.precond(broadcast=broadcast, method=precond_method)
    b = jnp.asarray(b, jnp.float32)
    if b.ndim == 2:
        if method != "gmres":
            raise ValueError("batched right-hand sides are supported for method='gmres' only")
        nb = b.shape[0]
        if bucket:
            b = _pad_rhs_batch(b, bucket_batch(nb))
        res = gmres_batched(matvec, b, precond,
                            tol=_pad_tols(tol, b.shape[0]), **kw)[:nb]
        return _annotate_reports(res, fact), fact
    if b.ndim != 1:
        raise ValueError(f"solve_sharded expects b of shape (n,) or (batch, n), got {b.shape}")
    fn = {"gmres": gmres, "bicgstab": bicgstab, "cg": cg}[method]
    res = fn(matvec, b, precond, tol=tol, **kw)
    return _annotate_reports(res, fact), fact


def warm_solve(a, k=1, batch_sizes=(1,), mesh=None, band_rows=32, rule="sum",
               broadcast="psum", method="gmres", tol=1e-5, sharded=True,
               ordering=None, precond_method=None,
               on_breakdown="raise", pivot_tol=None, **kw):
    """Serving warmup: pre-compile the whole factorize→precondition→solve
    stack for the given RHS batch-size buckets, so the first real request
    of a pre-warmed shape never pays the ~1–2 s first-dispatch XLA compile.

    Factors ``a`` once (cached on the matrix like ``solve_sharded`` /
    ``solve_with_ilu``), AOT-compiles the preconditioner sweep per bucket
    (``precond.warm``), then drives one zero-RHS solve per bucket through
    the real solver entry so the Krylov engine jits land in the same
    per-matrix caches a live solve will hit. With the persistent
    compilation cache on (``api.enable_jit_cache``) the compilations stay
    on disk, making warmup a once-per-machine cost. Returns {batch_size: warmup_seconds}.
    """
    import time

    out = {}
    for nb in batch_sizes:
        t0 = time.perf_counter()
        tgt = bucket_batch(nb) if nb > 1 else 1
        zb = np.zeros((tgt, a.n) if nb > 1 else a.n, np.float32)
        if sharded:
            _res, fact = solve_sharded(a, zb, k=k, band_rows=band_rows,
                                       rule=rule, broadcast=broadcast,
                                       method=method, tol=tol, mesh=mesh,
                                       ordering=ordering,
                                       precond_method=precond_method,
                                       on_breakdown=on_breakdown,
                                       pivot_tol=pivot_tol, **kw)
            fact.precond(broadcast=broadcast, method=precond_method).warm((tgt,))
        else:
            _res, fact = solve_with_ilu(a, zb, k=k, band_rows=band_rows,
                                        method=method, tol=tol,
                                        ordering=ordering,
                                        precond_method=precond_method,
                                        on_breakdown=on_breakdown,
                                        pivot_tol=pivot_tol, **kw)
            fact.precond(method=precond_method).warm((tgt,))
        out[nb] = time.perf_counter() - t0
    return out


def solve_with_ilu(a, b, k=1, method="gmres", backend="jax", tol=1e-5,
                   band_rows=32, ordering=None,
                   precond_method=None, on_breakdown="raise", pivot_tol=None,
                   **kw):
    """End-to-end: factorize with ILU(k), then solve. Returns (SolveResult, fact).

    ``ordering=`` solves the symmetrically permuted system instead
    (``"rcm"``, ``"fusion"``, an ``Ordering``, or a permutation array):
    ``A`` permutes once at plan time (cached on the matrix), ``b``/``x``
    un/permute at this boundary — including multi-RHS batches — and the
    returned ``fact`` describes the permuted system (its ``ordering``
    field carries the permutation).

    The SpMV runs through the ELL matvec and the preconditioner through
    the factorization's cached ``PrecondApply`` (fused wavefront sweep) —
    the whole iteration is device-resident. A 2-D ``b`` of shape
    (batch, n) routes through ``gmres_batched`` and returns a list of
    results sharing one factorization.

    ELL arrays, the matvec closure, and the factorization are memoized on
    the matrix object: the solver jits are keyed on (matvec, precond)
    identity, so repeated solves against the same matrix reuse one compiled
    engine instead of retracing (and the jit cache holds one entry per
    matrix, not per call). Mutating ``a`` in place invalidates none of
    this — build a fresh CSRMatrix instead.
    """
    from .api import ilu

    if ordering is not None:
        from .ordering import make_ordering, permuted_system

        ord_ = make_ordering(a, ordering, n_devices=1, band_rows=band_rows)
        if ord_ is not None:
            ap = permuted_system(a, ord_)
            res, fact = solve_with_ilu(
                ap, ord_.permute_vector(np.asarray(b, np.float32)), k=k,
                method=method, backend=backend, tol=tol, band_rows=band_rows,
                precond_method=precond_method,
                on_breakdown=on_breakdown, pivot_tol=pivot_tol, **kw)
            if fact is not None and fact.ordering is None:
                fact.ordering = ord_
            return _unpermute_results(res, ord_), fact

    cache = a.__dict__.setdefault("_solve_cache", {})
    mv_key = ("matvec",)
    if mv_key not in cache:
        cols, vals = csr_to_ell_arrays(a)
        cache[mv_key] = make_ell_matvec(cols, vals, a.n)
    matvec = cache[mv_key]
    fact = None
    precond = None
    if k is not None:
        f_key = ("fact", k, backend, band_rows)
        if on_breakdown != "raise" or pivot_tol is not None:
            f_key = f_key + (on_breakdown, pivot_tol)
        if f_key not in cache:
            cache[f_key] = ilu(a, k, backend=backend, band_rows=band_rows,
                               on_breakdown=on_breakdown, pivot_tol=pivot_tol)
        fact = cache[f_key]
        precond = fact.precond(method=precond_method)
    b = jnp.asarray(b, jnp.float32)
    if b.ndim == 2:
        if method != "gmres":
            raise ValueError("batched right-hand sides are supported for method='gmres' only")
        res = gmres_batched(matvec, b, precond, tol=tol, **kw)
        return _annotate_reports(res, fact), fact
    fn = {"gmres": gmres, "bicgstab": bicgstab, "cg": cg}[method]
    res = fn(matvec, b, precond, tol=tol, **kw)
    return _annotate_reports(res, fact), fact
