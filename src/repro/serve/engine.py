"""Structure-keyed, value-rebinding solve engines for the serve layer.

The multi-tenant cache problem: a tenant's matrix-value update must not
recompile anything, or the XLA compile counter climbs with tenant churn and
p99 is eventually paid by some request that drew the compile. The existing
solver engines bake factor/matrix values into the executable as closure
constants (fine for one matrix, fatal for a serving cache). This module
compiles ONE GMRES engine per *structure* (sparsity pattern + solver
knobs + bucket) in which every float operand — A's ELL values, the
level-major L/U sweep values, or the W/Z inverse-chain values — rides as a
runtime **argument**:

* value update ⇒ refactorize through the already-compiled ``FactorPlan``
  engine, re-scatter values host-side (``rebind_triangular_values`` /
  ``build_inverse_plan``), hand the new arrays to the same executable —
  zero XLA compiles end to end (:meth:`ServeEngine.bind` is pure data);
* two tenants with the same structure (common when tenants are shards of
  one model family) share one executable per bucket.

Bit-compat contract: the engine runs exactly the computation of the
single-request path — the same Pallas ELL SpMV, the same fused wavefront
sweep (or inverse SpMV chain), the same ``_gmres_core`` with its
fixed-topology ``bitmath`` reductions — ``vmap``-ped over (b, tol) lanes.
Values-as-arguments is the PR-6 idiom (constant-embedded operands let XLA
fold with different rounding; runtime operands keep the compiled
arithmetic fixed), so a lane's bits equal the same solve run alone. The
coalescing property test and the soak assert this, response by response.

``ShardedServeEngine`` adapts the same surface onto ``solve_sharded`` for
multi-device meshes. The sharded *sweep* already rebinds values as
arguments (``ShardedTriangularEngine``); the sharded SpMV and Krylov jits
are still closure-keyed, so a sharded rebind pre-warms its fresh engines in
the background refactor thread — compiles happen off the serving path,
though the counter records them (documented asymmetry, DESIGN.md §11).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import List, Optional, Sequence

import numpy as np

from repro import obs
from repro.core.sparse import CSRMatrix, ILUPattern

#: serving defaults — one place, shared by engines / service / bench
DEFAULT_RESTART = 30
DEFAULT_MAXITER = 20


@dataclasses.dataclass
class LaneResult:
    """Per-request outcome scattered out of a coalesced solve."""

    x: np.ndarray
    iterations: int
    residual: float
    converged: bool
    #: solver termination verdict (repro.core.solvers.VERDICTS) — the
    #: service's retry/quarantine policy keys on this
    verdict: str = ""


@dataclasses.dataclass
class EngineBinding:
    """One matrix *version* bound to an engine: pure device data, no code.

    ``value_args`` is the tuple the compiled run consumes; ``vals_csr``
    keeps the CSR-aligned factor values for audit/debug (host array).
    """

    version: int
    value_args: tuple
    vals_csr: np.ndarray
    #: the CSRMatrix this binding's *matvec* values came from — the
    #: shift-retry path refactors `A + α·diag(‖row‖₁)` from it while the
    #: solve keeps targeting this exact A (Manteuffel: shift the
    #: preconditioner, never the system)
    a: object = None
    #: diagonal shift α of the preconditioner factor (0 = unshifted)
    shift: float = 0.0
    #: True when this binding preconditions with the exact identity (the
    #: shift ladder exhausted under the cache's "fallback" policy)
    degraded: bool = False


def engine_fingerprint(a: CSRMatrix, pattern: ILUPattern, knobs: tuple) -> tuple:
    """Content key: same structure + same solver knobs ⇒ same engine.

    Hashes A's sparsity and the filled pattern (indices + levels — the
    factor structure), never values: two tenants with equal structure and
    different numbers share one compiled engine.
    """
    h = hashlib.sha1()
    h.update(a.indptr.tobytes())
    h.update(a.indices.tobytes())
    h.update(pattern.indptr.tobytes())
    h.update(pattern.indices.tobytes())
    h.update(pattern.levels.tobytes())
    return (a.n, pattern.k, h.hexdigest()) + knobs


class ServeEngine:
    """Single-device value-rebinding multi-RHS GMRES engine.

    Built once per (structure, ``precond_method``, restart/maxiter);
    ``bind`` attaches a value version, ``solve`` runs a
    coalesced bucket, ``warm`` AOT-compiles the bucket set.
    """

    #: binding identity-valued factors through the compiled sweep applies
    #: M^{-1} = I exactly — the cache's last-resort "fallback" degradation
    supports_identity_fallback = True

    def __init__(self, a: CSRMatrix, pattern: ILUPattern, vals_csr: np.ndarray,
                 restart: int = DEFAULT_RESTART, maxiter: int = DEFAULT_MAXITER,
                 precond_method: str = "sweep",
                 buckets: Optional[Sequence[int]] = None):
        import jax.numpy as jnp

        from repro.core.bitmath import hoisted_jit
        from repro.core.solvers import _csr_to_ell_host, batch_buckets

        if precond_method not in ("sweep", "inverse"):
            raise ValueError(f"ServeEngine: unknown precond_method {precond_method!r}")
        self.n = a.n
        self.pattern = pattern
        self.restart = int(restart)
        self.maxiter = int(maxiter)
        self.precond_method = precond_method
        self.buckets = tuple(batch_buckets() if buckets is None else sorted(buckets))
        self.fingerprint = engine_fingerprint(
            a, pattern, (precond_method, self.restart, self.maxiter))

        # --- A-side structure: ELL cols (constant) + the value scatter maps
        a_cols, _ = _csr_to_ell_host(a)
        self._a_ell_shape = a_cols.shape
        lens = np.diff(a.indptr)
        self._a_row_of = np.repeat(np.arange(a.n), lens)
        self._a_pos = np.arange(a.nnz, dtype=np.int64) - a.indptr[self._a_row_of]
        self._a_cols = jnp.asarray(a_cols)

        # --- preconditioner structure --------------------------------------
        if precond_method == "sweep":
            from repro.core.triangular import build_triangular_plan

            self._tri_plan = build_triangular_plan(pattern, vals_csr)
            d = self._tri_plan.device_arrays()
            self._p_static = {k: d[k] for k in
                              ("l_cols", "l_rhs_idx", "u_cols", "u_rhs_idx", "out_perm")}
        else:
            from repro.core.inverse import build_inverse_plan

            plan0 = build_inverse_plan(pattern, vals_csr, k=pattern.k)
            self._w_cols = jnp.asarray(plan0.w_cols)
            self._z_cols = jnp.asarray(plan0.z_cols)

        self._jit = hoisted_jit(self._make_run(), name="serve_gmres")
        self._aot = {}
        self._versions = 0

    # -- the compiled computation ------------------------------------------
    def _make_run(self):
        import jax

        from repro.core.solvers import _gmres_core, make_ell_matvec

        n = self.n
        m, maxiter = self.restart, self.maxiter
        a_cols = self._a_cols

        def run(vargs, bs, tols):
            matvec = make_ell_matvec(a_cols, vargs[0], n)

            if self.precond_method == "sweep":
                from repro.core.triangular import wavefront_sweeps_jnp

                s = self._p_static
                _, l_vals, u_vals, u_diag = vargs

                def M(x):
                    return wavefront_sweeps_jnp(
                        s["l_cols"], l_vals, s["l_rhs_idx"], s["u_cols"],
                        u_vals, u_diag, s["u_rhs_idx"], s["out_perm"], x)
            else:
                from repro.core.inverse import inverse_chain_jnp

                _, w_vals, z_vals = vargs
                wc, zc = self._w_cols, self._z_cols

                def M(x):
                    return inverse_chain_jnp(wc, w_vals, zc, z_vals, x)

            def lane(b, t):
                return _gmres_core(matvec, M, b, m=m, tol=t, maxiter=maxiter)

            return jax.vmap(lane)(bs, tols)

        return run

    # -- value binding ------------------------------------------------------
    def bind(self, a: CSRMatrix, vals_csr: np.ndarray) -> EngineBinding:
        """Attach one value version: host-side scatter (``ilu:push.rebind``)
        + device put (``ilu:push.put``), no compilation (the inverse method
        runs the already-compiled value sweep — same shapes, same
        executable)."""
        import jax
        import jax.numpy as jnp

        with obs.span("ilu:push.rebind"):
            a_vals = np.zeros(self._a_ell_shape, np.float32)
            a_vals[self._a_row_of, self._a_pos] = a.data
            if self.precond_method == "sweep":
                from repro.core.triangular import rebind_triangular_values

                p_vals = rebind_triangular_values(self._tri_plan, self.pattern, vals_csr)
            else:
                from repro.core.inverse import build_inverse_plan, compute_inverse_values

                plan = build_inverse_plan(self.pattern, vals_csr, k=self.pattern.k)
                w_vals, z_vals = compute_inverse_values(plan)
                if w_vals.shape != self._w_cols.shape or z_vals.shape != self._z_cols.shape:
                    raise ValueError("ServeEngine.bind: inverse pattern changed shape — "
                                     "values were bound against a different structure")
                p_vals = (w_vals, z_vals)
        with obs.span("ilu:push.put"):
            vargs = jax.tree.map(jnp.asarray, (a_vals,) + tuple(p_vals))
        self._versions += 1
        return EngineBinding(version=self._versions, value_args=vargs,
                             vals_csr=np.asarray(vals_csr, np.float32), a=a)

    def bind_degraded(self, a: CSRMatrix, shift: float, factorize) -> Optional[EngineBinding]:
        """One rung of the serve-side shift ladder: factor
        ``A + shift·diag(‖row‖₁)`` through ``factorize`` (the cache's
        already-compiled plan — same structure, zero compiles), audit it,
        and bind the shifted *sweep* values against the **original** A's
        matvec values. The solve still targets Ax=b; only M changes — and
        the bucketed executable is the very one the healthy path uses, so a
        retry costs a bind, never a compile. Returns None when this rung's
        factor is itself broken (the caller escalates α)."""
        from repro.core.guard import audit_values, shifted_matrix

        a_s = shifted_matrix(a, shift)
        vals_s = factorize(a_s)
        if not audit_values(self.pattern, vals_s).ok:
            return None
        binding = self.bind(a, vals_s)
        binding.shift = float(shift)
        return binding

    # -- solving ------------------------------------------------------------
    def bucket_for(self, nb: int) -> int:
        from repro.core.solvers import bucket_batch

        return bucket_batch(nb, self.buckets)

    def solve(self, binding: EngineBinding, bs: np.ndarray,
              tols: np.ndarray) -> List[LaneResult]:
        """Solve a coalesced (nb, n) stack with per-lane tolerances; pads to
        the nearest bucket, runs the one compiled engine, scatters per-lane
        results back. Padding lanes (zero RHS, tol 1) freeze immediately and
        are sliced off — they cannot touch a real lane's bits."""
        import jax.numpy as jnp

        bs = np.asarray(bs, np.float32)
        tols = np.asarray(tols, np.float32)
        nb = bs.shape[0]
        if bs.ndim != 2 or bs.shape[1] != self.n:
            raise ValueError(f"ServeEngine.solve: expected (nb, {self.n}), got {bs.shape}")
        if tols.shape != (nb,):
            raise ValueError(f"ServeEngine.solve: tols must be ({nb},), got {tols.shape}")
        tgt = self.bucket_for(nb)
        if tgt > nb:
            bs = np.concatenate([bs, np.zeros((tgt - nb, self.n), np.float32)])
            tols = np.concatenate([tols, np.ones(tgt - nb, np.float32)])
        ex = self._aot.get(tgt)
        fn = ex if ex is not None else self._jit
        x, rel, it, tot, hist, bnorm, verdict = fn(
            binding.value_args, jnp.asarray(bs), jnp.asarray(tols))
        from repro.core.solvers import VERDICTS

        x = np.asarray(x)
        rel = np.asarray(rel)
        tot = np.asarray(tot)
        verdict = np.asarray(verdict)
        return [
            LaneResult(x=x[i], iterations=int(tot[i]), residual=float(rel[i]),
                       converged=float(rel[i]) <= float(tols[i]) * 1.01,
                       verdict=VERDICTS[int(verdict[i])])
            for i in range(nb)
        ]

    def warm(self, binding: EngineBinding, buckets: Optional[Sequence[int]] = None) -> dict:
        """AOT-compile the engine for each bucket (serving warmup; with the
        persistent compilation cache on, the executables persist across
        processes). Returns {bucket: seconds}."""
        import jax

        out = {}
        for nb in buckets if buckets is not None else self.buckets:
            t0 = time.perf_counter()
            if nb not in self._aot:
                vargs_sds = jax.tree.map(
                    lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), binding.value_args)
                bs_sds = jax.ShapeDtypeStruct((nb, self.n), np.float32)
                tol_sds = jax.ShapeDtypeStruct((nb,), np.float32)
                self._aot[nb] = self._jit.lower(vargs_sds, bs_sds, tol_sds).compile()
            out[nb] = time.perf_counter() - t0
        return out


class ShardedServeEngine:
    """The same serve surface over the distributed stack (``solve_sharded``).

    Values still *rebind* (a new factorization swaps in behind the same
    tick loop), but the sharded SpMV/Krylov jits key on closure identity,
    so a rebind's fresh engines are pre-warmed inside :meth:`bind` — in the
    background refactor thread, never on the serving path. The sharded
    sweep itself reuses one compiled ``ShardedTriangularEngine`` across
    rebinds (values are arguments there), shared via the factorization's
    structure-keyed ``_shared`` store.
    """

    #: the sharded engine factors internally — it cannot bind caller-
    #: provided identity values, so ladder exhaustion rejects instead
    supports_identity_fallback = False

    def __init__(self, a: CSRMatrix, pattern: ILUPattern, vals_csr=None,
                 restart: int = DEFAULT_RESTART, maxiter: int = DEFAULT_MAXITER,
                 precond_method: str = "sweep", mesh=None, band_rows: int = 32,
                 k: Optional[int] = None, rule: str = "sum",
                 buckets: Optional[Sequence[int]] = None):
        from repro.core.solvers import batch_buckets
        from repro.core.top_ilu import band_mesh

        self.n = a.n
        self.pattern = pattern
        self.restart = int(restart)
        self.maxiter = int(maxiter)
        self.precond_method = precond_method
        self.mesh = band_mesh(mesh)
        self.band_rows = band_rows
        self.k = pattern.k if k is None else k
        self.rule = rule
        self.buckets = tuple(batch_buckets() if buckets is None else sorted(buckets))
        self.fingerprint = engine_fingerprint(
            a, pattern,
            ("sharded", precond_method, self.restart, self.maxiter, self.band_rows,
             tuple(d.id for d in self.mesh.devices.flat)))
        self._versions = 0
        self._prev_fact = None

    def bind(self, a: CSRMatrix, vals_csr=None) -> EngineBinding:
        """Factorize ``a`` on the mesh and pre-warm the fresh closure-keyed
        engines (one bucketed solve per bucket, off the serving path). The
        structure-keyed sweep engine carries over from the previous
        binding, so only the SpMV/Krylov jits recompile on a rebind."""
        from repro.core.api import ilu_sharded
        from repro.core.solvers import solve_sharded

        fact = ilu_sharded(a, self.k, rule=self.rule, band_rows=self.band_rows,
                           mesh=self.mesh, precond_method=self.precond_method,
                           on_breakdown="ignore")
        if self._prev_fact is not None:
            # same structure ⇒ the sharded triangular plan + compiled sweep
            # in `_shared` rebind to the new values without recompiling
            fact._shared = self._prev_fact._shared
        for nb in self.buckets:
            # warm the exact serving-path engine: per-lane tol ARRAY +
            # bucket=False (what solve() dispatches) — a scalar tol would
            # warm a different jit and leave serving to pay the compile
            zb = np.zeros((nb, self.n), np.float32)
            solve_sharded(a, zb, fact=fact, tol=np.ones(nb, np.float32),
                          bucket=False, restart=self.restart,
                          maxiter=self.maxiter, precond_method=self.precond_method)
        self._prev_fact = fact
        self._versions += 1
        binding = EngineBinding(
            version=self._versions, value_args=(a, fact),
            vals_csr=np.asarray(fact.values_csr(), np.float32), a=a)
        return binding

    def bind_degraded(self, a: CSRMatrix, shift: float, factorize=None) -> Optional[EngineBinding]:
        """Shift-retry rung, sharded: refactor ``A + shift·diag(‖row‖₁)`` on
        the mesh (the shifted matrix adopts A's engine stores, so the
        factorization re-executes without re-planning), audit on device, and
        bind ``(original A, shifted fact)`` — the sharded matvec stays on A
        while the sweep reads the shifted factor. ``factorize`` is unused
        (the mesh path factors itself); the fresh closure-keyed Krylov jits
        pre-warm here, off the healthy serving path."""
        from repro.core.api import ilu_sharded
        from repro.core.guard import shifted_matrix
        from repro.core.solvers import solve_sharded

        a_s = shifted_matrix(a, shift)
        fact = ilu_sharded(a_s, self.k, rule=self.rule, band_rows=self.band_rows,
                           mesh=self.mesh, precond_method=self.precond_method,
                           on_breakdown="ignore")
        if self._prev_fact is not None:
            fact._shared = self._prev_fact._shared
        if not fact.health.ok:
            return None
        for nb in self.buckets:
            zb = np.zeros((nb, self.n), np.float32)
            solve_sharded(a, zb, fact=fact, tol=np.ones(nb, np.float32),
                          bucket=False, restart=self.restart,
                          maxiter=self.maxiter, precond_method=self.precond_method)
        self._versions += 1
        return EngineBinding(
            version=self._versions, value_args=(a, fact),
            vals_csr=np.asarray(fact.values_csr(), np.float32),
            a=a, shift=float(shift))

    def bucket_for(self, nb: int) -> int:
        from repro.core.solvers import bucket_batch

        return bucket_batch(nb, self.buckets)

    def solve(self, binding: EngineBinding, bs: np.ndarray,
              tols: np.ndarray) -> List[LaneResult]:
        from repro.core.solvers import solve_sharded

        a, fact = binding.value_args
        bs = np.asarray(bs, np.float32)
        tols = np.asarray(tols, np.float32)
        nb = bs.shape[0]
        tgt = self.bucket_for(nb)
        if tgt > nb:
            bs = np.concatenate([bs, np.zeros((tgt - nb, self.n), np.float32)])
            tols = np.concatenate([tols, np.ones(tgt - nb, np.float32)])
        res, _ = solve_sharded(a, bs, fact=fact, tol=tols, bucket=False,
                               restart=self.restart, maxiter=self.maxiter,
                               precond_method=self.precond_method)
        return [
            LaneResult(x=r.x, iterations=r.iterations, residual=r.residual,
                       converged=r.converged, verdict=r.verdict)
            for r in res[:nb]
        ]

    def warm(self, binding: EngineBinding, buckets=None) -> dict:
        """Buckets are already warmed inside :meth:`bind` (the sharded
        engines key on the binding's closures); report zero-cost hits."""
        return {nb: 0.0 for nb in (buckets if buckets is not None else self.buckets)}
