"""Public API: ILU(k) preconditioning end-to-end.

    from repro.core.api import ilu
    fact = ilu(a, k=1, backend="jax")      # symbolic + numeric
    x = fact.solve(b)                      # apply M^{-1} (two triangular solves)

Backends:
  * ``oracle``   — sequential NumPy (the paper's sequential algorithm).
  * ``jax``      — single-device wavefront engine over a cached
                   ``FactorPlan`` (bit-compatible; ``band_rows`` ignored).
  * ``topilu``   — multi-device shard_map TOP-ILU over the band superstep
                   schedule (bit-compatible; bands of ``band_rows`` rows;
                   sharded value storage + halo exchange, DESIGN.md §5).

:func:`ilu_sharded` is the distributed entry point: same contract, but the
factor values stay device-resident/sharded and the preconditioner applies
in place (``ilu(backend="topilu")`` gathers the result to the host).

The whole ``factorize → precond → solve`` pipeline is plan→compile→execute
(DESIGN.md §3): each stage's plan and compiled engine are cached — the
``FactorPlan`` on the matrix, the ``PrecondApply`` on the factorization —
so repeated use retraces nothing.

``ordering=`` (both entry points) runs the pipeline on a symmetrically
permuted system ``P A Pᵀ`` (DESIGN.md §Ordering): ``"rcm"``, ``"fusion"``
(the fusion-aware subdomain layout from ``repro.core.ordering``), an
explicit permutation, or ``None``/``"natural"``. The permutation is
applied once at plan time and cached on the matrix; the factorization is
bitwise-equal to sequential ILU(k) of the *permuted* matrix, and
``solve`` un/permutes ``b``/``x`` at the boundary (pure gathers).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

import numpy as np

from .. import obs
from .sparse import CSRMatrix, ILUPattern, split_lu
from .symbolic import symbolic_ilu_k, pilu1_symbolic
from .numeric_ref import numeric_ilu_ref

_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    ".jax_cache")


def enable_jit_cache() -> None:
    """Turn on jax's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads the directory from
    it and nothing here overrides it. Otherwise the cache lives at the fixed
    path ``<repo>/.jax_cache``: the directory is part of each entry's key,
    so a path that moved between runs would never hit. The program's entry
    points (``chip_smoke.py``, the benchmark children, the examples) call
    this once at start; warm entry points then persist what they compile.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


@dataclasses.dataclass
class ILUFactorization:
    """Host-side factorization. With an ordering, ``a``/``pattern``/``vals``
    all describe the *permuted* system ``P A Pᵀ`` (the bit-compat contract
    is relative to that row order); ``solve`` handles the boundary."""

    a: CSRMatrix
    k: int
    pattern: ILUPattern
    vals: np.ndarray  # CSR-aligned filled values
    symbolic_seconds: float
    numeric_seconds: float
    # the row ordering the system was permuted with (None = natural);
    # solve() permutes b / unpermutes x so callers stay in original space
    ordering: Optional["Ordering"] = None
    # how M^{-1} applies: "sweep" (the exact triangular sweeps), "inverse"
    # (the level-truncated incomplete-inverse SpMV chain, DESIGN.md §Inverse),
    # or "auto" (cost-modeled; single-device resolves to sweep)
    precond_method: str = "sweep"
    # pivot-guard audit of this factor (core.guard.FactorHealth). None only
    # when the guard was bypassed; ``health.shift`` > 0 means ``a``/``vals``
    # describe the diagonally shifted system the ladder settled on, and
    # ``health.degraded`` routes ``precond()`` to the identity fallback.
    health: Optional["FactorHealth"] = None
    # lazily built apply engines, keyed by method — the plan
    # + compiled apply are built once and reused across every
    # solve/restart/RHS batch against this factorization
    _preconds: dict = dataclasses.field(default_factory=dict, repr=False, compare=False)

    def lu_matrices(self):
        return split_lu(self.pattern, self.vals)

    def precond(self, method: Optional[str] = None):
        """The cached device-resident M^{-1} apply: ``PrecondApply`` for the
        sweep method, ``InversePrecondApply`` for the inverse chain.
        ``method`` defaults to the factorization's ``precond_method``."""
        from .inverse import resolve_precond_method

        if self.health is not None and self.health.degraded:
            # last rung of the fallback chain: sweeping a broken factor
            # would inject NaN into every iterate, so M^{-1} = I
            from .guard import IdentityPrecondApply

            return self._preconds.setdefault("identity", IdentityPrecondApply())

        method = resolve_precond_method(
            method if method is not None else self.precond_method,
            self.pattern, n_devices=1)
        if method not in self._preconds:
            if method == "inverse":
                from .inverse import InversePrecondApply

                self._preconds[method] = InversePrecondApply(self.pattern, self.vals)
            else:
                from .triangular import PrecondApply

                self._preconds[method] = PrecondApply(self.pattern, self.vals)
        return self._preconds[method]

    def solve(self, b: np.ndarray) -> np.ndarray:
        """Apply the preconditioner: solve L y = b, then U x = y.

        Batched input (batch, n) is vmapped through the same cached plan.
        With an ordering, ``b`` is permuted in and ``x`` un-permuted out
        (pure gathers), so the caller stays in original row order."""
        apply = self.precond()
        b = np.asarray(b, np.float32)
        if self.ordering is not None:
            b = self.ordering.permute_vector(b)
        if np.ndim(b) == 2:
            out = np.asarray(apply.batched(b))
        else:
            out = np.asarray(apply(b))
        if self.ordering is not None:
            out = self.ordering.unpermute_vector(out)
        return out

    @property
    def nnz(self) -> int:
        return self.pattern.nnz


def _symbolic(a: CSRMatrix, k: int, rule: str):
    with obs.span("ilu:plan.symbolic"):
        if k == 1:
            return pilu1_symbolic(a, rule=rule)  # PILU(1), paper §IV-F
        return symbolic_ilu_k(a, k, rule=rule)


def _resolve_ordering(a: CSRMatrix, ordering, n_devices: int, band_rows: int):
    """Resolve ``ordering=`` and return ``(system, Ordering-or-None)``.

    The permuted matrix is cached on ``a`` (``ordering.permuted_system``),
    so repeated calls with one ordering reuse one matrix object — and with
    it every plan/engine cache hanging off it."""
    from .ordering import make_ordering, permuted_system

    ord_ = make_ordering(a, ordering, n_devices=n_devices, band_rows=band_rows)
    if ord_ is None:
        return a, None
    return permuted_system(a, ord_), ord_


def ilu_sharded(
    a: CSRMatrix,
    k: int,
    rule: str = "sum",
    band_rows: int = 32,
    mesh=None,
    broadcast: str = "psum",
    ordering=None,
    precond_method: str = "sweep",
    on_breakdown: str = "raise",
    pivot_tol: Optional[float] = None,
    shift0: Optional[float] = None,
    max_shifts: Optional[int] = None,
):
    """Distributed factorization whose output **stays sharded on the mesh**
    (``repro.core.top_ilu.ShardedILUFactorization``): each device holds only
    its bands' factor values, the preconditioner applies in place, and
    ``values_csr()`` gathers to the host only on explicit request. Bitwise
    contract identical to every other backend. ``mesh=None`` builds a 1-D
    band mesh over all available devices. ``ordering=`` permutes the system
    once at plan time (``"fusion"`` targets this mesh's band ownership, so
    sweep epochs fuse — see ``repro.core.ordering``); the sharded factors
    then equal sequential ILU(k) of the permuted matrix bitwise, and
    ``solve`` un/permutes at the boundary.

    ``on_breakdown`` selects the pivot-guard policy (``core.guard``): every
    factorization is audited on-device (a pure read — guarded factors are
    bitwise identical to unguarded ones); on a breakdown the shift ladder
    refactors ``A + α·diag(‖row‖₁)`` through the *same* cached engines (the
    shifted matrix shares A's structure, so a rung is a value re-scatter,
    not a compile), and each shifted factor is bitwise-anchored to the
    sequential oracle of the shifted matrix."""
    from .guard import audit_sharded, run_ladder
    from .top_ilu import band_mesh, topilu_factor_sharded

    mesh = band_mesh(mesh)
    a, ord_ = _resolve_ordering(a, ordering, int(mesh.devices.size), band_rows)
    t0 = time.perf_counter()
    pattern = _symbolic(a, k, rule)
    t1 = time.perf_counter()

    def factor(mat):
        f = topilu_factor_sharded(mat, pattern, band_rows=band_rows,
                                  mesh=mesh, broadcast=broadcast)
        f.loc_vals.block_until_ready()
        return f

    _sysmat, fact, health = run_ladder(
        a, factor, lambda f: audit_sharded(f, pivot_tol), on_breakdown,
        shift0=shift0, max_shifts=max_shifts)
    fact.symbolic_seconds = t1 - t0
    fact.numeric_seconds = time.perf_counter() - t1
    fact.ordering = ord_
    fact.precond_method = precond_method
    fact.health = health
    return fact


def ilu(
    a: CSRMatrix,
    k: int,
    rule: str = "sum",
    backend: str = "jax",
    band_rows: int = 32,
    mesh=None,
    broadcast: str = "psum",
    ordering=None,
    precond_method: str = "sweep",
    on_breakdown: str = "raise",
    pivot_tol: Optional[float] = None,
    shift0: Optional[float] = None,
    max_shifts: Optional[int] = None,
) -> ILUFactorization:
    """``on_breakdown`` (``"raise"|"shift"|"fallback"|"ignore"``) is the
    pivot-guard policy — see ``core.guard`` and :func:`ilu_sharded`. The
    audit is a pure read of the finished factor, so a healthy factorization
    is bitwise unaffected by the guard; when the ladder engages, the
    returned factorization's ``a``/``vals`` describe the *shifted* system
    (``health.shift`` records α) and stay bitwise-anchored to the shifted
    matrix's sequential oracle."""
    if backend == "topilu":
        from .top_ilu import band_mesh

        mesh = band_mesh(mesh)
        n_dev = int(mesh.devices.size)
    else:
        n_dev = 1
    a, ord_ = _resolve_ordering(a, ordering, n_dev, band_rows)
    t0 = time.perf_counter()
    pattern = _symbolic(a, k, rule)
    t1 = time.perf_counter()

    # one numeric closure per backend: the ladder refactors shifted matrices
    # through it, and because the shifted matrix shares a's structure caches
    # (FactorPlan / TOP-ILU engine stores ride along by reference in
    # guard.shifted_matrix) a ladder rung re-executes without re-planning
    def numeric(mat):
        if backend == "oracle":
            return np.asarray(numeric_ilu_ref(mat, pattern), np.float32)
        if backend == "jax":
            from .factor_plan import factor_plan_for

            # plan + compiled engine are memoized on the matrix (FactorPlan);
            # repeated/updated-value factorizations skip planning and compile
            return np.asarray(factor_plan_for(mat, pattern).factorize(mat),
                              np.float32)
        if backend == "topilu":
            from .top_ilu import topilu_numeric

            return np.asarray(
                topilu_numeric(mat, pattern, band_rows=band_rows, mesh=mesh,
                               broadcast=broadcast), np.float32)
        raise ValueError(f"unknown backend {backend!r}")

    from .guard import audit_values, run_ladder

    sysmat, vals, health = run_ladder(
        a, numeric, lambda v: audit_values(pattern, v, pivot_tol),
        on_breakdown, shift0=shift0, max_shifts=max_shifts)
    t2 = time.perf_counter()
    return ILUFactorization(
        a=sysmat, k=k, pattern=pattern, vals=vals,
        symbolic_seconds=t1 - t0, numeric_seconds=t2 - t1, ordering=ord_,
        precond_method=precond_method, health=health,
    )
