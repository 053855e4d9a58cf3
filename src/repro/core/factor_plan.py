"""FactorPlan — the plan→compile→execute pipeline for numeric ILU(k).

This is the factorization-side twin of ``TriangularPlan``/``PrecondApply``
(PR 1): one host-side *plan* object per (matrix structure, k) that owns

* the **schedule**: pivot-op wavefronts from the shared vectorized Kahn
  scheduler (:func:`repro.core.planner.wavefront_schedule`). The unit is a
  single pivot application (one lower-pattern entry (j, i): divide by the
  pivot, subtract the scaled pivot-row tail); op (j, p) waits on the
  previous pivot of the same row and on the *last* op of its pivot row.
  Every round therefore executes at most one op per row, all on distinct
  independent rows — exact sizes, no dense (rows × pivots) padding, which
  is what keeps heavily-filled patterns (where max-pivots-per-row and
  rows-per-level both skew badly) from exploding the padded schedule.
* the **gathers**: a flat per-op destination-lane map
  (:func:`repro.core.planner.pivot_dst_flat`) so applying a pivot is two
  row gathers + one lane scatter — no ``searchsorted`` on device, and
  O(nnz(L)·W) plan memory total.
* the **engines**: compiled factorizer executables cached on the plan the
  way ``PrecondApply`` caches the triangular sweep — build once, reuse
  across refactorizations of the same structure (the serving pattern:
  values change, pattern does not).

Bit-compatibility contract (paper §VI): the chain edges force each row's
pivots into ascending column order, each op is an f32 divide then a
barriered multiply-then-subtract — exactly the oracle's arithmetic
(:func:`repro.core.numeric_ref.numeric_ilu_ref`). The wavefront schedule
only reorders ops that share no data (different rows, finalized pivot
rows), where no floating-point op can observe the difference, so the
factor values equal the oracle's bitwise.

Under a row reordering (``repro.core.ordering``) the plan is simply built
for the permuted matrix — the contract, the schedule, and the caches are
all relative to the matrix object handed in, so an ordered pipeline reuses
this module unchanged (the permuted matrix carries its own plan cache).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .. import obs
from .planner import (
    ell_from_pattern,
    pivot_dst_flat,
    wavefront_schedule,
)
from .sparse import CSRMatrix, ILUPattern


@dataclasses.dataclass
class FactorPlan:
    """Round-major pivot-op schedule + cached engines.

    Shapes: ``NR`` rounds, ``MO`` ops per round (padded), ``W`` ELL width,
    ``n_ops = nnz(L)`` total pivot applications. Row id ``n`` is the
    scratch row; dst-map row ``n_ops`` is the all-dropped pad op.
    """

    n: int
    width: int  # W
    k: int
    n_ops: int
    n_rounds: int  # NR
    max_ops: int  # MO

    op_row: np.ndarray  # (NR, MO) int32 — reduced row j (n = pad)
    op_lane: np.ndarray  # (NR, MO) int32 — pivot lane p inside row j
    op_piv: np.ndarray  # (NR, MO) int32 — pivot row i (n = pad)
    op_dlane: np.ndarray  # (NR, MO) int32 — diagonal lane of row i
    op_dst: np.ndarray  # (NR, MO) int32 — row of dst_flat (n_ops = pad)
    dst_flat: np.ndarray  # (n_ops+1, W) int32 in [0, W]; W = dropped lane

    a_vals: np.ndarray  # (n+1, W) f32 — A on the pattern + zero scratch row
    cols: np.ndarray  # (n, W) int32 sentinel-padded (structure, host-side)
    row_len: np.ndarray  # (n,) int32
    a_scatter_lane: np.ndarray  # (a.nnz,) lane of each A entry (refactorize)
    csr_row: np.ndarray  # (pattern.nnz,) int64 — CSR flatten gather rows
    csr_lane: np.ndarray  # (pattern.nnz,) int64 — CSR flatten gather lanes

    # the compiled executable — built once, reused across refactorizations
    # of the same structure (see .engine())
    _engine: Optional[object] = dataclasses.field(default=None, repr=False, compare=False)
    _device_arrays: Optional[dict] = dataclasses.field(default=None, repr=False, compare=False)

    @property
    def depth(self) -> int:
        return self.n_rounds

    def device_arrays(self) -> dict:
        """The jnp schedule arrays the factor sweep consumes (cached)."""
        if self._device_arrays is None:
            import jax.numpy as jnp

            self._device_arrays = {
                "op_row": jnp.asarray(self.op_row),
                "op_lane": jnp.asarray(self.op_lane),
                "op_piv": jnp.asarray(self.op_piv),
                "op_dlane": jnp.asarray(self.op_dlane),
                "op_dst": jnp.asarray(self.op_dst),
                "dst_flat": jnp.asarray(self.dst_flat),
            }
        return self._device_arrays

    def engine(self):
        """Cached compiled factorizer: ``(n+1, W) A-values -> (n, W) factors``."""
        if self._engine is None:
            from .numeric_jax import make_wavefront_factorizer

            self._engine = make_wavefront_factorizer(self)
        return self._engine

    # -- host-side conveniences -------------------------------------------
    def scatter_values(self, a: CSRMatrix) -> np.ndarray:
        """New A values (same structure) -> (n+1, W) engine input."""
        vals = np.zeros_like(self.a_vals)
        rowlen = np.diff(a.indptr)
        row_of = np.repeat(np.arange(a.n, dtype=np.int64), rowlen)
        vals[row_of, self.a_scatter_lane] = a.data
        return vals

    def values_to_csr(self, vals_ell: np.ndarray) -> np.ndarray:
        """(n, W) padded factor values -> CSR-aligned flat values."""
        return np.asarray(vals_ell)[self.csr_row, self.csr_lane].astype(np.float32)

    def factorize(self, a: Optional[CSRMatrix] = None) -> np.ndarray:
        """Run the cached engine; returns CSR-aligned f32 factor values.

        ``a=None`` reuses the values captured at plan build; passing a new
        matrix with the same structure refactorizes without replanning.
        Each host step is an ``ilu:push.*`` span (``repro.obs``): the value
        scatter, the factorization up to its output being ready, the fetch
        to the host and the CSR gather.
        """
        with obs.span("ilu:push.scatter"):
            vals_in = self.a_vals if a is None else self.scatter_values(a)
        with obs.span("ilu:push.factorize"):
            out = self.engine()(vals_in).block_until_ready()
        with obs.span("ilu:push.fetch"):
            out = np.asarray(out)
        with obs.span("ilu:push.to_csr"):
            return self.values_to_csr(out)


def build_factor_plan(a: CSRMatrix, pattern: ILUPattern) -> FactorPlan:
    """Vectorized host planning: pattern -> round-major pivot-op schedule
    (the ``ilu:plan.factor`` span)."""
    with obs.span("ilu:plan.factor"):
        return _build_factor_plan(a, pattern)


def _build_factor_plan(a: CSRMatrix, pattern: ILUPattern) -> FactorPlan:
    n = pattern.n
    cols, vals, diag_pos, row_len, a_lane = ell_from_pattern(pattern, a, max(n, 1))
    W = cols.shape[1]

    # the pivot ops, in row-major ascending order = the lower pattern entries
    row_of = np.repeat(np.arange(n, dtype=np.int64), np.diff(pattern.indptr))
    pos = np.arange(pattern.nnz, dtype=np.int64) - pattern.indptr[row_of]
    lmask = pos < pattern.diag_ptr[row_of]
    o_row = row_of[lmask]  # reduced row j
    o_lane = pos[lmask]  # pivot lane p (== position among lower entries)
    o_piv = pattern.indices[lmask].astype(np.int64)  # pivot row i
    n_ops = int(o_row.size)
    npv = pattern.diag_ptr.astype(np.int64)  # ops per row
    op_start = np.zeros(n, np.int64)
    np.cumsum(npv[:-1], out=op_start[1:])

    # op DAG: (j,p) waits on (j,p-1) and on the last op of pivot row i
    opid = np.arange(n_ops, dtype=np.int64)
    chain = o_lane > 0
    cross = npv[o_piv] > 0
    src = np.concatenate([opid[chain] - 1, (op_start[o_piv] + npv[o_piv] - 1)[cross]])
    dst = np.concatenate([opid[chain], opid[cross]])
    sched = wavefront_schedule(src, dst, n_ops)  # (NR, MO), n_ops-padded
    NR, MO = sched.shape

    dst_flat = pivot_dst_flat(cols[:n], o_row, o_piv)  # (n_ops+1, W)

    pad = sched >= n_ops
    sid = np.minimum(sched, max(n_ops - 1, 0)).astype(np.int64)
    op_row = np.where(pad, n, o_row[sid]).astype(np.int32)
    op_lane = np.where(pad, 0, o_lane[sid]).astype(np.int32)
    op_piv = np.where(pad, n, o_piv[sid]).astype(np.int32)
    op_dlane = np.where(pad, 0, diag_pos[np.minimum(o_piv[sid], n - 1)]).astype(np.int32)
    op_dst = np.where(pad, n_ops, sid).astype(np.int32)

    a_vals = np.zeros((n + 1, W), dtype=np.float32)
    a_vals[:n] = vals[:n]

    rowlen = np.diff(pattern.indptr).astype(np.int64)
    csr_row = np.repeat(np.arange(n, dtype=np.int64), rowlen)
    csr_lane = np.arange(pattern.nnz, dtype=np.int64) - pattern.indptr[csr_row]

    return FactorPlan(
        n=n, width=W, k=pattern.k,
        n_ops=n_ops, n_rounds=NR, max_ops=MO,
        op_row=op_row, op_lane=op_lane, op_piv=op_piv,
        op_dlane=op_dlane, op_dst=op_dst, dst_flat=dst_flat,
        a_vals=a_vals, cols=cols[:n], row_len=row_len[:n],
        a_scatter_lane=a_lane, csr_row=csr_row, csr_lane=csr_lane,
    )


def _pattern_fingerprint(pattern: ILUPattern) -> tuple:
    """Content key for plan caching: two patterns with the same structure
    and levels produce the same plan, regardless of object identity (the
    public ``ilu()`` path builds a fresh pattern per call)."""
    import hashlib

    h = hashlib.sha1()
    h.update(pattern.indptr.tobytes())
    h.update(pattern.indices.tobytes())
    h.update(pattern.levels.tobytes())
    return (pattern.k, pattern.nnz, h.hexdigest())


def factor_plan_for(a: CSRMatrix, pattern: ILUPattern) -> FactorPlan:
    """Memoized :func:`build_factor_plan`: the plan (and its compiled
    engines) is cached on the matrix object, keyed by the pattern's
    *content* — repeated ``ilu()`` calls on the same matrix (each of which
    builds an equal-but-distinct pattern object) hit one plan and one
    compiled engine. Same lifetime rule as the solver-engine caches (dies
    with the matrix, so a stream of different matrices cannot grow device
    memory); entries per matrix are bounded by the distinct (k, rule)
    combinations used."""
    try:
        store = a.__dict__.setdefault("_factor_plans", {})
    except AttributeError:  # exotic container without __dict__
        return build_factor_plan(a, pattern)
    key = _pattern_fingerprint(pattern)
    plan = store.get(key)
    if plan is None:
        plan = store[key] = build_factor_plan(a, pattern)
    return plan
