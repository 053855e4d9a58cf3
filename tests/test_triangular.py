"""Triangular solves: exact substitution vs scipy, Jacobi variant."""
import numpy as np
import pytest
import scipy.sparse.linalg as spla

from repro.core import matgen, numeric_ilu_ref, poisson_2d, split_lu, symbolic_ilu_k
from repro.core.sparse import CSRMatrix
from repro.core.triangular import (
    PrecondApply,
    build_triangular_plan,
    level_groups,
    make_jacobi_triangular_solver,
    make_triangular_solver,
)


def _setup(n=80, k=1, seed=0):
    a = matgen(n, density=0.07, seed=seed)
    pat = symbolic_ilu_k(a, k)
    vals = numeric_ilu_ref(a, pat)
    return a, pat, vals


@pytest.mark.parametrize("k", [0, 1, 2])
def test_solve_matches_scipy(k):
    a, pat, vals = _setup(k=k)
    L, U = split_lu(pat, vals)
    rng = np.random.default_rng(1)
    b = rng.standard_normal(a.n).astype(np.float32)
    want = spla.spsolve_triangular(
        U.tocsr(), spla.spsolve_triangular(L.tocsr(), b, lower=True), lower=False
    )
    solve = make_triangular_solver(pat, vals)
    got = np.asarray(solve(b))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_solve_poisson():
    a = poisson_2d(8)
    pat = symbolic_ilu_k(a, 1)
    vals = numeric_ilu_ref(a, pat)
    L, U = split_lu(pat, vals)
    b = np.ones(a.n, np.float32)
    want = spla.spsolve_triangular(
        U.tocsr(), spla.spsolve_triangular(L.tocsr(), b, lower=True), lower=False
    )
    got = np.asarray(make_triangular_solver(pat, vals)(b))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_wavefront_schedule_is_valid():
    """Every row appears exactly once; dependencies respect level order."""
    _, pat, vals = _setup(k=2)
    plan = build_triangular_plan(pat, vals)
    n = plan.n
    seen = plan.l_levels[plan.l_levels < n]
    assert sorted(seen.tolist()) == list(range(n))
    level_of = np.zeros(n, np.int64)
    for l in range(plan.l_levels.shape[0]):
        for r in plan.l_levels[l]:
            if r < n:
                level_of[r] = l
    for j in range(n):
        deps = plan.l_cols[j][plan.l_cols[j] < n]
        assert np.all(level_of[deps] < level_of[j])


def test_wavefront_levels_match_sequential_recursion():
    """The vectorized Kahn frontier must reproduce the classical
    ``level[j] = 1 + max(level[deps])`` recursion exactly."""
    _, pat, vals = _setup(n=90, k=1, seed=3)
    plan = build_triangular_plan(pat, vals)
    n = plan.n
    for cols, levels, reverse in ((plan.l_cols, plan.l_levels, False),
                                  (plan.u_cols, plan.u_levels, True)):
        level = np.zeros(n, np.int64)
        order = range(n - 1, -1, -1) if reverse else range(n)
        for j in order:
            deps = cols[j][cols[j] < n]
            level[j] = 1 + max((level[i] for i in deps), default=-1)
        nlev = int(level.max()) + 1
        assert levels.shape[0] == nlev
        for l in range(nlev):
            want = np.nonzero(level == l)[0]
            got = levels[l][levels[l] < n]
            np.testing.assert_array_equal(np.sort(got), want)


def _substitute(pat, vals, b):
    """Pure NumPy float32 row-by-row substitution in exact sequential order
    (lane order within each row, matching ``masked_lane_sum``); it shares
    no code with the device implementations."""
    n = pat.n
    f32 = np.float32
    y = np.zeros(n, f32)
    x = np.zeros(n, f32)
    # forward sweep L y = b (unit diagonal), rows in order
    for j in range(n):
        s, e = pat.indptr[j], pat.indptr[j + 1]
        d = pat.diag_ptr[j]
        acc = f32(0.0)
        for c, v in zip(pat.indices[s:s + d], vals[s:s + d]):
            acc = f32(acc + f32(f32(v) * y[c]))
        y[j] = f32(b[j] - acc)
    # backward sweep U x = y, rows in reverse order
    for j in range(n - 1, -1, -1):
        s, e = pat.indptr[j], pat.indptr[j + 1]
        d = pat.diag_ptr[j]
        acc = f32(0.0)
        for c, v in zip(pat.indices[s + d + 1:e], vals[s + d + 1:e]):
            acc = f32(acc + f32(f32(v) * x[c]))
        x[j] = f32(f32(y[j] - acc) / f32(vals[s + d]))
    return x


def test_solver_bitwise_vs_sequential_numpy_substitution():
    """Independent oracle for the paper's bit-compatibility claim: the
    sequential substitution (``_substitute``) must agree *bitwise* with
    both the jnp reference solver and the fused apply."""
    for seed, k in ((0, 1), (2, 2)):
        a, pat, vals = _setup(n=72, k=k, seed=seed)
        b = np.random.default_rng(seed + 10).standard_normal(a.n).astype(np.float32)
        x = _substitute(pat, vals, b)
        for solver in (make_triangular_solver(pat, vals), PrecondApply(pat, vals)):
            got = np.asarray(solver(b))
            np.testing.assert_array_equal(got.view(np.int32), x.view(np.int32))


def _wide_first_level():
    """A random matrix whose first L level (the rows with no L entries) is
    over twice as wide as the next: its sweep has a dependency-free group."""
    return matgen(200, density=0.03, seed=1)


def _chains(m=6, p=9):
    """``m`` independent tridiagonal chains of ``p`` rows: every level of
    both sweeps holds ``m`` rows of one entry (a flat profile)."""
    rows, cols = [], []
    for i in range(m * p):
        for j in (i - 1, i, i + 1):
            if 0 <= j < m * p and j // p == i // p:
                rows.append(i)
                cols.append(j)
    indptr = np.searchsorted(rows, np.arange(m * p + 1)).astype(np.int64)
    data = np.where(np.asarray(rows) == np.asarray(cols), 4.0, -1.0).astype(np.float32)
    return CSRMatrix(n=m * p, indptr=indptr, indices=np.asarray(cols, np.int32), data=data)


@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("matrix", ["matgen-wide", "poisson"])
def test_grouped_apply_bitwise_vs_sequential_substitution(matrix, k, batched):
    """The grouped level-major apply, single and ``vmap``-batched, equals
    the sequential substitution bit for bit: on a random matrix with a
    wide, dependency-free first level and on a 2-D Poisson grid."""
    a = _wide_first_level() if matrix == "matgen-wide" else poisson_2d(12)
    pat = symbolic_ilu_k(a, k)
    vals = numeric_ilu_ref(a, pat)
    apply = PrecondApply(pat, vals)
    assert max(apply.plan.sweep_groups) > 1
    if matrix == "matgen-wide":
        assert apply.plan.lower.cols[0].shape[2] == 0  # the first level reads nothing
    B = np.random.default_rng(k).standard_normal((3, a.n)).astype(np.float32)
    got = np.asarray(apply.batched(B)) if batched else np.stack([np.asarray(apply(b)) for b in B])
    for i, b in enumerate(B):
        x = _substitute(pat, vals, b)
        np.testing.assert_array_equal(got[i].view(np.int32), x.view(np.int32))


def test_level_groups_cut_runs_of_like_width():
    assert level_groups([100, 40, 30, 35, 10, 9, 2, 1]) == [(0, 1), (1, 4), (4, 6), (6, 8)]
    assert level_groups([1, 2, 4, 8]) == [(0, 4)]  # a ramp doubling at most: one run
    assert level_groups([1, 2, 4, 9]) == [(0, 3), (3, 4)]
    assert level_groups([5] * 7) == [(0, 7)]
    assert level_groups([]) == []


def _group_spans(sweep):
    lo = 0
    for c in sweep.cols:
        yield lo, lo + c.shape[0], c.shape[1], c.shape[2]
        lo += c.shape[0]


@pytest.mark.parametrize("k", [1, 2])
def test_grouped_layout_fits_every_level(k):
    """On a skewed profile the sweep splits into several groups; each
    group's rows and lanes are exactly those of its widest level (two at
    least) and its longest row, every row has one slot, and an apply
    gathers no more lanes than the single-group layout (every level padded
    to the widest level and the longest row)."""
    a = _wide_first_level()
    pat = symbolic_ilu_k(a, k)
    plan = build_triangular_plan(pat, numeric_ilu_ref(a, pat))
    n = plan.n
    assert plan.sweep_groups[0] > 1
    single = 0
    for sweep, levels, cols in ((plan.lower, plan.l_levels, plan.l_cols),
                                (plan.upper, plan.u_levels, plan.u_cols)):
        counts = (levels < n).sum(axis=1)
        lens = (cols < n).sum(axis=1)
        spans = list(_group_spans(sweep))
        assert spans[-1][1] == levels.shape[0]
        for lo, hi, rows, width in spans:
            assert max(counts[lo:hi].max(), 2) == rows
            assert lens[levels[lo:hi][levels[lo:hi] < n]].max(initial=0) == width
        single += levels.shape[0] * levels.shape[1] * max(int(lens.max()), 1)
        assert sweep.n_slots == sum(c.shape[0] * c.shape[1] for c in sweep.cols)
    assert len(np.unique(plan.u_out_perm)) == n
    assert plan.factor_entries <= plan.lanes_per_apply <= single


def test_flat_profile_keeps_one_group():
    a = _chains()
    pat = symbolic_ilu_k(a, 1)
    vals = numeric_ilu_ref(a, pat)
    plan = build_triangular_plan(pat, vals)
    assert plan.sweep_groups == (1, 1)
    assert [c.shape for c in plan.lower.cols] == [(9, 6, 1)]
    b = np.random.default_rng(3).standard_normal(a.n).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(PrecondApply(pat, vals, plan=plan)(b)).view(np.int32),
                                  _substitute(pat, vals, b).view(np.int32))


def test_precond_apply_batched_bitwise():
    """vmap-ed applies must agree bitwise with one-at-a-time applies."""
    a, pat, vals = _setup(n=70, k=1, seed=4)
    apply = PrecondApply(pat, vals)
    B = np.random.default_rng(5).standard_normal((4, a.n)).astype(np.float32)
    got = np.asarray(apply.batched(B))
    want = np.stack([np.asarray(apply(B[i])) for i in range(4)])
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


def test_precond_apply_warm_aot_bitwise():
    """AOT warmup must be behavior-invariant: warmed (bucketed) applies
    return exactly the bits of the unwarmed path, for the single-RHS shape
    and for a ragged batch padded up to a warmed bucket."""
    a, pat, vals = _setup(n=70, k=1, seed=6)
    apply = PrecondApply(pat, vals)
    b = np.random.default_rng(7).standard_normal(a.n).astype(np.float32)
    B = np.random.default_rng(8).standard_normal((3, a.n)).astype(np.float32)
    want1 = np.asarray(apply(b))
    wantB = np.asarray(apply.batched(B))
    secs = apply.warm((1, 4))
    assert set(secs) == {1, 4} and set(apply._aot) == {1, 4}
    got1 = np.asarray(apply(b))  # AOT executable
    gotB = np.asarray(apply.batched(B))  # ragged 3 -> bucket 4, sliced back
    np.testing.assert_array_equal(got1.view(np.int32), want1.view(np.int32))
    np.testing.assert_array_equal(gotB.view(np.int32), wantB.view(np.int32))
    assert gotB.shape == (3, a.n)
    # warming again is free (executables cached)
    assert apply.warm((4,))[4] < 0.5


def test_jacobi_converges_to_exact():
    a, pat, vals = _setup(k=1)
    b = np.random.default_rng(2).standard_normal(a.n).astype(np.float32)
    exact = np.asarray(make_triangular_solver(pat, vals)(b))
    plan = build_triangular_plan(pat, vals)
    depth = plan.l_levels.shape[0] + plan.u_levels.shape[0]
    approx = np.asarray(make_jacobi_triangular_solver(pat, vals, sweeps=depth + 2)(b))
    np.testing.assert_allclose(approx, exact, rtol=1e-4, atol=1e-4)
