"""Preconditioned solvers: convergence + the paper's k-vs-iterations story."""
import numpy as np
import pytest

from repro.core import matgen, poisson_2d
from repro.core.solvers import solve_with_ilu


def _rhs(n, seed=0):
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def _check_residual(a, res, b, tol=5e-4):
    ax = a.to_scipy() @ res.x
    rel = np.linalg.norm(ax - b) / np.linalg.norm(b)
    assert rel < tol, f"relative residual {rel}"


def test_gmres_with_ilu1_converges():
    a = matgen(200, density=0.03, seed=1)
    b = _rhs(a.n)
    res, fact = solve_with_ilu(a, b, k=1, method="gmres", tol=1e-5)
    assert res.converged
    _check_residual(a, res, b)
    assert fact.nnz >= a.nnz


def test_bicgstab_with_ilu1_converges():
    a = matgen(200, density=0.03, seed=2)
    b = _rhs(a.n, 3)
    res, _ = solve_with_ilu(a, b, k=1, method="bicgstab", tol=1e-5)
    assert res.converged
    _check_residual(a, res, b)


def test_cg_poisson_ilu_reduces_iterations():
    """The reason preconditioning exists: fewer iterations with ILU."""
    a = poisson_2d(16)
    b = _rhs(a.n, 4)
    plain, _ = solve_with_ilu(a, b, k=None, method="cg", tol=1e-5, maxiter=2000)
    pre, _ = solve_with_ilu(a, b, k=1, method="cg", tol=1e-5, maxiter=2000)
    assert pre.converged
    assert pre.iterations < plain.iterations, (pre.iterations, plain.iterations)


def test_higher_k_not_worse():
    """Paper SV-B: larger k => better preconditioner (<= iterations)."""
    a = poisson_2d(14)
    b = _rhs(a.n, 5)
    it = {}
    for k in (0, 2):
        res, _ = solve_with_ilu(a, b, k=k, method="cg", tol=1e-6, maxiter=2000)
        assert res.converged
        it[k] = res.iterations
    assert it[2] <= it[0], it


def test_bicgstab_parallel_factorization_same_convergence():
    """Bit-compatibility corollary: solver behaviour is identical when the
    preconditioner is computed by the banded parallel engine."""
    a = matgen(150, density=0.04, seed=6)
    b = _rhs(a.n, 7)
    r_seq, _ = solve_with_ilu(a, b, k=1, method="bicgstab", backend="oracle")
    r_par, _ = solve_with_ilu(a, b, k=1, method="bicgstab", backend="jax")
    assert r_seq.iterations == r_par.iterations
    np.testing.assert_array_equal(r_seq.x, r_par.x)


def test_csr_to_ell_vectorized_matches_row_loop():
    from repro.core.planner import COL_SENTINEL
    from repro.core.solvers import csr_to_ell_arrays

    a = matgen(90, density=0.06, seed=20)
    cols, vals = csr_to_ell_arrays(a)
    cols, vals = np.asarray(cols), np.asarray(vals)
    lens = np.diff(a.indptr)
    W = int(lens.max())
    want_c = np.full((a.n, W), COL_SENTINEL, np.int32)
    want_v = np.zeros((a.n, W), np.float32)
    for j in range(a.n):
        c, v = a.row(j)
        want_c[j, : len(c)] = c
        want_v[j, : len(v)] = v
    np.testing.assert_array_equal(cols, want_c)
    np.testing.assert_array_equal(vals, want_v)


def test_residual_history_recorded_per_iteration():
    """cg/bicgstab record one relative residual per iteration inside the
    device loop (the paper's Fig-5 style convergence curves)."""
    a = poisson_2d(12)
    b = _rhs(a.n, 8)
    for method in ("cg", "bicgstab"):
        res, _ = solve_with_ilu(a, b, k=1, method=method, tol=1e-5, maxiter=500)
        assert res.converged
        assert len(res.history) == res.iterations
        assert res.history[-1] == pytest.approx(res.residual, rel=1e-3)
        # preconditioned convergence should show an overall downward trend
        assert res.history[-1] < res.history[0]


def test_gmres_history_per_restart():
    a = matgen(200, density=0.03, seed=9)
    b = _rhs(a.n, 10)
    res, _ = solve_with_ilu(a, b, k=1, method="gmres", restart=10, maxiter=30)
    assert res.converged
    assert 1 <= len(res.history) <= 30
    assert res.history[-1] == pytest.approx(res.residual, rel=1e-3)


def test_gmres_batched_multi_rhs():
    """One factorization + one dispatch serves a stack of right-hand sides."""
    a = matgen(150, density=0.05, seed=11)
    B = np.stack([_rhs(a.n, s) for s in (1, 2, 3)])
    results, fact = solve_with_ilu(a, B, k=1, method="gmres", tol=1e-5)
    assert len(results) == 3
    A = a.to_scipy()
    for i, r in enumerate(results):
        assert r.converged
        rel = np.linalg.norm(A @ r.x - B[i]) / np.linalg.norm(B[i])
        assert rel < 5e-4
    # lanes match the single-RHS engine (same iteration counts, same answer
    # to solver tolerance)
    from repro.core.solvers import csr_to_ell_arrays, gmres, make_ell_matvec

    cols, vals = csr_to_ell_arrays(a)
    matvec = make_ell_matvec(cols, vals, a.n)
    single = gmres(matvec, B[0], fact.precond(), tol=1e-5)
    assert single.iterations == results[0].iterations
    np.testing.assert_allclose(results[0].x, single.x, rtol=1e-4, atol=1e-5)


def test_batched_rejects_non_gmres():
    a = matgen(60, density=0.08, seed=12)
    B = np.stack([_rhs(a.n, 1), _rhs(a.n, 2)])
    with pytest.raises(ValueError):
        solve_with_ilu(a, B, k=1, method="cg")


def test_batch_buckets_env(monkeypatch):
    """Serving batch buckets: env-configurable, ragged sizes round up, and
    batches beyond every bucket keep their exact size."""
    from repro.core.solvers import batch_buckets, bucket_batch

    monkeypatch.delenv("REPRO_BATCH_BUCKETS", raising=False)
    assert batch_buckets() == (1, 2, 4, 8, 16, 32, 64)
    assert bucket_batch(1) == 1
    assert bucket_batch(3) == 4
    assert bucket_batch(33) == 64
    assert bucket_batch(100) == 100  # past the largest bucket: exact
    monkeypatch.setenv("REPRO_BATCH_BUCKETS", "2, 6")
    assert batch_buckets() == (2, 6)
    assert bucket_batch(3) == 6
    assert bucket_batch(7) == 7


def test_factorization_caches_precond_and_solver():
    """The triangular plan/compiled apply must be built once per
    factorization and reused across solves (the PR-1 plan-cache layer)."""
    from repro.core.api import ilu

    a = matgen(80, density=0.07, seed=13)
    fact = ilu(a, 1, backend="oracle")
    p1 = fact.precond()
    p2 = fact.precond()
    assert p1 is p2
    b = _rhs(a.n, 14)
    x1 = fact.solve(b)
    x2 = fact.solve(b)
    np.testing.assert_array_equal(x1, x2)
    # batched apply shares the same plan and matches single applies bitwise
    B = np.stack([b, _rhs(a.n, 15)])
    xb = fact.solve(B)
    np.testing.assert_array_equal(xb[0], x1)
