"""Hypothesis property tests on the system's invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import matgen, numeric_ilu_ref, pilu1_symbolic, symbolic_ilu_k
from repro.core.api import _symbolic, ilu
from repro.core.factor_plan import factor_plan_for
from repro.core.planner import make_plan
from repro.core.solvers import solve_with_ilu
from repro.serve import ServeEngine


matrices = st.builds(
    matgen,
    n=st.integers(min_value=8, max_value=72),
    density=st.floats(min_value=0.03, max_value=0.25),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)


@given(a=matrices, k=st.integers(min_value=0, max_value=3))
@settings(max_examples=25, deadline=None)
def test_pattern_invariants(a, k):
    pat = symbolic_ilu_k(a, k)
    pat.validate()
    # A's pattern is always contained, with level 0
    for j in range(a.n):
        acols, _ = a.row(j)
        pcols, plevs = pat.row(j)
        pos = np.searchsorted(pcols, acols)
        assert np.all(pcols[pos] == acols)
        assert np.all(plevs[pos] == 0)
    # levels bounded by k
    assert pat.levels.max(initial=0) <= k


@given(a=matrices)
@settings(max_examples=15, deadline=None)
def test_pilu1_always_equals_general(a):
    g = symbolic_ilu_k(a, 1)
    f = pilu1_symbolic(a)
    np.testing.assert_array_equal(g.indices, f.indices)
    np.testing.assert_array_equal(g.levels, f.levels)


@given(a=matrices, k=st.integers(min_value=0, max_value=2),
       band_rows=st.integers(min_value=1, max_value=24))
@settings(max_examples=12, deadline=None)
def test_bitcompat_any_banding(a, k, band_rows):
    """The central theorem: band decomposition never changes a single bit."""
    pat = symbolic_ilu_k(a, k)
    want = numeric_ilu_ref(a, pat)
    got = ilu(a, k, backend="jax", band_rows=band_rows).vals
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


@given(a=matrices, band_rows=st.integers(min_value=1, max_value=16),
       d=st.integers(min_value=1, max_value=6))
@settings(max_examples=15, deadline=None)
def test_planner_invariants(a, band_rows, d):
    pat = symbolic_ilu_k(a, 1)
    plan = make_plan(a, pat, band_rows=band_rows, n_devices=d)
    assert plan.n_bands % d == 0
    assert plan.n_pad == plan.n_bands * plan.band_rows
    assert plan.n_pad >= a.n
    # device-major permutation is a bijection
    x = np.arange(plan.n_pad, dtype=np.int64)
    rt = plan.rows_from_device_major(plan.rows_device_major(x))
    np.testing.assert_array_equal(rt, x)
    # pivot_start is monotone per row, bounded by diag
    assert np.all(np.diff(plan.pivot_start, axis=1) >= 0)
    assert np.all(plan.pivot_start[:, -1] <= plan.diag_pos)


@given(
    a=st.builds(matgen,
                n=st.integers(min_value=12, max_value=40),
                density=st.floats(min_value=0.06, max_value=0.2),
                seed=st.integers(min_value=0, max_value=2**31 - 1)),
    k=st.integers(min_value=0, max_value=2),
    method=st.sampled_from(["sweep", "inverse"]),
    nb=st.integers(min_value=2, max_value=4),
    pos=st.integers(min_value=0, max_value=3),
    data=st.data(),
)
@settings(max_examples=8, deadline=None)
def test_coalescing_never_changes_bits(a, k, method, nb, pos, data):
    """The serving theorem: coalescing a request into *any* batch — any
    bucket, any lane position, any neighbours, any mixed per-lane
    tolerances — returns bits identical to solving it alone."""
    pos = pos % nb
    seed = data.draw(st.integers(min_value=0, max_value=2**31 - 1),
                     label="rhs_seed")
    rng = np.random.default_rng(seed)
    pattern = _symbolic(a, k, "sum")
    v = np.asarray(factor_plan_for(a, pattern).factorize(a))
    eng = ServeEngine(a, pattern, v, restart=4, maxiter=30,
                      precond_method=method, buckets=(1, 2, 4))
    bind = eng.bind(a, v)

    b = rng.standard_normal(a.n).astype(np.float32)
    tol = 1e-5
    ref, _ = solve_with_ilu(a, b, k=k, tol=tol, restart=4, maxiter=30, precond_method=method)
    solo = eng.solve(bind, b[None, :], np.asarray([tol], np.float32))[0]
    np.testing.assert_array_equal(
        np.asarray(solo.x, np.float32).view(np.int32),
        np.asarray(ref.x, np.float32).view(np.int32),
        err_msg=f"solo serve lane != solve_with_ilu (k={k}, {method})")

    B = rng.standard_normal((nb, a.n)).astype(np.float32)
    tols = rng.choice(np.asarray([1e-4, 1e-5, 1e-6], np.float32), size=nb)
    B[pos] = b
    tols[pos] = tol
    lane = eng.solve(bind, B, tols.astype(np.float32))[pos]
    np.testing.assert_array_equal(
        np.asarray(lane.x, np.float32).view(np.int32),
        np.asarray(solo.x, np.float32).view(np.int32),
        err_msg=(f"lane {pos} of a {nb}-request batch (bucket "
                 f"{eng.bucket_for(nb)}) != solo (k={k}, {method})"))
    assert lane.iterations == solo.iterations
