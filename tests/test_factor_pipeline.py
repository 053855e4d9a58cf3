"""Plan→compile→execute factorization pipeline: bitwise regression suite.

The PR-2/PR-3 tentpole contract: every engine emitted from the
factorization plans — the single-device wavefront engine
(``backend="jax"``), the *sharded-value* band superstep TOP-ILU engine on
1, 2 or 4 devices — produces float32 factor values **exactly equal**
(int32 view) to the sequential oracle ``numeric_ilu_ref``, for both level
rules, across band sizes, while each device stores only its band-local
values + halo; the distributed precond/solve path matches the
single-device path bitwise; and the vectorized symbolic frontier equals
the per-row reference pattern-for-pattern. Multi-device cases run in
subprocesses (JAX locks the host device count at first init).
"""
import os
import sys

import numpy as np
import pytest

from subproc import run_checked

from repro.core import (
    matgen,
    numeric_ilu_ref,
    pilu1_symbolic,
    poisson_2d,
    symbolic_ilu_k,
    symbolic_ilu_k_ref,
)
from repro.core.api import ilu
from repro.core.factor_plan import build_factor_plan, factor_plan_for
from repro.core.top_ilu import topilu_numeric

MD_SCRIPT = os.path.join(os.path.dirname(__file__), "multidevice_check.py")


def _assert_bitwise(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    mism = np.nonzero(got.view(np.int32) != want.view(np.int32))[0]
    assert mism.size == 0, (
        f"{mism.size}/{want.size} entries differ bitwise; first={mism[:5]} "
        f"got={got[mism[:5]]} want={want[mism[:5]]}"
    )


def _pattern(a, k, rule):
    return pilu1_symbolic(a, rule=rule) if k == 1 else symbolic_ilu_k(a, k, rule=rule)


# --------------------------------------------------------------------------
# symbolic: vectorized frontier == per-row reference
# --------------------------------------------------------------------------
@pytest.mark.parametrize("rule", ["sum", "max"])
@pytest.mark.parametrize("k", [0, 1, 2, 3])
def test_symbolic_frontier_equals_reference(k, rule):
    for seed in (0, 1, 2):
        a = matgen(80, density=0.07, seed=seed + 13 * k)
        fast = symbolic_ilu_k(a, k, rule=rule)
        ref = symbolic_ilu_k_ref(a, k, rule=rule)
        np.testing.assert_array_equal(fast.indptr, ref.indptr)
        np.testing.assert_array_equal(fast.indices, ref.indices)
        np.testing.assert_array_equal(fast.levels, ref.levels)
        np.testing.assert_array_equal(fast.diag_ptr, ref.diag_ptr)


# --------------------------------------------------------------------------
# single-device engines vs the oracle, exact ==
# --------------------------------------------------------------------------
@pytest.mark.parametrize("rule", ["sum", "max"])
@pytest.mark.parametrize("k", [0, 1, 2])
def test_wavefront_engine_bitwise(k, rule):
    a = matgen(96, density=0.06, seed=7 * k + (rule == "max"))
    pat = _pattern(a, k, rule)
    want = numeric_ilu_ref(a, pat)
    _assert_bitwise(ilu(a, k, rule=rule, backend="jax").vals, want)


@pytest.mark.parametrize("band_rows", [8, 32])
@pytest.mark.parametrize("k", [0, 1, 2])
def test_superstep_engine_bitwise(k, band_rows):
    a = matgen(96, density=0.06, seed=10 * k + band_rows)
    pat = _pattern(a, k, "sum")
    want = numeric_ilu_ref(a, pat)
    _assert_bitwise(topilu_numeric(a, pat, band_rows=band_rows), want)


@pytest.mark.parametrize("k", [1, 2])
def test_factor_plan_engines_agree(k):
    """The plan's compiled wavefront engine == the oracle — exact ==."""
    a = poisson_2d(10)
    pat = _pattern(a, k, "sum")
    want = numeric_ilu_ref(a, pat)
    plan = build_factor_plan(a, pat)
    _assert_bitwise(plan.factorize(), want)


def test_structured_poisson_bitwise():
    a = poisson_2d(12)
    for k, rule in ((1, "sum"), (2, "sum"), (2, "max")):
        pat = _pattern(a, k, rule)
        want = numeric_ilu_ref(a, pat)
        _assert_bitwise(ilu(a, k, rule=rule, backend="jax").vals, want)
        _assert_bitwise(topilu_numeric(a, pat, band_rows=16), want)


# --------------------------------------------------------------------------
# plan/engine caching + refactorization
# --------------------------------------------------------------------------
def test_factor_plan_cached_on_matrix():
    a = matgen(64, density=0.08, seed=3)
    pat = pilu1_symbolic(a)
    p1 = factor_plan_for(a, pat)
    p2 = factor_plan_for(a, pat)
    assert p1 is p2
    assert p1.engine() is p1.engine()  # compiled engine cached on the plan


def test_refactorize_same_structure_new_values():
    """The serving pattern: same structure, new numbers — no replanning."""
    a = matgen(72, density=0.08, seed=5)
    pat = pilu1_symbolic(a)
    plan = build_factor_plan(a, pat)
    _assert_bitwise(plan.factorize(), numeric_ilu_ref(a, pat))
    import dataclasses

    a2 = dataclasses.replace(a, data=(a.data * 1.5 + 0.25).astype(np.float32))
    _assert_bitwise(plan.factorize(a2), numeric_ilu_ref(a2, pat))


def test_topilu_refactorize_updated_values_not_stale():
    """The cached sharded engine must re-read a.data on every call: an
    in-place value update followed by a refactorization yields the new
    factors, not the first call's."""
    a = matgen(72, density=0.08, seed=6)
    f1 = ilu(a, 1, backend="topilu", band_rows=8)
    a.data[:] = (a.data * 1.5 + 0.25).astype(np.float32)
    f2 = ilu(a, 1, backend="topilu", band_rows=8)
    _assert_bitwise(f2.vals, numeric_ilu_ref(a, f2.pattern))
    assert not np.array_equal(f2.vals.view(np.int32), f1.vals.view(np.int32))


# --------------------------------------------------------------------------
# end-to-end: solve_with_ilu unchanged vs the oracle-backend pipeline
# --------------------------------------------------------------------------
def test_solve_with_ilu_end_to_end_unchanged():
    from repro.core.solvers import solve_with_ilu

    a = poisson_2d(10)
    b = np.random.default_rng(0).standard_normal(a.n).astype(np.float32)
    res_jax, fact_jax = solve_with_ilu(a, b, k=1, backend="jax", tol=1e-6)
    res_orc, fact_orc = solve_with_ilu(a, b, k=1, backend="oracle", tol=1e-6)
    # identical factor values => identical preconditioner => identical solve
    _assert_bitwise(fact_jax.vals, fact_orc.vals)
    _assert_bitwise(res_jax.x, res_orc.x)
    assert res_jax.iterations == res_orc.iterations
    assert res_jax.converged


# --------------------------------------------------------------------------
# sharded factorization (1 device, in-process): device-resident output
# --------------------------------------------------------------------------
@pytest.mark.parametrize("rule", ["sum", "max"])
@pytest.mark.parametrize("k", [0, 1, 2])
def test_sharded_factorization_bitwise_single_device(k, rule):
    from repro.core.api import ilu_sharded

    a = matgen(96, density=0.06, seed=21 * k + (rule == "max"))
    pat = _pattern(a, k, rule)
    want = numeric_ilu_ref(a, pat)
    fact = ilu_sharded(a, k, rule=rule, band_rows=8)
    _assert_bitwise(fact.values_csr(), want)
    # sharded layout invariants hold even at D=1 (halo empty, all local)
    assert fact.plan.s_loc == fact.plan.n_pad
    assert fact.plan.halo_size == 0


def test_sharded_solve_matches_single_device():
    from repro.core.solvers import solve_sharded, solve_with_ilu

    a = poisson_2d(10)
    b = np.random.default_rng(2).standard_normal(a.n).astype(np.float32)
    r_ref, f_ref = solve_with_ilu(a, b, k=1, tol=1e-6)
    r_sh, f_sh = solve_sharded(a, b, k=1, tol=1e-6)
    _assert_bitwise(f_sh.values_csr(), f_ref.vals)
    _assert_bitwise(r_sh.x, r_ref.x)
    assert r_sh.converged and r_sh.iterations == r_ref.iterations


@pytest.mark.parametrize("k", [0, 1, 2])
def test_batched_sharded_solve_bitwise(k, monkeypatch):
    """Multi-RHS sharded solves: every column of a ragged (bucketed) batch
    must equal its per-column single-device solve bitwise — the padded
    vmap lanes are independent and sliced off."""
    from repro.core.solvers import bucket_batch, solve_sharded, solve_with_ilu

    monkeypatch.delenv("REPRO_BATCH_BUCKETS", raising=False)
    a = matgen(96, density=0.06, seed=31 + k)
    B = np.random.default_rng(3 + k).standard_normal((3, a.n)).astype(np.float32)
    assert bucket_batch(3) == 4  # ragged: rides the 4-bucket
    rs, fact = solve_sharded(a, B, k=k, band_rows=8, tol=1e-6)
    assert len(rs) == 3
    for i, r in enumerate(rs):
        r1, _ = solve_with_ilu(a, B[i], k=k, tol=1e-6)
        assert r.converged and r.iterations == r1.iterations
        _assert_bitwise(r.x, r1.x)
    # the batch shares the factorization and its cached precond
    rs2, fact2 = solve_sharded(a, B, k=k, band_rows=8, tol=1e-6, fact=fact)
    assert fact2 is fact
    for r, r2 in zip(rs, rs2):
        _assert_bitwise(r2.x, r.x)


def test_warm_solve_prepares_serving_buckets():
    """warm_solve pre-compiles the solve stack; a fresh RHS of a warmed
    bucket reuses the cached engines (identical bits, no new shapes)."""
    from repro.core.solvers import solve_sharded, solve_with_ilu, warm_solve

    a = poisson_2d(8)
    warm_solve(a, k=1, batch_sizes=(1, 2), band_rows=8, tol=1e-6)
    b = np.random.default_rng(5).standard_normal(a.n).astype(np.float32)
    r, fact = solve_sharded(a, b, k=1, band_rows=8, tol=1e-6)
    r1, _ = solve_with_ilu(a, b, k=1, tol=1e-6)
    assert r.converged
    _assert_bitwise(r.x, r1.x)
    # the sharded precond was AOT-warmed for the single-RHS shape
    assert 1 in fact.precond()._aot


# --------------------------------------------------------------------------
# multi-device engines (subprocess; exact == asserted by the check script).
# The sweep is the PR-3 acceptance contract: 1 vs 2 vs 4 devices, sharded
# value storage, bitwise equal to the oracle; 2-device cases also run the
# distributed precond+solve against the single-device path.
# --------------------------------------------------------------------------
def _run_md(devices, k, band_rows, broadcast="psum", solve=False, batch=False):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"  # don't probe for real TPUs (see test_topilu_multidevice)
    cmd = [sys.executable, MD_SCRIPT, "96", str(k), str(band_rows), broadcast]
    if solve:
        cmd.append("--solve")
    if batch:
        cmd.append("--batch")
    rc, out, err = run_checked(cmd, env=env, timeout=600)
    assert rc == 0, f"stdout:\n{out}\nstderr:\n{err[-2000:]}"
    assert "bitwise-equal" in out


@pytest.mark.parametrize("k,band_rows", [(1, 8), (1, 32), (2, 8), (2, 32)])
def test_two_device_bitwise(k, band_rows):
    # the band_rows=8 cases also cover the ragged multi-RHS distributed solve
    _run_md(2, k, band_rows, solve=(band_rows == 8), batch=(band_rows == 8))


@pytest.mark.parametrize("k", [0, 1, 2])
def test_four_device_bitwise(k):
    # k=2 additionally runs the batched distributed solve on 4 devices
    _run_md(4, k, band_rows=8, solve=(k == 2), batch=(k == 2))
