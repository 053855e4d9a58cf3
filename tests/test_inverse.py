"""Level-based incomplete inverse preconditioning: oracle, engine, fused
chain, and the ``precond_method`` auto policy.

The bit-compat contract under test (paper abstract, DESIGN.md §Inverse):
the inverse method is NOT bitwise-comparable to classical ILU(k) — it is a
different approximation of M^{-1} — but every execution path (jnp engine,
fused chain, precond apply, batched apply, warmed AOT apply) must
be bitwise-equal to the sequential NumPy oracle in
``repro.core.inverse_ref``. The auto-policy tests pin ``"auto"`` against
the modeled communication records with nothing compiled.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import matgen, numeric_ilu_ref, poisson_2d, symbolic_ilu_k  # noqa: E402
from repro.core.inverse import (  # noqa: E402
    AUTO_COLLECTIVE_COST_BYTES,
    InversePrecondApply,
    build_inverse_plan,
    compute_inverse_values,
    inverse_chain_jnp,
    inverse_comm_model,
    modeled_apply_cost,
    resolve_precond_method,
)
from repro.core.inverse_ref import (  # noqa: E402
    inverse_apply_ref,
    inverse_pattern_ref,
    inverse_values_ref,
)
from repro.core.planner import COL_SENTINEL  # noqa: E402


def _assert_bitwise(got, want, msg=""):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.array_equal(got.view(np.int32), want.view(np.int32)), msg


def _factored(n=64, k=1, seed=0, density=0.12):
    a = matgen(n, density=density, seed=seed)
    pat = symbolic_ilu_k(a, k)
    return a, pat, numeric_ilu_ref(a, pat)


# --------------------------------------------------------------------------
# oracle semantics
# --------------------------------------------------------------------------
def test_inverse_pattern_k0_equals_factor_pattern():
    """With k=0 every chain of length > 1 costs >= 1, so the truncated
    inverse keeps exactly the level-0 factor entries (plus the diagonal) —
    a structurally-ILU(0)-shaped inverse."""
    _a, pat, _vals = _factored(48, 0, seed=3)
    w_cols, z_cols = inverse_pattern_ref(pat)
    n = pat.n
    for i in range(n):
        s, e = int(pat.indptr[i]), int(pat.indptr[i + 1])
        d = int(pat.diag_ptr[i])
        want_w = set(pat.indices[s:e][: d].tolist()) | {i}
        want_z = set(pat.indices[s:e][d + 1 :].tolist()) | {i}
        assert set(w_cols[i][w_cols[i] < n].tolist()) == want_w, i
        assert set(z_cols[i][z_cols[i] < n].tolist()) == want_z, i


def test_inverse_full_fill_is_exact_triangular_inverse():
    """With k large enough to keep every chain, W and Z are the *exact*
    L^{-1} / U^{-1} (up to f32 rounding) — the truncation is the only
    approximation in the method."""
    a, pat, vals = _factored(24, 2, seed=1, density=0.2)
    n = pat.n
    w_cols, z_cols = inverse_pattern_ref(pat, k=n)  # keep everything
    w_vals, z_vals = inverse_values_ref(pat, vals, w_cols, z_cols)

    from repro.core import split_lu

    L, U = (np.asarray(m.todense(), np.float32) for m in split_lu(pat, vals))
    W = np.zeros((n, n), np.float32)
    Z = np.zeros((n, n), np.float32)
    for i in range(n):
        W[i, w_cols[i][w_cols[i] < n]] = w_vals[i][w_cols[i] < n]
        Z[i, z_cols[i][z_cols[i] < n]] = z_vals[i][z_cols[i] < n]
    np.testing.assert_allclose(W @ L, np.eye(n), atol=2e-4)
    np.testing.assert_allclose(Z @ U, np.eye(n), atol=2e-4)


@pytest.mark.parametrize("k", [0, 1, 2])
def test_truncated_inverse_still_preconditions(k):
    """GMRES with the truncated inverse converges on the standard fixtures
    (it may take a few more iterations than the exact sweep — that is the
    trade, not a failure)."""
    from repro.core.solvers import solve_with_ilu

    a = poisson_2d(8)
    b = np.random.default_rng(4).standard_normal(a.n).astype(np.float32)
    res, _ = solve_with_ilu(a, b, k=k, tol=1e-6, precond_method="inverse")
    assert res.converged


# --------------------------------------------------------------------------
# engine == oracle, bit for bit
# --------------------------------------------------------------------------
@pytest.mark.parametrize("k,seed", [(0, 0), (1, 1), (2, 2)])
def test_plan_values_bitwise_vs_oracle(k, seed):
    _a, pat, vals = _factored(56, k, seed=seed)
    w_cols, z_cols = inverse_pattern_ref(pat)
    want_w, want_z = inverse_values_ref(pat, vals, w_cols, z_cols)
    plan = build_inverse_plan(pat, vals)
    assert np.array_equal(plan.w_cols, w_cols)
    assert np.array_equal(plan.z_cols, z_cols)
    got_w, got_z = compute_inverse_values(plan)
    _assert_bitwise(got_w, want_w, "W values != sequential oracle")
    _assert_bitwise(got_z, want_z, "Z values != sequential oracle")


@pytest.mark.parametrize("k", [1, 2])
def test_precond_apply_bitwise_vs_oracle(k):
    """Single apply, batched apply, and the warmed AOT paths all reproduce
    the oracle chain bitwise."""
    _a, pat, vals = _factored(48, k, seed=5)
    w_cols, z_cols = inverse_pattern_ref(pat)
    w_vals, z_vals = inverse_values_ref(pat, vals, w_cols, z_cols)
    b = np.random.default_rng(6).standard_normal(pat.n).astype(np.float32)
    B = np.random.default_rng(7).standard_normal((3, pat.n)).astype(np.float32)
    want = inverse_apply_ref(w_cols, w_vals, z_cols, z_vals, b)
    want_B = inverse_apply_ref(w_cols, w_vals, z_cols, z_vals, B)

    p = InversePrecondApply(pat, vals)
    _assert_bitwise(p(b), want)
    _assert_bitwise(p.batched(B), want_B)
    p.warm((1, 4))  # AOT single + bucketed batch (3 pads to 4)
    _assert_bitwise(p(b), want)
    _assert_bitwise(p.batched(B), want_B)


def test_api_precond_inverse_bitwise_and_cached():
    """``ILUFactorization.precond(method=...)`` routes and caches per
    method; D=1 ``"auto"`` resolves to the sweep engine."""
    from repro.core.api import ilu

    a = matgen(64, density=0.1, seed=8)
    fact = ilu(a, 1, backend="jax")
    b = np.random.default_rng(9).standard_normal(a.n).astype(np.float32)
    w_cols, z_cols = inverse_pattern_ref(fact.pattern)
    w_vals, z_vals = inverse_values_ref(fact.pattern, fact.vals, w_cols, z_cols)
    want = inverse_apply_ref(w_cols, w_vals, z_cols, z_vals, b)
    p = fact.precond(method="inverse")
    _assert_bitwise(p(b), want)
    assert fact.precond(method="inverse") is p
    assert fact.precond(method="auto") is fact.precond(method="sweep")


def test_solve_with_ilu_inverse_converges_and_reuses_fact():
    from repro.core.solvers import solve_with_ilu

    a = matgen(96, density=0.1, seed=11)
    b = np.random.default_rng(1).standard_normal(a.n).astype(np.float32)
    r_sw, f1 = solve_with_ilu(a, b, k=1, tol=1e-6)
    r_inv, f2 = solve_with_ilu(a, b, k=1, tol=1e-6, precond_method="inverse")
    assert f1 is f2  # one factorization, two apply engines
    assert r_sw.converged and r_inv.converged
    # multi-RHS through gmres_batched with the inverse preconditioner
    B = np.random.default_rng(2).standard_normal((3, a.n)).astype(np.float32)
    rs, _ = solve_with_ilu(a, B, k=1, tol=1e-6, precond_method="inverse")
    assert all(r.converged for r in rs)


# --------------------------------------------------------------------------
# the fused chain
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,seed", [(64, 13), (40, 15)])
def test_inverse_chain_kernel_bitwise(n, seed):
    """The fused chain, eager and jitted, agrees with the sequential oracle
    apply, bit for bit."""
    _a, pat, vals = _factored(n, 1, seed=seed)
    w_cols, z_cols = inverse_pattern_ref(pat)
    w_vals, z_vals = inverse_values_ref(pat, vals, w_cols, z_cols)
    b = np.random.default_rng(seed + 1).standard_normal(pat.n).astype(np.float32)
    want = inverse_apply_ref(w_cols, w_vals, z_cols, z_vals, b)
    args = tuple(jnp.asarray(x) for x in (w_cols, w_vals, z_cols, z_vals, b))
    _assert_bitwise(inverse_chain_jnp(*args), want)
    _assert_bitwise(jax.jit(inverse_chain_jnp)(*args), want)


# --------------------------------------------------------------------------
# the "auto" selection policy — pinned against the modeled comm records,
# nothing compiled (host-only planning)
# --------------------------------------------------------------------------
def test_inverse_comm_model_fields():
    m = inverse_comm_model(100, 4)
    assert m["collectives_per_apply"] == 2  # one all_gather per SpMV
    assert m["payload_slots_per_apply"] == 2 * 25
    assert m["bytes_per_apply"] == 3 * 2 * 25 * 4
    assert inverse_comm_model(100, 1)["collectives_per_apply"] == 0
    assert modeled_apply_cost(m) == 2 * AUTO_COLLECTIVE_COST_BYTES + m["bytes_per_apply"]


def test_auto_single_device_is_sweep():
    _a, pat, _vals = _factored(48, 1, seed=17)
    assert resolve_precond_method("auto", pat, n_devices=1) == "sweep"
    assert resolve_precond_method("sweep", pat, n_devices=8) == "sweep"
    assert resolve_precond_method("inverse", pat, n_devices=1) == "inverse"
    with pytest.raises(ValueError):
        resolve_precond_method("newton", pat)


def test_auto_picks_inverse_when_epochs_dominate():
    """Natural-ordered Poisson at D=8: the sweep needs one collective per
    epoch (tens of them), the chain needs two — the modeled sweep cost
    dominates and auto must pick the inverse."""
    from repro.core.ordering import sweep_comm_model

    a = poisson_2d(16)  # n=256, natural ordering: deep wavefronts
    pat = symbolic_ilu_k(a, 1)
    sweep = sweep_comm_model(pat, 8, 8)
    assert sweep["collectives_per_apply"] > 2  # the premise of the pin
    assert modeled_apply_cost(sweep) > modeled_apply_cost(inverse_comm_model(pat.n, 8))
    assert resolve_precond_method("auto", pat, n_devices=8, band_rows=8) == "inverse"


def test_auto_picks_sweep_when_chain_is_longer():
    """Block-diagonal system with blocks aligned to device bands: every
    sweep epoch is device-local, so the whole apply fuses to one boundary
    collective with a tiny read set, while the chain still pays its two
    full-slice gathers — auto must keep the sweep."""
    from repro.core.ordering import sweep_comm_model
    from repro.core.sparse import CSRMatrix

    D, rows = 4, 16  # 4 tridiagonal blocks of 16 rows, bands of 16
    n = D * rows
    dense = np.zeros((n, n), np.float32)
    for blk in range(D):
        for i in range(rows):
            g = blk * rows + i
            dense[g, g] = 4.0
            if i > 0:
                dense[g, g - 1] = -1.0
            if i < rows - 1:
                dense[g, g + 1] = -1.0
    a = CSRMatrix.from_dense(dense)
    pat = symbolic_ilu_k(a, 1)
    sweep = sweep_comm_model(pat, rows, D)
    assert sweep["collectives_per_apply"] == 1  # one fused L->U boundary
    assert modeled_apply_cost(sweep) < modeled_apply_cost(inverse_comm_model(n, D))
    assert resolve_precond_method("auto", pat, n_devices=D, band_rows=rows) == "sweep"


def test_auto_respects_precomputed_sweep_summary():
    """``sweep_summary=`` short-circuits the model — the sharded
    factorization path feeds its actual plan's ``comm_summary`` in."""
    _a, pat, _vals = _factored(48, 1, seed=19)
    cheap = {"collectives_per_apply": 0, "bytes_per_apply": 0}
    dear = {"collectives_per_apply": 50, "bytes_per_apply": 10 * AUTO_COLLECTIVE_COST_BYTES}
    assert resolve_precond_method("auto", pat, n_devices=4, sweep_summary=cheap) == "sweep"
    assert resolve_precond_method("auto", pat, n_devices=4, sweep_summary=dear) == "inverse"


def test_plan_pad_lanes_are_positive_zero():
    """Engine pad lanes must be +0.0 exactly (the U sweep's pad arithmetic
    could round to -0.0 through a negative diagonal — the oracle never
    writes pads, so the engine normalizes them)."""
    _a, pat, vals = _factored(48, 2, seed=21)
    plan = build_inverse_plan(pat, vals)
    w, z = (np.asarray(x) for x in compute_inverse_values(plan))
    for cols, vals_ in ((plan.w_cols, w), (plan.z_cols, z)):
        pads = vals_[cols >= pat.n]
        assert np.all(pads.view(np.int32) == 0), "pad lane not +0.0"
    assert np.all(plan.w_cols[plan.w_cols >= pat.n] == COL_SENTINEL)
