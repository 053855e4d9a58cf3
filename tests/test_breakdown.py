"""Breakdown fixtures, the pivot guard, and the shifted-refactorization ladder.

The contract under test (DESIGN.md §12):

* the audit is a **pure read** — guarded and unguarded factors are bitwise
  identical; a healthy matrix's factorization is untouched by the guard;
* each breakdown fixture makes plain ILU(k) produce inf/NaN/zero pivots,
  the audit flags it, and ``on_breakdown="shift"`` settles on a shifted
  system whose factor is bitwise equal to the sequential oracle **of the
  shifted matrix**;
* ``on_breakdown="raise"`` raises with the offending row in the message;
* ``on_breakdown="fallback"`` with an exhausted ladder degrades to the
  identity preconditioner instead of failing;
* solver verdicts classify termination without perturbing the iterates.

Multi-device (2 and 4 virtual devices) runs via ``breakdown_check.py`` in a
subprocess (device count locks at first JAX init).
"""
import os
import sys

import numpy as np
import pytest

from subproc import run_checked

from repro.core import numeric_ilu_ref, pilu1_symbolic
from repro.core.api import ilu
from repro.core.guard import (
    BreakdownError,
    IdentityPrecondApply,
    audit_values,
    ladder_alphas,
    shifted_matrix,
)
from repro.core.matgen import (
    denormal_pivot_matrix,
    indefinite_matrix,
    matgen,
    singular_block_matrix,
    zero_diagonal_matrix,
)
from repro.core.solvers import VERDICTS, SolveReport, gmres, solve_with_ilu

SCRIPT = os.path.join(os.path.dirname(__file__), "breakdown_check.py")

FIXTURES = {
    "singular": lambda: singular_block_matrix(64, 0.1, seed=3),
    "zerodiag": lambda: zero_diagonal_matrix(64, 0.1, seed=4),
    "denormal": lambda: denormal_pivot_matrix(64, 0.1, seed=5),
}


def _diag_ok(a):
    for r in range(a.n):
        cols = a.indices[a.indptr[r]:a.indptr[r + 1]]
        assert r in cols, f"row {r} lacks a structural diagonal"


def test_fixtures_well_formed():
    """Every fixture keeps a structural diagonal (the shift is a pure value
    edit) and the intended defect: singular block / zero diag / subnormal
    row scale / indefinite diagonal."""
    for make in FIXTURES.values():
        _diag_ok(make())
    a = singular_block_matrix(64, 0.1, seed=3)
    assert a.indptr[2] == 4 and list(a.indices[:4]) == [0, 1, 0, 1]
    z = zero_diagonal_matrix(64, 0.1, seed=4, row=0)
    assert z.data[z.indptr[0] + np.searchsorted(
        z.indices[z.indptr[0]:z.indptr[1]], 0)] == 0.0
    d = denormal_pivot_matrix(64, 0.1, seed=5)
    lo, hi = d.indptr[0], d.indptr[1]
    piv = d.data[lo + np.searchsorted(d.indices[lo:hi], 0)]
    assert 0 < abs(float(piv)) < np.finfo(np.float32).tiny
    ind = indefinite_matrix(8)
    diags = [ind.data[ind.indptr[r] + np.searchsorted(
        ind.indices[ind.indptr[r]:ind.indptr[r + 1]], r)] for r in range(ind.n)]
    assert min(diags) < 0 < max(diags) or all(x < 4 for x in diags)
    _diag_ok(ind)


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_audit_flags_and_ladder_recovers(name):
    """Plain ILU(k) on the fixture is unhealthy; the ladder settles on a
    shift whose factor equals the sequential oracle of the shifted matrix
    bitwise.

    The denormal fixture anchors against the oracle *backend*: its rows
    carry subnormal values, and XLA's CPU backend flushes subnormal
    products to zero (FTZ) where numpy keeps them — a hardware-semantics
    boundary outside the bit-compat contract, which assumes normal-range
    arithmetic. The ladder/audit logic under test is backend-independent.
    """
    a = FIXTURES[name]()
    base = ilu(a, 1, backend="oracle", on_breakdown="ignore")
    assert base.health is not None and not base.health.ok
    assert base.health.worst_row >= 0

    backend = "oracle" if name == "denormal" else "jax"
    fact = ilu(a, 1, backend=backend, on_breakdown="shift")
    h = fact.health
    assert h.ok and h.shift > 0 and h.attempts > 1, h.summary()
    # the bit-compat anchor: shifted factor == sequential oracle of A+αD
    a_s = shifted_matrix(a, h.shift)
    want = numeric_ilu_ref(a_s, fact.pattern)
    assert np.array_equal(np.asarray(fact.vals).view(np.int32),
                          want.view(np.int32))
    # α follows the geometric ladder from the first rung
    assert h.shift in ladder_alphas()


def test_guard_is_a_pure_read_on_healthy_matrix():
    """A healthy factorization is bitwise identical with the guard on or
    off, and its health is clean."""
    a = matgen(64, 0.1, seed=6)
    f_off = ilu(a, 1, backend="jax", on_breakdown="ignore")
    f_on = ilu(a, 1, backend="jax", on_breakdown="raise")  # no raise: healthy
    assert f_on.health.ok and f_on.health.shift == 0.0
    assert f_on.health.attempts == 1
    assert np.array_equal(np.asarray(f_on.vals).view(np.int32),
                          np.asarray(f_off.vals).view(np.int32))


def test_raise_names_offending_row():
    a = zero_diagonal_matrix(64, 0.1, seed=4, row=0)
    with pytest.raises(BreakdownError) as ei:
        ilu(a, 1, backend="oracle", on_breakdown="raise")
    msg = str(ei.value)
    assert "row" in msg and ei.value.health is not None
    assert not ei.value.health.ok
    # the audit pinpoints a specific row in the message
    assert any(ch.isdigit() for ch in msg.split("row", 1)[1][:8])


def test_ladder_solve_converges_where_plain_nans():
    """End-to-end: the unguarded solve on the zero-diagonal fixture produces
    non-finite iterates; on_breakdown="shift" converges to a finite x with
    the shift recorded on the report."""
    a = zero_diagonal_matrix(64, 0.1, seed=4, row=0)
    b = np.random.default_rng(1).standard_normal(64).astype(np.float32)
    r_plain, _ = solve_with_ilu(a, b, k=1, tol=1e-5, maxiter=50, on_breakdown="ignore")
    assert not r_plain.converged or not np.isfinite(np.asarray(r_plain.x)).all()
    r, fact = solve_with_ilu(a, b, k=1, tol=1e-5, maxiter=200, on_breakdown="shift")
    assert r.converged and np.isfinite(np.asarray(r.x)).all()
    assert r.report.shift == fact.health.shift > 0
    assert r.verdict == "converged"


def test_indefinite_stagnates_then_shift_converges():
    """Indefiniteness is not breakdown: the Helmholtz-like fixture factors
    healthily at the default τ, but ILU(1)-preconditioned GMRES *stagnates*
    on it (the verdict catches what a bare converged-flag would miss).
    Raising ``pivot_tol`` makes the audit flag the small pivots, and the
    shift ladder turns stagnation into convergence — with the shifted
    factor still bitwise-anchored to the oracle of the shifted matrix."""
    a = indefinite_matrix(8)
    b = np.random.default_rng(2).standard_normal(a.n).astype(np.float32)
    plain = ilu(a, 1, backend="jax", on_breakdown="raise")  # default τ: healthy
    assert plain.health.ok and plain.health.shift == 0.0
    r0, _ = solve_with_ilu(a, b, k=1, tol=1e-5, maxiter=300)
    assert not r0.converged and r0.verdict == "stagnated"
    r, fact = solve_with_ilu(a, b, k=1, tol=1e-5, maxiter=300, on_breakdown="shift",
                             pivot_tol=1e-2)
    assert r.converged and r.verdict == "converged"
    assert r.report.shift == fact.health.shift > 0
    want = numeric_ilu_ref(shifted_matrix(a, fact.health.shift), fact.pattern)
    assert np.array_equal(np.asarray(fact.vals).view(np.int32),
                          want.view(np.int32))


def test_identity_fallback_when_ladder_exhausted():
    """fallback + an empty ladder (max_shifts=0) degrades to the identity
    preconditioner: health.degraded, precond() applies M⁻¹ = I bitwise."""
    a = zero_diagonal_matrix(64, 0.1, seed=4, row=0)
    fact = ilu(a, 1, backend="jax", on_breakdown="fallback", max_shifts=0)
    assert fact.health.degraded and not fact.health.ok
    p = fact.precond()
    assert isinstance(p, IdentityPrecondApply)
    b = np.random.default_rng(2).standard_normal(64).astype(np.float32)
    assert np.array_equal(np.asarray(p(b), np.float32).view(np.int32),
                          b.view(np.int32))
    B = np.random.default_rng(3).standard_normal((4, 64)).astype(np.float32)
    assert np.array_equal(np.asarray(p.batched(B), np.float32).view(np.int32),
                          B.view(np.int32))


def test_audit_values_channels():
    """audit_values counts each defect in its own channel."""
    a = matgen(64, 0.1, seed=7)
    pat = pilu1_symbolic(a)
    vals = numeric_ilu_ref(a, pat)
    h = audit_values(pat, vals)
    assert h.ok and h.n == 64 and h.n_nonfinite == 0
    bad = np.asarray(vals).copy()
    bad[0] = np.nan
    h2 = audit_values(pat, bad)
    assert not h2.ok and h2.n_nonfinite == 1 and h2.first_nonfinite_row == 0


def test_shift_exhaustion_raises_with_flag():
    a = zero_diagonal_matrix(64, 0.1, seed=4, row=0)
    with pytest.raises(BreakdownError) as ei:
        ilu(a, 1, backend="oracle", on_breakdown="shift", max_shifts=0)
    assert ei.value.exhausted


# ---------------------------------------------------------------------------
# solver verdicts
# ---------------------------------------------------------------------------
def _healthy_setup(n=64, seed=8):
    from repro.core.solvers import csr_to_ell_arrays, make_ell_matvec

    a = matgen(n, 0.1, seed=seed)
    fact = ilu(a, 1, backend="jax")
    pre = fact.precond()
    cols, vals = csr_to_ell_arrays(a)
    return a, make_ell_matvec(cols, vals, a.n), pre


def test_verdict_converged_and_report():
    a, matvec, pre = _healthy_setup()
    b = np.random.default_rng(4).standard_normal(a.n).astype(np.float32)
    r = gmres(matvec, b, pre, tol=1e-5)
    assert r.verdict == "converged" and r.converged
    assert isinstance(r.report, SolveReport)
    assert r.report.iterations == r.iterations
    assert not r.report.degraded and r.report.shift == 0.0


def test_verdict_maxiter():
    a, matvec, pre = _healthy_setup()
    b = np.random.default_rng(5).standard_normal(a.n).astype(np.float32)
    r = gmres(matvec, b, pre, tol=1e-30, restart=2, maxiter=2)
    assert r.verdict in ("maxiter", "stagnated") and not r.converged


def test_verdict_breakdown_on_nonfinite_rhs():
    """A non-finite ‖b‖ classifies as breakdown immediately — this is the
    lane-quarantine trigger the serve layer keys on."""
    a, matvec, pre = _healthy_setup()
    b = np.full(a.n, np.nan, np.float32)
    r = gmres(matvec, b, pre, tol=1e-5, maxiter=5)
    assert r.verdict == "breakdown" and not r.converged


def test_verdict_zero_rhs_converges_at_zero_iters():
    a, matvec, pre = _healthy_setup()
    r = gmres(matvec, np.zeros(a.n, np.float32), pre, tol=1e-5)
    assert r.verdict == "converged" and r.iterations == 0


def test_verdicts_enumeration_stable():
    assert VERDICTS == ("running", "converged", "maxiter", "stagnated",
                        "breakdown", "diverged")


# ---------------------------------------------------------------------------
# multi-device: ladder bitwise vs the sequential oracle of the shifted matrix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("devices", [2, 4])
def test_ladder_multidevice_bitwise(devices):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    rc, out, err = run_checked(
        [sys.executable, SCRIPT, "96", "1", "8"], env=env, timeout=300)
    assert rc == 0, f"stdout:\n{out}\nstderr:\n{err[-2000:]}"
    assert "ladder bitwise-equal" in out
