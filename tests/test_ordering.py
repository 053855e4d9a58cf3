"""Ordering layer: permutation invariants, symbolic consistency, the
fusion model claim, and the 1/2/4-device bitwise contract.

The contract under reordering (DESIGN.md §Ordering): every pipeline stage
runs on the permuted system ``P A Pᵀ``, where the existing bitwise
contracts hold verbatim — so an ordered factorization must equal the
sequential oracle *of the permuted matrix* bit for bit, and ordered
sharded solves must equal the single-device permuted solve mapped back
through the permutation. Multi-device cases run in subprocesses (JAX
locks the host device count at first init).
"""
import os
import sys

import numpy as np
import pytest

from subproc import run_checked

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import matgen, poisson_2d
from repro.core.ordering import (
    choose_band_rows,
    fusion_aware_ordering,
    make_ordering,
    natural_ordering,
    permute_csr,
    permuted_system,
    rcm_ordering,
    sweep_comm_model,
)
from repro.core.symbolic import pilu1_symbolic, symbolic_ilu_k, symbolic_ilu_k_ref

SCRIPT = os.path.join(os.path.dirname(__file__), "multidevice_check.py")


def _orderings(a, n_devices=2, band_rows=8):
    return [
        rcm_ordering(a),
        fusion_aware_ordering(a, n_devices, band_rows=band_rows),
        fusion_aware_ordering(a, n_devices, band_rows=None),  # block ownership
    ]


# --------------------------------------------------------------------------
# permutation invariants
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,density,seed", [(64, 0.08, 0), (97, 0.06, 3)])
def test_permutation_round_trip(n, density, seed):
    a = matgen(n, density=density, seed=seed)
    for ordering in _orderings(a, n_devices=3, band_rows=5):
        assert np.array_equal(np.sort(ordering.perm), np.arange(n)), ordering.name
        assert np.array_equal(ordering.iperm[ordering.perm], np.arange(n))
        assert np.array_equal(ordering.perm[ordering.iperm], np.arange(n))
        x = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
        assert np.array_equal(ordering.unpermute_vector(ordering.permute_vector(x)), x)
        # 2-D (batch) boundary
        xb = np.stack([x, 2 * x])
        assert np.array_equal(ordering.unpermute_vector(ordering.permute_vector(xb)), xb)


def test_permute_csr_matches_dense():
    a = matgen(48, density=0.1, seed=1)
    ordering = rcm_ordering(a)
    ap = permute_csr(a, ordering.perm)
    d = a.to_dense()
    assert np.array_equal(ap.to_dense(), d[np.ix_(ordering.perm, ordering.perm)])
    # permuting back is the inverse permutation
    back = permute_csr(ap, ordering.iperm)
    assert np.array_equal(back.to_dense(), d)
    # CSR invariants the plan builders rely on
    for j in range(ap.n):
        cols, _ = ap.row(j)
        assert np.all(np.diff(cols) > 0)
    assert ap.has_full_diagonal()


def test_make_ordering_resolution_and_cache():
    a = poisson_2d(8)
    assert make_ordering(a, None) is None
    assert make_ordering(a, "natural") is None
    assert make_ordering(a, natural_ordering(a.n)) is None
    assert make_ordering(a, np.arange(a.n)) is None  # identity array
    o1 = make_ordering(a, "rcm")
    assert o1.name == "rcm" and make_ordering(a, "rcm") is o1  # cached
    o2 = make_ordering(a, "fusion", n_devices=2, band_rows=8)
    assert o2.band_rows == 8
    perm = np.random.default_rng(0).permutation(a.n)
    o3 = make_ordering(a, perm)
    assert o3.name == "custom" and np.array_equal(o3.perm, perm)
    with pytest.raises(ValueError):
        make_ordering(a, "nested-dissection")
    # malformed user arrays must raise, not gather garbage downstream
    dup = np.arange(a.n)
    dup[1] = 0  # duplicate entry
    with pytest.raises(ValueError):
        make_ordering(a, dup)
    with pytest.raises(ValueError):
        make_ordering(a, np.arange(a.n - 1))  # wrong length
    with pytest.raises(ValueError):
        make_ordering(a, np.arange(1, a.n + 1))  # out of range
    # the permuted system is cached per permutation
    assert permuted_system(a, o1) is permuted_system(a, o1)


# --------------------------------------------------------------------------
# symbolic consistency on the permuted system
# --------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 2])
def test_symbolic_fill_of_permuted_matches_ref(k):
    """Symbolic ILU(k) of the permuted A == Algorithm-1 reference on the
    permuted pattern — the ordering layer hands Phase I a system it treats
    exactly like any other."""
    a = matgen(72, density=0.07, seed=11)
    for ordering in _orderings(a):
        ap = permuted_system(a, ordering)
        got = symbolic_ilu_k(ap, k) if k != 1 else pilu1_symbolic(ap)
        want = symbolic_ilu_k_ref(ap, k)
        assert np.array_equal(got.indptr, want.indptr), ordering.name
        assert np.array_equal(got.indices, want.indices), ordering.name
        assert np.array_equal(got.levels, want.levels), ordering.name
        assert np.array_equal(got.diag_ptr, want.diag_ptr), ordering.name


# --------------------------------------------------------------------------
# the fusion model claim (host-side, nothing compiled)
# --------------------------------------------------------------------------
def test_fusion_ordering_reduces_modeled_epochs_on_poisson():
    """The tentpole claim, on the 2-D Poisson fixture at D=2: the
    fusion-aware ordering's modeled collective-epoch count is no worse
    than natural order (measured: 128 -> 4 at n=1024; asserted on the
    smaller fixture with strict improvement)."""
    a = poisson_2d(16)  # n = 256
    d, r = 2, 8
    nat = sweep_comm_model(pilu1_symbolic(a), r, d)
    ordering = fusion_aware_ordering(a, d, band_rows=r)
    fus = sweep_comm_model(pilu1_symbolic(permuted_system(a, ordering)), r, d)
    assert fus["epochs"] <= nat["epochs"]
    assert fus["epochs"] < nat["epochs"]  # Poisson fuses massively
    assert fus["collectives_per_apply"] <= nat["collectives_per_apply"]


def test_choose_band_rows_scores_candidates():
    a = poisson_2d(12)
    best, scores = choose_band_rows(a, k=1, n_devices=2, candidates=(8, 36))
    assert set(scores) == {8, 36}
    assert best.name == "fusion" and best.band_rows in scores
    best_rec = scores[best.band_rows]
    for rec in scores.values():
        assert (best_rec["epochs"], best_rec["bytes_per_apply"]) <= (
            rec["epochs"], rec["bytes_per_apply"])


# --------------------------------------------------------------------------
# single-device bitwise contract through the public API
# --------------------------------------------------------------------------
@pytest.mark.parametrize("spec", ["rcm", "fusion"])
def test_ordered_factorization_bitwise_oracle_on_permuted(spec):
    from repro.core import numeric_ilu_ref
    from repro.core.api import ilu

    a = matgen(80, density=0.07, seed=5)
    fact = ilu(a, 1, ordering=spec)
    assert fact.ordering is not None and fact.ordering.name == spec
    ap = permuted_system(a, fact.ordering)
    want = numeric_ilu_ref(ap, pilu1_symbolic(ap))
    assert np.array_equal(fact.vals.view(np.int32), want.view(np.int32))


@pytest.mark.parametrize("spec", ["rcm", "fusion"])
def test_ordered_solve_boundary(spec):
    """solve_with_ilu(ordering=...) == the manual permute→solve→unpermute,
    bitwise, for single and batched right-hand sides — and the returned x
    solves the *original* system."""
    from repro.core.solvers import solve_with_ilu

    a = poisson_2d(10)
    rng = np.random.default_rng(2)
    b = rng.standard_normal(a.n).astype(np.float32)
    bs = rng.standard_normal((3, a.n)).astype(np.float32)

    res, fact = solve_with_ilu(a, b, k=1, tol=1e-6, ordering=spec)
    ordering = fact.ordering
    ap = permuted_system(a, ordering)
    ref, _ = solve_with_ilu(ap, b[ordering.perm], k=1, tol=1e-6)
    assert res.converged and res.iterations == ref.iterations
    assert np.array_equal(res.x.view(np.int32), ref.x[ordering.iperm].view(np.int32))
    r = b - a.to_dense() @ res.x
    assert np.linalg.norm(r) <= 1e-5 * np.linalg.norm(b) * 10

    rs, _ = solve_with_ilu(a, bs, k=1, tol=1e-6, ordering=spec)
    refs, _ = solve_with_ilu(ap, bs[:, ordering.perm], k=1, tol=1e-6)
    for got, want in zip(rs, refs):
        assert np.array_equal(got.x.view(np.int32), want.x[ordering.iperm].view(np.int32))


def test_solve_sharded_rejects_mismatched_fact_ordering():
    """A caller-supplied fact factored under one row order must not be
    silently combined with a different `ordering=` (matvec and precond
    would run on different systems) — and the fact must not be stamped."""
    from repro.core.solvers import solve_sharded

    a = poisson_2d(8)
    b = np.random.default_rng(4).standard_normal(a.n).astype(np.float32)
    _, nat_fact = solve_sharded(a, b, k=1, band_rows=16, tol=1e-6)
    assert nat_fact.ordering is None
    with pytest.raises(ValueError, match="different row ordering"):
        solve_sharded(a, b, k=1, band_rows=16, tol=1e-6, fact=nat_fact, ordering="rcm")
    assert nat_fact.ordering is None  # unstamped: fact.solve stays natural
    # the legitimate round-trips still work: adopt, or pass the same spec
    _, of = solve_sharded(a, b, k=1, band_rows=16, tol=1e-6, ordering="rcm")
    assert of.ordering is not None
    r1, _ = solve_sharded(a, b, k=1, band_rows=16, tol=1e-6, fact=of)
    r2, _ = solve_sharded(a, b, k=1, band_rows=16, tol=1e-6, fact=of, ordering="rcm")
    assert np.array_equal(r1.x.view(np.int32), r2.x.view(np.int32))


def test_ordered_fact_solve_boundary():
    from repro.core.api import ilu

    a = poisson_2d(8)
    b = np.random.default_rng(3).standard_normal(a.n).astype(np.float32)
    fact = ilu(a, 1, ordering="rcm")
    ref = ilu(permuted_system(a, fact.ordering), 1)
    got = fact.solve(b)
    want = fact.ordering.unpermute_vector(ref.solve(fact.ordering.permute_vector(b)))
    assert np.array_equal(np.asarray(got).view(np.int32), np.asarray(want).view(np.int32))


# --------------------------------------------------------------------------
# 1/2/4-device bitwise contract (subprocess: device count locks at init)
# --------------------------------------------------------------------------
def _run_ordered(devices, ordering, n=96, k=1, band_rows=8, broadcast="psum"):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"  # never probe for a real TPU
    rc, out, err = run_checked(
        [sys.executable, SCRIPT, str(n), str(k), str(band_rows), broadcast,
         "--ordering", ordering],
        env=env, timeout=300,
    )
    assert rc == 0, f"stdout:\n{out}\nstderr:\n{err[-2000:]}"
    assert "bitwise-equal" in out


@pytest.mark.parametrize("devices", [1, 2, 4])
def test_ordered_sharded_solve_bitwise(devices):
    """Sharded ordered solves == the single-device permuted path, bitwise,
    on 1/2/4 devices (single and bucketed multi-RHS)."""
    _run_ordered(devices, "fusion")


def test_ordered_sharded_solve_bitwise_rcm():
    _run_ordered(2, "rcm", k=2)
