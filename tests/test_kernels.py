"""Kernels vs their oracles: the BILU Pallas kernels against pure-jnp
references (interpret mode on the CPU), and the solve path's jnp SpMV,
sweeps and factorization against sequential NumPy oracles."""
import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.bitmath import hoisted_jit
from repro.core.planner import COL_SENTINEL
from repro.core.solvers import make_ell_matvec
from repro.kernels import ops
from repro.kernels import ref


RNG = np.random.default_rng(0)


def _tri_upper(bs, dtype):
    # diagonally dominant: random triangular matrices are exponentially
    # ill-conditioned, which would make the sweep test meaningless
    u = np.triu(RNG.standard_normal((bs, bs)).astype(dtype))
    np.fill_diagonal(u, np.abs(u).sum(1) + 1.0)
    return u


def _tri_unit_lower(bs, dtype):
    l = np.tril(RNG.standard_normal((bs, bs)).astype(dtype), -1)
    l /= np.maximum(np.abs(l).sum(1, keepdims=True), 1.0) * 1.5
    np.fill_diagonal(l, 1.0)
    return l


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,n,k", [(8, 8, 8), (64, 64, 32), (128, 256, 128), (96, 40, 72), (256, 128, 256)]
)
def test_panel_update_sweep(m, n, k, dtype):
    a = RNG.standard_normal((m, k)).astype(np.float32)
    b = RNG.standard_normal((k, n)).astype(np.float32)
    c = RNG.standard_normal((m, n)).astype(np.float32)
    a, b, c = (jnp.asarray(x, dtype) for x in (a, b, c))
    got = ops.panel_update(c, a, b, bm=64, bn=64, bk=32)
    want = ref.panel_update_ref(c, a, b)
    # blocked-k accumulation reorders the f32 sum; tolerance scales with k
    rtol, atol = (2e-3, 2e-4) if dtype == np.float32 else (5e-2, 1.0)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=rtol, atol=atol
    )


@pytest.mark.parametrize("bs", [8, 32, 128])
@pytest.mark.parametrize("m", [8, 64, 200])
def test_trsm_right_upper_sweep(bs, m):
    a = RNG.standard_normal((m, bs)).astype(np.float32)
    u = _tri_upper(bs, np.float32)
    got = np.asarray(ops.trsm_right_upper(jnp.asarray(a), jnp.asarray(u), bm=64))
    want = np.asarray(ref.trsm_right_upper_ref(jnp.asarray(a), jnp.asarray(u)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # X @ U == A
    np.testing.assert_allclose(got @ u, a, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("bs", [8, 32, 128])
@pytest.mark.parametrize("n", [8, 64, 200])
def test_trsm_left_unit_lower_sweep(bs, n):
    a = RNG.standard_normal((bs, n)).astype(np.float32)
    l = _tri_unit_lower(bs, np.float32)
    got = np.asarray(ops.trsm_left_unit_lower(jnp.asarray(l), jnp.asarray(a), bn=64))
    want = np.asarray(ref.trsm_left_unit_lower_ref(jnp.asarray(l), jnp.asarray(a)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(l @ got, a, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("n,w", [(16, 4), (128, 9), (500, 17), (1024, 33)])
def test_spmv_ell_sweep(n, w):
    cols = np.full((n, w), COL_SENTINEL, np.int32)
    vals = np.zeros((n, w), np.float32)
    for j in range(n):
        m = RNG.integers(1, w + 1)
        c = np.sort(RNG.choice(n, size=m, replace=False)).astype(np.int32)
        cols[j, :m] = c
        vals[j, :m] = RNG.standard_normal(m)
    x = RNG.standard_normal(n).astype(np.float32)
    got = np.asarray(make_ell_matvec(jnp.asarray(cols), jnp.asarray(vals), n)(jnp.asarray(x)))
    dense = np.zeros((n, n))
    rows = np.repeat(np.arange(n), w).reshape(n, w)
    live = cols < n
    dense[rows[live], cols[live]] = vals[live]
    np.testing.assert_allclose(got, dense @ x, rtol=1e-5, atol=1e-5)


def test_spmv_matches_csr():
    """Against scipy CSR matvec on a real matrix."""
    from repro.core import matgen
    from repro.core.solvers import csr_to_ell_arrays

    a = matgen(96, density=0.08, seed=1)
    cols, vals = csr_to_ell_arrays(a)
    x = RNG.standard_normal(a.n).astype(np.float32)
    got = np.asarray(make_ell_matvec(cols, vals, a.n)(jnp.asarray(x)))
    want = a.to_scipy() @ x
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# Bitwise contracts: the BILU kernels vs their substitution-order references,
# and the solve-path jnp forms vs sequential NumPy oracles with explicit f32
# rounding per operation. The comparison is exact (int32 view), not
# allclose — across odd widths, fully-padded sentinel rows, and block sizes
# that do not divide the data.
# --------------------------------------------------------------------------
def _assert_bitwise(got, want):
    np.testing.assert_array_equal(
        np.asarray(got, np.float32).view(np.int32),
        np.asarray(want, np.float32).view(np.int32),
    )


def _rand_ell(n, w, rng, empty_every=5):
    """Sentinel-padded ELL with ragged rows; every ``empty_every``-th row is
    fully padded (pure sentinel) to exercise the masked lanes."""
    cols = np.full((n, w), COL_SENTINEL, np.int32)
    vals = np.zeros((n, w), np.float32)
    for j in range(n):
        if empty_every and j % empty_every == 0:
            continue
        m = int(rng.integers(1, w + 1))
        c = np.sort(rng.choice(n, size=m, replace=False)).astype(np.int32)
        cols[j, :m] = c
        vals[j, :m] = rng.standard_normal(m)
    return cols, vals


def _lane_sum_ref(cols, vals, x, limit):
    """Sequential lane-order row sums in NumPy f32: every product rounded,
    then added left to right; masked lanes add +0.0 (masked_lane_sum)."""
    f32 = np.float32
    out = np.zeros(cols.shape[0], f32)
    for j in range(cols.shape[0]):
        acc = f32(0.0)
        for c, v in zip(cols[j], vals[j]):
            acc = f32(acc + (f32(f32(v) * x[c]) if c < limit else f32(0.0)))
        out[j] = acc
    return out


@pytest.mark.parametrize("n,w", [(64, 3), (100, 7), (33, 1), (129, 5), (256, 13)])
def test_spmv_ell_bitwise_vs_ref(n, w):
    rng = np.random.default_rng(n * 31 + w)
    cols, vals = _rand_ell(n, w, rng)
    x = rng.standard_normal(n).astype(np.float32)
    mv = make_ell_matvec(jnp.asarray(cols), jnp.asarray(vals), n)
    want = _lane_sum_ref(cols, vals, x, n)
    _assert_bitwise(mv(jnp.asarray(x)), want)
    # compiled with its ELL arrays as runtime operands: the same bits
    _assert_bitwise(hoisted_jit(mv)(jnp.asarray(x)), want)


@pytest.mark.parametrize("bs,m,bm", [(8, 24, 8), (32, 200, 64), (16, 24, 16), (128, 96, 64)])
def test_trsm_right_upper_bitwise_vs_subst_ref(bs, m, bm):
    a = RNG.standard_normal((m, bs)).astype(np.float32)
    u = _tri_upper(bs, np.float32)
    got = ops.trsm_right_upper(jnp.asarray(a), jnp.asarray(u), bm=bm)
    want = ref.trsm_right_upper_subst_ref(jnp.asarray(a), jnp.asarray(u))
    _assert_bitwise(got, want)


@pytest.mark.parametrize("bs,n,bn", [(8, 24, 8), (32, 200, 64), (16, 24, 16), (128, 96, 64)])
def test_trsm_left_unit_lower_bitwise_vs_subst_ref(bs, n, bn):
    a = RNG.standard_normal((bs, n)).astype(np.float32)
    l = _tri_unit_lower(bs, np.float32)
    got = ops.trsm_left_unit_lower(jnp.asarray(l), jnp.asarray(a), bn=bn)
    want = ref.trsm_left_unit_lower_subst_ref(jnp.asarray(l), jnp.asarray(a))
    _assert_bitwise(got, want)


@pytest.mark.parametrize("seed,k", [(0, 1), (2, 2)])
def test_factor_wavefront_kernel_bitwise_vs_oracle(seed, k):
    """The fused round-major wavefront factorization == the sequential
    oracle, bit for bit."""
    from repro.core import matgen, numeric_ilu_ref, symbolic_ilu_k
    from repro.core.factor_plan import build_factor_plan
    from repro.core.numeric_jax import factor_wavefront_sweeps_jnp

    a = matgen(110, density=0.06, seed=seed)
    pat = symbolic_ilu_k(a, k)
    want = numeric_ilu_ref(a, pat)
    plan = build_factor_plan(a, pat)
    dev = plan.device_arrays()
    got = factor_wavefront_sweeps_jnp(
        dev["op_row"], dev["op_lane"], dev["op_piv"], dev["op_dlane"],
        dev["op_dst"], dev["dst_flat"], jnp.asarray(plan.a_vals),
    )
    _assert_bitwise(plan.values_to_csr(np.asarray(got)), want)


# --------------------------------------------------------------------------
# Compiled (non-interpret) lowering: the platform chooses the kernel mode,
# so this runs only where a TPU is the default backend.
# --------------------------------------------------------------------------
@pytest.fixture
def tpu():
    if jax.default_backend() != "tpu":
        pytest.skip("the compiled Pallas lowering needs a TPU backend")


def test_compiled_panel_update_matches_interpret(tpu):
    pu = importlib.import_module("repro.kernels.panel_update")

    a = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
    c = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
    got = pu.panel_update(c, a, b, bm=128, bn=128, bk=128, interpret=False)
    want = pu.panel_update(c, a, b, bm=128, bn=128, bk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("seed,k", [(0, 1), (3, 2)])
def test_wavefront_kernel_bit_identical_to_triangular_solver(seed, k):
    """The cached fused wavefront apply == the same sweep run eagerly on
    the plan's grouped arrays (a tuple of one array per level group for each
    table), bit for bit: compiling it into an executable with its plan
    arrays changes no rounding."""
    from repro.core import matgen, numeric_ilu_ref, symbolic_ilu_k
    from repro.core.triangular import PrecondApply, wavefront_sweeps_jnp

    a = matgen(120, density=0.06, seed=seed)
    pat = symbolic_ilu_k(a, k)
    vals = numeric_ilu_ref(a, pat)
    b = np.random.default_rng(seed + 1).standard_normal(a.n).astype(np.float32)
    fused = PrecondApply(pat, vals)
    dev = fused.plan.device_arrays()
    assert (len(dev["l_cols"]), len(dev["u_cols"])) == fused.plan.sweep_groups
    args = (dev["l_cols"], dev["l_vals"], dev["l_rhs_idx"], dev["u_cols"],
            dev["u_vals"], dev["u_diag"], dev["u_rhs_idx"], dev["out_perm"],
            jnp.asarray(b))
    _assert_bitwise(fused(jnp.asarray(b)), wavefront_sweeps_jnp(*args))


def _epoch_args(k=1, seed=5):
    """Real epoch tables from a sharded triangular plan (D=1: one epoch per
    sweep, every address local) + synthetic values."""
    from repro.core import matgen, symbolic_ilu_k
    from repro.core.triangular import build_sharded_triangular_plan

    a = matgen(96, density=0.06, seed=seed)
    pat = symbolic_ilu_k(a, k)
    plan = build_sharded_triangular_plan(pat, 8, 1)
    s = plan.l_sched
    rng = np.random.default_rng(seed + 1)
    cols = s.cols_local[0]
    vals = rng.standard_normal(cols.shape).astype(np.float32)
    rhs = rng.standard_normal(cols.shape[:2]).astype(np.float32)
    diag = (rng.standard_normal(cols.shape[:2]) + 3).astype(np.float32)
    x0 = np.zeros(s.scratch + 1, np.float32)
    return x0, cols, vals, rhs, diag, s.scratch


@pytest.mark.parametrize("with_diag", [False, True])
def test_epoch_sweep_kernel_bitwise(with_diag):
    """The level scan over one sharded epoch == a sequential NumPy sweep of
    the same epoch, bit for bit, for both the L (unit-diagonal) and U
    (divide) variants."""
    from repro.core.triangular import level_scan_jnp

    x0, cols, vals, rhs, diag, scratch = _epoch_args()
    d = diag if with_diag else None
    got = level_scan_jnp(*(jnp.asarray(t) for t in (x0, cols, vals, rhs)),
                         None if d is None else jnp.asarray(d), 0, scratch)
    want = x0.copy()
    maxr = cols.shape[1]
    for lev in range(cols.shape[0]):
        y = rhs[lev] - _lane_sum_ref(cols[lev], vals[lev], want, scratch)
        if d is not None:
            y = y / d[lev]
        want[lev * maxr:(lev + 1) * maxr] = y
    _assert_bitwise(got, want)


@pytest.mark.parametrize("offset", [-2, -1, 0, 1, 2])
def test_nearest_quotient_repairs_an_ulp(offset):
    """exact_div's repair step: from a quotient up to two ulps off either
    way (what the TPU's divide returns), it recovers NumPy's correctly
    rounded a / b, bit for bit."""
    from repro.core.bitmath import exact_div, nearest_quotient

    rng = np.random.default_rng(17)
    a = (rng.standard_normal(4096) * 3).astype(np.float32)
    b = (rng.standard_normal(4096) + 3).astype(np.float32)
    want = a / b
    q = want
    for _ in range(abs(offset)):
        q = np.nextafter(q, np.float32(np.inf * offset))
    got = jax.jit(nearest_quotient)(jnp.asarray(a), jnp.asarray(b), jnp.asarray(q))
    _assert_bitwise(got, want)
    _assert_bitwise(jax.jit(exact_div)(jnp.asarray(a), jnp.asarray(b)), want)


@pytest.mark.parametrize("a_scale,b_scale", [
    (1e20, 1e-17),   # quotients near the f32 maximum: a tiny pivot
    (1e37, 1e36),    # operands whose Veltkamp split would overflow unscaled
    (1e-30, 1e-8),   # small operands, small quotients (normal range)
    (1e-37, 3e-1),   # quotients just above the normal range's floor
])
def test_nearest_quotient_repairs_at_any_magnitude(a_scale, b_scale):
    """The repair holds for quotients and operands of any magnitude whose
    quotient is a normal f32: from two ulps off either way it recovers
    NumPy's correctly rounded a / b, bit for bit."""
    from repro.core.bitmath import exact_div, nearest_quotient, rounded_quotient

    rng = np.random.default_rng(23)
    a = (rng.uniform(1, 8, 4096) * rng.choice([-1, 1], 4096) * a_scale).astype(np.float32)
    b = (rng.uniform(1, 8, 4096) * rng.choice([-1, 1], 4096) * b_scale).astype(np.float32)
    with np.errstate(over="ignore"):
        want = a / b
    live = np.isfinite(want) & (np.abs(want) >= np.finfo(np.float32).tiny)
    a, b, want = a[live], b[live], want[live]
    assert want.size > 1000
    repair = jax.jit(nearest_quotient)
    for offset in (-2, 2):
        q = np.nextafter(np.nextafter(want, np.float32(np.inf * offset)),
                         np.float32(np.inf * offset))
        live_q = np.isfinite(q)
        got = repair(jnp.asarray(a[live_q]), jnp.asarray(b[live_q]), jnp.asarray(q[live_q]))
        _assert_bitwise(got, want[live_q])
    _assert_bitwise(jax.jit(exact_div)(jnp.asarray(a), jnp.asarray(b)), want)
    # the TPU form of exact_div, whatever the backend
    _assert_bitwise(jax.jit(rounded_quotient)(jnp.asarray(a), jnp.asarray(b)), want)


@pytest.mark.parametrize("offset", [-2, -1, 1, 2])
def test_nearest_quotient_at_binade_edges(offset):
    """Quotients whose neighbours cross a power of two (significands at
    the ends of [1, 2), so a / b lies next to 0.5, 1 or 2) are repaired
    like any other."""
    from repro.core.bitmath import nearest_quotient

    one_up, two_down = np.nextafter(np.float32(1), np.float32(2)), np.nextafter(
        np.float32(2), np.float32(0))
    edges = np.asarray([1, one_up, 1.5, two_down], np.float32)
    a, b = (x.ravel() for x in np.meshgrid(edges, edges))
    a = np.concatenate([a * s for s in (1, -1e-20, 3e25)]).astype(np.float32)
    b = np.concatenate([b * s for s in (1, 1e10, -7e-5)]).astype(np.float32)
    want = a / b
    q = want
    for _ in range(abs(offset)):
        q = np.nextafter(q, np.float32(np.inf * offset))
    got = jax.jit(nearest_quotient)(jnp.asarray(a), jnp.asarray(b), jnp.asarray(q))
    _assert_bitwise(got, want)
