"""Subprocess body for multi-device TOP-ILU tests.

Run as:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         python tests/multidevice_check.py <n> <k> <band_rows> <broadcast> \
             [--solve] [--batch]

Exits 0 iff the multi-device sharded TOP-ILU factorization is bitwise equal
to the sequential oracle AND each device's value shard has the sharded
(s_loc, W) shape, not the replicated (n_pad, W) one. With ``--solve`` it
additionally runs the distributed preconditioner apply + GMRES solve and
asserts both bitwise equal to the single-device path; ``--batch`` further
runs a ragged multi-RHS ``solve_sharded`` (bucketed batch) and asserts
every column bitwise equal to its per-column single-device solve.
(Separate process because the device count is locked at first JAX init.)

``--ordering NAME`` runs the *reordered* pipeline instead (works at any
device count, including 1): resolve the named ordering for this mesh,
assert the sharded ordered factorization bitwise-equal to the sequential
oracle on the permuted matrix, and assert single- and multi-RHS
``solve_sharded(ordering=...)`` bitwise-equal to the single-device
*permuted* solve mapped back through the permutation.

``--inverse`` runs the incomplete-inverse contract instead (any device
count, including 1): over ordering ∈ {natural, rcm, fusion} × k ∈ {0,1,2},
the inverse factors and the distributed SpMV-chain apply (single and
batched RHS) of the permuted system must be bitwise-equal to the
single-threaded inverse oracle of the permuted matrix; plus one
end-to-end ``solve_sharded(precond_method="inverse")`` bitwise vs the
single-device inverse solve mapped back through the permutation.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def check_ordering(n, k, band_rows, broadcast, name):
    import numpy as np
    import jax

    from repro.core import matgen, numeric_ilu_ref, symbolic_ilu_k, pilu1_symbolic
    from repro.core.api import ilu_sharded
    from repro.core.ordering import make_ordering, permuted_system
    from repro.core.solvers import solve_sharded, solve_with_ilu

    d = len(jax.devices())
    a = matgen(n, density=min(0.08, 12.0 / n), seed=42)
    # one Ordering object shared by the sharded run and the single-device
    # reference: the bitwise contract is relative to a fixed permutation
    ord_ = make_ordering(a, name, n_devices=d, band_rows=band_rows)
    assert ord_ is not None and np.array_equal(
        np.sort(ord_.perm), np.arange(n)), "not a permutation"
    ap = permuted_system(a, ord_)

    # sharded factors == sequential oracle of the permuted matrix
    pat = pilu1_symbolic(ap) if k == 1 else symbolic_ilu_k(ap, k)
    want = numeric_ilu_ref(ap, pat)
    fact = ilu_sharded(a, k, band_rows=band_rows, broadcast=broadcast, ordering=ord_)
    got = fact.values_csr()
    assert np.array_equal(got.view(np.int32), want.view(np.int32)), \
        "ordered sharded factors != sequential oracle on permuted matrix"

    # ordered sharded solve == single-device permuted solve, mapped back
    b = np.random.default_rng(7).standard_normal(n).astype(np.float32)
    r_sh, _ = solve_sharded(a, b, k=k, band_rows=band_rows, tol=1e-6,
                            broadcast=broadcast, fact=fact)
    r_1p, _ = solve_with_ilu(ap, b[ord_.perm], k=k, tol=1e-6)
    assert r_sh.converged and r_sh.iterations == r_1p.iterations
    assert np.array_equal(r_sh.x.view(np.int32),
                          r_1p.x[ord_.iperm].view(np.int32)), \
        "ordered distributed solve != single-device permuted solve"

    # multi-RHS through the bucketed batch path: per-column bitwise
    B = np.random.default_rng(8).standard_normal((3, n)).astype(np.float32)
    rs, _ = solve_sharded(a, B, k=k, band_rows=band_rows, tol=1e-6, broadcast=broadcast, fact=fact)
    assert len(rs) == 3
    for i, r in enumerate(rs):
        r1, _ = solve_with_ilu(ap, B[i][ord_.perm], k=k, tol=1e-6)
        assert r.converged and r.iterations == r1.iterations, i
        assert np.array_equal(r.x.view(np.int32),
                              r1.x[ord_.iperm].view(np.int32)), \
            f"ordered batched column {i} != single-device permuted solve"

    print(f"OK: n={n} k={k} band_rows={band_rows} broadcast={broadcast} "
          f"devices={d} ordering={name} nnz={pat.nnz} bitwise-equal")


def check_inverse(n, band_rows, broadcast):
    import numpy as np
    import jax

    from repro.core import matgen, numeric_ilu_ref, symbolic_ilu_k, pilu1_symbolic
    from repro.core.inverse import InversePrecondApply, ShardedInversePrecondApply
    from repro.core.inverse_ref import (
        inverse_apply_ref,
        inverse_pattern_ref,
        inverse_values_ref,
    )
    from repro.core.ordering import make_ordering, permuted_system
    from repro.core.solvers import solve_sharded, solve_with_ilu

    d = len(jax.devices())
    mesh = None
    if d > 1:
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()), ("band",))
    a = matgen(n, density=min(0.08, 12.0 / n), seed=42)
    rng = np.random.default_rng(7)
    b = rng.standard_normal(n).astype(np.float32)
    B = rng.standard_normal((3, n)).astype(np.float32)

    for name in ("natural", "rcm", "fusion"):
        ord_ = make_ordering(a, name, n_devices=d, band_rows=band_rows)
        ap = a if ord_ is None else permuted_system(a, ord_)
        for k in (0, 1, 2):
            # the single-threaded oracle of the *permuted* matrix is the
            # anchor: pattern, values, and applies must all match it bitwise
            pat = pilu1_symbolic(ap) if k == 1 else symbolic_ilu_k(ap, k)
            vals = numeric_ilu_ref(ap, pat)
            wc, zc = inverse_pattern_ref(pat)
            wv, zv = inverse_values_ref(pat, vals, wc, zc)
            if d > 1:
                p = ShardedInversePrecondApply(pat, vals, mesh)
                got_w, got_z = np.asarray(p.base.w_vals), np.asarray(p.base.z_vals)
            else:
                p = InversePrecondApply(pat, vals)
                got_w, got_z = np.asarray(p.w_vals), np.asarray(p.z_vals)
            assert np.array_equal(p.plan.w_cols, wc), (name, k)
            assert np.array_equal(p.plan.z_cols, zc), (name, k)
            assert np.array_equal(got_w.view(np.int32), wv.view(np.int32)), \
                f"W values != inverse oracle ({name}, k={k})"
            assert np.array_equal(got_z.view(np.int32), zv.view(np.int32)), \
                f"Z values != inverse oracle ({name}, k={k})"
            want_1 = inverse_apply_ref(wc, wv, zc, zv, b)
            want_B = inverse_apply_ref(wc, wv, zc, zv, B)
            assert np.array_equal(np.asarray(p(b)).view(np.int32),
                                  want_1.view(np.int32)), \
                f"inverse apply != oracle ({name}, k={k}, devices={d})"
            assert np.array_equal(np.asarray(p.batched(B)).view(np.int32),
                                  want_B.view(np.int32)), \
                f"batched inverse apply != oracle ({name}, k={k}, devices={d})"

    # one end-to-end integration config: the full sharded pipeline with
    # precond_method="inverse" == the single-device inverse solve, mapped
    # back through the permutation (single RHS + bucketed 3-RHS batch)
    name = "fusion" if d > 1 else "natural"
    ord_ = make_ordering(a, name, n_devices=d, band_rows=band_rows)
    ap = a if ord_ is None else permuted_system(a, ord_)
    bp = b if ord_ is None else b[ord_.perm]
    r_sh, fact = solve_sharded(a, b, k=1, band_rows=band_rows, tol=1e-6,
                               broadcast=broadcast, ordering=ord_,
                               precond_method="inverse")
    r_1p, _ = solve_with_ilu(ap, bp, k=1, tol=1e-6, precond_method="inverse")
    x_sh = r_sh.x if ord_ is None else r_sh.x[ord_.perm]
    assert r_sh.converged and r_sh.iterations == r_1p.iterations
    assert np.array_equal(x_sh.view(np.int32), r_1p.x.view(np.int32)), \
        "inverse-preconditioned distributed solve != single-device solve"
    rs, _ = solve_sharded(a, B, k=1, band_rows=band_rows, tol=1e-6,
                          broadcast=broadcast, fact=fact,
                          precond_method="inverse")
    assert len(rs) == 3
    for i, r in enumerate(rs):
        r1, _ = solve_with_ilu(ap, B[i] if ord_ is None else B[i][ord_.perm],
                               k=1, tol=1e-6,
                               precond_method="inverse")
        assert r.converged and r.iterations == r1.iterations, i
        xi = r.x if ord_ is None else r.x[ord_.perm]
        assert np.array_equal(xi.view(np.int32), r1.x.view(np.int32)), \
            f"inverse-preconditioned batched column {i} != single-device solve"

    print(f"OK: n={n} band_rows={band_rows} broadcast={broadcast} devices={d} "
          f"inverse orderings=natural,rcm,fusion k=0,1,2 bitwise-equal")


def main():
    n, k, band_rows, broadcast = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
    if "--inverse" in sys.argv:
        return check_inverse(n, band_rows, broadcast)
    if "--ordering" in sys.argv:
        return check_ordering(n, k, band_rows, broadcast,
                              sys.argv[sys.argv.index("--ordering") + 1])
    check_solve = "--solve" in sys.argv
    import numpy as np
    import jax

    from repro.core import matgen, numeric_ilu_ref, symbolic_ilu_k, pilu1_symbolic
    from repro.core.top_ilu import topilu_factor_sharded

    devs = jax.devices()
    assert len(devs) >= 2, f"expected multi-device, got {devs}"
    a = matgen(n, density=min(0.08, 12.0 / n), seed=42)
    pat = pilu1_symbolic(a) if k == 1 else symbolic_ilu_k(a, k)
    want = numeric_ilu_ref(a, pat)
    fact = topilu_factor_sharded(a, pat, band_rows=band_rows, broadcast=broadcast)
    got = fact.values_csr()
    mism = np.nonzero(got.view(np.int32) != want.view(np.int32))[0]
    if mism.size:
        print(f"FAIL: {mism.size}/{want.size} bitwise mismatches; first {mism[:5]}")
        print("got ", got[mism[:5]])
        print("want", want[mism[:5]])
        sys.exit(1)

    # sharded storage: every device holds exactly its (s_loc, W) block
    plan = fact.plan
    shapes = {s.data.shape for s in fact.loc_vals.addressable_shards}
    assert shapes == {(1, plan.s_loc, plan.width)}, shapes
    assert plan.s_loc == plan.n_pad // len(devs)
    assert plan.per_device_value_bytes() < plan.replicated_value_bytes()

    check_batch = "--batch" in sys.argv
    if check_solve or check_batch:
        from repro.core.api import ilu
        from repro.core.solvers import solve_with_ilu, solve_sharded

        b = np.random.default_rng(7).standard_normal(n).astype(np.float32)
        ref_fact = ilu(a, k, backend="jax")
        y_ref = np.asarray(ref_fact.precond()(b))
        y_sh = np.asarray(fact.precond()(b))
        assert np.array_equal(y_ref.view(np.int32), y_sh.view(np.int32)), \
            "sharded precond apply != single-device apply"
        r_ref, _ = solve_with_ilu(a, b, k=k, tol=1e-6)
        r_sh, _ = solve_sharded(a, b, k=k, band_rows=band_rows, tol=1e-6,
                                broadcast=broadcast, fact=fact)
        assert r_sh.converged
        assert np.array_equal(r_ref.x.view(np.int32), r_sh.x.view(np.int32)), \
            "distributed solve solution != single-device solution"

    if check_batch:
        # ragged batch: 3 RHS pad to the 4-bucket; every real column must
        # equal its per-column single-device solve bitwise
        B = np.random.default_rng(8).standard_normal((3, n)).astype(np.float32)
        rs, _ = solve_sharded(a, B, k=k, band_rows=band_rows, tol=1e-6,
                              broadcast=broadcast, fact=fact)
        assert len(rs) == 3
        for i, r in enumerate(rs):
            r1, _ = solve_with_ilu(a, B[i], k=k, tol=1e-6)
            assert r.converged and r.iterations == r1.iterations, i
            assert np.array_equal(r.x.view(np.int32), r1.x.view(np.int32)), \
                f"batched sharded column {i} != single-device solve"

    print(f"OK: n={n} k={k} band_rows={band_rows} broadcast={broadcast} "
          f"devices={len(devs)} nnz={pat.nnz} s_loc={plan.s_loc} "
          f"halo={plan.halo_size} solve={check_solve} batch={check_batch} "
          f"bitwise-equal")


if __name__ == "__main__":
    main()
