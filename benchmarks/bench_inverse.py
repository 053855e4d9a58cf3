"""Incomplete-inverse (SpMV-chain) preconditioner trajectory.

    python benchmarks/bench_inverse.py <grid> <devices> [--json PATH]

Spawns itself with ``XLA_FLAGS=--xla_force_host_platform_device_count``
(device count locks at first JAX init). Measures the head-to-head the
inverse method exists for: the sharded sweep pays one collective per fused
epoch (tens per apply on a Poisson structure), while the level-truncated
inverse apply ``x = Z (W b)`` is two ELL SpMVs with exactly two untiled
all-gathers — communication independent of wavefront depth. Per device
count the record holds:

* steady apply wall times — distributed inverse apply (single RHS and an
  8-RHS batch) vs the *fusion-ordered* sweep apply (the best sweep number
  on the committed ``BENCH_sweep.json`` trajectory);
* distributed inverse-preconditioned GMRES on the Poisson fixture —
  iterations, convergence, and the bitwise-vs-single-device anchor — plus
  convergence on the random ``matgen`` fixture;
* the modeled communication both sides of the ``"auto"`` policy see
  (``sweep_comm_model`` vs ``inverse_comm_model``) and the method the
  policy actually picks.

``benchmarks/run.py --emit-json BENCH_inverse.json`` aggregates 1/2/8
devices into the committed trajectory.
"""
import json
import os
import subprocess
import sys

if os.environ.get("_BENCH_INVERSE_CHILD") != "1" and __name__ == "__main__":
    d = sys.argv[2] if len(sys.argv) > 2 else "2"
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
    env.setdefault("JAX_PLATFORMS", "cpu")  # don't probe for real TPUs
    env["_BENCH_INVERSE_CHILD"] = "1"
    sys.exit(subprocess.run([sys.executable, __file__] + sys.argv[1:], env=env).returncode)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import numpy as np


def _steady_apply(apply_fn, arg, reps=20):
    import jax

    np.asarray(apply_fn(arg))  # warm the cached executable
    t0 = time.perf_counter()
    for _ in range(reps):
        out = apply_fn(arg)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def measure(grid: int, band_rows: int = 16, batch: int = 8) -> dict:
    import jax

    from repro.core import matgen, poisson_2d
    from repro.core.inverse import (
        inverse_comm_model,
        modeled_apply_cost,
        resolve_precond_method,
    )
    from repro.core.ordering import make_ordering, sweep_comm_model
    from repro.core.solvers import solve_sharded, solve_with_ilu, warm_solve
    from repro.core.symbolic import pilu1_symbolic

    d = len(jax.devices())
    a = poisson_2d(grid)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(a.n).astype(np.float32)
    bs = rng.standard_normal((batch, a.n)).astype(np.float32)

    # --- serving warmup: inverse-chain compiles land here ------------------
    t0 = time.perf_counter()
    warm_solve(a, k=1, batch_sizes=(1, batch), band_rows=band_rows, tol=1e-6,
               precond_method="inverse")
    warm_seconds = time.perf_counter() - t0

    res, fact = solve_sharded(a, b, k=1, band_rows=band_rows, tol=1e-6, precond_method="inverse")
    assert res.converged

    # bitwise anchor: distributed inverse solve == single-device inverse solve
    res1, _ = solve_with_ilu(a, b, k=1, tol=1e-6,
                             precond_method="inverse")
    bitwise = bool(np.array_equal(res.x.view(np.int32), res1.x.view(np.int32)))

    # --- steady apply: inverse chain vs the fusion-ordered sweep -----------
    ap_inv = fact.precond(method="inverse")
    inv_apply = _steady_apply(ap_inv, b)
    inv_apply_batched = _steady_apply(ap_inv.batched, bs)

    if d > 1:
        ordering = make_ordering(a, "fusion", n_devices=d, band_rows=band_rows)
        res_sw, fact_sw = solve_sharded(a, b, k=1, band_rows=band_rows, tol=1e-6, ordering=ordering)
        sweep_ordering = "fusion"
        sw_b = ordering.permute_vector(b)
    else:
        res_sw, fact_sw = solve_sharded(a, b, k=1, band_rows=band_rows, tol=1e-6)
        sweep_ordering = "natural"
        sw_b = b
    assert res_sw.converged
    sweep_apply = _steady_apply(fact_sw.precond(), sw_b)

    t0 = time.perf_counter()
    solve_reps = 3
    for _ in range(solve_reps):
        r2, _ = solve_sharded(a, b, k=1, band_rows=band_rows, tol=1e-6,
                              precond_method="inverse", fact=fact)
    gmres_steady = (time.perf_counter() - t0) / solve_reps
    assert r2.iterations == res.iterations

    # --- the two sides of the "auto" cost model ----------------------------
    pat = pilu1_symbolic(a)
    sweep_model = sweep_comm_model(pat, band_rows, d)
    inv_model = inverse_comm_model(a.n, d)
    plan = ap_inv.plan  # the factorization's own inverse plan (built once)
    auto = resolve_precond_method("auto", pat, n_devices=d, band_rows=band_rows)

    # --- random matgen fixture: the chain still preconditions --------------
    r_mat = matgen(a.n, density=0.006, seed=3)
    br = rng.standard_normal(r_mat.n).astype(np.float32)
    res_r, _ = solve_sharded(r_mat, br, k=1, band_rows=band_rows, tol=1e-6,
                             precond_method="inverse")
    res_r1, _ = solve_with_ilu(r_mat, br, k=1, tol=1e-6, precond_method="inverse")
    random_bitwise = bool(np.array_equal(res_r.x.view(np.int32), res_r1.x.view(np.int32)))

    return {
        "devices": d,
        "n": a.n,
        "grid": grid,
        "k": 1,
        "band_rows": band_rows,
        "batch": batch,
        "bitwise_equal_single_device": bitwise,
        "iterations_inverse": res.iterations,
        "iterations_sweep": res_sw.iterations,
        "inverse_nnz": plan.nnz_inverse(),
        "factor_nnz": pat.nnz,
        "value_depth": plan.depth,
        # communication per apply, as the "auto" policy models it
        "sweep_collectives_per_apply": sweep_model["collectives_per_apply"],
        "sweep_bytes_per_apply": sweep_model["bytes_per_apply"],
        "inverse_collectives_per_apply": inv_model["collectives_per_apply"],
        "inverse_bytes_per_apply": inv_model["bytes_per_apply"],
        "modeled_cost_sweep": modeled_apply_cost(sweep_model),
        "modeled_cost_inverse": modeled_apply_cost(inv_model),
        "auto_method": auto,
        # wall times (all D virtual devices time-slice one CPU)
        "warm_seconds": warm_seconds,
        "inverse_apply_steady_seconds": inv_apply,
        "inverse_apply_batched_seconds_per_rhs": inv_apply_batched / batch,
        "sweep_ordering": sweep_ordering,
        "sweep_apply_steady_seconds": sweep_apply,
        "gmres_steady_seconds": gmres_steady,
        # random matgen fixture: convergence + the same bitwise anchor
        "random": {
            "n": r_mat.n,
            "converged": bool(res_r.converged),
            "iterations": res_r.iterations,
            "bitwise_equal_single_device": random_bitwise,
        },
    }


def main():
    from repro.core.api import enable_jit_cache

    enable_jit_cache()
    grid = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    out = None
    if "--json" in sys.argv:
        out = sys.argv[sys.argv.index("--json") + 1]
    m = measure(grid)
    text = json.dumps(m, indent=2)
    if out:
        with open(out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
