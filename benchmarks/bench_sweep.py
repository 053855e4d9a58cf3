"""Distributed epoch-fused sweep trajectory — one JSON record per device count.

    python benchmarks/bench_sweep.py <grid> <devices> [--json PATH]

Spawns itself with ``XLA_FLAGS=--xla_force_host_platform_device_count``
(device count locks at first JAX init). Measures the solve-side hot path of
the sharded preconditioner on the simulated mesh:

* communication per apply — collectives and wire bytes from the host
  epoch/read-set model (DESIGN.md §5.5), cross-checked against the
  compiled HLO (``repro.roofline.analysis``), vs the PR-3 per-level model;
* steady preconditioner-apply and distributed-GMRES wall times (single RHS
  and an 8-RHS batch riding the same collectives);
* serving warmup — ``warm_solve`` wall time and the first fresh-RHS solve
  latency after it (the "pre-warmed shape never pays the compile" number);
* the **ordering axis** (PR 5): modeled epochs/collectives/bytes per apply
  for natural vs RCM vs fusion-aware row ordering on the Poisson *and* a
  random matgen structure (quantifying the ROADMAP "2-3x fusion" item),
  plus measured steady apply latency and a bitwise-vs-single-device-
  permuted assert for every ordered Poisson solve.

``benchmarks/run.py --emit-json BENCH_sweep.json`` aggregates 1/2/8 devices
into the committed trajectory.
"""
import json
import os
import subprocess
import sys

if os.environ.get("_BENCH_SWEEP_CHILD") != "1" and __name__ == "__main__":
    d = sys.argv[2] if len(sys.argv) > 2 else "2"
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
    env.setdefault("JAX_PLATFORMS", "cpu")  # don't probe for real TPUs
    env["_BENCH_SWEEP_CHILD"] = "1"
    sys.exit(subprocess.run([sys.executable, __file__] + sys.argv[1:], env=env).returncode)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import numpy as np


def _model_axis(mat, band_rows: int, d: int) -> list:
    """Modeled sweep communication per ordering (host-only, nothing
    compiled — the same epoch/read-set model the HLO tests pin)."""
    from repro.core.ordering import make_ordering, permuted_system, sweep_comm_model
    from repro.core.symbolic import pilu1_symbolic

    out = []
    for name in ("natural", "rcm", "fusion"):
        ordering = make_ordering(mat, name, n_devices=d, band_rows=band_rows)
        mp = mat if ordering is None else permuted_system(mat, ordering)
        pat = pilu1_symbolic(mp)
        rec = sweep_comm_model(pat, band_rows, d)
        out.append({
            "ordering": name,
            "levels": rec["levels"],
            "epochs": rec["epochs"],
            "collectives_per_apply": rec["collectives_per_apply"],
            "bytes_per_apply": rec["bytes_per_apply"],
            "fill_nnz": pat.nnz,
        })
    return out


def measure(grid: int, band_rows: int = 16, batch: int = 8) -> dict:
    import jax

    from repro.core import matgen, poisson_2d
    from repro.core.ordering import make_ordering, permuted_system
    from repro.core.solvers import solve_sharded, solve_with_ilu, warm_solve
    from repro.roofline.analysis import (
        collective_bytes_per_device,
        collective_op_counts,
    )

    d = len(jax.devices())
    a = poisson_2d(grid)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(a.n).astype(np.float32)
    bs = rng.standard_normal((batch, a.n)).astype(np.float32)

    # --- serving warmup: all compiles land here ---------------------------
    t0 = time.perf_counter()
    warm_solve(a, k=1, batch_sizes=(1, batch), band_rows=band_rows, tol=1e-6)
    warm_seconds = time.perf_counter() - t0

    # first fresh-RHS solve after warmup (the pre-warmed-shape latency)
    t0 = time.perf_counter()
    res, fact = solve_sharded(a, b, k=1, band_rows=band_rows, tol=1e-6)
    warm_first_solve = time.perf_counter() - t0
    assert res.converged

    # single-device comparison: bitwise-equal x; its first solve is NOT
    # warmed — the compile cost a cold process pays without warm_solve
    t0 = time.perf_counter()
    res1, _ = solve_with_ilu(a, b, k=1, tol=1e-6)
    single_unwarmed_first_solve = time.perf_counter() - t0
    bitwise = bool(np.array_equal(res.x.view(np.int32), res1.x.view(np.int32)))

    # --- steady state ------------------------------------------------------
    ap = fact.precond()
    reps = 20
    np.asarray(ap(b))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = ap(b)
    jax.block_until_ready(out)
    apply_steady = (time.perf_counter() - t0) / reps

    np.asarray(ap.batched(bs))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = ap.batched(bs)
    jax.block_until_ready(out)
    apply_batched_steady = (time.perf_counter() - t0) / reps

    t0 = time.perf_counter()
    solve_reps = 3
    for _ in range(solve_reps):
        r2, _ = solve_sharded(a, b, k=1, band_rows=band_rows, tol=1e-6, fact=fact)
    gmres_steady = (time.perf_counter() - t0) / solve_reps

    t0 = time.perf_counter()
    rb, _ = solve_sharded(a, bs, k=1, band_rows=band_rows, tol=1e-6, fact=fact)
    gmres_batched = time.perf_counter() - t0
    assert all(r.converged for r in rb)

    # --- ordering axis: model on two structures + measured Poisson latency -
    orderings = {
        "poisson": _model_axis(a, band_rows, d),
        "random": _model_axis(matgen(a.n, density=0.006, seed=3),
                              band_rows, d),
    }
    for rec in orderings["poisson"]:
        name = rec["ordering"]
        if name == "natural":
            o_apply, o_b, r_o = fact.precond(), b, res
        else:
            ordering = make_ordering(a, name, n_devices=d, band_rows=band_rows)
            r_o, o_fact = solve_sharded(a, b, k=1, band_rows=band_rows, tol=1e-6, ordering=ordering)
            o_apply = o_fact.precond()
            o_b = ordering.permute_vector(b)
        # ordered distributed solve == single-device solve of the same
        # permuted system (the PR's bitwise acceptance contract)
        ap_mat = a if name == "natural" else permuted_system(
            a, make_ordering(a, name, n_devices=d, band_rows=band_rows))
        r_1, _ = solve_with_ilu(ap_mat, o_b, k=1, tol=1e-6)
        x_sh = r_o.x if name == "natural" else r_o.x[
            make_ordering(a, name, n_devices=d, band_rows=band_rows).perm]
        rec["bitwise_equal_single_device_permuted"] = bool(
            np.array_equal(x_sh.view(np.int32), r_1.x.view(np.int32)))
        np.asarray(o_apply(o_b))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = o_apply(o_b)
        jax.block_until_ready(out)
        rec["precond_apply_steady_seconds"] = (time.perf_counter() - t0) / reps

    # --- communication model vs compiled HLO -------------------------------
    plan = ap.plan
    hlo = ap._engine.lower_sweep(1).compile().as_text()
    hlo_bytes = sum(collective_bytes_per_device(hlo).values())
    hlo_count = sum(collective_op_counts(hlo).values())
    return {
        "devices": d,
        "n": a.n,
        "grid": grid,
        "k": 1,
        "band_rows": band_rows,
        "batch": batch,
        "bitwise_equal_single_device": bitwise,
        "iterations": res.iterations,
        # communication per preconditioner apply
        "levels_unfused": plan.nl_levels + plan.nu_levels,
        "epochs": plan.l_sched.n_epochs + plan.u_sched.n_epochs,
        "collectives_per_apply": plan.sweep_collectives_per_apply(),
        "hlo_collectives_per_apply": hlo_count,
        "bytes_per_apply": plan.sweep_bytes_per_apply(),
        "hlo_bytes_per_apply": hlo_bytes,
        "bytes_per_apply_unfused_pr3": plan.sweep_bytes_per_apply_unfused(),
        "bytes_per_apply_batched": plan.sweep_bytes_per_apply(batch),
        # wall times (all D virtual devices time-slice one CPU)
        "warm_seconds": warm_seconds,
        "warm_first_solve_seconds": warm_first_solve,
        "single_device_unwarmed_first_solve_seconds": single_unwarmed_first_solve,
        "precond_apply_steady_seconds": apply_steady,
        "precond_apply_batched_seconds_per_rhs": apply_batched_steady / batch,
        "gmres_steady_seconds": gmres_steady,
        "gmres_batched_seconds_per_rhs": gmres_batched / batch,
        # ordering axis: natural vs rcm vs fusion on two structures
        "orderings": orderings,
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
