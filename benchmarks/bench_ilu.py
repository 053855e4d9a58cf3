"""Paper-table benchmarks for TOP-ILU. One function per table/figure.

All matrices are scaled to container time budgets (paper densities kept);
sequential phase times are MEASURED on this implementation, cluster
speedups come from the calibrated model in ``repro.core.perf_model``
(1-core container — see DESIGN.md §8.2). Quick mode shrinks sizes further.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (
    matgen,
    convection_diffusion_2d,
    numeric_ilu_ref,
    pilu1_symbolic,
    symbolic_ilu_k,
)
from repro.core.api import ilu
from repro.core.perf_model import (
    GIG_E, INFINIBAND, ClusterSpec, WorkloadStats, predict_times, speedup_curve,
)


def _measure(a, k):
    t0 = time.perf_counter()
    pat = pilu1_symbolic(a) if k == 1 else symbolic_ilu_k(a, k)
    t1 = time.perf_counter()
    numeric_ilu_ref(a, pat)
    t2 = time.perf_counter()
    return pat, t1 - t0, t2 - t1


def table1_load_balancing(quick=True):
    """Table I: dynamic vs static LB, k=2/3 — static wins at every P."""
    n = 2000 if quick else 8000
    a = matgen(n, density=0.0025 if quick else 0.001, seed=0)
    rows = []
    for k, cpus in ((2, 4), (3, 7), (3, 10)):
        pat, ts, tn = _measure(a, k)
        w = WorkloadStats(n=n, n_f=pat.nnz, t_symbolic=ts, t_numeric=tn,
                          n_bands=max(n // 64, 1), k=k)
        spec = ClusterSpec(bandwidth=GIG_E)
        dyn = predict_times(w, cpus, spec, dynamic_lb=True)
        sta = predict_times(w, cpus, spec, dynamic_lb=False)
        rows.append((n, "D", cpus, k, round(dyn["speedup"], 1)))
        rows.append((n, "S", cpus, k, round(sta["speedup"], 1)))
    return ("n,LB,cpus,k,speedup", rows,
            all(rows[i][4] <= rows[i + 1][4] for i in range(0, len(rows), 2)))


def fig6_symbolic_vs_numeric(quick=True):
    """Fig 6: the symbolic/numeric time ratio does not decrease with k."""
    sizes = [512, 1024] if quick else [1024, 2048, 4096, 8192]
    dens = {512: 0.073, 1024: 0.073, 2048: 0.036, 4096: 0.009, 8192: 0.002}
    rows = []
    for n in sizes:
        ratios = []
        for k in range(1, 4 if quick else 6):
            a = matgen(n, density=dens[n], seed=1)
            _, ts, tn = _measure(a, k)
            ratios.append(round(ts / max(tn, 1e-9), 3))
        rows.append((n, ratios, all(ratios[i + 1] >= ratios[i] * 0.5
                                    for i in range(len(ratios) - 1))))
    return ("n,sym/num ratios by k", rows)


def tables23_pilu1(quick=True):
    """Tables II/III: sequential vs PILU(1), k=1, paper-style densities."""
    cases = ([(2000, 0.01)] if quick else [(4000, 0.003), (8000, 0.001), (16000, 0.0006)])
    rows = []
    for n, dens in cases:
        a = matgen(n, density=dens, seed=2)
        pat, ts, tn = _measure(a, 1)
        w = WorkloadStats(n=n, n_f=pat.nnz, t_symbolic=ts, t_numeric=tn,
                          n_bands=max(n // 8, 1), k=1)
        for cpus in (30, 40, 50, 60):
            pred = predict_times(w, cpus, ClusterSpec(bandwidth=GIG_E))
            rows.append((n, cpus, pat.nnz, round(ts, 3), round(tn, 3), round(pred["speedup"], 1)))
    return ("n,cpus,final_entries,t_sym,t_num,predicted_speedup", rows)


def fig8_infiniband(quick=True):
    """Fig 8: more bandwidth (InfiniBand) extends scaling to 80-100 CPUs."""
    n = 2000 if quick else 16000
    a = matgen(n, density=0.01 if quick else 0.0006, seed=3)
    pat, ts, tn = _measure(a, 1)
    w = WorkloadStats(n=n, n_f=pat.nnz, t_symbolic=ts, t_numeric=tn, n_bands=max(n // 8, 1), k=1)
    ps = (20, 40, 60, 80, 100)
    ge = speedup_curve(w, ps, ClusterSpec(bandwidth=GIG_E))
    ib = speedup_curve(w, ps, ClusterSpec(bandwidth=INFINIBAND))
    better = all(ib[p] >= ge[p] for p in ps)
    peak_ge = max(ge, key=ge.get)
    peak_ib = max(ib, key=ib.get)
    return ("P,gigE,infiniband", [(p, round(ge[p], 1), round(ib[p], 1)) for p in ps],
            better, peak_ib >= peak_ge)


def fig9_grid_latency(quick=True):
    """Fig 9: inter-cluster latency degrades speedup gracefully."""
    n = 2000 if quick else 8000
    a = matgen(n, density=0.0046 if not quick else 0.01, seed=4)
    pat, ts, tn = _measure(a, 1)
    w = WorkloadStats(n=n, n_f=pat.nnz, t_symbolic=ts, t_numeric=tn, n_bands=max(n // 16, 1), k=1)
    rows = []
    for n_clusters, lat_ms in ((1, 0.0), (2, 17.0), (2, 24.0), (3, 17.0)):
        p = 100 if n_clusters == 1 else n_clusters * 50
        pred = predict_times(
            w, p, ClusterSpec(bandwidth=GIG_E, n_clusters=n_clusters,
                              inter_latency=lat_ms * 1e-3)
        )
        rows.append((f"{n_clusters}x{p//n_clusters}", lat_ms, round(pred["speedup"], 1)))
    monotone = rows[0][2] >= rows[1][2] >= rows[2][2]
    return ("clusters,latency_ms,speedup", rows, monotone)


def solver_engine(quick=True, n_rhs=4):
    """Device-resident preconditioned GMRES on the default solver problem
    (2-D Poisson, n≈16k full / n≈1k quick, ILU(1)).

    Measures what the paper says dominates at scale: preconditioner-apply
    latency and sustained GMRES iteration throughput. Returns a metrics
    dict (also serialized by ``run.py --emit-json``). ``first_solve``
    includes the one-time jit of the fused engine; ``steady_solve`` is what
    every later solve against the same factorization costs (the plan,
    device arrays, and compiled engine are all cached on it).
    """
    import jax.numpy as jnp

    from repro.core import poisson_2d
    from repro.core.solvers import csr_to_ell_arrays, gmres, gmres_batched, make_ell_matvec

    nx = 32 if quick else 128
    a = poisson_2d(nx)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(a.n).astype(np.float32)

    t0 = time.perf_counter()
    fact = ilu(a, 1, backend="oracle")
    t1 = time.perf_counter()
    cols, vals = csr_to_ell_arrays(a)
    matvec = make_ell_matvec(cols, vals, a.n)
    precond = fact.precond()
    t2 = time.perf_counter()

    res = gmres(matvec, jnp.asarray(b), precond, tol=1e-5)
    t3 = time.perf_counter()
    reps = 3
    t4 = time.perf_counter()
    for r in range(reps):
        res = gmres(matvec, jnp.asarray(b), precond, tol=1e-5)
    t5 = time.perf_counter()
    steady = (t5 - t4) / reps

    # preconditioner-apply latency (the per-iteration hot path)
    bj = jnp.asarray(b)
    precond(bj).block_until_ready()
    t6 = time.perf_counter()
    for _ in range(50):
        out = precond(bj)
    out.block_until_ready()
    t7 = time.perf_counter()
    apply_s = (t7 - t6) / 50

    B = rng.standard_normal((n_rhs, a.n)).astype(np.float32)
    gmres_batched(matvec, jnp.asarray(B), precond, tol=1e-5)  # compile
    t8 = time.perf_counter()
    outs = gmres_batched(matvec, jnp.asarray(B), precond, tol=1e-5)
    t9 = time.perf_counter()

    return {
        "problem": {"kind": "poisson_2d", "n": a.n, "nnz": a.nnz, "k": 1,
                    "fill_nnz": fact.nnz, "tol": 1e-5, "restart": 30},
        "factorize_seconds": t1 - t0,
        "engine_build_seconds": t2 - t1,
        "gmres_first_solve_seconds": t3 - t2,  # includes one-time jit
        "gmres_steady_solve_seconds": steady,
        "gmres_iterations": res.iterations,
        "gmres_iters_per_sec": res.iterations / steady,
        "precond_apply_seconds": apply_s,
        "precond_applies_per_sec": 1.0 / apply_s,
        "batched_rhs": n_rhs,
        "batched_steady_seconds_per_rhs": (t9 - t8) / n_rhs,
        "batched_converged": all(o.converged for o in outs),
        "converged": res.converged,  # health flag — the harness always completes
        "residual": res.residual,
    }


def factorization(quick=True, sizes=None, k=1):
    """PR-2 tentpole metrics: the plan→compile→execute factorization
    pipeline on 2-D Poisson at n∈{4k,16k} (quick: {1k,4k}).

    Per size: vectorized symbolic, FactorPlan build, wavefront numeric
    engine (first call = includes the one-time jit; steady = what every
    refactorization of the same structure costs), and the sequential
    oracle for the speedup ratio + the bitwise check. Serialized by
    ``run.py --emit-json`` into BENCH_factor.json.
    """
    from repro.core import poisson_2d
    from repro.core.factor_plan import build_factor_plan

    if sizes is None:
        sizes = (32, 64) if quick else (64, 128)  # nx; n = nx^2
    out = {"bench": "factorization", "k": k, "cases": []}
    for nx in sizes:
        a = poisson_2d(nx)
        t0 = time.perf_counter()
        pat = pilu1_symbolic(a) if k == 1 else symbolic_ilu_k(a, k)
        t1 = time.perf_counter()
        plan = build_factor_plan(a, pat)
        t2 = time.perf_counter()
        plan.factorize()  # first call: one-time engine jit
        t3 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            vals = plan.factorize()
        t4 = time.perf_counter()
        t5 = time.perf_counter()
        want = numeric_ilu_ref(a, pat)
        t6 = time.perf_counter()
        steady = (t4 - t3) / reps
        out["cases"].append({
            "n": a.n, "nnz": a.nnz, "fill_nnz": pat.nnz,
            "rounds": plan.n_rounds, "max_ops": plan.max_ops,
            "symbolic_seconds": t1 - t0,
            "plan_build_seconds": t2 - t1,
            "numeric_first_seconds": t3 - t2,  # includes one-time jit
            "numeric_steady_seconds": steady,
            "oracle_numeric_seconds": t6 - t5,
            "steady_speedup_vs_oracle": (t6 - t5) / max(steady, 1e-9),
            "bitwise_equal_oracle": bool(
                np.array_equal(vals.view(np.int32), want.view(np.int32))
            ),
        })
    return out


def fig5_e40r3000(quick=True):
    """Fig 5: driven-cavity surrogate — parallel ILU(3)/ILU(6) both finish
    fast; ILU(6) is far more expensive sequentially."""
    nx = 40 if quick else 131  # 131^2 = 17161 ~ e40r3000's 17281
    a = convection_diffusion_2d(nx, seed=5)
    out = []
    for k in (3, 6) if not quick else (2, 3):
        pat, ts, tn = _measure(a, k)
        w = WorkloadStats(n=a.n, n_f=pat.nnz, t_symbolic=ts, t_numeric=tn,
                          n_bands=max(a.n // 32, 1), k=k)
        par = predict_times(w, 6, ClusterSpec(bandwidth=GIG_E))
        out.append((k, pat.nnz, round(ts + tn, 3), round(par["t_total"], 3)))
    seq_ratio = out[1][2] / max(out[0][2], 1e-9)
    par_ratio = out[1][3] / max(out[0][3], 1e-9)
    return ("k,entries,t_seq,t_par6", out, seq_ratio, par_ratio)
