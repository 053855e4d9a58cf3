"""Benchmark harness — one entry per paper table/figure + kernel microbench.

    PYTHONPATH=src python -m benchmarks.run [--full] [--emit-json PATH]
    PYTHONPATH=src python -m benchmarks.run --smoke [--emit-json PATH]

``--smoke`` is the CI gate: validate every committed ``BENCH_*.json``
trajectory against the checked-in schemas (``benchmarks/bench_schema.py``)
without running anything heavy (no jax import), so a malformed trajectory
commit fails CI instead of silently breaking the README tables. With
``--emit-json`` it also writes the validation report.

Prints ``name,us_per_call,derived`` CSV. Paper-table benches report their
headline derived quantity (a speedup or a ratio); kernel benches report
measured interpret-mode microseconds per call (CPU — TPU numbers come from
the roofline, EXPERIMENTS.md §Roofline).

``--emit-json BENCH_solver.json`` additionally serializes the
device-resident solver-engine metrics (preconditioner-apply latency, GMRES
iterations/sec, first/steady solve wall times) so later PRs have a perf
trajectory to compare against. ``--emit-json BENCH_topilu.json`` runs the
*distributed* sharded-TOP-ILU trajectory instead: 1/2/8 simulated devices,
per-device value bytes, and the per-superstep halo collective payload from
the roofline model (cross-checked against compiled HLO). Every process that
runs jax turns on the persistent compilation cache
(``repro.core.api.enable_jit_cache``); this parent imports jax only on the
in-process CSV path.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _t(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6, out


def bench_kernels(rows, quick=True):
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(0)
    m = 256 if quick else 1024
    a = jnp.asarray(rng.standard_normal((m, 128)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((128, m)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((m, m)), jnp.float32)
    us, _ = _t(ops.panel_update, c, a, b)
    rows.append(("kernel.panel_update", us, f"gflops={2*m*m*128/us/1e3:.1f}"))

    u = np.triu(rng.standard_normal((128, 128)).astype(np.float32))
    np.fill_diagonal(u, np.abs(u).sum(1) + 1)
    us, _ = _t(ops.trsm_right_upper, a, jnp.asarray(u))
    rows.append(("kernel.trsm_right_upper", us, f"panel={m}x128"))


def bench_paper_tables(rows, quick=True):
    from benchmarks import bench_ilu as B

    t0 = time.perf_counter()
    hdr, data, static_wins = B.table1_load_balancing(quick)
    rows.append(("paper.table1_static_vs_dynamic", (time.perf_counter() - t0) * 1e6,
                 f"static_wins={static_wins}"))

    t0 = time.perf_counter()
    hdr, data = B.fig6_symbolic_vs_numeric(quick)
    rows.append(("paper.fig6_sym_vs_num", (time.perf_counter() - t0) * 1e6, f"ratios={data[0][1]}"))

    t0 = time.perf_counter()
    hdr, data = B.tables23_pilu1(quick)
    best = max(r[5] for r in data)
    rows.append(("paper.tables23_pilu1_speedup", (time.perf_counter() - t0) * 1e6,
                 f"best_speedup={best}"))

    t0 = time.perf_counter()
    hdr, data, ib_better, ib_peak = B.fig8_infiniband(quick)
    rows.append(("paper.fig8_infiniband", (time.perf_counter() - t0) * 1e6,
                 f"ib_extends_scaling={ib_better and ib_peak}"))

    t0 = time.perf_counter()
    hdr, data, monotone = B.fig9_grid_latency(quick)
    rows.append(("paper.fig9_grid_latency", (time.perf_counter() - t0) * 1e6,
                 f"graceful_degradation={monotone} {data}"))

    t0 = time.perf_counter()
    hdr, data, seq_ratio, par_ratio = B.fig5_e40r3000(quick)
    rows.append(("paper.fig5_e40r3000", (time.perf_counter() - t0) * 1e6,
                 f"seq_k6/k3={seq_ratio:.1f} par_k6/k3={par_ratio:.1f}"))


def bench_bitcompat(rows, quick=True):
    """Not a paper table but THE paper property: parallel == sequential."""
    from repro.core import matgen, numeric_ilu_ref, pilu1_symbolic
    from repro.core.api import ilu

    n = 256 if quick else 1024
    a = matgen(n, density=0.03, seed=9)
    pat = pilu1_symbolic(a)
    want = numeric_ilu_ref(a, pat)
    t0 = time.perf_counter()
    got = ilu(a, 1, backend="jax", band_rows=16).vals
    us = (time.perf_counter() - t0) * 1e6
    eq = bool(np.array_equal(got.view(np.int32), want.view(np.int32)))
    rows.append(("paper.bitcompat_banded", us, f"bitwise_equal={eq}"))


def bench_factorization(rows, quick=True):
    """Plan→compile→execute factorization pipeline (PR-2 tentpole).

    Always measures the full sizes (n∈{4k,16k}) so BENCH_factor.json
    records the acceptance numbers; ``--full`` only raises solver sizes.
    """
    from benchmarks import bench_ilu as B

    m = B.factorization(quick=False)  # n in {4096, 16384}
    for c in m["cases"]:
        rows.append((f"factor.symbolic_n{c['n']}", c["symbolic_seconds"] * 1e6,
                     f"fill_nnz={c['fill_nnz']}"))
        rows.append((f"factor.plan_build_n{c['n']}", c["plan_build_seconds"] * 1e6,
                     f"rounds={c['rounds']}"))
        rows.append((f"factor.numeric_n{c['n']}", c["numeric_steady_seconds"] * 1e6,
                     f"speedup_vs_oracle={c['steady_speedup_vs_oracle']:.1f} "
                     f"bitwise={c['bitwise_equal_oracle']}"))
    return m


def bench_topilu(rows, devices=(1, 2, 8)):
    """Distributed sharded-TOP-ILU trajectory (PR-3 tentpole).

    Spawns one subprocess per simulated device count (the host device count
    locks at first JAX init) and aggregates the per-device memory +
    collective-payload records from ``benchmarks/bench_topilu.py``. Only
    runs when the ``--emit-json`` basename contains ``topilu`` (the same
    filename convention that selects the factorization payload): the three
    jax subprocesses are too slow to fold into every CSV run.
    """
    import subprocess

    grid = 32  # n=1024 — small enough for the 1-core CI, supersteps > 60
    child = os.path.join(os.path.dirname(__file__), "bench_topilu.py")
    cases = []
    for d in devices:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["_BENCH_TOPILU_CHILD"] = "1"
        out = subprocess.run(
            [sys.executable, child, str(grid)], env=env, capture_output=True,
            text=True, timeout=600,
        )
        if out.returncode != 0:
            raise RuntimeError(f"bench_topilu D={d} failed:\n{out.stderr[-2000:]}")
        m = json.loads(out.stdout)
        cases.append(m)
        rows.append((f"topilu.factor_d{d}", m["factor_steady_seconds"] * 1e6,
                     f"bitwise={m['bitwise_equal_oracle']} "
                     f"per_dev_B={m['per_device_value_bytes']} "
                     f"halo_B_per_step={m['halo_bytes_per_superstep']}"))
    return {"cases": cases, "grid": grid}


def bench_sweep(rows, devices=(1, 2, 8)):
    """Epoch-fused distributed sweep trajectory (PR-4 tentpole).

    One subprocess per simulated device count (the host device count locks
    at first JAX init); aggregates the sweep-communication records from
    ``benchmarks/bench_sweep.py`` (collectives/solve, bytes/solve, steady
    distributed GMRES, serving-warmup latency). Selected by an
    ``--emit-json`` basename containing ``sweep``.
    """
    import subprocess

    grid = 32  # n=1024 — same problem as the BENCH_topilu trajectory
    child = os.path.join(os.path.dirname(__file__), "bench_sweep.py")
    cases = []
    for d in devices:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["_BENCH_SWEEP_CHILD"] = "1"
        out = subprocess.run(
            [sys.executable, child, str(grid)], env=env, capture_output=True,
            text=True, timeout=1800,
        )
        if out.returncode != 0:
            raise RuntimeError(f"bench_sweep D={d} failed:\n{out.stderr[-2000:]}")
        m = json.loads(out.stdout)
        cases.append(m)
        rows.append((f"sweep.gmres_d{d}", m["gmres_steady_seconds"] * 1e6,
                     f"bitwise={m['bitwise_equal_single_device']} "
                     f"coll/apply={m['collectives_per_apply']} "
                     f"(unfused={m['levels_unfused']}) "
                     f"B/apply={m['bytes_per_apply']} "
                     f"(pr3={m['bytes_per_apply_unfused_pr3']})"))
        rows.append((f"sweep.warm_first_solve_d{d}",
                     m["warm_first_solve_seconds"] * 1e6,
                     f"batched_ms_per_rhs="
                     f"{m['gmres_batched_seconds_per_rhs'] * 1e3:.1f}"))
        by_name = {r["ordering"]: r for r in m["orderings"]["poisson"]}
        for name in ("rcm", "fusion"):
            r = by_name[name]
            rows.append((f"sweep.ordering_{name}_d{d}",
                         r["precond_apply_steady_seconds"] * 1e6,
                         f"epochs={r['epochs']} "
                         f"(natural={by_name['natural']['epochs']}) "
                         f"B/apply={r['bytes_per_apply']} "
                         f"bitwise={r['bitwise_equal_single_device_permuted']}"))
    return {"cases": cases, "grid": grid}


def bench_inverse(rows, devices=(1, 2, 8)):
    """Incomplete-inverse SpMV-chain trajectory (PR-6 tentpole).

    One subprocess per simulated device count; aggregates the
    sweep-vs-inverse apply latencies, the modeled communication both sides
    of the ``"auto"`` policy, and the bitwise anchors from
    ``benchmarks/bench_inverse.py``. Selected by an ``--emit-json``
    basename containing ``inverse``.
    """
    import subprocess

    grid = 32  # n=1024 — same problem as the BENCH_sweep trajectory
    child = os.path.join(os.path.dirname(__file__), "bench_inverse.py")
    cases = []
    for d in devices:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["_BENCH_INVERSE_CHILD"] = "1"
        out = subprocess.run(
            [sys.executable, child, str(grid)], env=env, capture_output=True,
            text=True, timeout=1800,
        )
        if out.returncode != 0:
            raise RuntimeError(f"bench_inverse D={d} failed:\n{out.stderr[-2000:]}")
        m = json.loads(out.stdout)
        cases.append(m)
        rows.append((f"inverse.apply_d{d}",
                     m["inverse_apply_steady_seconds"] * 1e6,
                     f"sweep_{m['sweep_ordering']}="
                     f"{m['sweep_apply_steady_seconds'] * 1e6:.0f}us "
                     f"coll/apply={m['inverse_collectives_per_apply']} "
                     f"(sweep={m['sweep_collectives_per_apply']}) "
                     f"bitwise={m['bitwise_equal_single_device']}"))
        rows.append((f"inverse.gmres_d{d}", m["gmres_steady_seconds"] * 1e6,
                     f"iters={m['iterations_inverse']} "
                     f"(sweep={m['iterations_sweep']}) "
                     f"auto={m['auto_method']} "
                     f"random_converged={m['random']['converged']}"))
    return {"cases": cases, "grid": grid}


def bench_serve(rows, quick=True):
    """Multi-tenant coalesced serving trajectory (PR-8 tentpole).

    One subprocess (pinned CPU platform) running the seeded 4-tenant soak
    from ``benchmarks/bench_serve.py``: end-to-end solves/sec, per-tenant
    p50/p99, compile flatness after warmup, and a seeded bitwise sample
    against solo solves. Selected by an ``--emit-json`` basename
    containing ``serve``.
    """
    import subprocess

    child = os.path.join(os.path.dirname(__file__), "bench_serve.py")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    n_requests = "2000"
    out = subprocess.run(
        [sys.executable, child, n_requests], env=env, capture_output=True,
        text=True, timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(f"bench_serve failed:\n{out.stderr[-2000:]}")
    m = json.loads(out.stdout)
    rows.append(("serve.solves_per_sec", 1e6 / m["solves_per_sec"],
                 f"solves_per_sec={m['solves_per_sec']:.0f} "
                 f"(raw={m['raw_solve_solves_per_sec']:.0f}) "
                 f"occupancy={m['occupancy_mean']:.2f}"))
    rows.append(("serve.p99_latency", m["p99_seconds"] * 1e6,
                 f"p50={m['p50_seconds'] * 1e3:.1f}ms "
                 f"batch_solve={m['mean_batch_solve_seconds'] * 1e3:.1f}ms"))
    rows.append(("serve.compile_flatness", m["warmup_seconds"] * 1e6,
                 f"after_warmup={m['compiles_after_warmup']} "
                 f"refactors={m['refactorizations']} "
                 f"bitwise={m['bitwise_equal_solo']}"))
    rb = m["robustness"]
    rows.append(("serve.robustness", rb["requests_failed"],
                 f"degraded_ok={rb['degraded_ok']} "
                 f"healthy_unaffected={rb['healthy_unaffected']} "
                 f"shifted_bindings={rb['counters']['shifted_bindings']} "
                 f"breakdown_lanes={rb['counters']['breakdown_lanes']} "
                 f"deadline_expired={rb['counters']['deadline_expired']}"))
    for c in m["sharded"]:
        rows.append((f"serve.sharded_d{c['devices']}",
                     1e6 / c["solves_per_sec"],
                     f"solves_per_sec={c['solves_per_sec']:.0f} "
                     f"after_warmup={c['compiles_after_warmup']} "
                     f"bitwise={c['bitwise_equal_solo']}"))
    return m


def bench_solver(rows, quick=True):
    """Device-resident preconditioned Krylov engine (PR-1 tentpole)."""
    from benchmarks import bench_ilu as B

    m = B.solver_engine(quick)
    rows.append(("solver.precond_apply", m["precond_apply_seconds"] * 1e6,
                 f"applies_per_sec={m['precond_applies_per_sec']:.0f}"))
    rows.append(("solver.gmres_steady", m["gmres_steady_solve_seconds"] * 1e6,
                 f"iters_per_sec={m['gmres_iters_per_sec']:.1f}"))
    rows.append(("solver.gmres_first", m["gmres_first_solve_seconds"] * 1e6,
                 f"n={m['problem']['n']} converged={m['converged']} rel={m['residual']:.1e}"))
    rows.append(("solver.gmres_batched", m["batched_steady_seconds_per_rhs"] * 1e6,
                 f"rhs={m['batched_rhs']} all_converged={m['batched_converged']}"))
    return m


def smoke(emit_json=None) -> int:
    """Validate the committed BENCH_*.json trajectories against the
    checked-in schemas. Returns the number of invalid files (CI exit code).
    Deliberately light: no jax import, runs in seconds."""
    from benchmarks.bench_schema import SCHEMAS, validate_file

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = {}
    bad = 0
    for name in sorted(SCHEMAS):
        path = os.path.join(root, name)
        errors = validate_file(path)
        report[name] = {"ok": not errors, "errors": errors}
        status = "ok" if not errors else f"INVALID ({len(errors)} errors)"
        print(f"bench-schema,{name},{status}")
        for e in errors[:20]:
            print(f"  {e}", file=sys.stderr)
        bad += bool(errors)
    if emit_json:
        with open(emit_json, "w") as f:
            json.dump({"bench": "schema_smoke", "results": report}, f, indent=2)
        print(f"wrote {emit_json}", file=sys.stderr)
    return bad


def main() -> None:
    argv = sys.argv[1:]
    quick = "--full" not in argv
    emit_json = None
    if "--emit-json" in argv:
        i = argv.index("--emit-json") + 1
        if i >= len(argv) or argv[i].startswith("--"):
            sys.exit("--emit-json requires a file path")
        emit_json = argv[i]
    if "--smoke" in argv:
        sys.exit(smoke(emit_json))
    rows = []
    topilu_metrics = None
    base = os.path.basename(emit_json) if emit_json else ""
    if "topilu" in base or "sweep" in base or "inverse" in base or "serve" in base:
        # subprocess trajectories only: spawning jax subprocesses is too
        # slow to fold into every CSV run
        if "serve" in base:
            payload = {"bench": "serve_coalescing", "quick": quick, "metrics": bench_serve(rows)}
        elif "inverse" in base:
            payload = {"bench": "inverse_chain", "quick": quick, "metrics": bench_inverse(rows)}
        elif "sweep" in base:
            payload = {"bench": "sweep_epoch_fused", "quick": quick, "metrics": bench_sweep(rows)}
        else:
            topilu_metrics = bench_topilu(rows)
            payload = {"bench": "topilu_sharded", "quick": quick, "metrics": topilu_metrics}
        print("name,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        with open(emit_json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {emit_json}", file=sys.stderr)
        return
    # the CSV benches run jax in this process; the trajectories above run
    # it only in their children, which turn the cache on themselves
    from repro.core.api import enable_jit_cache

    enable_jit_cache()
    solver_metrics = bench_solver(rows, quick)
    factor_metrics = bench_factorization(rows, quick)
    bench_bitcompat(rows, quick)
    bench_kernels(rows, quick)
    bench_paper_tables(rows, quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if emit_json:
        # BENCH_solver.json-style path keeps the PR-1 shape; any other path
        # (e.g. BENCH_factor.json) gets the factorization trajectory.
        if "factor" in os.path.basename(emit_json):
            payload = {"bench": "factorization", "quick": quick,
                       "metrics": factor_metrics,
                       "solver_engine": solver_metrics}
        else:
            payload = {"bench": "solver_engine", "quick": quick,
                       "metrics": solver_metrics,
                       "factorization": factor_metrics}
        with open(emit_json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {emit_json}", file=sys.stderr)


if __name__ == "__main__":
    main()
