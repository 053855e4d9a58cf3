"""Serve-layer trajectory: multi-tenant coalesced solves/sec (PR-8 tentpole).

Drives the production :class:`repro.serve.SolveService` with seeded
4-tenant traffic against an n=1024-class matrix — warmup, then a few
thousand coalesced solves with a mid-stream background value update —
and records the service-level acceptance numbers:

* end-to-end **solves/sec** (admission → coalesce → bucketed solve →
  scatter, ticks included) and raw solve-loop throughput,
* per-tenant p50/p99 latency and the mean batch solve time that should
  dominate it,
* the compile counter split at warmup (``after_warmup`` must be 0),
* cache hit rate + refactorization count,
* a seeded sample of responses re-solved solo
  (``solve_with_ilu(...)``) and compared **bitwise** on
  the exact value version each request was admitted under.

PR 9 adds two axes:

* ``robustness`` — a deterministic fault-injection segment (breakdown
  matrix registered under ``on_breakdown="shift"``, an expired deadline,
  a lane that goes non-finite mid-flight) recording the degradation
  counters (``shifted_bindings``, ``breakdown_lanes``, ``shift_retries``,
  ``deadline_expired``, ...) and that healthy traffic is unharmed.
* ``sharded`` — a scaled-down soak against :class:`ShardedServeEngine`
  on 2 and 4 virtual devices (one subprocess each — the host device
  count locks at first JAX init), with the same compile-flatness and
  bitwise-vs-solo bars.

Run via ``python -m benchmarks.run --emit-json BENCH_serve.json`` (which
spawns this file as a subprocess with a pinned CPU platform), or directly:

    JAX_PLATFORMS=cpu python benchmarks/bench_serve.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

# the throughput configuration: matgen(1024, 0.004) converges in ~4 inner
# steps, so a right-sized restart (GMRES always runs the full masked
# restart window per outer iteration) is the solves/sec lever
N = 1024
DENSITY = 0.004
K = 1
RESTART = 4
MAXITER = 40
BUCKETS = (1, 2, 4, 8, 16, 32, 64)
TENANTS = ("t0", "t1", "t2", "t3")
BITWISE_SAMPLE = 24


def serve_trajectory(n_requests: int = 2000, seed: int = 17) -> dict:
    from repro.core.matgen import matgen
    from repro.core.solvers import solve_with_ilu
    from repro.core.sparse import CSRMatrix
    from repro.serve import ServeConfig, SolveService, run_traffic

    a = matgen(N, DENSITY, seed=5)
    svc = SolveService(ServeConfig(buckets=BUCKETS, restart=RESTART,
                                   maxiter=MAXITER, k=K))
    svc.register_matrix("m0", a)
    t0 = time.perf_counter()
    svc.warmup()
    warmup_seconds = time.perf_counter() - t0

    updates = {"m0": [(a.data * 1.1).astype(np.float32)]}
    t0 = time.perf_counter()
    result = run_traffic(svc, ["m0"], n_requests, seed=seed, tenants=TENANTS,
                         burst_max=max(BUCKETS), update_prob=0.01,
                         update_values=updates)
    wall = time.perf_counter() - t0
    snap = svc.metrics_snapshot()  # before reference solves (they compile)

    assert len(result.responses) == n_requests
    assert all(r.ok for r in result.responses)

    # seeded bitwise sample across value versions, buckets, lane positions
    rng = np.random.default_rng(seed)
    ref_mats = {1: a}
    for i, data in enumerate(result.updates["m0"]):
        ref_mats[2 + i] = CSRMatrix(n=a.n, indptr=a.indptr, indices=a.indices,
                                    data=data)
    by_id = {r.request_id: r for r in result.responses}
    sample = rng.choice(len(result.records), size=BITWISE_SAMPLE, replace=False)
    bitwise_ok = True
    for i in sample:
        rec = result.records[int(i)]
        resp = by_id[rec.request_id]
        ref, _ = solve_with_ilu(ref_mats[rec.expected_version], rec.b, k=K,
                                tol=rec.tol, restart=RESTART, maxiter=MAXITER)
        bitwise_ok &= bool(np.array_equal(
            np.asarray(resp.x, np.float32).view(np.int32),
            np.asarray(ref.x, np.float32).view(np.int32)))

    co, ca, cp = snap["coalescing"], snap["cache"], snap["compiles"]
    lat = [snap["tenants"][t] for t in TENANTS]
    return {
        "n": N,
        "k": K,
        "restart": RESTART,
        "maxiter": MAXITER,
        "buckets": list(BUCKETS),
        "tenants": len(TENANTS),
        "requests": n_requests,
        "wall_seconds": wall,
        "solves_per_sec": n_requests / wall,
        "raw_solve_solves_per_sec": co["solved_lanes"] / co["solve_seconds_total"],
        "batches": co["batches"],
        "occupancy_mean": co["occupancy_mean"],
        "mean_batch_solve_seconds": co["solve_seconds_total"] / co["batches"],
        "warmup_seconds": warmup_seconds,
        "compiles_warmup": cp["warmup"],
        "compiles_after_warmup": cp["after_warmup"],
        "cache_hit_rate": ca["hit_rate"],
        "refactorizations": ca["refactorizations"],
        "p50_seconds": float(np.median([h["p50_seconds"] for h in lat])),
        "p99_seconds": float(max(h["p99_seconds"] for h in lat)),
        "per_tenant": [
            {"tenant": t, "count": snap["tenants"][t]["count"],
             "p50_seconds": snap["tenants"][t]["p50_seconds"],
             "p99_seconds": snap["tenants"][t]["p99_seconds"]}
            for t in TENANTS],
        "bitwise_equal_solo": bitwise_ok,
        "bitwise_checked": int(BITWISE_SAMPLE),
    }


#: counters every trajectory reports (0 when the fault never fired) so the
#: BENCH_serve.json schema can pin the robustness section shape
ROBUST_COUNTERS = ("broken_factorizations", "shifted_bindings",
                   "degraded_responses", "breakdown_lanes", "shift_retries",
                   "retry_recoveries", "deadline_expired",
                   "quarantined_batches", "identity_fallbacks",
                   "rejected_updates")


def robustness_trajectory(seed: int = 23) -> dict:
    """Deterministic fault-injection segment: every injected breakdown is
    absorbed by the degradation ladder, healthy traffic is untouched."""
    from repro.core.matgen import matgen, zero_diagonal_matrix
    from repro.serve import ServeConfig, SolveService

    n = 48
    rng = np.random.default_rng(seed)
    good = matgen(n, density=0.12, seed=7)
    fragile = zero_diagonal_matrix(n, 0.12, seed=4, row=0)  # zero pivot
    svc = SolveService(ServeConfig(buckets=(1, 2, 4), restart=8, k=K,
                                   on_breakdown="shift"))
    svc.register_matrix("good", good)
    svc.register_matrix("fragile", fragile)  # ladder shifts at register
    svc.warmup()

    def rhs():
        return rng.standard_normal(n).astype(np.float32)

    reqs = []
    for _ in range(6):
        reqs.append(("good", svc.submit("t0", "good", rhs())))
        reqs.append(("fragile", svc.submit("t1", "fragile", rhs())))
    svc.run_until_idle()

    # an already-expired deadline: swept before it can occupy a lane
    late = svc.submit("t0", "good", rhs(), deadline_seconds=1e-4)
    time.sleep(0.005)
    # a lane that goes non-finite mid-flight (post-admission poke — the
    # admission gate itself rejects non-finite b): fails alone, the
    # co-batched healthy lanes are unharmed
    poisoned = svc.submit("t0", "good", rhs())
    poisoned.b = np.full(n, np.nan, np.float32)
    survivors = [svc.submit("t1", "good", rhs()) for _ in range(2)]
    svc.run_until_idle()

    snap = svc.metrics_snapshot()

    def resp(r):
        return r.result(timeout=60)

    degraded_ok = all(resp(r).ok and resp(r).degraded and resp(r).shift > 0
                      for mid, r in reqs if mid == "fragile")
    healthy = [resp(r) for mid, r in reqs if mid == "good"]
    healthy += [resp(r) for r in survivors]
    late_resp, poisoned_resp = resp(late), resp(poisoned)
    assert not late_resp.ok and late_resp.error_reason == "deadline_exceeded"
    assert not poisoned_resp.ok and poisoned_resp.verdict == "breakdown"
    return {
        "n": n,
        "requests_ok": int(sum(r.ok for r in healthy)
                           + sum(resp(r).ok for mid, r in reqs
                                 if mid == "fragile")),
        "requests_failed": 2,  # the expired deadline + the poisoned lane
        "degraded_ok": bool(degraded_ok),
        "healthy_unaffected": bool(all(r.ok and not r.degraded
                                       for r in healthy)),
        "counters": {k: int(snap["robustness"].get(k, 0))
                     for k in ROBUST_COUNTERS},
    }


def sharded_trajectory(n: int = 256, n_requests: int = 60,
                       seed: int = 33) -> dict:
    """Scaled-down sharded serve soak on however many devices this process
    sees (run under ``XLA_FLAGS=--xla_force_host_platform_device_count=D``)."""
    import jax

    from repro.core.matgen import matgen
    from repro.core.solvers import solve_sharded
    from repro.serve import ServeConfig, SolveService, run_traffic

    band_rows = 32
    a = matgen(n, density=min(0.02, 12.0 / n), seed=21)
    svc = SolveService(ServeConfig(sharded=True, band_rows=band_rows,
                                   buckets=(1, 2, 4), k=K, restart=8,
                                   maxiter=20))
    svc.register_matrix("m0", a)
    t0 = time.perf_counter()
    svc.warmup()
    warmup_seconds = time.perf_counter() - t0

    t0 = time.perf_counter()
    result = run_traffic(svc, ["m0"], n_requests, seed=seed,
                         tenants=("t0", "t1"), burst_max=4,
                         tol_choices=(1e-4, 1e-5))
    wall = time.perf_counter() - t0
    snap = svc.metrics_snapshot()  # before reference solves (they compile)
    assert all(r.ok for r in result.responses)

    rng = np.random.default_rng(seed)
    by_id = {r.request_id: r for r in result.responses}
    k_sample = min(12, len(result.records))
    sample = rng.choice(len(result.records), size=k_sample, replace=False)
    bitwise_ok, fact = True, None
    for i in sample:
        rec = result.records[int(i)]
        ref, fact = solve_sharded(a, rec.b, k=K, band_rows=band_rows,
                                  tol=rec.tol, restart=8, maxiter=20,
                                  fact=fact)
        bitwise_ok &= bool(np.array_equal(
            np.asarray(by_id[rec.request_id].x, np.float32).view(np.int32),
            np.asarray(ref.x, np.float32).view(np.int32)))

    co, cp = snap["coalescing"], snap["compiles"]
    return {
        "devices": len(jax.devices()),
        "n": n,
        "band_rows": band_rows,
        "requests": n_requests,
        "wall_seconds": wall,
        "solves_per_sec": n_requests / wall,
        "batches": co["batches"],
        "occupancy_mean": co["occupancy_mean"],
        "warmup_seconds": warmup_seconds,
        "compiles_after_warmup": cp["after_warmup"],
        "bitwise_equal_solo": bitwise_ok,
        "bitwise_checked": int(k_sample),
    }


def _sharded_case(devices: int, n: int = 256, n_requests: int = 60) -> dict:
    """One subprocess per device count: the host device count locks at
    first JAX init, and this parent already initialized jax."""
    import subprocess

    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--sharded",
         str(n), str(n_requests)],
        env=env, capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded serve bench D={devices} failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout)


if __name__ == "__main__":
    from repro.core.api import enable_jit_cache

    enable_jit_cache()
    if "--sharded" in sys.argv:
        i = sys.argv.index("--sharded")
        print(json.dumps(sharded_trajectory(int(sys.argv[i + 1]),
                                            int(sys.argv[i + 2]))))
        sys.exit(0)
    n_requests = int(sys.argv[1]) if len(sys.argv) > 1 else 2000
    metrics = serve_trajectory(n_requests)
    metrics["robustness"] = robustness_trajectory()
    metrics["sharded"] = [_sharded_case(d) for d in (2, 4)]
    print(json.dumps(metrics))
