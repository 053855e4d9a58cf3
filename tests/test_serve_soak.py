"""Deterministic seeded soak of the solve service.

One seeded :func:`repro.serve.run_traffic` run — thousands of requests,
four tenants, two resident matrices, mid-stream value updates and
malformed injections — then three audits over the full trail:

1. **Metrics schema**: the JSON snapshot has exactly the documented shape
   (this is the contract ``BENCH_serve.json`` and dashboards consume).
2. **Compile flatness**: ``compiles.after_warmup == 0`` — the serving
   path never re-enters XLA after :meth:`SolveService.warmup`, across
   every bucket size, coalescing mix, and background refactorization.
3. **Bitwise fidelity**: every response equals the solo
   ``solve_with_ilu(...)`` reference for the exact
   value version the request was admitted under.

The compile snapshot is taken *before* computing references — reference
solves compile their own engines and must not pollute the counter.
"""
import numpy as np
import pytest

from repro.core.matgen import matgen
from repro.core.solvers import solve_with_ilu
from repro.core.sparse import CSRMatrix
from repro.serve import ServeConfig, SolveService, run_traffic

N = 256
K = 1
RESTART = 8
MAXITER = 20
N_REQUESTS = 2000
SEED = 2026


def _metrics_schema_check(snap):
    assert set(snap) >= {"uptime_seconds", "ticks", "requests", "queue",
                         "coalescing", "cache", "compiles", "tenants"}
    req = snap["requests"]
    assert set(req) >= {"admitted", "completed", "failed", "rejected_by_reason"}
    assert isinstance(req["rejected_by_reason"], dict)
    q = snap["queue"]
    assert set(q) >= {"depth_samples", "depth_mean", "depth_max"}
    co = snap["coalescing"]
    assert set(co) >= {"batches", "solved_lanes", "padded_lanes",
                       "occupancy_mean", "occupancy_min", "solve_seconds_total"}
    ca = snap["cache"]
    assert set(ca) >= {"hits", "misses", "hit_rate", "evictions",
                       "refactorizations", "engines_shared"}
    cp = snap["compiles"]
    assert set(cp) >= {"total", "warmup", "after_warmup"}
    for tenant, hist in snap["tenants"].items():
        assert set(hist) >= {"count", "mean_seconds", "p50_seconds",
                             "p99_seconds", "max_seconds",
                             "bucket_bounds_seconds", "bucket_counts"}
        assert hist["count"] == sum(hist["bucket_counts"])
        assert hist["p50_seconds"] <= hist["p99_seconds"] <= hist["max_seconds"]


@pytest.mark.slow
def test_soak_seeded_traffic_bitwise_and_compile_flat():
    a0 = matgen(N, 0.02, seed=41)
    a1 = matgen(N, 0.02, seed=42)
    svc = SolveService(ServeConfig(buckets=(1, 2, 4, 8), restart=RESTART,
                                   maxiter=MAXITER, k=K))
    svc.register_matrix("acct-0/pressure", a0)
    svc.register_matrix("acct-1/pressure", a1)
    svc.warmup()

    # two value pushes per matrix, queued for run_traffic to inject
    updates = {
        "acct-0/pressure": [(a0.data * s).astype(np.float32) for s in (1.2, 0.9)],
        "acct-1/pressure": [(a1.data * s).astype(np.float32) for s in (1.1, 1.3)],
    }
    result = run_traffic(
        svc, ["acct-0/pressure", "acct-1/pressure"], N_REQUESTS, seed=SEED,
        tenants=("t0", "t1", "t2", "t3"), burst_max=8,
        malformed_prob=0.05, update_prob=0.02, update_values=updates)
    snap = svc.metrics_snapshot()   # BEFORE reference solves (they compile)

    # -- schema + accounting -------------------------------------------------
    _metrics_schema_check(snap)
    assert snap["requests"]["admitted"] == N_REQUESTS
    assert snap["requests"]["completed"] == N_REQUESTS
    assert snap["requests"]["failed"] == 0
    assert len(result.responses) == N_REQUESTS
    assert len(result.rejected) > 0          # malformed injections happened
    assert all(not r.ok for r in result.rejected)
    assert set(snap["tenants"]) == {"t0", "t1", "t2", "t3"}
    assert sum(h["count"] for h in snap["tenants"].values()) == N_REQUESTS

    # -- service-level SLO invariants ---------------------------------------
    assert snap["compiles"]["after_warmup"] == 0, (
        "serving path re-entered XLA after warmup: "
        f"{snap['compiles']}")
    assert snap["cache"]["hit_rate"] >= 0.9
    assert snap["cache"]["evictions"] == 0   # capacity 8, two residents
    n_updates = sum(len(v) for v in result.updates.values())
    assert snap["cache"]["refactorizations"] == n_updates
    assert n_updates > 0                     # updates actually fired
    assert snap["coalescing"]["occupancy_mean"] > 0.5

    # -- bitwise fidelity: every response == its solo reference -------------
    mats = {"acct-0/pressure": a0, "acct-1/pressure": a1}
    # version v matrices: v=1 is the registered data, v=1+i after update i;
    # one CSRMatrix object per (matrix, version) so reference engines cache
    ref_mats = {}
    for mid, a in mats.items():
        ref_mats[(mid, 1)] = a
        for i, data in enumerate(result.updates[mid]):
            ref_mats[(mid, 2 + i)] = CSRMatrix(
                n=a.n, indptr=a.indptr, indices=a.indices, data=data)

    by_id = {r.request_id: r for r in result.responses}
    checked = 0
    for rec in result.records:
        resp = by_id[rec.request_id]
        assert resp.ok, f"request {rec.request_id} failed: {resp.error}"
        assert resp.matrix_version == rec.expected_version, (
            "response solved against a different value version than the "
            "one pinned at admission")
        ref = ref_mats[(rec.matrix_id, rec.expected_version)]
        sol, _ = solve_with_ilu(ref, rec.b, k=K, tol=rec.tol,
                                restart=RESTART)
        np.testing.assert_array_equal(
            np.asarray(resp.x, np.float32).view(np.int32),
            np.asarray(sol.x, np.float32).view(np.int32),
            err_msg=(f"coalesced response for {rec.matrix_id} v"
                     f"{rec.expected_version} (lane of a {resp.batch_lanes}-"
                     "bucket) is not bitwise equal to its solo solve"))
        checked += 1
    assert checked == N_REQUESTS
