"""Production solve service, end to end on CPU: multi-tenant request
coalescing over the warm bucketed ILU(k)-preconditioned solver.

Registers two tenants' matrices (same sparsity structure — they share one
compiled engine and one factor plan), warms every bucket ahead of traffic,
then drives a seeded burst mix through admit → coalesce → bucketed
multi-RHS solve → scatter. Along the way one tenant pushes new matrix
values: the refactorization runs in the background and in-flight requests
keep solving the version they were admitted under. Ends with the two
service-level proofs:

* the XLA compile counter is **flat** after warmup (zero serving-path
  compiles across every batch shape and the value update), and
* a spot-checked response is **bitwise identical** to solving that
  request alone.

    python examples/serve_ilu.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import json

import numpy as np

from repro.core.matgen import matgen
from repro.core.solvers import solve_with_ilu
from repro.serve import ServeConfig, SolveService, run_traffic


def main():
    n = 256
    a_acme = matgen(n, 0.02, seed=7)
    # same structure, different values → engine + factor plan are shared
    a_initech = type(a_acme)(n=a_acme.n, indptr=a_acme.indptr,
                             indices=a_acme.indices,
                             data=(a_acme.data * 1.25).astype(np.float32))

    svc = SolveService(ServeConfig(buckets=(1, 2, 4, 8), restart=8, k=1))
    svc.register_matrix("acme/reservoir", a_acme)
    svc.register_matrix("initech/reservoir", a_initech)
    warm = svc.warmup()
    print("warmup (seconds per bucket):")
    for mid, per_bucket in warm.items():
        pretty = {b: round(s, 3) for b, s in per_bucket.items()}
        print(f"  {mid}: {pretty}")

    # seeded multi-tenant traffic; one value push for acme mid-stream
    updates = {"acme/reservoir": [(a_acme.data * 0.8).astype(np.float32)]}
    result = run_traffic(svc, ["acme/reservoir", "initech/reservoir"],
                         n_requests=200, seed=11, burst_max=8,
                         update_prob=0.25, update_values=updates)
    snap = svc.metrics_snapshot()

    print(f"\nserved {len(result.responses)} requests in "
          f"{snap['coalescing']['batches']} coalesced batches "
          f"(mean occupancy {snap['coalescing']['occupancy_mean']:.2f})")
    print(f"cache: hit rate {snap['cache']['hit_rate']:.2f}, "
          f"{snap['cache']['refactorizations']} refactorization(s), "
          f"{snap['cache']['engines_shared']} engine(s) shared by structure")
    print(f"compiles: {snap['compiles']['warmup']} during warmup, "
          f"{snap['compiles']['after_warmup']} after")
    assert snap["compiles"]["after_warmup"] == 0, "serving path re-entered XLA"

    for tenant, hist in sorted(snap["tenants"].items()):
        print(f"  {tenant}: n={hist['count']}  p50={hist['p50_seconds']*1e3:.1f}ms"
              f"  p99={hist['p99_seconds']*1e3:.1f}ms")

    # bit-compat spot check: a coalesced response vs its solo solve, on the
    # exact value version the request was admitted under
    rec = next(r for r in result.records
               if r.matrix_id == "acme/reservoir" and r.expected_version == 1)
    resp = next(r for r in result.responses if r.request_id == rec.request_id)
    ref, _ = solve_with_ilu(a_acme, rec.b, k=1, tol=rec.tol, restart=8)
    same = np.array_equal(np.asarray(resp.x, np.float32).view(np.int32),
                          np.asarray(ref.x, np.float32).view(np.int32))
    print(f"\ncoalesced (bucket {resp.batch_lanes}) vs solo: "
          f"bitwise {'EQUAL' if same else 'DIFFERENT'}")
    assert same

    print("\nmetrics snapshot (what BENCH_serve.json embeds):")
    print(json.dumps({k: snap[k] for k in ("requests", "coalescing", "cache",
                                           "compiles")}, indent=2)[:600], "...")


if __name__ == "__main__":
    main()
