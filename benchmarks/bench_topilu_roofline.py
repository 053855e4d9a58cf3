"""Roofline + hillclimb for TOP-ILU itself (§Perf hillclimb #3 — the cell
most representative of the paper's technique).

Runs in a subprocess with simulated devices (device count locked at jax
init). For each (band_rows, broadcast) variant it:

  * lowers the shard_map factorization on a D-device ring,
  * extracts per-superstep collective bytes from the compiled HLO
    (the superstep loop is a single `while`; XLA cost_analysis counts the
    body once, so totals are body-costs x n_supersteps — exact here since
    every superstep issues one identically-shaped collective),
  * combines with exact host-side op counts (planner) into the three
    roofline terms on TPU v5e constants,
  * MEASURES wall time on the simulated devices for a small matrix
    (schedule correctness + relative comparison only; 1 CPU core).

Usage:  python benchmarks/bench_topilu_roofline.py [n] [D]
        (spawns itself with XLA_FLAGS when needed)
"""
import os
import sys

if os.environ.get("_TOPILU_CHILD") != "1":
    import subprocess

    d = sys.argv[2] if len(sys.argv) > 2 else "16"
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
    env["_TOPILU_CHILD"] = "1"
    sys.exit(subprocess.run([sys.executable] + [__file__] + sys.argv[1:], env=env).returncode)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import numpy as np

from repro.roofline.analysis import V5E, collective_bytes_per_device, peaks


def exact_op_counts(a, pattern):
    """Host-side exact multiply-subtract counts of Phase II (planner data)."""
    total = 0
    for j in range(pattern.n):
        s, e = pattern.indptr[j], pattern.indptr[j + 1]
        cols = pattern.indices[s:e]
        d = pattern.diag_ptr[j]
        for i in cols[:d]:
            si, ei = pattern.indptr[i], pattern.indptr[i + 1]
            icols = pattern.indices[si:ei]
            tail = icols[pattern.diag_ptr[i] + 1 :]
            pos = np.searchsorted(cols, tail)
            inb = pos < len(cols)
            total += int(np.sum(cols[pos[inb]] == tail[inb])) + 1  # +1 for l=x/piv
    return total


def main():
    import jax

    from repro.core import matgen, pilu1_symbolic, numeric_ilu_ref
    from repro.core.top_ilu import lower_topilu, topilu_numeric

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
    D = len(jax.devices())
    from repro.launch.mesh import make_mesh

    mesh = make_mesh(np.asarray(jax.devices()).reshape(D), ("band",))
    a = matgen(n, density=min(0.02, 16.0 / n), seed=0)
    pat = pilu1_symbolic(a)
    ops = exact_op_counts(a, pat)
    flops = 2.0 * ops  # mul+sub per update
    want = numeric_ilu_ref(a, pat)

    print(f"n={n} nnz={pat.nnz} devices={D} exact_update_ops={ops:.3g}")
    print(f"{'variant':28s} {'bands':>6s} {'coll_B/dev':>12s} {'coll_s':>10s} "
          f"{'comp_s':>10s} {'wall_ms':>9s} bitwise")
    results = []
    for band_rows in (8, 32, 128):
        for broadcast in ("psum", "ring"):
            lowered, plan = lower_topilu(a, pat, band_rows, mesh, broadcast=broadcast)
            compiled = lowered.compile()
            # per-superstep collective bytes (loop body counted once) x n_sup
            step_coll = sum(collective_bytes_per_device(compiled.as_text()).values())
            coll_bytes = step_coll * plan.n_supersteps
            coll_s = coll_bytes / peaks(V5E)["link_bw"]
            comp_s = flops / D / peaks(V5E)["flops"]
            t0 = time.perf_counter()
            got = topilu_numeric(a, pat, band_rows=band_rows, mesh=mesh, broadcast=broadcast)
            wall = (time.perf_counter() - t0) * 1e3
            ok = bool(np.array_equal(got.view(np.int32), want.view(np.int32)))
            name = f"R={band_rows},bcast={broadcast}"
            print(f"{name:28s} {plan.n_bands:6d} {coll_bytes:12.3g} {coll_s:10.3g} "
                  f"{comp_s:10.3g} {wall:9.1f} {ok}")
            results.append((name, coll_bytes, ok))
            assert ok
    best = min(results, key=lambda r: r[1])
    print(f"\nbest-by-collective: {best[0]}  "
          f"({best[1]/max(r[1] for r in results):.2%} of worst)")


if __name__ == "__main__":
    main()
