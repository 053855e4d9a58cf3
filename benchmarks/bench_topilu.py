"""Distributed sharded TOP-ILU trajectory — one JSON record per device count.

    python benchmarks/bench_topilu.py <grid> <devices> [--json PATH]

Spawns itself with ``XLA_FLAGS=--xla_force_host_platform_device_count``
(device count locks at first JAX init). Measures the sharded factorization
wall time on the simulated mesh and reports the per-device memory and the
per-superstep collective payload from the halo model, cross-checked against
the compiled HLO (``repro.roofline.analysis.collective_bytes_per_device``).
``benchmarks/run.py --emit-json BENCH_topilu.json`` aggregates 1/2/8
devices into the committed trajectory.
"""
import json
import os
import subprocess
import sys

if os.environ.get("_BENCH_TOPILU_CHILD") != "1" and __name__ == "__main__":
    d = sys.argv[2] if len(sys.argv) > 2 else "4"
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
    env.setdefault("JAX_PLATFORMS", "cpu")  # don't probe for real TPUs
    env["_BENCH_TOPILU_CHILD"] = "1"
    sys.exit(subprocess.run([sys.executable, __file__] + sys.argv[1:], env=env).returncode)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import numpy as np


def measure(grid: int, band_rows: int = 16) -> dict:
    import jax

    from repro.core import numeric_ilu_ref, pilu1_symbolic, poisson_2d
    from repro.core.top_ilu import lower_topilu, topilu_factor_sharded
    from repro.launch.mesh import make_band_mesh
    from repro.roofline.analysis import collective_bytes_per_device

    d = len(jax.devices())
    mesh = make_band_mesh()
    a = poisson_2d(grid)
    pat = pilu1_symbolic(a)
    want = numeric_ilu_ref(a, pat)

    t0 = time.perf_counter()
    fact = topilu_factor_sharded(a, pat, band_rows=band_rows, mesh=mesh)
    fact.loc_vals.block_until_ready()
    first = time.perf_counter() - t0
    got = fact.values_csr()
    bitwise = bool(np.array_equal(got.view(np.int32), want.view(np.int32)))

    # steady state: re-factorize on the already-compiled engine
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        f2 = topilu_factor_sharded(a, pat, band_rows=band_rows, mesh=mesh)
        f2.loc_vals.block_until_ready()
    steady = (time.perf_counter() - t0) / reps

    plan = fact.plan
    lowered, _ = lower_topilu(a, pat, band_rows, mesh)
    hlo_step = sum(collective_bytes_per_device(lowered.compile().as_text()).values())

    # pad-to-max-E histogram: the fori-loop engine ships a fixed (E, W)
    # payload every superstep; how much of it is padding on this workload?
    sizes = plan.egress_sizes()  # (n_sup, D) exact rows shipped
    hist = np.bincount(sizes.reshape(-1), minlength=plan.egress_max + 1)
    exact_rows = int(sizes.sum())
    padded_rows = plan.egress_max * sizes.size
    return {
        "devices": d,
        "n": a.n,
        "grid": grid,
        "k": 1,
        "band_rows": band_rows,
        "n_bands": plan.n_bands,
        "n_supersteps": plan.n_supersteps,
        "bitwise_equal_oracle": bitwise,
        "factor_first_seconds": first,
        "factor_steady_seconds": steady,
        "s_loc": plan.s_loc,
        "halo_size": plan.halo_size,
        "egress_max": plan.egress_max,
        "per_device_value_bytes": plan.per_device_value_bytes(),
        "replicated_value_bytes": plan.replicated_value_bytes(),
        "halo_bytes_per_superstep": plan.halo_bytes_per_superstep(),
        "replicated_bytes_per_superstep": plan.replicated_bytes_per_superstep(),
        "hlo_collective_bytes_per_superstep": hlo_step,
        "total_collective_bytes_per_device":
            plan.halo_bytes_per_superstep() * plan.n_supersteps,
        # per-superstep egress histogram: exact E per (step, device) vs the
        # global max the static loop pads to (ROADMAP "pad to max E" item)
        "egress_exact_rows": exact_rows,
        "egress_padded_rows": padded_rows,
        "egress_pad_fraction":
            1.0 - exact_rows / padded_rows if padded_rows else 0.0,
        "egress_size_histogram": {str(i): int(c) for i, c in enumerate(hist) if c},
        # ordering axis (PR 5, model-only — the halo model is exactly what
        # the HLO check above pins): factorization-side communication under
        # natural vs RCM vs fusion-aware row ordering
        "orderings": _ordering_axis(a, band_rows, d),
    }


def _ordering_axis(a, band_rows: int, d: int) -> list:
    """Modeled factorization communication per row ordering (host-only)."""
    from repro.core import pilu1_symbolic
    from repro.core.ordering import factor_comm_model, make_ordering, permuted_system

    out = []
    for name in ("natural", "rcm", "fusion"):
        ordering = make_ordering(a, name, n_devices=d, band_rows=band_rows)
        ap = a if ordering is None else permuted_system(a, ordering)
        pat = pilu1_symbolic(ap)
        rec = factor_comm_model(ap, pat, band_rows, d)
        out.append({
            "ordering": name,
            "n_supersteps": rec["n_supersteps"],
            "halo_bytes_per_superstep": rec["halo_bytes_per_superstep"],
            "per_device_value_bytes": rec["per_device_value_bytes"],
            "fill_nnz": rec["fill_nnz"],
        })
    return out


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
