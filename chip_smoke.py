"""Smoke run of the main path on the chip: the f32 division the factor
rests on, the ILU(k) factorization, the preconditioned GMRES solves and
the solve service, checked bitwise against the repository's own oracles.

    python chip_smoke.py                      # one chip, Poisson 400x400 (n = 160,000)
    python chip_smoke.py --chips 4            # four chips: ilu_sharded + solve_sharded only
    JAX_PLATFORMS=cpu python chip_smoke.py --n 4096   # rehearsal: every phase, then exit 1

One process, no subprocesses. Each phase prints one line with what it
checked, its wall seconds, and the XLA compile seconds inside them (from
jax.monitoring) apart from the rest. The last line is one JSON object:
``{"ok": ..., "device": {"platform", "kind", "count"}}``. Any mismatch,
exception or failed response fails its phase and the run exits non-zero
(the later phases still run and report); so does any platform other than
``tpu``, after the phases have run. These are smoke timings of one cold
run, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np

import jax
import jax.monitoring

from repro.core import numeric_ilu_ref
from repro.core.api import enable_jit_cache, ilu, ilu_sharded
from repro.core.matgen import poisson_2d
from repro.core.solvers import solve_sharded, solve_with_ilu

K = 1
TOL = 1e-5
RESTART = 50
MAXITER = 40  # outer GMRES restarts
SERVE_BUCKETS = (1, 2, 4, 8)
SERVE_REQUESTS = 8
DIVIDE_SAMPLES = 1 << 20

_compile_seconds = [0.0]


def _on_duration(name, seconds, **_):
    if name == "/jax/core/compile/backend_compile_duration":
        _compile_seconds[0] += seconds


class Phase:
    """Times one phase; ``line`` prints what it checked with wall seconds
    and the XLA compile seconds spent inside them."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.c0, self.t0 = _compile_seconds[0], time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = _compile_seconds[0] - self.c0
        return False

    def line(self, checked):
        print(f"phase {self.name}: {checked} | wall_s={self.wall:.3f} "
              f"compile_s={self.compile:.3f} rest_s={self.wall - self.compile:.3f}",
              flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def bitwise_equal(got, want) -> bool:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return got.shape == want.shape and bool(np.array_equal(got.view(np.int32),
                                                           want.view(np.int32)))


def host_residual(a, x, b) -> float:
    """‖b - A x‖ / ‖b‖ in float64 on the host."""
    r = b.astype(np.float64) - a.to_scipy().astype(np.float64) @ x.astype(np.float64)
    return float(np.linalg.norm(r) / np.linalg.norm(b.astype(np.float64)))


def rhs(a, seed):
    """b = A x_true for a seeded x_true: a right-hand side whose solution
    is O(1), so a float32 residual can reach TOL."""
    x_true = np.random.default_rng(seed).standard_normal(a.n).astype(np.float32)
    return (a.to_scipy() @ x_true).astype(np.float32)


def ulp_report(got, want) -> str:
    """How many entries differ, and by how many units in the last place."""
    gi = np.asarray(got, np.float32).view(np.int32).astype(np.int64)
    wi = np.asarray(want, np.float32).view(np.int32).astype(np.int64)
    d = np.abs(gi - wi)
    return f"mismatched={int((d > 0).sum())}/{d.size} max_ulp={int(d.max(initial=0))}"


def phase_divide():
    """The f32 arithmetic the bitwise factor rests on, on random operands
    spread over 2**±40: the device's divide and ``exact_div`` against
    NumPy's correctly rounded ``a / b``, and whether a multiply feeding a
    subtract rounds like NumPy's two operations (no FMA contraction)."""
    import jax.numpy as jnp

    from repro.core.bitmath import exact_div

    rng = np.random.default_rng(7)

    def operand():
        x = rng.standard_normal(DIVIDE_SAMPLES) * np.exp2(rng.integers(-40, 41, DIVIDE_SAMPLES))
        return x.astype(np.float32)

    a, b, c = operand(), operand(), operand()
    with Phase("divide") as p:
        raw = np.asarray(jax.jit(jnp.divide)(a, b))
        fixed = np.asarray(jax.jit(exact_div)(a, b))
        mul_sub = np.asarray(jax.jit(lambda a, b, c: a * b - c)(a, b, c))
    want = a / b
    p.line(f"{DIVIDE_SAMPLES} random f32 pairs vs NumPy: device a/b {ulp_report(raw, want)}; "
           f"exact_div {ulp_report(fixed, want)}; a*b-c {ulp_report(mul_sub, a * b - c)}")
    check(bitwise_equal(fixed, want), "exact_div differs from NumPy's a / b")


def phase_factor(a):
    with Phase("ilu") as p:
        fact = ilu(a, K)
        want = numeric_ilu_ref(a, fact.pattern)
        same = bitwise_equal(fact.vals, want)
    p.line(f"ilu(a, {K}) nnz={fact.pattern.nnz} factor==numeric_ilu_ref bitwise={same} "
           f"{ulp_report(fact.vals, want)}")
    check(same, "factor values differ from the NumPy oracle")


def phase_solve(a, precond_method):
    b = rhs(a, seed=1)
    with Phase(f"gmres_{precond_method}") as p:
        res, _ = solve_with_ilu(a, b, k=K, method="gmres", tol=TOL, restart=RESTART,
                                maxiter=MAXITER, precond_method=precond_method)
        rel = host_residual(a, res.x, b)
    p.line(f"solve_with_ilu gmres precond={precond_method} converged={res.converged} "
           f"verdict={res.verdict} iterations={res.iterations} "
           f"host_f64_rel_residual={rel:.3e} tol={TOL:g}")
    check(res.converged, f"{precond_method} GMRES did not converge ({res.verdict})")
    check(rel <= TOL, f"{precond_method} host residual {rel:.3e} above tol {TOL:g}")


def phase_serve(a):
    from repro.serve import ServeConfig, SolveService

    with Phase("serve") as p:
        svc = SolveService(ServeConfig(k=K, restart=RESTART, maxiter=MAXITER,
                                       buckets=SERVE_BUCKETS))
        svc.register_matrix("poisson", a)
        svc.warmup()
        sent = {}
        for i in range(SERVE_REQUESTS):
            b = rhs(a, seed=100 + i)
            req = svc.submit(f"tenant{i % 2}", "poisson", b, tol=TOL)
            check(getattr(req, "ok", True), f"request {i} rejected at admission")
            sent[req.request_id] = b
        responses = svc.run_until_idle()
        snap = svc.metrics_snapshot()
    check(len(responses) == SERVE_REQUESTS,
          f"{len(responses)} responses for {SERVE_REQUESTS} requests")
    bad = [r for r in responses if not r.ok or r.degraded or not r.converged]
    check(not bad, f"failed or degraded responses: {[(r.request_id, r.error) for r in bad]}")
    with Phase("serve_anchor") as q:
        mismatched = []
        for r in responses:
            solo, _ = solve_with_ilu(a, sent[r.request_id], k=K, tol=TOL,
                                     restart=RESTART, maxiter=MAXITER)
            if not bitwise_equal(r.x, solo.x):
                mismatched.append(r.request_id)
    after = snap["compiles"]["after_warmup"]
    p.line(f"SolveService tenants=2 requests={SERVE_REQUESTS} ok={len(responses)} "
           f"batches={snap['coalescing']['batches']} compiles_after_warmup={after}")
    q.line(f"every response x == solo solve_with_ilu bitwise={not mismatched}")
    check(not mismatched, f"responses differ from their solo solves: {mismatched}")
    check(after == 0, f"{after} compiles after warmup")


def phase_sharded(a):
    """ilu_sharded + solve_sharded on a 4-device mesh against the oracle
    and the single-device solve of the same (fusion-ordered) system."""
    from repro.core.ordering import make_ordering, permuted_system
    from repro.launch.mesh import make_band_mesh

    band_rows = 32
    mesh = make_band_mesh(4)
    b = rhs(a, seed=1)
    with Phase("ilu_sharded") as p:
        fact = ilu_sharded(a, K, band_rows=band_rows, mesh=mesh, ordering="fusion")
        ord_ = fact.ordering
        ap = permuted_system(a, ord_)
        got, want = fact.values_csr(), numeric_ilu_ref(ap, fact.pattern)
        same_f = bitwise_equal(got, want)
    p.line(f"ilu_sharded devices=4 ordering=fusion factor==numeric_ilu_ref(PAP^T) "
           f"bitwise={same_f} {ulp_report(got, want)}")
    check(same_f, "sharded factor values differ from the NumPy oracle")
    check(ord_ is make_ordering(a, "fusion", n_devices=4, band_rows=band_rows),
          "sharded factorization did not adopt the fusion ordering")
    with Phase("solve_sharded") as p:
        res, _ = solve_sharded(a, b, k=K, band_rows=band_rows, tol=TOL, fact=fact,
                               restart=RESTART, maxiter=MAXITER)
    p.line(f"solve_sharded converged={res.converged} iterations={res.iterations} "
           f"host_f64_rel_residual={host_residual(a, res.x, b):.3e}")
    check(res.converged, f"sharded GMRES did not converge ({res.verdict})")
    with Phase("single_device_anchor") as p:
        ref, _ = solve_with_ilu(ap, ord_.permute_vector(b), k=K, tol=TOL,
                                restart=RESTART, maxiter=MAXITER)
        want_x = ord_.unpermute_vector(ref.x)
        same_x = bitwise_equal(res.x, want_x)
    p.line(f"solve_sharded x == single-device solve_with_ilu bitwise={same_x} "
           f"iterations={ref.iterations} {ulp_report(res.x, want_x)}")
    check(same_x, "sharded solution differs from the single-device solution")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=160_000,
                    help="unknowns; a perfect square (2-D Poisson side**2)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the distributed phase on a 4-device mesh")
    args = ap.parse_args(argv)
    side = math.isqrt(args.n)
    if side * side != args.n:
        ap.error(f"--n must be a perfect square, got {args.n}")

    enable_jit_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    devs = jax.devices()
    dev = devs[0]
    print(f"devices: {len(devs)} x {dev.platform} ({dev.device_kind})", flush=True)
    if args.chips > len(devs):
        print(f"--chips {args.chips} needs {args.chips} devices, found {len(devs)}")
        return 1

    a = poisson_2d(side)
    print(f"matrix: poisson_2d({side}) n={a.n} nnz={a.nnz} k={K}", flush=True)
    if args.chips == 4:
        phases = [lambda: phase_sharded(a)]
    else:
        phases = [phase_divide, lambda: phase_factor(a), lambda: phase_solve(a, "sweep"),
                  lambda: phase_solve(a, "inverse"), lambda: phase_serve(a)]
    failed = 0
    for phase in phases:  # a failed phase fails the run; the later ones still report
        try:
            phase()
        except Exception:  # noqa: BLE001 — reported here, counted in the exit code
            traceback.print_exc()
            print(f"phase FAILED: {traceback.format_exc(limit=0).strip()}", flush=True)
            failed += 1

    ok = dev.platform == "tpu" and not failed
    print(json.dumps({"ok": ok, "device": {"platform": dev.platform,
                                           "kind": dev.device_kind,
                                           "count": len(devs)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
