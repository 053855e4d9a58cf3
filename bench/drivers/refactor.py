"""A sequence of value pushes on one fixed sparsity pattern, as in a
transient or Newton iteration: each step pushes new values to the solve
service (``update_matrix_values``, refactorized through the compiled plan
of the pattern before it returns), then submits one right-hand side and
ticks the service until it answers.

Traffic parameters: ``value_ring`` (value sets drawn from the seed before
the window and used in turn: new off-diagonals uniform in [-1, 1], the
diagonal their absolute row sum plus ``margin``, so each stays strictly
diagonally dominant), ``factor_samples`` (steps of the window, drawn from the
seed, whose factor as the step's solve used it is compared with the
reference), ``tenant``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import check, work

MATRIX_ID = "m"


def revalue(run, rng) -> np.ndarray:
    m = run.matrix
    row = np.repeat(np.arange(m["n"]), np.diff(m["indptr"]))
    on_diag = np.asarray(m["indices"]) == row
    data = rng.uniform(-1.0, 1.0, size=len(m["data"])).astype(np.float32)
    data[on_diag] = 0.0
    rowsum = np.bincount(row, weights=np.abs(data), minlength=m["n"])
    data[on_diag] = (rowsum + float(run.traffic["margin"])).astype(np.float32)
    return data


def generate(run):
    rng = run.rng("values")
    ring = int(run.traffic["value_ring"])
    run.state["values"] = [revalue(run, rng) for _ in range(ring)]
    xs = run.rng("rhs").standard_normal((ring, run.matrix["n"]), dtype=np.float32)
    run.state["x_true"] = xs
    run.state["b"] = np.stack([check.rhs(run, d, x) for d, x in zip(run.state["values"], xs)])


def _service(run):
    from repro.serve import ServeConfig, SolveService

    s = run.config["solver"]
    return SolveService(ServeConfig(k=s["k"], restart=s["restart"], maxiter=s["maxiter"],
                                    precond_method=s["precond_method"], buckets=(1,)))


def _step(run, svc, k):
    with run.span("push_values"):
        svc.update_matrix_values(MATRIX_ID, run.state["values"][k], background=False)
        binding = svc.cache.entry(MATRIX_ID).binding
    with run.span("submit"):
        svc.submit(run.traffic["tenant"], MATRIX_ID, run.state["b"][k],
                   tol=float(run.config["solver"]["tol"]))
    with run.span("tick"):
        responses = svc.run_until_idle()
    return binding, responses


def setup(run):
    svc = _service(run)
    with run.plan():
        svc.register_matrix(MATRIX_ID, check.program_matrix(run))
    svc.warmup()
    run.state["svc"] = svc


def window(run):
    svc = run.state["svc"]
    ring = len(run.state["values"])
    done, each = [], []
    t0 = t = time.perf_counter()
    end = t0 + run.seconds
    i = 0
    while True:
        k = i % ring
        binding, responses = _step(run, svc, k)
        done.append((k, binding.version, binding.vals_csr, responses))
        i += 1
        now = time.perf_counter()
        each.append(now - t)
        t = now
        if t >= end:
            break
    run.state["done"] = done
    run.counters["each_s"] = each
    run.counters["iterations"] = [r.iterations for *_, rs in done for r in rs if r.ok]
    return {"step_s": (t - t0) / len(done)}


def probes(run):
    pass


def count_work(run):
    n = run.matrix["n"]
    p_indptr, p_indices, diag = check.reference_pattern(run)
    ups = work.update_count(n, p_indptr, p_indices, diag)
    run.work["factor"] = work.factor(n, len(run.matrix["data"]), len(p_indices),
                                     int(diag.sum()), ups)


def sampled_steps(run, steps: int) -> list:
    pick = run.rng("sample").permutation(steps)
    return sorted(int(i) for i in pick[:int(run.traffic["factor_samples"])])


def check_outputs(run):
    svc = run.state.pop("svc")
    pattern = svc.cache.entry(MATRIX_ID).pattern
    got_pattern = (pattern.indptr, pattern.indices)
    del svc, pattern
    done = run.state.pop("done")
    tol = float(run.config["solver"]["tol"])
    limit = run.limits["residual_over_tol"]
    bits = 0
    for i in sampled_steps(run, len(done)):
        k, _version, vals, _responses = done[i]
        want = check.reference_factor(run, run.state["values"][k])
        bits = max(bits, check.factor_bits_differ(run, got_pattern, vals, want))
    worst, missing, failed = 0.0, 0, 0
    for k, version, _vals, responses in done:
        ok = [r for r in responses if r.ok and r.matrix_version == version]
        if len(ok) != 1:
            missing += 1
            failed += 1
            continue
        ratio = check.residual_over_tol(run, run.state["values"][k], ok[0].x,
                                        run.state["b"][k], tol)
        worst = max(worst, ratio)
        failed += ratio > limit
    if bits > run.limits["factor_bits_differ"]:
        failed = len(done)
    return ({"factor_bits_differ": bits, "residual_over_tol": worst, "missing": missing},
            len(done), failed)


def control(run):
    """The numbers with the reference computed in bfloat16 in the program's
    place: a value set's factor, and each step's exact solution
    rounded to bfloat16."""
    tol = float(run.config["solver"]["tol"])
    st = run.state
    bits = check.control_factor_bits(run, st["values"][0])
    worst = max(check.residual_over_tol(run, d, check.bf16_round(x), b, tol)
                for d, x, b in zip(st["values"], st["x_true"], st["b"]))
    return {"factor_bits_differ": bits, "residual_over_tol": worst, "missing": 0}
