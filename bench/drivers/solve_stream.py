"""A stream of right-hand sides solved one after another against one
matrix, through the library entry ``solve_with_ilu``: the factorization
and the compiled solver are built by the first call, in set-up, and every
call in the window reuses them (a closed loop of one caller).

Traffic parameters: ``rhs_ring`` (distinct right-hand sides ``b = A x``
drawn from the seed, used in turn) and ``probe_calls`` (in the traced
run, after the window: the preconditioner apply and the SpMV each
dispatched alone this many times, for their device time).
"""
from __future__ import annotations

import time

import numpy as np

from bench import check, work


def generate(run):
    n = run.matrix["n"]
    xs = run.rng("rhs").standard_normal((int(run.traffic["rhs_ring"]), n), dtype=np.float32)
    run.state["x_true"] = xs
    run.state["b"] = np.stack([check.rhs(run, run.matrix["data"], x) for x in xs])


def _solve(run, b):
    from repro.core.solvers import solve_with_ilu

    s = run.config["solver"]
    return solve_with_ilu(run.state["a"], b, k=s["k"], method=s["method"], tol=s["tol"],
                          precond_method=s["precond_method"], restart=s["restart"],
                          maxiter=s["maxiter"])


def setup(run):
    import repro.core.api as api

    a = run.state["a"] = check.program_matrix(run)
    s = run.config["solver"]
    with run.plan():
        api.ilu(a, s["k"], precond_method=s["precond_method"]).precond()
    # ``solve_with_ilu`` takes no factorization: its first call runs the
    # symbolic phase, the factorization (on the factor plan memoized on
    # ``a``) and the triangular plan again, and builds the solver engine. A
    # zero right-hand side runs no Krylov step, so no solve is in set-up.
    _res, fact = _solve(run, np.zeros(a.n, np.float32))
    run.state["fact"] = fact
    if run.tracing:
        import jax.numpy as jnp

        from repro.core.bitmath import hoisted_jit
        from repro.core.solvers import csr_to_ell_arrays, make_ell_matvec

        mv = hoisted_jit(make_ell_matvec(*csr_to_ell_arrays(a), a.n))
        b = jnp.asarray(run.state["b"][0])
        fact.precond()(b).block_until_ready()
        mv(b).block_until_ready()
        run.state["probe_mv"] = mv


def window(run):
    bs = run.state["b"]
    done, each = [], []
    t0 = t = time.perf_counter()
    end = t0 + run.seconds
    i = 0
    while True:
        k = i % len(bs)
        with run.span("solve"):
            res, fact = _solve(run, bs[k])
        done.append((k, res.x, res.iterations, fact is run.state["fact"]))
        i += 1
        now = time.perf_counter()
        each.append(now - t)
        t = now
        if t >= end:
            break
    run.state["done"] = done
    run.counters["each_s"] = each
    run.counters["iterations"] = [d[2] for d in done]
    return {"solve_s": (t - t0) / len(done)}


def probes(run):
    import jax.numpy as jnp

    b = jnp.asarray(run.state["b"][0])
    pre, mv = run.state["fact"].precond(), run.state["probe_mv"]
    calls = int(run.traffic["probe_calls"])
    for name, fn in (("probe.sweep", pre), ("probe.spmv", mv)):
        for _ in range(calls):
            with run.span(name):
                fn(b).block_until_ready()
    run.counters["probe_calls"] = calls


def count_work(run):
    n = run.matrix["n"]
    _p, p_indices, _d = check.reference_pattern(run)
    run.work["spmv"] = work.spmv(n, len(run.matrix["data"]))
    run.work["sweep"] = work.sweep(n, len(p_indices))


def check_outputs(run):
    fact = run.state["fact"]
    got = ((fact.pattern.indptr, fact.pattern.indices), fact.vals)
    done = run.state.pop("done")
    for key in ("a", "fact", "probe_mv"):  # the program's state goes first
        run.state.pop(key, None)
    data = run.matrix["data"]
    tol = float(run.config["solver"]["tol"])
    bits = check.factor_bits_differ(run, got[0], got[1], check.reference_factor(run, data))
    ratios = [check.residual_over_tol(run, data, x, run.state["b"][k], tol)
              for k, x, _it, _same in done]
    foreign = sum(not same for *_, same in done)
    limit = run.limits["residual_over_tol"]
    failed = sum(r > limit for r in ratios) + foreign
    if bits > run.limits["factor_bits_differ"]:
        failed = len(done)
    numbers = {"factor_bits_differ": bits, "residual_over_tol": max(ratios),
               "missing": foreign}
    return numbers, len(done), min(failed, len(done))


def control(run):
    """The numbers with the reference computed in bfloat16 in the program's
    place: its factor, and each exact solution rounded to bfloat16."""
    tol = float(run.config["solver"]["tol"])
    data = run.matrix["data"]
    worst = max(check.residual_over_tol(run, data, check.bf16_round(x), b, tol)
                for x, b in zip(run.state["x_true"], run.state["b"]))
    return {"factor_bits_differ": check.control_factor_bits(run, data),
            "residual_over_tol": worst, "missing": 0}
