"""The program's own instrumentation: spans and counters (``repro.obs``),
engine names (``bitmath.hoisted_jit(fn, name=...)``) and the named scopes
that reach the compiled ops' ``op_name`` metadata."""
import gc
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import poisson_2d
from repro.core.api import ilu
from repro.core.bitmath import hoisted_jit
from repro.core.solvers import csr_to_ell_arrays, gmres_engine, make_ell_matvec


def _op_names(compiled_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def test_span_accumulates_without_a_profiler():
    before = obs.totals().get("ilu:test.span", (0, 0.0))
    for _ in range(3):
        with obs.span("ilu:test.span"):
            sum(range(1000))
    count, seconds = obs.totals()["ilu:test.span"]
    assert count == before[0] + 3
    assert seconds > before[1]


def test_span_records_when_the_body_raises():
    before = obs.totals().get("ilu:test.raises", (0, 0.0))[0]
    try:
        with obs.span("ilu:test.raises"):
            raise ValueError("inside")
    except ValueError:
        pass
    assert obs.totals()["ilu:test.raises"][0] == before + 1


def _noted(module: str) -> list:
    gc.collect()
    return [t for t in obs.program_texts() if t.startswith(f"HloModule {module},")]


def test_named_engine_is_noted_without_a_second_compile():
    obs.install_compile_listener()
    # a constant no other program holds, so nothing cached can serve it
    bump = float(np.random.default_rng().uniform(1.0, 2.0))
    engine = hoisted_jit(lambda v: v * bump + 3.0, name="noted")
    x = jnp.ones(8, jnp.float32)
    before = obs.compile_count()
    engine(x)
    engine(x + 1.0)
    after_calls = obs.compile_count()
    (text,) = engine.program_texts()
    # the text is made when asked, from the executable the calls built
    assert obs.compile_count() == after_calls
    assert text.startswith("HloModule jit__eval_noted,")
    assert 'op_name="jit(_eval_noted)/' in text
    assert after_calls - before <= 2  # the engine's compile and x + 1.0's
    assert _noted("jit__eval_noted") == [text]


def test_noted_programs_go_with_their_engines():
    x = jnp.ones(4, jnp.float32)
    engine = hoisted_jit(lambda v: v - 1.0, name="short_lived")
    engine(x)
    compiled = hoisted_jit(lambda v: v + 1.0, name="short_lived_aot").lower(x).compile()
    assert len(_noted("jit__eval_short_lived")) == 1
    assert _noted("jit__eval_short_lived_aot") == [compiled.compiled.as_text()]
    del engine, compiled
    assert _noted("jit__eval_short_lived") == [] == _noted("jit__eval_short_lived_aot")
    # unnamed engines are never noted
    hoisted_jit(lambda v: v * 3.0)(x)
    assert not any(t.startswith("HloModule jit__eval,") for t in obs.program_texts())


def test_named_engine_lowers_as_its_own_module():
    x = jnp.ones(8, jnp.float32)
    named = hoisted_jit(lambda v: v * 2.0, name="probe")
    plain = hoisted_jit(lambda v: v * 2.0)
    assert "module @jit__eval_probe " in named.lower(x)._lowered.as_text()
    assert "module @jit__eval " in plain.lower(x)._lowered.as_text()
    np.testing.assert_array_equal(np.asarray(named(x)), np.asarray(plain(x)))


def test_gmres_engine_carries_its_scopes():
    a = poisson_2d(6)
    fact = ilu(a, 1)
    mv = make_ell_matvec(*csr_to_ell_arrays(a), a.n)
    engine = gmres_engine(mv, fact.precond(), restart=5, tol=1e-5, maxiter=3)
    lowered = engine.lower(jax.ShapeDtypeStruct((a.n,), jnp.float32))
    assert "module @jit__eval_gmres " in lowered._lowered.as_text()
    names = _op_names(lowered.compile().compiled.as_text())
    for scope in ("gmres.orthogonalize", "gmres.precond", "gmres.spmv", "gmres.qr",
                  "gmres.update"):
        assert any(f"/{scope}/" in n for n in names), scope
    # the preconditioner's own scopes nest inside the GMRES one
    assert any("/gmres.precond/sweep.lower/" in n for n in names)
    assert any("/gmres.precond/sweep.upper/" in n for n in names)


def test_every_sweep_group_loop_carries_its_scope():
    """Each level group of the sweep is a loop of its own; every such loop
    (and every loop inside the preconditioner) carries
    ``gmres.precond/sweep.lower`` or ``sweep.upper`` in its ``op_name``, so
    the metrics that read those scopes read the whole sweep. A group of one
    level compiles to no loop; its ops carry the scope as well."""
    from repro.core import matgen

    a = matgen(120, density=0.06, seed=0)
    fact = ilu(a, 1)
    pre = fact.precond()
    mv = make_ell_matvec(*csr_to_ell_arrays(a), a.n)
    engine = gmres_engine(mv, pre, restart=5, tol=1e-5, maxiter=3)
    text = engine.lower(jax.ShapeDtypeStruct((a.n,), jnp.float32)).compile().compiled.as_text()
    loops = [n for line in text.splitlines() if " while(" in line
             for n in re.findall(r'op_name="([^"]*)"', line)]
    in_precond = [n for n in loops if "/gmres.precond/" in n]
    assert all("/gmres.precond/sweep.lower/" in n or "/gmres.precond/sweep.upper/" in n
               for n in in_precond)
    for scope, sweep in (("sweep.lower", pre.plan.lower), ("sweep.upper", pre.plan.upper)):
        multi = sum(c.shape[0] > 1 for c in sweep.cols)
        assert multi > 1
        # one group loop per multi-level group at each of the two M(...) calls
        assert sum(n.endswith(f"/gmres.precond/{scope}/while") for n in in_precond) == 2 * multi


def test_factor_engine_is_named_and_scoped():
    from repro.core.factor_plan import factor_plan_for

    a = poisson_2d(6)
    fact = ilu(a, 1)
    plan = factor_plan_for(a, fact.pattern)
    lowered = plan.engine().lower(plan.a_vals)
    assert "module @jit__eval_factorize " in lowered._lowered.as_text()
    assert any("/factor.rounds/" in n for n in _op_names(lowered.compile().compiled.as_text()))


def test_planning_and_push_spans_are_recorded():
    before = obs.totals()
    ilu(poisson_2d(5), 1).precond()
    after = obs.totals()
    for name in ("ilu:plan.symbolic", "ilu:plan.factor", "ilu:plan.triangular",
                 "ilu:push.scatter", "ilu:push.factorize", "ilu:push.fetch",
                 "ilu:push.to_csr"):
        assert after[name][0] > before.get(name, (0, 0.0))[0], name
    assert all(k.startswith(obs.PREFIX) for k in after)


def test_value_push_records_every_push_span():
    from repro.serve import ServeConfig, SolveService

    a = poisson_2d(5)
    svc = SolveService(ServeConfig(k=1, restart=5, maxiter=3, buckets=(1,)))
    svc.register_matrix("m", a)
    before = obs.totals()
    svc.update_matrix_values("m", a.data * 2.0, background=False)
    after = obs.totals()
    for name in ("ilu:push.scatter", "ilu:push.factorize", "ilu:push.fetch",
                 "ilu:push.to_csr", "ilu:push.audit", "ilu:push.rebind", "ilu:push.put"):
        assert after[name][0] == before.get(name, (0, 0.0))[0] + 1, name
    for name in ("ilu:plan.symbolic", "ilu:plan.factor", "ilu:plan.triangular"):
        assert after.get(name) == before.get(name), name  # a push plans nothing


def test_compile_counter_still_reachable_through_serve_metrics():
    from repro.serve import CompileWatch, compile_count
    from repro.serve import metrics as serve_metrics

    assert serve_metrics.compile_count is obs.compile_count
    assert serve_metrics.CompileWatch is obs.CompileWatch
    assert compile_count is obs.compile_count and CompileWatch is obs.CompileWatch
    watch = serve_metrics.CompileWatch()
    base = serve_metrics.compile_count()
    # a constant no other program holds, so nothing cached can serve it
    bump = float(np.random.default_rng().uniform(1.0, 2.0))
    jax.jit(lambda v: v * bump + 41.0)(jnp.ones(3)).block_until_ready()
    assert serve_metrics.compile_count() > base
    assert watch.since_mark() >= 1
