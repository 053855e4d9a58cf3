"""The control of every cell, at a small size: the plain reference computed
in bfloat16 in the program's place comes out as not correct, by the same
comparison that decides ``correct`` in a run, and reads above the limit on
the number that compares solutions too."""
import numpy as np
import pytest

from bench import control, harness
from bench.tests import tiny


@pytest.mark.parametrize("workload", tiny.workloads())
def test_control_fails(workload):
    spec = tiny.spec(workload)
    got = control.readings(tiny.ROOT, workload, tiny.SEED, 1.0, spec)
    checks, correct = harness.verdict(got, spec["limits"])
    assert correct is False
    for name in ("factor_bits_differ", "residual_over_tol"):
        assert checks[name]["value"] > checks[name]["limit"]


def test_control_in_the_programs_place(monkeypatch):
    """A whole run of the solve cell with the program's factor replaced by
    the reference factor computed in bfloat16: ``correct`` is false."""
    import ml_dtypes

    import repro.core.api as api

    ref = harness.load_module(tiny.ROOT, "references", "ilu1")
    real = api.ilu

    def bf16_factor(a, *args, **kw):
        fact = real(a, *args, **kw)
        p_indptr, p_indices, diag = ref.pattern(a.n, a.indptr, a.indices)
        vals = ref.scatter(a.n, p_indptr, p_indices, a.indptr, a.indices, a.data,
                           ml_dtypes.bfloat16)
        fact.vals = ref.factor(a.n, p_indptr, p_indices, diag, vals,
                               ml_dtypes.bfloat16).astype(np.float32)
        return fact

    monkeypatch.setattr(api, "ilu", bf16_factor)
    r = tiny.run("poisson2d-400.solve", seconds=0.5)
    assert r["correct"] is False and r["checks"]["factor_bits_differ"]["value"] > 0


def test_control_is_the_reference_rounded_lower():
    """At float32 the same code is the reference itself: no bit differs."""
    from bench import check

    spec = tiny.spec("poisson2d-400.solve")
    run = harness.Run(tiny.ROOT, spec, tiny.SEED, 1.0, False, None)
    run.matrix = harness.load_module(tiny.ROOT, "generators", "poisson2d").generate(
        spec["config"]["matrix"], run.rng("matrix"))
    want = check.reference_factor(run, run.matrix["data"])
    p = check.reference_pattern(run)
    assert check.factor_bits_differ(run, p[:2], want.copy(), want) == 0
    assert check.control_factor_bits(run, run.matrix["data"]) > 0
