"""Each fault the cells can have, planted under a whole run at a small size
on the CPU (the look for a chip skipped): ``correct`` comes out false.

* an answer altered where it is produced (every cell);
* a step that returns its state unchanged (the value push of the
  refactorization cell keeps the old factor).

No cell can leave out half of a batch (the refactorization cell's service
solves one right-hand side at a time, in buckets of one) or an exchange
between chips (no cell runs on more than one).
"""
import numpy as np

from bench.tests import tiny


def test_solve_answer_altered(monkeypatch):
    import repro.core.solvers as solvers

    real = solvers.gmres

    def altered(*a, **k):
        res = real(*a, **k)
        res.x = res.x.copy()
        res.x[len(res.x) // 3] += 1.0
        return res

    monkeypatch.setattr(solvers, "gmres", altered)
    r = tiny.run("poisson2d-400.solve", seconds=0.5)
    assert not r["correct"] and r["failed"] == r["attempted"]


def test_refactor_state_unchanged(monkeypatch):
    from repro.serve.cache import PlanCache

    monkeypatch.setattr(PlanCache, "update_values", lambda self, *a, **k: None)
    r = tiny.run("matgen-160k.refactor", seconds=0.5)
    assert not r["correct"]
    assert r["checks"]["factor_bits_differ"]["value"] > 0


def test_refactor_answer_altered(monkeypatch):
    _alter_lane(monkeypatch)
    r = tiny.run("matgen-160k.refactor", seconds=0.5)
    assert not r["correct"]


def _alter_lane(monkeypatch):
    from repro.serve.engine import ServeEngine

    real = ServeEngine.solve

    def altered(self, binding, bs, tols):
        lanes = real(self, binding, bs, tols)
        lanes[0].x = lanes[0].x.copy()
        lanes[0].x[7] *= np.float32(1.5)
        return lanes

    monkeypatch.setattr(ServeEngine, "solve", altered)
