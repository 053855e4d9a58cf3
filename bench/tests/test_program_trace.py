"""The readers of the program's own instrumentation (bench/program_trace.py)
on hand-written intervals: scoped device time, device idle inside the
program's host spans placed on the trace's clock, and the seven metrics'
files on a program with and without ``repro.obs``."""
import copy

import pytest

from bench import harness
from bench import program_trace as pt
from bench.tests import tiny
from bench.trace import Event, Trace

# one GMRES program [1, 9] holding an Arnoldi loop; inside it the
# Gram-Schmidt loop [2, 6] and two ops nested in that loop, then the sweep
PROGRAMS = {
    "jit__eval_gmres": {
        "while.1": "jit(_eval_gmres)/while",
        "while.2": "jit(_eval_gmres)/while/body/gmres.orthogonalize/while",
        "fusion.3": "jit(_eval_gmres)/while/body/gmres.orthogonalize/while/body/add",
        "while.4": "jit(_eval_gmres)/while/body/gmres.precond/sweep.lower/while",
        "fusion.5": "jit(_eval_gmres)/while/body/gmres.precond/sweep.upper/mul",
    },
    "jit__eval_factorize": {"while.1": "jit(_eval_factorize)/factor.rounds/while"},
}


def _hlo(module: str, table: dict) -> str:
    """A compiled program's HLO text holding ``table``'s op names."""
    lines = [f"HloModule {module}, is_scheduled=true", "", "ENTRY %main {"]
    lines += [f'  %{ins} = f32[8]{{0}} add(f32[8]{{0}} %p, f32[8]{{0}} %p), '
              f'metadata={{op_name="{path}" source_file="x.py"}}' for ins, path in table.items()]
    lines += ["  ROOT %t = (f32[8]{0}) tuple(f32[8]{0} %p)", "}"]
    return "\n".join(lines)


TEXTS = [_hlo(m, t) for m, t in PROGRAMS.items()]
NAMED = ["factor_plan_s", "tri_plan_s", "symbolic_plan_s", "orth_ms.solve", "precond_ms.solve",
         "orth_ms.step", "lsweep_ms.step", "push_idle_ms.step"]


def _trace():
    ops = [Event("%while.1 = (s32[], f32[8]) while(...)", 1.0, 9.0),
           Event("%while.2 = (s32[]) while(...)", 2.0, 6.0),
           Event("%fusion.3 = f32[8] fusion(...)", 2.5, 3.0),
           Event("%fusion.3 = f32[8] fusion(...)", 5.0, 5.5),
           Event("%while.4 = (s32[]) while(...)", 6.5, 8.0),
           Event("%fusion.5 = f32[8] fusion(...)", 8.0, 8.5),
           # the factorization program: its own while.1, another loop
           Event("%while.1 = (s32[], f32[9]) while(...)", 12.0, 14.0)]
    modules = [Event("jit__eval_gmres(7)", 1.0, 9.0), Event("jit__eval_factorize(3)", 12.0, 14.0)]
    # the solve cell's span and the refactor cell's, around the one solve
    spans = [Event("window", 0.0, 20.0), Event("solve", 0.6, 9.4), Event("tick", 0.5, 9.5),
             Event("push_values", 10.0, 16.0)]
    return Trace.from_events(ops=ops, modules=modules, spans=spans, n_devices=1)


def test_op_tables_parse_the_compiled_text():
    assert pt.op_tables(TEXTS) == PROGRAMS
    # the same program noted twice (two engines of one shape) is one table
    assert pt.op_tables(TEXTS + TEXTS[:1]) == PROGRAMS
    assert pt.op_tables(["not a module"]) == {}


def test_programs_that_share_a_module_name_are_not_guessed(monkeypatch):
    other = dict(PROGRAMS["jit__eval_gmres"], **{"while.2": "jit(_eval_gmres)/while/body/x"})
    tables = pt.op_tables(TEXTS + [_hlo("jit__eval_gmres", other)])
    assert tables["jit__eval_gmres"] is None
    assert tables["jit__eval_factorize"] == PROGRAMS["jit__eval_factorize"]
    t = _trace()
    path_of, paths = pt.op_paths(t, tables)
    got = [paths[i] for i in path_of]
    assert got[:6] == [""] * 6
    assert got[6] == "jit(_eval_factorize)/factor.rounds/while"
    assert pt.scope_seconds(t, path_of, paths, "gmres.orthogonalize", 0, 20) == 0.0
    texts = TEXTS + [_hlo("jit__eval_gmres", other)]
    monkeypatch.setattr(pt, "instrumentation", lambda: _Obs(texts=texts))
    assert harness.load_module(tiny.ROOT, "metrics", "orth_ms.solve").read(_Run(t)) is None


def test_ops_take_the_op_name_of_their_program_and_instruction():
    t = _trace()
    path_of, paths = pt.op_paths(t, PROGRAMS)
    got = [paths[i] for i in path_of]
    assert got[0] == "jit(_eval_gmres)/while"
    assert got[2] == got[3] == PROGRAMS["jit__eval_gmres"]["fusion.3"]
    # the same instruction name in another program is that program's op
    assert got[6] == "jit(_eval_factorize)/factor.rounds/while"
    path_of, paths = pt.op_paths(t, {})
    assert set(paths) == {""}


def test_scope_counts_a_loop_and_the_ops_inside_it_once():
    t = _trace()
    path_of, paths = pt.op_paths(t, PROGRAMS)
    assert pt.scope_seconds(t, path_of, paths, "gmres.orthogonalize", 0, 20) == \
        pytest.approx(4.0)
    assert pt.scope_seconds(t, path_of, paths, "gmres.precond", 0, 20) == pytest.approx(2.0)
    assert pt.scope_seconds(t, path_of, paths, "gmres.precond/sweep.lower", 0, 20) == \
        pytest.approx(1.5)
    assert pt.scope_seconds(t, path_of, paths, "sweep.lower", 7.0, 20) == pytest.approx(1.0)
    assert pt.scope_seconds(t, path_of, paths, "factor.rounds", 0, 20) == pytest.approx(2.0)
    # a segment matches whole scopes only
    assert pt.scope_seconds(t, path_of, paths, "gmres.ortho", 0, 20) == 0.0
    assert pt.scope_seconds(t, path_of, paths, "sweep", 0, 20) == 0.0


def test_idle_in_counts_the_free_device_time_inside_the_intervals():
    t = _trace()  # busy [1, 9] and [12, 14]
    assert pt.idle_in(t, [(10.0, 13.0)], 0, 20) == pytest.approx(2.0)
    # overlapping intervals count once; the window cuts them
    assert pt.idle_in(t, [(10.0, 13.0), (11.0, 12.5), (15.0, 25.0)], 0, 20) == \
        pytest.approx(2.0 + 5.0)
    assert pt.idle_in(t, [(2.0, 8.0)], 0, 20) == 0.0
    assert pt.idle_in(t, [], 0, 20) == 0.0


def test_program_spans_are_placed_by_the_factorization_they_wait_for():
    t = _trace()
    # host clock = trace clock + 100; the push after set-up's (which is
    # not in the trace) waits for the factorization program [12, 14]
    recent = [("ilu:push.factorize", 101.0, 103.0), ("ilu:push.fetch", 103.0, 103.5),
              ("ilu:push.scatter", 111.0, 111.8), ("ilu:push.factorize", 111.9, 114.0),
              ("ilu:push.fetch", 114.0, 115.0)]
    placed = pt.place_spans(t, recent)
    assert placed[-1] == ("ilu:push.fetch", pytest.approx(14.0), pytest.approx(15.0))
    assert placed[2] == ("ilu:push.scatter", pytest.approx(11.0), pytest.approx(11.8))
    # a span that cannot hold its program: nothing is placed
    assert pt.place_spans(t, [("ilu:push.factorize", 113.5, 114.0)]) is None
    assert pt.place_spans(t, recent[1:3]) is None  # no anchor span


class _Obs:
    def __init__(self, recent=(), totals=None, texts=TEXTS):
        self._recent, self._totals, self._texts = list(recent), totals or {}, list(texts)

    def program_texts(self):
        return self._texts

    def recent_spans(self):
        return self._recent

    def totals(self):
        return self._totals


class _Run:
    def __init__(self, trace):
        self.trace, self.state = trace, {}


def test_metrics_read_the_program_and_leave_the_trace_as_it_was(monkeypatch):
    t = _trace()
    gaps, ops = t.idle_by_span(0, 20), t.top_ops(0, 20)
    recent = [("ilu:push.scatter", 110.0, 111.0), ("ilu:push.factorize", 111.0, 114.0),
              ("ilu:push.fetch", 114.0, 115.0), ("ilu:push.put", 115.0, 115.5)]
    monkeypatch.setattr(pt, "instrumentation", lambda: _Obs(
        recent, {"ilu:plan.factor": (1, 27.5), "ilu:plan.triangular": (2, 9.0),
                 "ilu:plan.symbolic": (1, 1.25)}))
    run = _Run(t)
    load = lambda name: harness.load_module(tiny.ROOT, "metrics", name).read  # noqa: E731
    # one solve (one GMRES program) in the window
    assert load("orth_ms.solve")(run) == pytest.approx(4000.0)
    assert load("orth_ms.step")(run) == pytest.approx(4000.0)
    assert load("precond_ms.solve")(run) == pytest.approx(2000.0)
    assert load("lsweep_ms.step")(run) == pytest.approx(1500.0)
    # scatter [10, 11], fetch [14, 15], put [15, 15.5]: the device is idle
    # in all of them; one push_values span
    assert load("push_idle_ms.step")(run) == pytest.approx(2500.0)
    assert load("factor_plan_s")(run) == 27.5 and load("tri_plan_s")(run) == 9.0
    assert load("symbolic_plan_s")(run) == 1.25
    assert t.idle_by_span(0, 20) == gaps and t.top_ops(0, 20) == ops


def test_scoped_time_is_per_solve_span(monkeypatch):
    """Two solves whose programs the profile shows as one module event: the
    time is divided by the solves, not by the module events."""
    t = _trace()
    t.spans.append(Event("solve", 9.45, 9.9))
    monkeypatch.setattr(pt, "instrumentation", lambda: _Obs())
    run = _Run(t)
    assert harness.load_module(tiny.ROOT, "metrics", "orth_ms.solve").read(run) == \
        pytest.approx(2000.0)


def test_metrics_are_silent_on_a_program_without_instrumentation(monkeypatch):
    monkeypatch.setattr(pt, "instrumentation", lambda: None)
    run = _Run(_trace())
    for name in NAMED:
        assert harness.load_module(tiny.ROOT, "metrics", name).read(run) is None


def test_metrics_are_silent_on_unnamed_engines(monkeypatch):
    """The parent's program: every engine is ``jit__eval`` and no op has a
    scope, so the device-trace readers find nothing to read."""
    t = _trace()
    for m in t.modules:
        m.name = "jit__eval(1)"
    monkeypatch.setattr(pt, "instrumentation", lambda: _Obs())
    run = _Run(t)
    for name in ("orth_ms.solve", "precond_ms.solve", "lsweep_ms.step", "push_idle_ms.step"):
        assert harness.load_module(tiny.ROOT, "metrics", name).read(run) is None


@pytest.mark.parametrize("workload", tiny.workloads())
def test_planning_totals_are_set_up_alone(workload):
    """The ``ilu:plan.*`` totals the planning metrics read do not grow in
    the window, and a traced CPU run reports both planning metrics."""
    from repro import obs

    spec = copy.deepcopy(tiny.spec(workload))
    mix = harness.load_module(tiny.ROOT, "drivers", spec["traffic"]["driver"])
    seen = {}
    window = mix.window

    def watched(run):
        before = obs.totals()
        out = window(run)
        after = obs.totals()
        seen.update({k: (before.get(k), after.get(k)) for k in after if "plan." in k})
        return out

    mix.window = watched
    try:
        r = tiny.run(workload, seconds=0.5, trace=True, s=spec)
    finally:
        mix.window = window
    assert seen and all(b == a for b, a in seen.values())
    assert r["correct"]
    for name in ("factor_plan_s", "tri_plan_s", "symbolic_plan_s"):
        assert r["metrics"][name]["value"] > 0
