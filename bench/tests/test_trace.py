"""The trace reduction (bench/trace.py): busy time as the union of device
operations, the idle share, a program's device time by name and host span,
and idle gaps charged to the host span open during them."""
import os

import numpy as np
import pytest

from bench import trace as tr
from bench.trace import Event, Trace


def _trace():
    # one chip; window [0, 10] s; ops overlap in [1, 3] and [2, 4]
    ops = [Event("fusion.1", 1.0, 3.0), Event("fusion.2", 2.0, 4.0),
           Event("while.3", 6.0, 7.0), Event("fusion.1", 9.0, 9.5), Event("late", 11.0, 12.0)]
    modules = [Event("jit__eval(12)", 1.0, 4.0), Event("jit__eval(12)", 6.0, 7.0),
               Event("jit_other", 9.0, 9.5)]
    spans = [Event("window", 0.0, 10.0), Event("solve", 0.8, 4.5),
             Event("tick", 5.0, 8.0), Event("push_values", 5.5, 7.5),
             Event("probe.sweep", 8.5, 9.8)]
    return Trace.from_events(ops=ops, modules=modules, spans=spans, n_devices=1)


def test_busy_is_the_union_of_overlapping_ops():
    t = _trace()
    # [1,4] + [6,7] + [9,9.5] inside the window; the op after it is cut off
    assert t.busy_seconds(0.0, 10.0) == pytest.approx(3.0 + 1.0 + 0.5)
    s, e = tr.union_arrays(np.array([2.0, 1, 5, 6.5, 6]), np.array([4.0, 3, 6, 6.8, 7]))
    assert s.tolist() == [1, 5] and e.tolist() == [4, 7]
    assert tr.covered_arrays(np.array([1.0]), np.array([4.0]), 2.0, 10.0) == pytest.approx(2.0)


def test_busy_averages_over_chips():
    t = _trace()
    t.ops = tr.Ops.from_events([Event(e, s, f) for e, s, f in
                                zip(t.ops.names, t.ops.start, t.ops.end)] +
                               [Event("fusion.1", 0.0, 10.0, device=1)])
    t.n_devices = 2
    assert t.busy_seconds(0.0, 10.0) == pytest.approx((4.5 + 10.0) / 2)


def test_idle_share_from_the_window(tmp_path):
    from bench.readers import idle_share

    class R:
        trace = _trace()

    assert idle_share(R) == pytest.approx(100.0 * (1 - 4.5 / 10.0))
    R.trace = Trace.from_events(ops=[], modules=[], spans=[Event("window", 0, 1)], n_devices=0)
    assert idle_share(R) is None  # no device operation: nothing to read


def test_program_seconds_by_name_and_span():
    t = _trace()
    assert t.program_seconds("jit__eval*", "solve") == (pytest.approx(3.0), 1)
    assert t.program_seconds("jit__eval*", "push_values") == (pytest.approx(1.0), 1)
    assert t.program_seconds("jit__eval*", "probe.sweep") == (0.0, 0)
    assert t.program_seconds("jit_other", "probe.sweep") == (pytest.approx(0.5), 1)


def test_idle_gaps_go_to_the_innermost_span():
    t = _trace()
    got = dict(t.idle_by_span(0.0, 10.0))
    # gaps: [0,1] window, [4,6] midpoint 5 -> tick, [7,9] midpoint 8 -> tick,
    # [9.5,10] midpoint 9.75 -> probe.sweep
    assert got == pytest.approx({"window": 1.0, "tick": 4.0, "probe.sweep": 0.5})
    g0, g1 = tr.gaps_arrays(np.array([1.0, 6]), np.array([4.0, 7]), 0.0, 10.0)
    assert list(zip(g0.tolist(), g1.tolist())) == [(0, 1), (4, 6), (7, 10)]


def test_idle_gaps_inside_a_running_program_are_named_so():
    t = _trace()
    t.modules.append(Event("jit__eval(12)", 4.2, 5.8))  # the chip waits inside it
    got = dict(t.idle_by_span(0.0, 10.0))
    assert got["tick (in program)"] == pytest.approx(2.0)
    assert got["tick"] == pytest.approx(2.0)


def test_top_ops_ranked_within_the_window():
    got = _trace().top_ops(0.0, 10.0)
    assert got[0] == ["fusion.1", pytest.approx(2.5)]
    assert ["late", pytest.approx(0.0)] not in got


def test_spans_read_from_a_recorded_trace(tmp_path):
    """A trace recorded here on the CPU: the benchmark's host spans come
    back with their nesting and times; the CPU has no device plane."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones(1024)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("solve"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("not-ours"):
            pass
    jax.profiler.stop_trace()
    t = tr.load(tr.find(str(tmp_path)))
    names = [s.name for s in t.spans]
    assert names.count("solve") == 3 and names.count("window") == 1
    assert "not-ours" not in names
    w = t.span("window")
    assert all(w.start <= s.start <= s.end <= w.end for s in t.spans)
    assert t.n_devices == 0 and len(t.ops) == 0
    assert os.path.getsize(tr.find(str(tmp_path))) > 0
