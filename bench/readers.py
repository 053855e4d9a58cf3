"""What the per-layer metric files (``bench/metrics/<name>.py``) share: each
names its own span, program and work key and calls one of these. Each
returns None where the run has nothing to read, and the harness then leaves
the metric out of the result line."""
from __future__ import annotations

from bench import work


def program_ms(run, program: str, span: str, calls_key: str = None):
    """Device milliseconds per dispatch of ``program`` started inside
    ``span``. Where ``calls_key`` names a counter, the device time is
    divided by it, else by the number of such spans."""
    if run.trace is None:
        return None
    seconds, count = run.trace.program_seconds(program, span)
    calls = run.counters.get(calls_key) if calls_key else \
        sum(1 for s in run.trace.spans if s.name == span)
    if not calls or seconds <= 0.0:
        return None
    return 1e3 * seconds / calls


def roofline(run, program: str, span: str, work_key: str, calls_key: str = None):
    """Percent of the roofline of one call, from the work ``bench/work.py``
    counts and the peak of the run's device kind."""
    ms = program_ms(run, program, span, calls_key)
    if ms is None or run.peaks is None or work_key not in run.work:
        return None
    share, _bound = work.roofline_share(run.work[work_key], ms * 1e-3, run.peaks)
    return share


def idle_share(run):
    """Percent of the measured window in which no operation ran on the chip."""
    if run.trace is None or not len(run.trace.ops):
        return None
    w = run.trace.span("window")
    if w is None or w.end <= w.start:
        return None
    return 100.0 * (1.0 - run.trace.busy_seconds(w.start, w.end) / (w.end - w.start))


def mean(values):
    return sum(values) / len(values) if values else None
