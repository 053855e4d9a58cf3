"""What the per-layer metrics read of the program's own instrumentation
(``repro.obs``), joined to the traced run's device operations.

``bench/trace.py`` keeps the device operations by instruction, the device
programs by module name and the benchmark's own host spans. The program
adds three records that this module joins to them:

* op names: the HLO text of every program the named engines compiled
  (``obs.program_texts()``, made when asked), parsed here into
  module -> instruction -> ``op_name`` (:func:`op_tables`). An operation of
  the trace is looked up by the program it runs in (the ``XLA Modules``
  event around its start) and its instruction (the head of its name); its
  op name is its ``jax.named_scope`` path, such as
  ``jit(_eval_gmres)/while/body/.../gmres.precond/sweep.lower/while``.
  The profile's program id is not in the text, so where two different
  programs share a module name their operations are not looked up at all;
* spans: ``obs.recent_spans()``, on the host's ``perf_counter`` clock. They
  are placed on the trace's clock by the factorization program that each
  ``ilu:push.factorize`` span waits for: the span ends when that program's
  output is ready;
* totals: ``obs.totals()``, ``{name: (count, seconds)}`` of every span.

On a program without ``repro.obs`` each reader returns None, and the
harness leaves the metric out of the result line.
"""
from __future__ import annotations

import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import trace as tr

#: the programs of the Krylov solvers (library GMRES, batched, the service's)
GMRES = "jit__eval*gmres*"
#: the span that waits for each factorization, and that program's module
ANCHOR_SPAN = "ilu:push.factorize"
ANCHOR_MODULE = "jit__eval_factorize"
#: how far a mapped anchor span may end before its program does (seconds)
ANCHOR_SLACK = 2e-3

_INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w.\-]*)")
_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"', re.M)


def instrumentation():
    """The program's ``repro.obs``, or None where the program has none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def module_base(name: str) -> str:
    """A module event's name without the program id a profile may append:
    ``jit__eval_gmres(12)`` -> ``jit__eval_gmres``."""
    return name.split("(", 1)[0].strip()


def instruction(op: str) -> str:
    """The instruction an operation event is named after (its name may be
    the HLO text, ``%while.290 = (s32[], ...) while(...)``)."""
    m = _INSTRUCTION.match(op)
    return m.group(1) if m else op


def op_tables(texts: Sequence[str]) -> Dict[str, Optional[Dict[str, str]]]:
    """``{module: {instruction: op_name}}`` of compiled HLO texts. A module
    name that two different programs share maps to None: a profile names
    both alike, so neither table can be trusted for its operations."""
    tables: Dict[str, Optional[Dict[str, str]]] = {}
    for text in texts:
        module = _MODULE.match(text)
        if module is None:
            continue
        name, ops = module.group(1), dict(_OP_NAME.findall(text))
        tables[name] = ops if tables.get(name, ops) == ops else None
    return tables


def op_paths(trace, programs: Dict[str, Optional[Dict[str, str]]]
             ) -> Tuple[np.ndarray, List[str]]:
    """For each device operation of ``trace``, an index into the returned
    list of op names ('' where its program or instruction is not known, or
    its module name is shared by programs :func:`op_tables` cannot tell
    apart)."""
    ops = trace.ops
    module_of = np.full(len(ops), -1, np.int64)
    base_id: Dict[str, int] = {}
    for d in sorted({m.device for m in trace.modules}):
        mods = sorted((m for m in trace.modules if m.device == d), key=lambda m: m.start)
        starts = np.array([m.start for m in mods])
        ends = np.array([m.end for m in mods])
        ids = np.array([base_id.setdefault(module_base(m.name), len(base_id)) for m in mods])
        on = np.flatnonzero(ops.device == d)
        k = np.searchsorted(starts, ops.start[on], side="right") - 1
        inside = (k >= 0) & (ops.start[on] <= ends[np.maximum(k, 0)])
        module_of[on[inside]] = ids[k[inside]]
    bases = sorted(base_id, key=base_id.get)
    pair = ops.name_id * (len(bases) + 1) + (module_of + 1)
    uniq, inverse = np.unique(pair, return_inverse=True)
    paths = []
    for p in uniq.tolist():
        name_id, m = divmod(p, len(bases) + 1)
        table = (programs.get(bases[m - 1]) if m else None) or {}
        paths.append(table.get(instruction(ops.names[name_id]), ""))
    return inverse.reshape(-1), paths


def has_segment(path: str, segment: str) -> bool:
    """Whether ``segment`` (one scope, or several joined by ``/``) is a run
    of whole segments of the op name ``path``."""
    return f"/{segment}/" in f"/{path}/"


def scope_seconds(trace, path_of: np.ndarray, paths: Sequence[str], segment: str,
                  lo: float, hi: float) -> float:
    """Device seconds inside [lo, hi] of the operations whose op name has
    ``segment``, as the union of their intervals (a loop and the operations
    inside it count once), averaged over the chips."""
    want = np.flatnonzero([has_segment(p, segment) for p in paths])
    devs = trace.devices()
    if not len(want) or not devs:
        return 0.0
    sel = np.isin(path_of, want)
    total = 0.0
    for d in devs:
        on = sel & (trace.ops.device == d)
        total += tr.covered_arrays(*tr.union_arrays(trace.ops.start[on], trace.ops.end[on]),
                                   lo, hi)
    return total / max(trace.n_devices, len(devs))


def idle_in(trace, intervals: Sequence[Tuple[float, float]], lo: float, hi: float) -> float:
    """Device-idle seconds inside the union of ``intervals`` within
    [lo, hi], averaged over the chips."""
    if not intervals:
        return 0.0
    s, e = tr.union_arrays(np.array([a for a, _ in intervals], np.float64),
                           np.array([b for _, b in intervals], np.float64))
    s, e = np.clip(s, lo, hi), np.clip(e, lo, hi)
    length = float((e - s).sum())
    devs = trace.devices()
    busy = 0.0
    for d in devs:
        ms, me = trace.ops.merged(d)
        for a, b in zip(s.tolist(), e.tolist()):
            i, j = np.searchsorted(me, a), np.searchsorted(ms, b)
            busy += tr.covered_arrays(ms[i:j], me[i:j], a, b)
    return length - busy / max(trace.n_devices, len(devs), 1)


def place_spans(trace, recent: Sequence[Tuple[str, float, float]]
                ) -> Optional[List[Tuple[str, float, float]]]:
    """The program's spans on the trace's clock, or None where they cannot
    be placed. The last k ``ilu:push.factorize`` spans are paired, in order,
    with the last k factorization programs of the first chip; the offset is
    the median of (program end - span end), and each paired span must then
    hold its program."""
    mods = sorted((m for m in trace.modules
                   if m.device == 0 and module_base(m.name) == ANCHOR_MODULE),
                  key=lambda m: m.end)
    anchors = [s for s in recent if s[0] == ANCHOR_SPAN]
    k = min(len(mods), len(anchors))
    if k == 0:
        return None
    pairs = list(zip(anchors[-k:], mods[-k:]))
    offset = statistics.median(m.end - t1 for (_n, _t0, t1), m in pairs)
    for (_n, t0, t1), m in pairs:
        if t0 + offset > m.start or t1 + offset < m.end - ANCHOR_SLACK:
            return None
    return [(name, t0 + offset, t1 + offset) for name, t0, t1 in recent]


# --------------------------------------------------------------------------
# what the metric files call
# --------------------------------------------------------------------------
def scope_ms(run, segment: str, per: str, span: str = "window"):
    """Device milliseconds of the operations scoped ``segment`` inside the
    benchmark span ``span``, per benchmark span ``per`` in it (each of which
    dispatches one GMRES program). Programs are not counted from the
    profile: its ``XLA Modules`` line shows programs run back to back as
    one event. Where no named GMRES program (``jit__eval*gmres*``) starts
    in ``span`` there is nothing to read."""
    obs = instrumentation()
    if obs is None or run.trace is None:
        return None
    w = run.trace.span(span)
    if w is None or not run.trace.program_seconds(GMRES, span)[1]:
        return None
    count = sum(1 for s in run.trace.spans if s.name == per and w.start <= s.start <= w.end)
    if not count:
        return None
    cached = run.state.get("op_paths")
    if cached is None or cached[0] is not run.trace:
        tables = op_tables(obs.program_texts())
        cached = run.state["op_paths"] = (run.trace, *op_paths(run.trace, tables))
    _trace, path_of, paths = cached
    seconds = scope_seconds(run.trace, path_of, paths, segment, w.start, w.end)
    return 1e3 * seconds / count if seconds > 0.0 else None


def push_idle_ms(run, per: str = "push_values", span: str = "window"):
    """Device-idle milliseconds inside the program's ``ilu:push.*`` host
    steps other than the factorization itself, per benchmark span ``per``
    inside ``span``."""
    obs = instrumentation()
    if obs is None or run.trace is None:
        return None
    w = run.trace.span(span)
    placed = place_spans(run.trace, obs.recent_spans())
    if w is None or placed is None:
        return None
    steps = [(s, e) for name, s, e in placed
             if name.startswith("ilu:push.") and name != ANCHOR_SPAN]
    pushes = sum(1 for s in run.trace.spans if s.name == per and w.start <= s.start <= w.end)
    if not pushes or not steps:
        return None
    return 1e3 * idle_in(run.trace, steps, w.start, w.end) / pushes


def span_total_s(name: str):
    """Seconds of every span ``name`` the program has recorded so far."""
    obs = instrumentation()
    if obs is None:
        return None
    hit = obs.totals().get(name)
    return hit[1] if hit else None
