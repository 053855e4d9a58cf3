"""Instrumentation inside the program: host spans, program handles, compile count.

* :class:`span` times a host-side step of a layer. It opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so under a profiler
  the span lands in the trace on the clock of the device operations, and
  it adds its duration to a process-wide table (:func:`totals`) and its
  start and end to a bounded list of recent spans (:func:`recent_spans`)
  whether a profiler runs or not. With none running it costs a
  ``perf_counter`` pair, one dict update and one append under a lock and
  the annotation's enabled check; nothing is written anywhere.
* :func:`note_program` keeps a weak handle to each named engine
  (``bitmath.hoisted_jit``) and each compiled program it hands out, and
  :func:`program_texts` asks them for the HLO text of what they compiled.
  The text is made only when asked, and nothing here reads it: a trace
  reader maps a profile's operations, named by instruction, to the
  ``op_name`` metadata of that text, which carries the
  ``jax.named_scope`` path each operation was traced under.
* The XLA backend-compile counter (:func:`install_compile_listener`,
  :func:`compile_count`, :class:`CompileWatch`): a process-global listener
  on jax's ``/jax/core/compile/backend_compile_duration`` event. After a
  warmup it must stay flat; any increment on a serving path means a
  request paid a compile.

Every span name starts with :data:`PREFIX`, so a trace reader can select
the program's spans by prefix without importing the program. Device-side
layers are named with ``jax.named_scope`` where the work is traced (the
solvers, the sweeps, the factor loop) and engines by
``bitmath.hoisted_jit(fn, name=...)``; those are compile-time metadata and
cost nothing at run time.
"""
from __future__ import annotations

import collections
import threading
import time
import weakref
from typing import Dict, List, Tuple

#: first characters of every program span name
PREFIX = "ilu:"

#: spans kept with their ``perf_counter`` start and end (the newest)
RECENT = 4096

_lock = threading.Lock()
_totals: Dict[str, list] = {}
_recent: collections.deque = collections.deque(maxlen=RECENT)
_programs: "weakref.WeakSet" = weakref.WeakSet()
_annotation = None


def _trace_annotation():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class span:
    """``with span("ilu:push.fetch"): ...`` — a named, timed host step."""

    __slots__ = ("name", "_t0", "_note")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._note = _trace_annotation()(self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._note.__exit__(*exc)
        with _lock:
            row = _totals.get(self.name)
            if row is None:
                _totals[self.name] = [1, t1 - self._t0]
            else:
                row[0] += 1
                row[1] += t1 - self._t0
            _recent.append((self.name, self._t0, t1))
        return False


def totals() -> Dict[str, Tuple[int, float]]:
    """``{name: (count, seconds)}`` of every span so far."""
    with _lock:
        return {k: (v[0], v[1]) for k, v in _totals.items()}


def recent_spans() -> List[Tuple[str, float, float]]:
    """The newest :data:`RECENT` spans as ``(name, start, end)``, oldest
    first, in ``time.perf_counter`` seconds."""
    with _lock:
        return list(_recent)


# --------------------------------------------------------------------------
# the named engines' compiled programs
# --------------------------------------------------------------------------
def note_program(owner) -> None:
    """Keep a weak handle to ``owner``, a named engine or a compiled program
    of one; its ``program_texts()`` gives the HLO text of what it compiled.
    The handle goes with the owner."""
    with _lock:
        _programs.add(owner)


def program_texts() -> List[str]:
    """The HLO text of every program the live named engines compiled, made
    now (after a run, not on its path)."""
    with _lock:
        owners = list(_programs)
    return [text for owner in owners for text in owner.program_texts()]


# --------------------------------------------------------------------------
# XLA compile counter
# --------------------------------------------------------------------------
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_lock = threading.Lock()
_compile_count = 0
_listener_installed = False


def _on_event_duration(name: str, *args, **kw) -> None:
    global _compile_count
    if name == _COMPILE_EVENT:
        with _compile_lock:
            _compile_count += 1


def install_compile_listener() -> None:
    """Idempotently register the process-global backend-compile listener.

    Must be installed before warmup for ``since_mark`` deltas to mean
    anything; installing twice is a no-op (jax keeps listeners forever, so
    a duplicate would double-count)."""
    global _listener_installed
    with _compile_lock:
        if _listener_installed:
            return
        _listener_installed = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


def compile_count() -> int:
    """Total XLA backend compiles observed since the listener installed."""
    with _compile_lock:
        return _compile_count


class CompileWatch:
    """Snapshot-and-delta view of the process compile counter."""

    def __init__(self):
        install_compile_listener()
        self._mark = compile_count()

    def mark(self) -> int:
        """Reset the baseline (call when warmup finishes); returns it."""
        self._mark = compile_count()
        return self._mark

    def since_mark(self) -> int:
        return compile_count() - self._mark
