"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Two steps. :func:`load` reads the file with ``jax.profiler.ProfileData``
into plain interval lists: device operations and device programs (XLA
modules) per chip, and the benchmark's own host spans
(``jax.profiler.TraceAnnotation``). Everything after that works on those
lists alone, so it is tested on intervals written by hand:

* busy time is the union of the device operation intervals inside a
  window, averaged over the chips, and the idle share is one minus busy
  over the window;
* a program's device time is the summed duration of the programs of that
  name that start inside a host span of a given name;
* each idle gap inside the window is charged to the innermost benchmark
  span open at its midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import fnmatch
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]

#: line names of a device plane that hold operations and programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: characters of a device operation's name kept (the HLO instruction text,
#: which runs to kilobytes for a loop)
NAME_CHARS = 100

#: host spans the benchmark's own files record around calls into a layer
SPANS = ("window", "generate", "plan", "push_values", "solve", "submit", "tick",
         "check", "probe.sweep", "probe.spmv")


@dataclasses.dataclass(slots=True)
class Event:
    name: str
    start: float  # seconds
    end: float
    device: int = 0


class Ops:
    """Device operations as arrays (a trace holds millions of them):
    ``start``/``end`` in seconds, ``device`` index, ``name_id`` into
    ``names``."""

    def __init__(self, names, name_id, start, end, device):
        self.names = list(names)
        self.name_id = np.asarray(name_id, np.int64)
        self.start = np.asarray(start, np.float64)
        self.end = np.asarray(end, np.float64)
        self.device = np.asarray(device, np.int64)

    @classmethod
    def from_events(cls, events: Sequence[Event]) -> "Ops":
        ids: Dict[str, int] = {}
        nid = [ids.setdefault(e.name, len(ids)) for e in events]
        return cls(list(ids), nid, [e.start for e in events], [e.end for e in events],
                   [e.device for e in events])

    def __len__(self):
        return len(self.start)

    def merged(self, device: int) -> Tuple[np.ndarray, np.ndarray]:
        on = self.device == device
        return union_arrays(self.start[on], self.end[on])


@dataclasses.dataclass
class Trace:
    ops: Ops
    modules: List[Event]
    spans: List[Event]
    n_devices: int

    @classmethod
    def from_events(cls, ops, modules, spans, n_devices) -> "Trace":
        return cls(Ops.from_events(ops), list(modules), list(spans), n_devices)

    def span(self, name: str) -> Optional[Event]:
        """The longest host span of that name (the window is recorded once)."""
        hits = [s for s in self.spans if s.name == name]
        return max(hits, key=lambda s: s.end - s.start) if hits else None

    def devices(self) -> List[int]:
        return sorted(set(self.ops.device.tolist()))

    def busy_seconds(self, lo: float, hi: float) -> float:
        """Union of the device operations inside [lo, hi], averaged over the
        chips."""
        devs = self.devices()
        if not devs:
            return 0.0
        total = sum(covered_arrays(*self.ops.merged(d), lo, hi) for d in devs)
        return total / max(self.n_devices, len(devs))

    def program_seconds(self, pattern: str, span: str) -> Tuple[float, int]:
        """(device seconds, count) of programs whose name matches ``pattern``
        (``fnmatch``) and that start inside a host span named ``span``,
        averaged over the chips."""
        inside = sorted((s.start, s.end) for s in self.spans if s.name == span)
        total, count, devices = 0.0, 0, set()
        for m in self.modules:
            if fnmatch.fnmatchcase(m.name, pattern) and _within(m.start, inside):
                total += m.end - m.start
                count += 1
                devices.add(m.device)
        d = max(len(devices), 1)
        return total / d, count // d

    def idle_by_span(self, lo: float, hi: float, top: int = 10) -> List[list]:
        """Idle seconds of the first chip inside [lo, hi] per innermost
        benchmark span; a gap while a program is running on the chip (a
        wait inside the program, not on the host) is named
        ``<span> (in program)``."""
        g0, g1 = gaps_arrays(*self.ops.merged(0), lo, hi)
        mid = 0.5 * (g0 + g1)
        label = np.full(len(mid), -1)
        names = []
        for s in sorted(self.spans, key=lambda s: s.start - s.end):  # longest first
            hit = (mid >= s.start) & (mid <= s.end)
            if hit.any():
                label[hit] = len(names)
                names.append(s.name)
        in_program = np.zeros(len(mid), bool)
        for m in self.modules:
            if m.device == 0:
                in_program |= (mid >= m.start) & (mid <= m.end)
        out: Dict[str, float] = collections.defaultdict(float)
        for lab, inp, length in zip(label.tolist(), in_program.tolist(), (g1 - g0).tolist()):
            name = names[lab] if lab >= 0 else "none"
            out[name + (" (in program)" if inp else "")] += length
        return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:top]]

    def top_ops(self, lo: float, hi: float, top: int = 10) -> List[list]:
        """Device seconds per operation name inside [lo, hi], largest first
        (an operation that contains others, a loop, counts their time too)."""
        inside = np.clip(np.minimum(self.ops.end, hi) - np.maximum(self.ops.start, lo), 0, None)
        per_name = np.bincount(self.ops.name_id, weights=inside,
                               minlength=len(self.ops.names))
        d = max(self.n_devices, 1)
        order = np.argsort(-per_name, kind="stable")[:top]
        return [[self.ops.names[i], float(per_name[i]) / d] for i in order if per_name[i] > 0]


def union_arrays(start: np.ndarray, end: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merge overlapping or touching intervals given as arrays; returns the
    merged starts and ends, sorted."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start, kind="stable")
    s, e = np.asarray(start)[order], np.asarray(end)[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], reach[last]


def covered_arrays(s: np.ndarray, e: np.ndarray, lo: float, hi: float) -> float:
    """Length of disjoint intervals ``(s, e)`` inside [lo, hi]."""
    return float(np.clip(np.minimum(e, hi) - np.maximum(s, lo), 0, None).sum())


def gaps_arrays(s: np.ndarray, e: np.ndarray, lo: float, hi: float):
    """The parts of [lo, hi] that disjoint sorted intervals leave free."""
    keep = (e > lo) & (s < hi)
    s, e = s[keep], e[keep]
    g0 = np.concatenate([[lo], e])
    g1 = np.concatenate([s, [hi]])
    free = g1 > g0
    return g0[free], g1[free]


def _within(t: float, spans: Sequence[Interval]) -> bool:
    return any(s <= t <= e for s, e in spans)


def find(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str, span_names: Sequence[str] = SPANS) -> Trace:
    """Read the device planes' operations and programs and the host spans
    named in ``span_names`` (times in seconds on the trace's clock)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules, spans, devices = [], [], 0
    ids: Dict[str, int] = {}
    nid, start, dur, dev_of = [], [], [], []
    wanted = set(span_names)
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in lines:  # a chip
            dev = devices
            devices += 1
            for ev in lines[OPS_LINE].events:
                nid.append(ids.setdefault(ev.name[:NAME_CHARS], len(ids)))
                start.append(ev.start_ns)
                dur.append(ev.duration_ns)
                dev_of.append(dev)
            for ev in lines[MODULES_LINE].events if MODULES_LINE in lines else ():
                s = ev.start_ns * 1e-9
                modules.append(Event(ev.name, s, s + ev.duration_ns * 1e-9, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        s = ev.start_ns * 1e-9
                        spans.append(Event(ev.name, s, s + ev.duration_ns * 1e-9))
    start = np.asarray(start, np.float64) * 1e-9
    ops = Ops(list(ids), nid, start, start + np.asarray(dur, np.float64) * 1e-9, dev_of)
    return Trace(ops=ops, modules=modules, spans=spans, n_devices=devices)
