"""Bounded-LRU multi-tenant plan/factorization cache with pinning.

One :class:`CacheEntry` per registered ``matrix_id``: the canonical matrix
object (``a0`` — the host the structure-keyed ``FactorPlan`` and solver
memos live on), the filled pattern, a (possibly shared) :class:`ServeEngine`,
and the *current* :class:`EngineBinding` (value version). Three protocols:

**LRU + pinning.** Capacity bounds device memory. Every in-flight request
holds a pin on its entry; eviction only reclaims unpinned entries
(least-recently-used first). If the cache is full of pinned entries the
insert fails with ``QUEUE_FULL`` semantics rather than evicting a solve's
data out from under it. An evicted matrix can be re-registered — with the
engine shared by structure, re-admission recompiles nothing if a
structure-mate is still resident.

**Engine sharing.** Engines are keyed by :func:`engine_fingerprint`
(structure + knobs, never values) in a ``WeakValueDictionary``: tenants
with identical sparsity share one compiled engine per bucket; the engine
dies with its last entry.

**Background refactorization.** ``update_values`` refactorizes the new
values through the entry's already-compiled ``FactorPlan`` engine and
binds them to the engine — in a worker thread, so a tenant's value push
never blocks other tenants' solves. The swap is atomic (one reference
assignment under the cache lock); requests admitted before the swap keep
their pinned old binding (``SolveRequest.binding``) and solve against the
values they were admitted under — a racing update can never retarget an
in-flight solve mid-batch.
"""
from __future__ import annotations

import collections
import threading
import weakref
from typing import Callable, Dict, Optional

import numpy as np

from repro import obs
from repro.core.sparse import CSRMatrix

from .admission import BREAKDOWN, QUEUE_FULL, UNKNOWN_MATRIX, AdmissionError
from .engine import ServeEngine


def identity_values(pattern) -> np.ndarray:
    """Pattern-aligned factor values of the identity (diag 1, rest 0).

    Swept through the already-compiled triangular executable these apply
    M^{-1} = I exactly — every L lane contributes ``barred(0·y) = 0`` and
    every U diagonal divides by 1.0 — so the serve layer's last-resort
    degradation costs a bind, never a new executable."""
    vals = np.zeros(pattern.nnz, np.float32)
    vals[np.asarray(pattern.indptr[:-1]) + np.asarray(pattern.diag_ptr)] = 1.0
    return vals


class CacheEntry:
    """One resident matrix: canonical host objects + current binding."""

    def __init__(self, matrix_id: str, a0: CSRMatrix, pattern, engine, binding,
                 plan_host: Optional[CSRMatrix] = None):
        self.matrix_id = matrix_id
        self.a0 = a0              # this entry's own matrix (structure + values)
        self.pattern = pattern
        self.engine = engine
        self.binding = binding    # current EngineBinding (atomic-swap target)
        # canonical same-structure matrix the compiled FactorPlan memoizes on
        # (the first registrant of this structure — possibly a0 itself)
        self.plan_host = plan_host if plan_host is not None else a0
        self.pins = 0
        self.version = binding.version
        # lazily built shifted-preconditioner bindings for breakdown
        # retries, keyed by ("shift", base binding version) — one ladder
        # climb per value version, shared by every retrying request
        self.degraded_bindings: dict = {}


class PlanCache:
    """The bounded-LRU store. All public methods are thread-safe; solves,
    submits, and background refactor threads may interleave freely."""

    def __init__(self, capacity: int = 8, metrics=None,
                 engine_factory: Optional[Callable] = None,
                 on_breakdown: str = "shift", pivot_tol: Optional[float] = None):
        if capacity < 1:
            raise ValueError(f"PlanCache capacity must be >= 1, got {capacity}")
        if on_breakdown not in ("raise", "shift", "fallback", "ignore"):
            raise ValueError(f"PlanCache: unknown on_breakdown {on_breakdown!r}")
        self.capacity = capacity
        self.metrics = metrics
        # pivot-guard policy for every factorization this cache performs
        # (serve default "shift": a tenant's broken matrix registers with a
        # shifted preconditioner instead of poisoning its future batches)
        self.on_breakdown = on_breakdown
        self.pivot_tol = pivot_tol
        self._engine_factory = engine_factory or self._default_engine_factory
        self._lock = threading.RLock()
        self._entries: "collections.OrderedDict[str, CacheEntry]" = collections.OrderedDict()
        # structure-keyed engine sharing; weak so engines die with their entries
        self._engines_by_structure = weakref.WeakValueDictionary()
        # structure-keyed canonical factor-plan hosts: FactorPlan memoizes on
        # a matrix object, so same-structure registrations route through the
        # first registrant's matrix and its already-compiled factor engine
        self._factor_hosts = weakref.WeakValueDictionary()
        self._refactor_threads: Dict[str, threading.Thread] = {}

    # -- construction helpers ----------------------------------------------
    @staticmethod
    def _default_engine_factory(a, pattern, vals_csr, **knobs):
        return ServeEngine(a, pattern, vals_csr, **knobs)

    def _factorize(self, entry_a0: CSRMatrix, pattern, a: CSRMatrix) -> np.ndarray:
        """CSR-aligned ILU values of ``a`` via the structure-keyed compiled
        factor engine memoized on the *canonical* matrix: the first call per
        structure compiles, every refactorization after is a pure execute."""
        from repro.core.factor_plan import factor_plan_for

        plan = factor_plan_for(entry_a0, pattern)
        return np.asarray(plan.factorize(a))

    # -- registration -------------------------------------------------------
    def register(self, matrix_id: str, a: CSRMatrix, k: int = 1, **engine_knobs) -> CacheEntry:
        """Insert (or replace) a matrix: symbolic fill, numeric factorize,
        engine lookup/build, value bind. May evict an unpinned LRU entry.
        Same-structure registrations share one compiled factor engine (via
        the structure-keyed plan host) and one solver engine — the second
        tenant of a structure onboards without a single XLA compile."""
        import hashlib

        from repro.core.api import _symbolic

        pattern = _symbolic(a, k, "sum")
        h = hashlib.sha1()
        h.update(a.indptr.tobytes())
        h.update(a.indices.tobytes())
        h.update(pattern.levels.tobytes())
        skey = (a.n, k, h.hexdigest())
        with self._lock:
            host = self._factor_hosts.get(skey)
            if host is None:
                host = self._factor_hosts[skey] = a
        vals_csr = self._factorize(host, pattern, a)
        with self._lock:
            self._evict_for_insert(exclude=matrix_id)
            engine = self._shared_engine(a, pattern, vals_csr, engine_knobs)
            binding = self._guarded_bind(engine, host, pattern, a, vals_csr)
            entry = CacheEntry(matrix_id, a, pattern, engine, binding, plan_host=host)
            self._entries[matrix_id] = entry
            self._entries.move_to_end(matrix_id)
            return entry

    def _guarded_bind(self, engine, host, pattern, a, vals_csr):
        """Audit the fresh factor values and bind per ``on_breakdown``:
        healthy values bind as-is (the audit is a pure read — the binding
        is bitwise what an unguarded bind produces); broken ones climb the
        shift ladder through the same compiled engines, and exhaustion
        either binds the exact identity preconditioner (``"fallback"``,
        single-device) or rejects the matrix with a structured BREAKDOWN."""
        from repro.core.guard import audit_values, ladder_alphas

        if self.on_breakdown == "ignore":
            return engine.bind(a, vals_csr)
        with obs.span("ilu:push.audit"):
            health = audit_values(pattern, vals_csr, self.pivot_tol)
        if health.ok:
            return engine.bind(a, vals_csr)
        if self.metrics is not None:
            self.metrics.record_robustness("broken_factorizations")
        if self.on_breakdown == "raise":
            raise AdmissionError(BREAKDOWN, health.summary())
        def factorize(m):
            return self._factorize(host, pattern, m)
        for alpha in ladder_alphas():
            b2 = engine.bind_degraded(a, alpha, factorize)
            if b2 is not None:
                if self.metrics is not None:
                    self.metrics.record_robustness("shifted_bindings")
                return b2
        if self.on_breakdown == "fallback" and getattr(
                engine, "supports_identity_fallback", False):
            b2 = engine.bind(a, identity_values(pattern))
            b2.degraded = True
            if self.metrics is not None:
                self.metrics.record_robustness("identity_fallbacks")
            return b2
        raise AdmissionError(
            BREAKDOWN, f"shift ladder exhausted: {health.summary()}")

    def _shared_engine(self, a, pattern, vals_csr, knobs):
        probe = self._engine_factory(a, pattern, vals_csr, **knobs)
        fp = getattr(probe, "fingerprint", None)
        if fp is None:
            return probe
        existing = self._engines_by_structure.get(fp)
        if existing is not None:
            if self.metrics is not None:
                self.metrics.record_cache("engine_shared")
            return existing
        self._engines_by_structure[fp] = probe
        return probe

    def _evict_for_insert(self, exclude: str) -> None:
        while len(self._entries) >= self.capacity + (1 if exclude in self._entries else 0):
            victim = None
            for mid, e in self._entries.items():  # OrderedDict: LRU first
                if mid != exclude and e.pins == 0:
                    victim = mid
                    break
            if victim is None:
                raise AdmissionError(
                    QUEUE_FULL,
                    f"plan cache full ({self.capacity} entries, all pinned by "
                    "in-flight solves); retry after current batches drain")
            del self._entries[victim]
            if self.metrics is not None:
                self.metrics.record_cache("evict")

    # -- lookup + pinning ----------------------------------------------------
    def dim_of(self, matrix_id: str) -> Optional[int]:
        with self._lock:
            e = self._entries.get(matrix_id)
            return None if e is None else e.a0.n

    def acquire(self, matrix_id: str):
        """Pin the entry's *current* binding for one request; returns
        ``(entry, binding)``. The pin blocks eviction; the binding reference
        keeps the value arrays alive even across a racing update (the solve
        runs on the version the request was admitted under)."""
        with self._lock:
            e = self._entries.get(matrix_id)
            if e is None:
                if self.metrics is not None:
                    self.metrics.record_cache("miss")
                raise AdmissionError(
                    UNKNOWN_MATRIX, f"matrix_id {matrix_id!r} is not resident")
            e.pins += 1
            self._entries.move_to_end(matrix_id)
            if self.metrics is not None:
                self.metrics.record_cache("hit")
            return e, e.binding

    def release(self, matrix_id: str) -> None:
        with self._lock:
            e = self._entries.get(matrix_id)
            if e is not None and e.pins > 0:
                e.pins -= 1

    # -- value updates -------------------------------------------------------
    def update_values(self, matrix_id: str, data: np.ndarray,
                      background: bool = True) -> threading.Thread:
        """Refactorize ``matrix_id`` with new values (same structure) and
        atomically swap the entry's binding. Runs in a worker thread by
        default — registration lookups and other tenants' solves proceed
        during the numeric factorization; only the final reference swap
        takes the lock. Returns the worker (already joined if
        ``background=False``)."""
        with self._lock:
            e = self._entries.get(matrix_id)
            if e is None:
                raise AdmissionError(
                    UNKNOWN_MATRIX, f"matrix_id {matrix_id!r} is not resident")
            a0, pattern, engine, host = e.a0, e.pattern, e.engine, e.plan_host
            data = np.asarray(data, np.float32)
            if data.shape != a0.data.shape:
                raise ValueError(
                    f"update_values: expected {a0.data.shape[0]} values for the "
                    f"structure of {matrix_id!r}, got {data.shape}")

        def work():
            a_new = CSRMatrix(n=a0.n, indptr=a0.indptr, indices=a0.indices, data=data)
            vals_csr = self._factorize(host, pattern, a_new)
            try:
                binding = self._guarded_bind(engine, host, pattern, a_new, vals_csr)
            except AdmissionError:
                # a value push that breaks down unrecoverably keeps the old
                # binding serving — existing requests stay healthy; the
                # counter records the rejected update
                if self.metrics is not None:
                    self.metrics.record_robustness("rejected_updates")
                return
            with self._lock:
                cur = self._entries.get(matrix_id)
                if cur is not None and cur.engine is engine:
                    cur.binding = binding      # the atomic swap
                    cur.version = binding.version
            if self.metrics is not None:
                self.metrics.record_cache("refactor")

        t = threading.Thread(target=work, name=f"refactor-{matrix_id}", daemon=True)
        with self._lock:
            self._refactor_threads[matrix_id] = t
        t.start()
        if not background:
            t.join()
        return t

    def degraded_binding(self, matrix_id: str, binding) -> Optional["object"]:
        """A shifted-preconditioner binding for retrying breakdown lanes.

        Climbs the α ladder against the *exact matrix of the base binding*
        (``binding.a`` — not the entry's possibly newer values: the retry
        must solve the system the request was admitted under), audits each
        rung, and caches the first healthy binding per base version so one
        ladder climb serves every retrying request of that version. The
        retried solve's matvec still targets the original A — only the
        preconditioner is shifted. Returns None when the ladder exhausts
        (the caller fails the lane with a structured BREAKDOWN)."""
        from repro.core.guard import ladder_alphas

        with self._lock:
            e = self._entries.get(matrix_id)
            if e is None or binding.a is None:
                return None
            key = ("shift", binding.version)
            cached = e.degraded_bindings.get(key)
            if cached is not None:
                return cached
            engine, pattern, host = e.engine, e.pattern, e.plan_host
        def factorize(m):
            return self._factorize(host, pattern, m)
        for alpha in ladder_alphas():
            try:
                b2 = engine.bind_degraded(binding.a, alpha, factorize)
            except Exception:
                return None
            if b2 is not None:
                with self._lock:
                    cur = self._entries.get(matrix_id)
                    if cur is not None:
                        cur.degraded_bindings[key] = b2
                return b2
        return None

    def wait_refactors(self, timeout: Optional[float] = None) -> None:
        """Join all outstanding refactor workers (tests / drain)."""
        with self._lock:
            threads = list(self._refactor_threads.values())
        for t in threads:
            t.join(timeout)

    # -- introspection -------------------------------------------------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, matrix_id: str) -> bool:
        with self._lock:
            return matrix_id in self._entries

    def entry(self, matrix_id: str) -> Optional[CacheEntry]:
        with self._lock:
            return self._entries.get(matrix_id)

    def resident_ids(self):
        with self._lock:
            return list(self._entries)
