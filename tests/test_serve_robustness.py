"""Graceful degradation in the serve path.

The acceptance bar from the issue: injected breakdowns (NaN RHS slipping
in post-admission, singular/zero-pivot matrices, a raising engine) never
crash the service and never poison co-batched lanes — each failing request
gets a structured error or degraded response, and **every healthy lane in
the same tick stays bitwise-equal to its solo solve**. Plus: deadlines,
health probes, the async dispatcher, and the robustness metrics schema.
"""
import threading
import time

import numpy as np
import pytest

from repro.core.matgen import matgen, zero_diagonal_matrix
from repro.core.solvers import solve_with_ilu
from repro.serve import (
    AdmissionError,
    Dispatcher,
    ServeConfig,
    SolveRequest,
    SolveResponse,
    SolveService,
)

N = 48


def _svc(**kw):
    kw.setdefault("cache_capacity", 4)
    kw.setdefault("buckets", (1, 2, 4))
    kw.setdefault("restart", 8)
    return SolveService(ServeConfig(**kw))


def _rhs(n, seed):
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def _assert_bitwise_vs_solo(resp, a, b, tol=1e-5, restart=8, k=1):
    ref, _ = solve_with_ilu(a, b, k=k, tol=tol, restart=restart)
    np.testing.assert_array_equal(np.asarray(resp.x, np.float32).view(np.int32),
                                  np.asarray(ref.x, np.float32).view(np.int32))


# ---------------------------------------------------------------------------
# lane-level quarantine
# ---------------------------------------------------------------------------
def test_nan_lane_fails_alone_healthy_lanes_bitwise():
    """A NaN RHS that slips past admission (mutated post-submit) classifies
    as a breakdown verdict: that request fails with a structured BREAKDOWN
    response after the shift retry also breaks down; its co-batched
    neighbours succeed bitwise-equal to their solo solves."""
    svc = _svc()
    a = matgen(N, 0.12, seed=1)
    svc.register_matrix("m0", a, k=1)
    good_bs = [_rhs(N, 10 + i) for i in range(3)]
    good = [svc.submit("t0", "m0", b) for b in good_bs]
    poisoned = svc.submit("t1", "m0", _rhs(N, 20))
    assert isinstance(poisoned, SolveRequest)
    poisoned.b = np.full(N, np.nan, np.float32)  # post-admission poisoning

    resps = {r.request_id: r for r in svc.tick()}
    bad = resps[poisoned.request_id]
    assert not bad.ok and bad.error_reason == "breakdown"
    assert bad.verdict == "breakdown"
    for req, b in zip(good, good_bs):
        r = resps[req.request_id]
        assert r.ok and r.verdict == "converged" and not r.degraded
        _assert_bitwise_vs_solo(r, a, b)
    snap = svc.metrics_snapshot()
    assert snap["robustness"]["breakdown_lanes"] == 1
    assert snap["robustness"]["shift_retries"] == 1
    assert svc.cache.entry("m0").pins == 0


def test_engine_raise_quarantines_to_solo_lanes():
    """An engine that raises on multi-lane batches but works solo: the
    batch quarantines, every request is re-dispatched alone and succeeds
    bitwise — nobody pays for the co-batching."""
    svc = _svc()
    a = matgen(N, 0.12, seed=2)
    svc.register_matrix("m0", a, k=1)
    engine = svc.cache.entry("m0").engine
    orig = engine.solve

    def flaky(binding, bs, tols):
        if np.asarray(bs).shape[0] > 1:
            raise RuntimeError("injected multi-lane failure")
        return orig(binding, bs, tols)

    engine.solve = flaky
    try:
        bs = [_rhs(N, 30 + i) for i in range(3)]
        reqs = [svc.submit(f"t{i}", "m0", b) for i, b in enumerate(bs)]
        resps = {r.request_id: r for r in svc.tick()}
        assert len(resps) == 3
        for req, b in zip(reqs, bs):
            r = resps[req.request_id]
            assert r.ok, r.error
            _assert_bitwise_vs_solo(r, a, b)
    finally:
        engine.solve = orig
    snap = svc.metrics_snapshot()
    assert snap["robustness"]["quarantined_batches"] == 1
    assert svc.cache.entry("m0").pins == 0


def test_solo_poison_fails_structured_survivors_redispatch():
    """One request whose lane makes the whole engine raise: quarantine
    re-dispatches everyone solo; survivors succeed, the poisoned one gets
    its own structured solve_failed."""
    svc = _svc()
    a = matgen(N, 0.12, seed=3)
    svc.register_matrix("m0", a, k=1)
    engine = svc.cache.entry("m0").engine
    orig = engine.solve

    def poisoned_engine(binding, bs, tols):
        if not np.isfinite(np.asarray(bs)).all():
            raise RuntimeError("poisoned lane blew up the kernel")
        return orig(binding, bs, tols)

    engine.solve = poisoned_engine
    try:
        good_bs = [_rhs(N, 40 + i) for i in range(2)]
        good = [svc.submit("t0", "m0", b) for b in good_bs]
        doomed = svc.submit("t1", "m0", _rhs(N, 50))
        doomed.b = np.full(N, np.inf, np.float32)
        resps = {r.request_id: r for r in svc.tick()}
        assert not resps[doomed.request_id].ok
        assert resps[doomed.request_id].error_reason == "solve_failed"
        for req, b in zip(good, good_bs):
            assert resps[req.request_id].ok
            _assert_bitwise_vs_solo(resps[req.request_id], a, b)
    finally:
        engine.solve = orig
    assert svc.metrics_snapshot()["robustness"]["quarantined_batches"] == 1


# ---------------------------------------------------------------------------
# degraded registration + responses
# ---------------------------------------------------------------------------
def test_breakdown_matrix_registers_shifted_and_serves_degraded():
    """Registering a matrix whose ILU(k) breaks down under
    on_breakdown="shift": the binding lands shifted, solves succeed, and
    responses are marked degraded with the shift α attached."""
    svc = _svc(on_breakdown="shift")
    a = zero_diagonal_matrix(N, 0.12, seed=4, row=0)
    svc.register_matrix("m0", a, k=1)
    binding = svc.cache.entry("m0").binding
    assert binding.shift > 0
    req = svc.submit("t0", "m0", _rhs(N, 60))
    (resp,) = svc.tick()
    assert resp.ok and resp.request_id == req.request_id
    assert resp.degraded and resp.shift == binding.shift
    assert np.isfinite(np.asarray(resp.x)).all()
    snap = svc.metrics_snapshot()
    assert snap["robustness"]["broken_factorizations"] == 1
    assert snap["robustness"]["shifted_bindings"] == 1
    assert snap["robustness"]["degraded_responses"] == 1


def test_breakdown_matrix_raises_at_register_when_policy_raise():
    svc = _svc(on_breakdown="raise")
    a = zero_diagonal_matrix(N, 0.12, seed=4, row=0)
    with pytest.raises(AdmissionError) as ei:
        svc.register_matrix("m0", a, k=1)
    assert ei.value.reason == "breakdown"
    assert "m0" not in svc.cache


def test_breaking_value_update_rejected_old_binding_serves():
    """A value push that breaks down under on_breakdown="raise" is
    rejected: the old binding keeps serving bitwise-correct."""
    svc = _svc(on_breakdown="raise")
    a = matgen(N, 0.12, seed=5)
    svc.register_matrix("m0", a, k=1)
    bad = a.data.copy()
    lo, hi = a.indptr[0], a.indptr[1]
    bad[lo + int(np.searchsorted(a.indices[lo:hi], 0))] = 0.0  # zero pivot
    t = svc.update_matrix_values("m0", bad)
    t.join()
    assert svc.cache.entry("m0").binding.version == 1  # swap refused
    b = _rhs(N, 70)
    svc.submit("t0", "m0", b)
    (resp,) = svc.tick()
    assert resp.ok and resp.matrix_version == 1
    _assert_bitwise_vs_solo(resp, a, b)
    assert svc.metrics_snapshot()["robustness"]["rejected_updates"] == 1


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------
def test_deadline_expired_before_dispatch():
    svc = _svc()
    a = matgen(N, 0.12, seed=6)
    svc.register_matrix("m0", a, k=1)
    late = svc.submit("t0", "m0", _rhs(N, 80), deadline_seconds=0.001)
    ok_b = _rhs(N, 81)
    fine = svc.submit("t1", "m0", ok_b)        # no deadline
    time.sleep(0.01)
    resps = {r.request_id: r for r in svc.tick()}
    assert not resps[late.request_id].ok
    assert resps[late.request_id].error_reason == "deadline_exceeded"
    assert resps[fine.request_id].ok
    _assert_bitwise_vs_solo(resps[fine.request_id], a, ok_b)
    assert svc.metrics_snapshot()["robustness"]["deadline_expired"] == 1
    assert svc.cache.entry("m0").pins == 0


def test_default_deadline_from_config_and_bad_deadline():
    svc = _svc(default_deadline_seconds=0.001)
    a = matgen(N, 0.12, seed=7)
    svc.register_matrix("m0", a, k=1)
    req = svc.submit("t0", "m0", _rhs(N, 82))
    assert req.deadline_seconds == 0.001
    time.sleep(0.01)
    (resp,) = svc.tick()
    assert not resp.ok and resp.error_reason == "deadline_exceeded"
    bad = svc.submit("t0", "m0", _rhs(N, 83), deadline_seconds=-2)
    assert isinstance(bad, SolveResponse) and bad.error_reason == "bad_deadline"


# ---------------------------------------------------------------------------
# probes + metrics schema
# ---------------------------------------------------------------------------
def test_probes_and_robustness_schema():
    svc = _svc()
    hz = svc.healthz()
    assert hz["ok"] and hz["resident_matrices"] == 0
    assert not svc.readyz()["ready"]            # nothing resident, not warm
    a = matgen(N, 0.12, seed=8)
    svc.register_matrix("m0", a, k=1)
    assert not svc.readyz()["ready"]            # resident but not warmed
    svc.warmup()
    assert svc.readyz()["ready"]
    svc.submit("t0", "m0", _rhs(N, 90))
    svc.tick()
    snap = svc.metrics_snapshot()
    assert isinstance(snap["robustness"], dict)
    th = snap["tick_health"]
    assert set(th) >= {"observed", "slow_ticks", "deadline_factor",
                       "mean_seconds", "p99_seconds"}
    assert th["observed"] == snap["ticks"] >= 1
    assert th["mean_seconds"] > 0.0


# ---------------------------------------------------------------------------
# async dispatcher
# ---------------------------------------------------------------------------
def test_dispatcher_mini_soak_bitwise_and_clean_shutdown():
    """Two tenant threads push 20 requests each through the dispatcher;
    every response arrives via result(), bitwise-equal to its solo solve;
    stop() joins cleanly and leaves nothing queued."""
    svc = _svc()
    a = matgen(N, 0.12, seed=9)
    svc.register_matrix("m0", a, k=1)
    svc.warmup()
    results = {}
    lock = threading.Lock()

    def tenant(tag, seed0):
        rng_seed = seed0
        for i in range(20):
            b = _rhs(N, rng_seed + i)
            req = disp.submit(tag, "m0", b, tol=1e-5)
            resp = req.result(timeout=60)
            with lock:
                results[req.request_id] = (b, resp)

    with Dispatcher(svc, idle_wait=0.01) as disp:
        threads = [threading.Thread(target=tenant, args=(f"t{j}", 100 * (j + 1)))
                   for j in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert disp.running
    assert not disp.running
    assert len(svc.queue) == 0
    assert len(results) == 40
    # snapshot before the reference solves: they compile their own engines
    # and must not pollute the serving-path counter
    assert svc.metrics_snapshot()["compiles"]["after_warmup"] == 0
    for b, resp in results.values():
        assert resp is not None and resp.ok
        _assert_bitwise_vs_solo(resp, a, b)


def test_dispatcher_stop_drains_queued_work():
    svc = _svc()
    a = matgen(N, 0.12, seed=11)
    svc.register_matrix("m0", a, k=1)
    disp = Dispatcher(svc)           # never started: queue work, stop drains
    disp.start()
    disp.stop()
    req = svc.submit("t0", "m0", _rhs(N, 120))
    disp2 = Dispatcher(svc)
    disp2.start()
    resp = req.result(timeout=60)
    disp2.stop()
    assert resp is not None and resp.ok
