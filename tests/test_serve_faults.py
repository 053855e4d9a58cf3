"""Fault injection for the solve service.

The contract under test: every fault fails exactly the request(s) it
belongs to — never the coalesced batch it would have ridden in, never
another tenant's requests, never the process. Scenarios from the issue:

* cache eviction while a solve is in flight,
* a matrix-value update racing an in-flight solve on the old factorization,
* malformed requests (wrong shape, unknown matrix_id, non-finite entries),
* a compatible group exceeding the largest bucket,
* an engine blowing up mid-batch (the one case that can take its whole
  batch down — but nothing outside it).
"""
import numpy as np
import pytest

from repro.core.matgen import matgen
from repro.core.solvers import solve_with_ilu
from repro.core.sparse import CSRMatrix
from repro.serve import ServeConfig, SolveRequest, SolveResponse, SolveService


def _svc(capacity=4, buckets=(1, 2, 4), restart=8):
    return SolveService(ServeConfig(cache_capacity=capacity, buckets=buckets,
                                    restart=restart))


def _rhs(n, seed):
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def _assert_bitwise_vs_solo(resp, a, b, tol, restart=8, k=1):
    ref, _ = solve_with_ilu(a, b, k=k, tol=tol, restart=restart)
    np.testing.assert_array_equal(np.asarray(resp.x, np.float32).view(np.int32),
                                  np.asarray(ref.x, np.float32).view(np.int32))


def test_eviction_while_solve_in_flight():
    """A queued request pins its entry; eviction pressure takes the LRU
    *unpinned* entry instead, the in-flight solve completes bitwise-correct,
    and only later requests to the evicted matrix fail (their own error)."""
    svc = _svc(capacity=2)
    a0, a1, a2 = (matgen(48, 0.12, seed=s) for s in (1, 2, 3))
    svc.register_matrix("m0", a0, k=1)
    svc.register_matrix("m1", a1, k=1)

    b = _rhs(48, 0)
    req = svc.submit("tenant-a", "m0", b, tol=1e-5)   # pins m0
    assert isinstance(req, SolveRequest)
    svc.register_matrix("m2", a2, k=1)                # evicts m1 (unpinned LRU)
    assert "m1" not in svc.cache and "m0" in svc.cache

    resps = svc.tick()                                # in-flight solve lands
    assert len(resps) == 1 and resps[0].ok
    _assert_bitwise_vs_solo(resps[0], a0, b, 1e-5)

    late = svc.submit("tenant-b", "m1", _rhs(48, 1))  # only this one fails
    assert isinstance(late, SolveResponse) and not late.ok
    assert late.error_reason == "unknown_matrix"
    ok = svc.submit("tenant-b", "m2", _rhs(48, 2))
    assert isinstance(ok, SolveRequest)
    assert all(r.ok for r in svc.tick())


def test_value_update_racing_in_flight_solve():
    """A request admitted before a value push solves against the binding it
    pinned (the old factorization, bitwise), not the half-swapped new one;
    requests admitted after the swap get the new values (bitwise too)."""
    svc = _svc()
    a = matgen(48, 0.12, seed=5)
    svc.register_matrix("m0", a, k=1)
    b = _rhs(48, 3)

    req_old = svc.submit("t0", "m0", b, tol=1e-5)     # pins version 1
    t = svc.update_matrix_values("m0", (a.data * 1.3).astype(np.float32))
    t.join()                                           # update wins the race
    req_new = svc.submit("t1", "m0", b, tol=1e-5)     # pins version 2
    resps = {r.request_id: r for r in svc.run_until_idle()}

    r_old, r_new = resps[req_old.request_id], resps[req_new.request_id]
    assert r_old.ok and r_old.matrix_version == 1
    assert r_new.ok and r_new.matrix_version == 2
    _assert_bitwise_vs_solo(r_old, a, b, 1e-5)        # old values
    a_new = CSRMatrix(n=a.n, indptr=a.indptr, indices=a.indices,
                      data=(a.data * 1.3).astype(np.float32))
    _assert_bitwise_vs_solo(r_new, a_new, b, 1e-5)    # new values
    assert not np.array_equal(r_old.x, r_new.x)


def test_malformed_requests_fail_alone():
    """Wrong shape / unknown matrix / non-finite b / bad tol each reject at
    admission with their reason code while good requests coalesced around
    them are untouched."""
    svc = _svc()
    a = matgen(48, 0.12, seed=6)
    svc.register_matrix("m0", a, k=1)

    good1 = svc.submit("t0", "m0", _rhs(48, 4))
    bad_shape = svc.submit("t1", "m0", np.ones(50, np.float32))
    bad_nan = svc.submit("t2", "m0", np.full(48, np.nan, np.float32))
    bad_id = svc.submit("t3", "ghost", _rhs(48, 5))
    bad_tol = svc.submit("t0", "m0", _rhs(48, 6), tol=0.0)
    good2 = svc.submit("t1", "m0", _rhs(48, 7))

    for resp, reason in ((bad_shape, "bad_shape"), (bad_nan, "non_finite"),
                         (bad_id, "unknown_matrix"), (bad_tol, "bad_tol")):
        assert isinstance(resp, SolveResponse) and not resp.ok
        assert resp.error_reason == reason

    resps = svc.tick()
    assert sorted(r.request_id for r in resps) == sorted(
        [good1.request_id, good2.request_id])
    assert all(r.ok for r in resps)
    snap = svc.metrics_snapshot()
    assert snap["requests"]["completed"] == 2
    assert sum(snap["requests"]["rejected_by_reason"].values()) == 4


def test_queue_full_sheds_load_not_state():
    svc = SolveService(ServeConfig(buckets=(1, 2), restart=8, max_queue_depth=2))
    a = matgen(32, 0.15, seed=7)
    svc.register_matrix("m0", a, k=1)
    r1 = svc.submit("t0", "m0", _rhs(32, 1))
    r2 = svc.submit("t0", "m0", _rhs(32, 2))
    shed = svc.submit("t0", "m0", _rhs(32, 3))
    assert isinstance(shed, SolveResponse) and shed.error_reason == "queue_full"
    assert svc.cache.entry("m0").pins == 2  # shed request left no pin behind
    resps = svc.tick()
    assert {r.request_id for r in resps} == {r1.request_id, r2.request_id}
    assert all(r.ok for r in resps)
    assert svc.cache.entry("m0").pins == 0


def test_group_beyond_largest_bucket_chunks():
    """11 compatible requests with buckets (1,2,4): three batches (4+4+3→4),
    all solved in one tick, every response bitwise-correct."""
    svc = _svc(buckets=(1, 2, 4))
    a = matgen(48, 0.12, seed=8)
    svc.register_matrix("m0", a, k=1)
    bs = [_rhs(48, 100 + i) for i in range(11)]
    reqs = [svc.submit(f"t{i % 4}", "m0", b) for i, b in enumerate(bs)]
    resps = {r.request_id: r for r in svc.tick()}
    assert len(resps) == 11
    snap = svc.metrics_snapshot()
    assert snap["coalescing"]["batches"] == 3
    assert all(r.batch_lanes <= 4 for r in resps.values())
    for req, b in zip(reqs, bs):
        assert resps[req.request_id].ok
        _assert_bitwise_vs_solo(resps[req.request_id], a, b, 1e-5)


def test_engine_failure_fails_batch_not_process(monkeypatch):
    """An engine exception marks that batch's requests solve_failed and
    releases their pins; the service keeps serving other matrices."""
    svc = _svc()
    a0, a1 = matgen(48, 0.12, seed=9), matgen(40, 0.15, seed=10)
    svc.register_matrix("m0", a0, k=1)
    svc.register_matrix("m1", a1, k=1)

    def boom(binding, bs, tols):
        raise RuntimeError("injected engine failure")

    monkeypatch.setattr(svc.cache.entry("m0").engine, "solve", boom)
    doomed = svc.submit("t0", "m0", _rhs(48, 11))
    fine = svc.submit("t1", "m1", _rhs(40, 12))
    resps = {r.request_id: r for r in svc.tick()}

    assert not resps[doomed.request_id].ok
    assert resps[doomed.request_id].error_reason == "solve_failed"
    assert "injected engine failure" in resps[doomed.request_id].error
    assert resps[fine.request_id].ok
    assert svc.cache.entry("m0").pins == 0  # pins released on failure too
    # the service still serves m0 once the engine behaves again
    monkeypatch.undo()
    again = svc.submit("t0", "m0", _rhs(48, 13))
    assert svc.tick()[0].request_id == again.request_id


def test_update_does_not_block_other_tenants(monkeypatch):
    """While m0's refactorization is (artificially) slow, m1 solves keep
    landing — the value push never serializes the tick loop."""
    import time as _time

    svc = _svc()
    a0, a1 = matgen(48, 0.12, seed=14), matgen(48, 0.12, seed=15)
    svc.register_matrix("m0", a0, k=1)
    svc.register_matrix("m1", a1, k=1)
    svc.submit("t1", "m1", _rhs(48, 99))
    assert svc.tick()[0].ok          # compile m1's engine before the race

    orig = svc.cache._factorize

    def slow_factorize(host, pattern, a):
        _time.sleep(0.5)
        return orig(host, pattern, a)

    monkeypatch.setattr(svc.cache, "_factorize", slow_factorize)
    t = svc.update_matrix_values("m0", (a0.data * 1.1).astype(np.float32))
    b = _rhs(48, 16)
    svc.submit("t1", "m1", b)
    resps = svc.tick()              # completes while the refactor sleeps
    assert t.is_alive()
    assert len(resps) == 1 and resps[0].ok
    t.join()
    assert svc.cache.entry("m0").binding.version == 2


def test_warmup_compile_failure_raises(monkeypatch):
    """A compile or lowering error during warmup raises out of ``warmup()``
    — it is never recorded as a failed batch for a request to meet later."""
    svc = _svc()
    svc.register_matrix("m0", matgen(48, 0.12, seed=14), k=1)

    def refuse(*args):
        raise RuntimeError("injected compiler refusal")

    monkeypatch.setattr(svc.cache.entry("m0").engine._jit, "lower", refuse)
    with pytest.raises(RuntimeError, match="injected compiler refusal"):
        svc.warmup()
    assert svc.metrics_snapshot()["requests"]["failed"] == 0
