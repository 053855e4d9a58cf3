"""Unit tests for the serve layer: admission, buckets, coalescer, cache,
metrics, and the value-rebinding engine's bitwise contract.

The service-level soak lives in test_serve_soak.py; fault injection in
test_serve_faults.py. Everything here is small and fast — tiny matrices,
few buckets, stub engines where compilation isn't the thing under test.
"""
import threading
import types

import numpy as np
import pytest

from repro.core.api import _symbolic
from repro.core.factor_plan import factor_plan_for
from repro.core.matgen import matgen
from repro.core.solvers import batch_buckets, parse_batch_buckets, solve_with_ilu
from repro.core.sparse import CSRMatrix
from repro.serve import (
    AdmissionError,
    AdmissionQueue,
    LatencyHistogram,
    PlanCache,
    ServeConfig,
    ServiceMetrics,
    SolveRequest,
    SolveResponse,
    SolveService,
    coalesce,
    validate_request,
)
from repro.serve.engine import ServeEngine


# --------------------------------------------------------------------------
# batch bucket spec parsing (env hardening)
# --------------------------------------------------------------------------
class TestParseBatchBuckets:
    def test_valid_specs(self):
        assert parse_batch_buckets("1,2,4,8") == (1, 2, 4, 8)
        assert parse_batch_buckets(" 1 , 2 ,4 ") == (1, 2, 4)
        assert parse_batch_buckets("7") == (7,)

    def test_zero_and_negative_rejected(self):
        with pytest.raises(ValueError, match="positive.*0"):
            parse_batch_buckets("0,4,8")
        with pytest.raises(ValueError, match="positive.*-4"):
            parse_batch_buckets("-4,8")

    def test_the_issue_spec_rejected(self):
        # the historically silently-accepted spec must now fail loudly
        with pytest.raises(ValueError, match="REPRO_BATCH_BUCKETS"):
            parse_batch_buckets("0,-4,8")

    def test_non_integer_names_token_and_spec(self):
        with pytest.raises(ValueError, match=r"'two'.*'1,two,4'"):
            parse_batch_buckets("1,two,4")

    def test_duplicates_rejected(self):
        with pytest.raises(ValueError, match=r"duplicate.*\[4\]"):
            parse_batch_buckets("1,4,4,8")

    def test_non_ascending_rejected(self):
        with pytest.raises(ValueError, match="ascending"):
            parse_batch_buckets("8,4,2")

    def test_empty_rejected(self):
        with pytest.raises(ValueError, match="empty"):
            parse_batch_buckets("")
        with pytest.raises(ValueError, match="empty"):
            parse_batch_buckets(" , ,")

    def test_env_path(self, monkeypatch):
        monkeypatch.setenv("REPRO_BATCH_BUCKETS", "2,4,16")
        assert batch_buckets() == (2, 4, 16)
        monkeypatch.setenv("REPRO_BATCH_BUCKETS", "0,-4,8")
        with pytest.raises(ValueError, match="REPRO_BATCH_BUCKETS"):
            batch_buckets()
        monkeypatch.delenv("REPRO_BATCH_BUCKETS")
        assert batch_buckets() == (1, 2, 4, 8, 16, 32, 64)


# --------------------------------------------------------------------------
# admission
# --------------------------------------------------------------------------
class TestAdmission:
    def test_unknown_matrix(self):
        with pytest.raises(AdmissionError) as e:
            validate_request("t", "nope", np.ones(4, np.float32), 1e-5, None)
        assert e.value.reason == "unknown_matrix"

    def test_bad_shape(self):
        for bad in (np.ones(5, np.float32), np.ones((4, 1), np.float32), "junk"):
            with pytest.raises(AdmissionError) as e:
                validate_request("t", "m", bad, 1e-5, 4)
            assert e.value.reason == "bad_shape"

    def test_non_finite(self):
        b = np.ones(4, np.float32)
        b[2] = np.inf
        with pytest.raises(AdmissionError) as e:
            validate_request("t", "m", b, 1e-5, 4)
        assert e.value.reason == "non_finite"

    def test_bad_tol(self):
        for bad in (0.0, -1e-5, np.nan, "x"):
            with pytest.raises(AdmissionError) as e:
                validate_request("t", "m", np.ones(4, np.float32), bad, 4)
            assert e.value.reason == "bad_tol"

    def test_valid_passes_and_casts(self):
        out = validate_request("t", "m", [1, 2, 3, 4], 1e-5, 4)
        assert out.dtype == np.float32 and out.shape == (4,)

    def test_queue_fifo_bound_and_requeue(self):
        q = AdmissionQueue(max_depth=3)
        reqs = [SolveRequest("t", "m", np.zeros(2, np.float32), 1e-5) for _ in range(3)]
        for r in reqs:
            q.push(r)
        with pytest.raises(AdmissionError) as e:
            q.push(SolveRequest("t", "m", np.zeros(2, np.float32), 1e-5))
        assert e.value.reason == "queue_full"
        got = q.drain(2)
        assert [g.request_id for g in got] == [r.request_id for r in reqs[:2]]
        q.requeue_front(got)  # preserves FIFO: requeued go back in front
        assert [g.request_id for g in q.drain(None)] == [r.request_id for r in reqs]


# --------------------------------------------------------------------------
# coalescer
# --------------------------------------------------------------------------
def _stub_entry(buckets=(1, 2, 4)):
    eng = types.SimpleNamespace(
        buckets=tuple(buckets),
        bucket_for=lambda nb, bs=tuple(buckets): next((w for w in bs if w >= nb), nb))
    return types.SimpleNamespace(engine=eng)


def _req(mid, entry, binding):
    r = SolveRequest("t", mid, np.zeros(2, np.float32), 1e-5)
    r.binding = (entry, binding)
    return r


class TestCoalescer:
    def test_groups_by_matrix_and_binding(self):
        e1, e2 = _stub_entry(), _stub_entry()
        b1, b2 = object(), object()
        reqs = [_req("a", e1, b1), _req("b", e2, b2), _req("a", e1, b1)]
        batches = coalesce(reqs)
        assert [(b.matrix_id, b.real_lanes) for b in batches] == [("a", 2), ("b", 1)]
        assert batches[0].bucket == 2 and batches[1].bucket == 1

    def test_value_versions_do_not_mix(self):
        e = _stub_entry()
        old, new = object(), object()
        reqs = [_req("a", e, old), _req("a", e, new), _req("a", e, old)]
        batches = coalesce(reqs)
        assert [(b.binding, b.real_lanes) for b in batches] == [(old, 2), (new, 1)]

    def test_chunks_over_largest_bucket(self):
        e = _stub_entry(buckets=(1, 2, 4))
        b = object()
        batches = coalesce([_req("a", e, b) for _ in range(10)])
        assert [x.real_lanes for x in batches] == [4, 4, 2]
        assert [x.bucket for x in batches] == [4, 4, 2]
        # FIFO preserved across the chunk boundary
        ids = [r.request_id for x in batches for r in x.requests]
        assert ids == sorted(ids)


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------
class TestMetrics:
    def test_histogram_quantiles_and_buckets(self):
        h = LatencyHistogram()
        for v in np.linspace(1e-4, 1e-1, 1000):
            h.observe(float(v))
        d = h.to_dict()
        assert d["count"] == 1000
        assert sum(d["bucket_counts"]) == 1000
        assert d["p50_seconds"] == pytest.approx(0.05, rel=0.05)
        assert d["p99_seconds"] == pytest.approx(0.099, rel=0.05)
        assert d["max_seconds"] <= 0.1

    def test_snapshot_shape_and_counters(self):
        m = ServiceMetrics()
        m.record_admission(True)
        m.record_admission(False, "bad_tol")
        m.record_queue_depth(3)
        m.record_batch("m0", real=3, bucket=4, seconds=0.25)
        m.record_response("tenant-a", True, 0.3)
        m.record_cache("hit")
        m.record_cache("miss")
        m.record_cache("evict")
        m.record_cache("refactor")
        m.record_tick()
        s = m.snapshot()
        assert s["requests"]["admitted"] == 1
        assert s["requests"]["rejected_by_reason"] == {"bad_tol": 1}
        assert s["queue"]["depth_max"] == 3
        assert s["coalescing"]["solved_lanes"] == 3
        assert s["coalescing"]["padded_lanes"] == 1
        assert s["coalescing"]["occupancy_mean"] == pytest.approx(0.75)
        assert s["cache"]["hit_rate"] == pytest.approx(0.5)
        assert s["cache"]["refactorizations"] == 1
        assert "tenant-a" in s["tenants"]
        assert s["compiles"]["after_warmup"] >= 0

    def test_unknown_cache_event_rejected(self):
        with pytest.raises(ValueError):
            ServiceMetrics().record_cache("nope")


# --------------------------------------------------------------------------
# plan cache (stub engines: LRU/pin logic only, no XLA)
# --------------------------------------------------------------------------
class _StubEngine:
    def __init__(self, a, pattern, vals_csr, **kw):
        self.fingerprint = ("stub", a.n, pattern.k)
        self.buckets = (1, 2, 4)
        self._v = 0

    def bind(self, a, vals_csr):
        self._v += 1
        return types.SimpleNamespace(version=self._v, value_args=(), vals_csr=vals_csr)


def _cache(capacity=2):
    return PlanCache(capacity=capacity, metrics=ServiceMetrics(),
                     engine_factory=_StubEngine)


def _mat(n=16, seed=0):
    return matgen(n, 0.2, seed=seed)


class TestPlanCache:
    def test_lru_eviction_of_unpinned(self):
        c = _cache(capacity=2)
        c.register("a", _mat(seed=1))
        c.register("b", _mat(seed=2))
        c.acquire("a")  # refreshes a's recency AND pins it
        c.release("a")
        c.register("c", _mat(seed=3))  # evicts b (LRU, unpinned)
        assert "b" not in c and "a" in c and "c" in c

    def test_pinned_entries_survive_eviction(self):
        c = _cache(capacity=2)
        c.register("a", _mat(seed=1))
        c.register("b", _mat(seed=2))
        c.acquire("b")  # pin b; a becomes the only evictable entry
        c.register("c", _mat(seed=3))
        assert "b" in c and "a" not in c
        c.release("b")

    def test_all_pinned_raises_instead_of_evicting(self):
        c = _cache(capacity=1)
        c.register("a", _mat(seed=1))
        c.acquire("a")
        with pytest.raises(AdmissionError) as e:
            c.register("b", _mat(seed=2))
        assert e.value.reason == "queue_full"
        c.release("a")

    def test_acquire_unknown_raises(self):
        c = _cache()
        with pytest.raises(AdmissionError) as e:
            c.acquire("ghost")
        assert e.value.reason == "unknown_matrix"

    def test_engine_shared_by_structure(self):
        c = _cache(capacity=4)
        a1 = _mat(seed=5)
        a2 = CSRMatrix(n=a1.n, indptr=a1.indptr, indices=a1.indices,
                       data=(a1.data * 3.0).astype(np.float32))
        e1 = c.register("a1", a1)
        e2 = c.register("a2", a2)
        assert e1.engine is e2.engine
        assert c.metrics.snapshot()["cache"]["engines_shared"] == 1
        assert e2.plan_host is a1  # factor plan rides the first registrant

    def test_update_values_swaps_binding_atomically(self):
        c = _cache(capacity=2)
        a = _mat(seed=7)
        e = c.register("a", a)
        _, old = c.acquire("a")
        t = c.update_values("a", (a.data * 1.5).astype(np.float32), background=True)
        t.join()
        assert e.binding.version == old.version + 1
        assert e.binding is not old  # pinned old binding still intact
        c.release("a")

    def test_update_unknown_or_wrong_shape(self):
        c = _cache()
        a = _mat(seed=8)
        c.register("a", a)
        with pytest.raises(AdmissionError):
            c.update_values("ghost", a.data)
        with pytest.raises(ValueError, match="expected"):
            c.update_values("a", np.zeros(3, np.float32))


# --------------------------------------------------------------------------
# engine: bind/rebind bitwise (real XLA, one small matrix)
# --------------------------------------------------------------------------
def test_engine_rebind_is_bitwise_and_version_monotone():
    a = matgen(60, 0.08, seed=21)
    pattern = _symbolic(a, 1, "sum")
    v1 = np.asarray(factor_plan_for(a, pattern).factorize(a))
    eng = ServeEngine(a, pattern, v1, restart=8, buckets=(1, 2))
    b1 = eng.bind(a, v1)

    a2 = CSRMatrix(n=a.n, indptr=a.indptr, indices=a.indices,
                   data=(a.data * 1.25).astype(np.float32))
    v2 = np.asarray(factor_plan_for(a, pattern).factorize(a2))
    b2 = eng.bind(a2, v2)
    assert b2.version == b1.version + 1

    rng = np.random.default_rng(0)
    B = rng.standard_normal((2, a.n)).astype(np.float32)
    tols = np.full(2, 1e-6, np.float32)
    for bind, mat in ((b1, a), (b2, a2)):
        lanes = eng.solve(bind, B, tols)
        for i in range(2):
            ref, _ = solve_with_ilu(mat, B[i], k=1, tol=1e-6, restart=8)
            np.testing.assert_array_equal(
                np.asarray(lanes[i].x, np.float32).view(np.int32),
                np.asarray(ref.x, np.float32).view(np.int32))
            assert lanes[i].iterations == ref.iterations
            assert lanes[i].converged


def test_engine_rebind_matches_a_fresh_plan_bitwise():
    """Binding new values rebinds the grouped sweep's value arrays: they,
    and the apply they give, equal a triangular plan built afresh on the
    new values, bit for bit."""
    import jax.numpy as jnp

    from repro.core.triangular import PrecondApply, build_triangular_plan, wavefront_sweeps_jnp

    a = matgen(200, 0.03, seed=1)  # a wide, dependency-free first L level
    pattern = _symbolic(a, 1, "sum")
    v1 = np.asarray(factor_plan_for(a, pattern).factorize(a))
    eng = ServeEngine(a, pattern, v1, restart=8, buckets=(1,))
    a2 = CSRMatrix(n=a.n, indptr=a.indptr, indices=a.indices,
                   data=(a.data * 0.75).astype(np.float32))
    v2 = np.asarray(factor_plan_for(a, pattern).factorize(a2))
    bound = eng.bind(a2, v2).value_args[1:]
    fresh = build_triangular_plan(pattern, v2)
    assert fresh.sweep_groups[0] > 1
    for got, want in zip(bound, (fresh.l_vals_lm, fresh.u_vals_lm, fresh.u_diag_lm)):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g).view(np.int32), w.view(np.int32))
    b = np.random.default_rng(3).standard_normal(a.n).astype(np.float32)
    s = eng._p_static
    x = wavefront_sweeps_jnp(s["l_cols"], bound[0], s["l_rhs_idx"], s["u_cols"], bound[1],
                             bound[2], s["u_rhs_idx"], s["out_perm"], jnp.asarray(b))
    want = PrecondApply(pattern, v2, plan=fresh)(jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(x).view(np.int32), np.asarray(want).view(np.int32))


# --------------------------------------------------------------------------
# seeded coalescing-invariance check (the no-hypothesis fallback for the
# property test in test_property.py — runs everywhere)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("seed,k,method", [(0, 0, "sweep"), (1, 1, "inverse"),
                                           (2, 2, "sweep")])
def test_coalescing_invariance_seeded(seed, k, method):
    """A request's bits do not depend on batch membership, lane position,
    bucket, or its neighbours' tolerances."""
    rng = np.random.default_rng(seed)
    a = matgen(48, 0.12, seed=seed)
    pattern = _symbolic(a, k, "sum")
    v = np.asarray(factor_plan_for(a, pattern).factorize(a))
    eng = ServeEngine(a, pattern, v, restart=6, maxiter=30,
                      precond_method=method, buckets=(1, 2, 4))
    bind = eng.bind(a, v)

    b = rng.standard_normal(a.n).astype(np.float32)
    tol = 1e-6
    solo = eng.solve(bind, b[None, :], np.asarray([tol], np.float32))[0]
    ref, _ = solve_with_ilu(a, b, k=k, tol=tol, restart=6, maxiter=30, precond_method=method)
    np.testing.assert_array_equal(np.asarray(solo.x, np.float32).view(np.int32),
                                  np.asarray(ref.x, np.float32).view(np.int32))

    for nb, pos in ((2, 0), (2, 1), (4, 2), (3, 0)):  # 3 pads up to bucket 4
        B = rng.standard_normal((nb, a.n)).astype(np.float32)
        tols = rng.choice([1e-4, 1e-5, 1e-6], size=nb).astype(np.float32)
        B[pos] = b
        tols[pos] = tol
        lane = eng.solve(bind, B, tols)[pos]
        np.testing.assert_array_equal(
            np.asarray(lane.x, np.float32).view(np.int32),
            np.asarray(solo.x, np.float32).view(np.int32),
            err_msg=f"lane {pos} of batch {nb} != solo (k={k}, {method})")
        assert lane.iterations == solo.iterations


# --------------------------------------------------------------------------
# service-level basics (register / submit / tick / scatter)
# --------------------------------------------------------------------------
def test_service_round_trip_and_scatter():
    a = matgen(60, 0.08, seed=33)
    svc = SolveService(ServeConfig(buckets=(1, 2, 4), restart=8))
    v0 = svc.register_matrix("m0", a, k=1)
    assert v0 == 1
    rng = np.random.default_rng(3)
    bs = [rng.standard_normal(a.n).astype(np.float32) for _ in range(3)]
    reqs = [svc.submit(f"t{i}", "m0", b, tol=1e-5) for i, b in enumerate(bs)]
    assert all(isinstance(r, SolveRequest) for r in reqs)
    resps = svc.tick()
    assert len(resps) == 3
    by_id = {r.request_id: r for r in resps}
    for req, b in zip(reqs, bs):
        r = by_id[req.request_id]  # scatter: response matches its request
        assert r.ok and r.tenant == req.tenant and r.batch_lanes == 4
        ref, _ = solve_with_ilu(a, b, k=1, tol=1e-5, restart=8)
        np.testing.assert_array_equal(np.asarray(r.x, np.float32).view(np.int32),
                                      np.asarray(ref.x, np.float32).view(np.int32))
    # pins released: the entry is evictable again
    assert svc.cache.entry("m0").pins == 0
    snap = svc.metrics_snapshot()
    assert snap["requests"]["completed"] == 3
    assert snap["coalescing"]["batches"] == 1


def test_service_rejects_return_failed_response():
    a = matgen(40, 0.1, seed=34)
    svc = SolveService(ServeConfig(buckets=(1, 2), restart=8))
    svc.register_matrix("m0", a, k=1)
    r = svc.submit("t0", "ghost", np.ones(a.n, np.float32))
    assert isinstance(r, SolveResponse) and not r.ok
    assert r.error_reason == "unknown_matrix"
    snap = svc.metrics_snapshot()
    assert snap["requests"]["rejected_by_reason"]["unknown_matrix"] == 1


def test_service_thread_safe_submits():
    a = matgen(40, 0.1, seed=35)
    svc = SolveService(ServeConfig(buckets=(1, 2, 4), restart=8))
    svc.register_matrix("m0", a, k=1)
    rng = np.random.default_rng(0)
    bs = rng.standard_normal((16, a.n)).astype(np.float32)

    def submit_some(lo):
        for i in range(lo, lo + 4):
            svc.submit(f"t{lo}", "m0", bs[i])

    threads = [threading.Thread(target=submit_some, args=(i * 4,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    resps = svc.run_until_idle()
    assert len(resps) == 16 and all(r.ok for r in resps)
