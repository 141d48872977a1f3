"""repro.serving: continuous batcher, warm pool, loadgen, tenant serving."""

import threading
import time

import numpy as np
import pytest

from repro.core import clustering
from repro.core.clustering import select_k_and_cluster
from repro.sampling import ArtifactStore, get_method
from repro.sampling.base import plan_from_labels
from repro.sampling.engine import (
    PlanEngine, PlanRequest, bucket_key, normalize_embeddings,
)
from repro.serving import (
    PlanService, parse_buckets, poisson_arrivals, run_open_loop,
    synthetic_fleet,
)
from repro.sim.simulate import SamplingPlan
from repro.tracing.programs import get_program

KW = dict(k_max=6, iters=10)


def _req(n, d=8, seed=0, method="t"):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    return PlanRequest(x, np.arange(n), method, seed=seed)


class _GateEngine(PlanEngine):
    """Engine whose dispatches block on an event + log their batch."""

    def __init__(self, gate, calls, **kw):
        super().__init__(**kw)
        self.gate, self.calls = gate, calls

    def plan_many(self, requests, errors="raise"):
        self.gate.wait(5.0)
        self.calls.append([bucket_key(r.embeddings) for r in requests])
        return super().plan_many(requests, errors=errors)


def test_parse_buckets():
    assert parse_buckets("64x16,128x8") == [(64, 16), (128, 8)]
    assert parse_buckets(" 32x4 ,") == [(32, 4)]


def test_fill_flush_batches_same_bucket():
    gate = threading.Event()
    calls = []
    eng = _GateEngine(gate, calls, max_batch=4, **KW)
    with PlanService(eng, max_batch=4, max_delay_ms=10_000.0) as svc:
        futs = [svc.submit(_req(40, seed=i)) for i in range(4)]
        gate.set()  # requests queue while the dispatcher is held
        plans = [f.result(10.0) for f in futs]
    assert all(isinstance(p, SamplingPlan) for p in plans)
    # one full-batch dispatch, counted as a fill flush
    assert [len(c) for c in calls] == [4]
    s = svc.stats()
    assert s["flush_causes"]["fill"] == 1
    assert s["served"] == 4 and s["failed"] == 0
    assert s["batch_occupancy"] == 1.0


def test_deadline_flush_partial_batch():
    with PlanService(max_batch=8, max_delay_ms=5.0, **KW) as svc:
        plan = svc.submit(_req(40)).result(30.0)
    assert isinstance(plan, SamplingPlan)
    s = svc.stats()
    assert s["flush_causes"]["deadline"] + s["flush_causes"]["drain"] >= 1
    assert s["flush_causes"]["fill"] == 0


def test_bucket_isolation_interleaved_sizes():
    """Interleaved submissions never share a dispatch across buckets."""
    gate = threading.Event()
    calls = []
    eng = _GateEngine(gate, calls, max_batch=4, **KW)
    with PlanService(eng, max_batch=4, max_delay_ms=10_000.0) as svc:
        futs = []
        for i in range(4):  # alternate 64-point and 128-point buckets
            futs.append(svc.submit(_req(40, seed=i)))
            futs.append(svc.submit(_req(100, seed=10 + i)))
        gate.set()
        for f in futs:
            assert isinstance(f.result(10.0), SamplingPlan)
    assert len(calls) == 2
    for batch in calls:
        assert len(set(batch)) == 1  # every dispatch is single-bucket
    assert {batch[0] for batch in calls} == {(64, 8), (128, 8)}


def test_served_plans_match_sequential_reference():
    fleet = synthetic_fleet(6, d=8, seed=3)
    with PlanService(max_batch=4, max_delay_ms=2.0, **KW) as svc:
        plans = [f.result(60.0) for f in [svc.submit(r) for r in fleet]]
    for req, plan in zip(fleet, plans):
        labels, info = select_k_and_cluster(
            normalize_embeddings(req.embeddings), seed=req.seed, **KW)
        ref = plan_from_labels(labels, req.seqs, req.method, extra=info)
        assert np.array_equal(ref.labels, plan.labels)
        assert ref.reps == plan.reps
        assert plan.extra["k"] == info["k"]
        # record_timings (on by default for service-owned engines) stamps
        # dispatch telemetry into the plan
        assert plan.extra["serve"]["points_bucket"] == bucket_key(
            req.embeddings)[0]


def test_warmup_takes_builds_off_serving_path():
    clustering._ENGINE_CACHE.clear()
    with PlanService(max_batch=4, max_delay_ms=2.0, **KW) as svc:
        built = svc.warmup("64x8", batch_sizes=[1, 2, 4])
        assert built > 0
        assert svc.warmup([(64, 8)], batch_sizes=[1, 2, 4]) == 0  # idempotent
        before = clustering.ENGINE_STATS["builds"]
        futs = [svc.submit(_req(40, seed=i)) for i in range(5)]
        for f in futs:
            assert isinstance(f.result(30.0), SamplingPlan)
    assert clustering.ENGINE_STATS["builds"] == before
    assert svc.stats()["engine"]["warmed_executables"] == built


def test_poison_request_fails_only_its_future():
    with PlanService(max_batch=4, max_delay_ms=10_000.0, **KW) as svc:
        bad = svc.submit(PlanRequest(np.float32(3.0), np.arange(1), "bad"))
        good = [svc.submit(_req(40, seed=i)) for i in range(4)]
        with pytest.raises(ValueError):
            bad.result(10.0)
        for f in good:
            assert isinstance(f.result(10.0), SamplingPlan)
    s = svc.stats()
    assert s["failed"] == 1 and s["served"] == 4


def test_submit_after_close_fails_cleanly():
    svc = PlanService(max_batch=2, max_delay_ms=1.0, **KW)
    svc.close()
    with pytest.raises(RuntimeError):
        svc.submit(_req(16)).result(5.0)


def test_close_drains_pending_requests():
    gate = threading.Event()
    eng = _GateEngine(gate, [], max_batch=8, **KW)
    svc = PlanService(eng, max_batch=8, max_delay_ms=10_000.0)
    futs = [svc.submit(_req(40, seed=i)) for i in range(3)]
    gate.set()
    svc.close()
    for f in futs:
        assert isinstance(f.result(1.0), SamplingPlan)
    s = svc.stats()
    assert s["flush_causes"]["drain"] == 1 and s["served"] == 3


def test_submit_program_pka_and_sieve_fallback(tmp_path):
    prog = get_program("3mm")
    store = ArtifactStore(str(tmp_path), cache=True)
    method = get_method("pka")
    with PlanService(max_batch=4, max_delay_ms=2.0,
                     k_max=method.k_max, seed=method.seed) as svc:
        served = svc.submit_program(method, prog, store=store).result(120.0)
        direct, _ = get_method("pka").run(prog, store=store)
        # sieve has no engine request -> resolved via its own plan, already
        # done when the future comes back
        fb = svc.submit_program(get_method("sieve"), prog, store=store)
        assert fb.done() and isinstance(fb.result(), SamplingPlan)
    assert np.array_equal(served.labels, direct.labels)
    assert served.reps == direct.reps
    # the second pka prepare replayed through the in-process cache
    assert store.cache_stats["hits"] >= 1


def test_submit_program_gcl_replays_encoder(tmp_path):
    prog = get_program("3mm")
    store = ArtifactStore(str(tmp_path), cache=True)
    gcl_kw = dict(steps=6, batch_size=4, cap_instr=48)
    m1 = get_method("gcl", **gcl_kw)
    with PlanService(max_batch=4, max_delay_ms=2.0,
                     k_max=m1.cfg.k_max, seed=m1.cfg.train.seed) as svc:
        p1 = svc.submit_program(m1, prog, store=store).result(240.0)
        # a SECOND tenant with the same config replays the stored encoder
        # through the in-process artifact cache: no refit
        m2 = get_method("gcl", **gcl_kw)
        calls = {"prepare": 0}
        orig = m2.prepare

        def counting_prepare(program):
            calls["prepare"] += 1
            return orig(program)

        m2.prepare = counting_prepare
        p2 = svc.submit_program(m2, prog, store=store).result(240.0)
    assert calls["prepare"] == 0
    assert np.array_equal(p1.labels, p2.labels)
    assert p1.reps == p2.reps
    assert store.cache_stats["hits"] >= 1


def test_loadgen_poisson_and_open_loop():
    arr = poisson_arrivals(50, rate_hz=100.0, seed=0)
    assert len(arr) == 50 and np.all(np.diff(arr) > 0)
    assert 0.1 < arr[-1] < 2.5  # ~0.5s expected span

    fleet = synthetic_fleet(8, d=8, seed=1)
    with PlanService(max_batch=4, max_delay_ms=2.0, **KW) as svc:
        svc.warmup(sorted({bucket_key(r.embeddings) for r in fleet}))
        res = run_open_loop(svc, fleet, rate_hz=200.0, seed=2)
    assert res.n_ok == 8 and res.n_err == 0
    assert res.latency_ms["p99"] >= res.latency_ms["p50"] > 0
    assert res.plans_per_s > 0
    j = res.to_json()
    assert j["service"]["served"] == 8
    assert j["service"]["engine"]["programs"] == 8


def test_stats_reset_windows_counters():
    with PlanService(max_batch=2, max_delay_ms=2.0, **KW) as svc:
        svc.submit(_req(16)).result(30.0)
        assert svc.stats()["served"] == 1
        svc.reset_stats()
        s = svc.stats()
        assert s["served"] == 0 and s["latency_ms"]["p50"] is None
        assert s["queue_wait_ms"]["p50"] is None
        assert s["engine"]["programs"] == 0


def test_queue_wait_runs_from_submit_to_dispatch():
    """stats()["queue_wait_ms"] times each request from submit to the
    dispatcher's pop: three requests wait for a fourth to fill the batch,
    which then waits for nothing."""
    with PlanService(max_batch=4, max_delay_ms=10_000.0, **KW) as svc:
        futs = [svc.submit(_req(40, seed=i)) for i in range(3)]
        time.sleep(0.2)
        futs.append(svc.submit(_req(40, seed=3)))
        for f in futs:
            assert isinstance(f.result(30.0), SamplingPlan)
        s = svc.stats()
    q = s["queue_wait_ms"]
    # the first three waited >= 0.2 s, the fourth about nothing
    assert 190.0 <= q["p50"] <= q["p99"] < s["latency_ms"]["p99"]
    assert 0.75 * 190.0 <= q["mean"] < q["p99"]
