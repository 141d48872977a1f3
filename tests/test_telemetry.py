"""repro.telemetry: spans and counts at the sampler's layer boundaries,
recorded only inside a profiler session and on the trace's own clock."""

import glob
import os

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.core.graphs import build_kernel_graph
from repro.core.rgcn import RGCNConfig
from repro.core.sampler import GCLSamplerConfig
from repro.core.train import ContrastiveTrainer, GCLTrainConfig
from repro.ingest.engine import IngestConfig
from repro.sampling.engine import PlanRequest
from repro.sampling.methods import GCLMethod
from repro.tracing.programs import get_program
from repro.tracing.templates import make_kernel

#: every span of the prepare + plan path
PATH_SPANS = {
    "gcl.prepare", "ingest.build", "embed.next", "embed.stage",
    "embed.pack", "embed.upload", "embed.wait", "embed.encode", "gcl.plan",
    "plan.seed", "plan.sweep", "plan.select", "plan.build", "train.fit",
    "fit.plan_epoch", "fit.next", "fit.stage", "fit.pack", "fit.keys",
    "fit.wait", "fit.chunk",
}


def _tc(**kw):
    return GCLTrainConfig(**dict(dict(steps=6, batch_size=4, scan_chunk=4),
                                 **kw))


def _prepare_and_plan():
    """A tiny streaming GCL prepare (encoder fit, then embed) and plan of
    3mm.  No ingest memo, so the streaming embed pass traces every kernel
    again, on the staging thread."""
    cfg = GCLSamplerConfig(
        cap_instr=48, train=_tc(),
        ingest=IngestConfig(cache=False, memo=0))
    method = GCLMethod(cfg, streaming=True)
    prog = get_program("3mm")
    art = method.prepare(prog)
    return art, method.plan_batch([(prog, art)])[0]


def _start_trace(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def _host_events(log_dir):
    """(profile start on the wall clock, {name: [(start, end), ...]}) of the
    host-plane events of the trace under ``log_dir``, in absolute ns."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    t0 = next(int(v) for p in pd.planes for k, v in p.stats
              if k == "profile_start_time")
    events: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (t0 + int(ev.start_ns),
                         t0 + int(ev.start_ns + ev.duration_ns)))
    return t0, events


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The same prepare + plan outside and inside a profiler session."""
    telemetry.clear()
    off = _prepare_and_plan()
    off_spans = telemetry.spans()
    log_dir = tmp_path_factory.mktemp("trace")
    _start_trace(log_dir)
    try:
        on = _prepare_and_plan()
    finally:
        jax.profiler.stop_trace()
    on_spans = telemetry.spans()
    telemetry.clear()
    return {"off": off, "off_spans": off_spans, "on": on,
            "spans": on_spans, "log_dir": log_dir}


def test_no_span_outside_a_profiler_session(runs):
    assert not telemetry.enabled()
    assert runs["off_spans"] == []


def test_traced_run_records_every_span_with_its_counts(runs):
    recs = runs["spans"]
    assert {r.name for r in recs} == PATH_SPANS
    assert telemetry.dropped() == 0
    by = {}
    for r in recs:
        by.setdefault(r.name, []).append(r)
        assert r.end_ns >= r.start_ns
    assert by["gcl.prepare"][0].counts == {"invocations": 9}
    assert by["gcl.plan"][0].counts == {"programs": 1}
    assert by["fit.plan_epoch"][0].counts == {"steps": 6}
    sweep, = by["plan.sweep"]
    # 3mm's 9 points in the smallest points bucket, one program
    assert sweep.counts == {"points": 9, "padded": 32, "k_max": 48}
    # the streaming embed encodes each distinct graph once
    packed = sum(r.counts["graphs"] for r in by["embed.pack"])
    assert packed == sum(r.counts["graphs"] for r in by["embed.encode"])
    for r in by["embed.pack"]:
        assert 0 < r.counts["real_nodes"] <= r.counts["padded_nodes"]
    # 3mm's 9 invocations, traced by the fit's pass and again by the embed's
    assert len(by["ingest.build"]) == 18


def test_spans_nest_under_their_roots_across_threads(runs):
    recs = runs["spans"]
    by_id = {r.span_id: r for r in recs}

    def ancestors(r):
        out = []
        while r.parent is not None:
            r = by_id[r.parent]
            out.append(r.name)
        return out

    prepare, = [r for r in recs if r.name == "gcl.prepare"]
    plan, = [r for r in recs if r.name == "gcl.plan"]
    fit, = [r for r in recs if r.name == "train.fit"]
    assert prepare.parent is None and plan.parent is None
    assert len({prepare.request, plan.request, fit.request}) == 3
    staged = [r for r in recs if r.thread.startswith("stage-prefetch")]
    names = {r.name for r in staged}
    assert {"ingest.build", "embed.next", "embed.pack", "fit.next",
            "fit.stage", "fit.pack", "fit.keys"} <= names
    for r in staged:
        if r.name.startswith("fit."):
            assert r.request == fit.request
            assert ancestors(r)[-2:] == ["train.fit", "gcl.prepare"]
        else:
            assert r.request == prepare.request
            assert "gcl.prepare" in ancestors(r)
    for r in recs:
        if r.name in ("fit.next", "fit.stage"):
            assert r.parent == fit.span_id
        if r.name in ("fit.pack", "fit.keys"):
            assert by_id[r.parent].name == "fit.stage"
        if r.name.startswith("plan."):
            assert r.request == plan.request and r.parent == plan.span_id
    # the fit runs inside the prepare, under a request of its own
    assert fit.parent == prepare.span_id
    # the fit's pass builds its graphs under the prepare; the streaming
    # embed's pass builds them inside the staging thread's pulls
    parents = [by_id[r.parent].name for r in recs if r.name == "ingest.build"]
    assert set(parents) == {"gcl.prepare", "embed.next"}


def test_spans_share_the_trace_clock(runs):
    """Each in-memory span starts and ends within 100 us of the host event
    of the same name in the profiler's trace."""
    _, events = _host_events(runs["log_dir"])
    recs = runs["spans"]
    for name in PATH_SPANS:
        mine = sorted((r.start_ns, r.end_ns) for r in recs if r.name == name)
        theirs = sorted(events.get(name, []))
        assert len(mine) == len(theirs), name
        for (s, e), (ts, te) in zip(mine, theirs):
            assert abs(s - ts) < 100_000, (name, s - ts)
            assert abs(e - te) < 100_000, (name, e - te)


def test_results_identical_with_recorder_on_and_off(runs):
    (art0, plan0), (art1, plan1) = runs["off"], runs["on"]
    assert np.array_equal(art0.payload["embeddings"],
                          art1.payload["embeddings"])
    assert plan0.num_clusters == plan1.num_clusters
    assert np.array_equal(plan0.labels, plan1.labels)


@pytest.mark.parametrize("prefetch", [True, False])
def test_fit_chunk_counts_match_the_schedule(tmp_path, prefetch):
    """fit.chunk counts the step bodies the device runs (the live steps)
    and the scan iterations its branch passed through: 6 steps in chunks of
    4 pad the last chunk with dead steps, which compute nothing."""
    graphs = [build_kernel_graph(make_kernel(
        f"k{i}", "gemm", {"M": 128 * (i % 3 + 1), "N": 128, "K": 128}, i,
        seed=i).trace(cap_warps=2, cap_instr=48)) for i in range(6)]
    telemetry.clear()
    _start_trace(tmp_path)
    try:
        _, info = ContrastiveTrainer(
            RGCNConfig(), _tc(prefetch=prefetch)).fit(graphs)
    finally:
        jax.profiler.stop_trace()
    chunks = [r for r in telemetry.spans() if r.name == "fit.chunk"]
    stages = [r for r in telemetry.spans() if r.name == "fit.stage"]
    telemetry.clear()
    assert len(chunks) == info["scan_chunks"] == len(stages)
    computed = sum(r.counts["computed"] for r in chunks)
    skipped = sum(r.counts["skipped"] for r in chunks)
    assert computed == sum(r.counts["live"] for r in chunks) == \
        len(info["history"]) == 6
    assert computed + skipped == info["scan_chunks"] * info["chunk_len"]
    assert info["skipped_steps"] == skipped > 0


def test_span_outside_a_session_measures_only_when_timed():
    assert not telemetry.enabled()
    with telemetry.span("x.y", n=1) as s:
        s.count(m=2)
    assert s.seconds == 0.0
    with telemetry.span("x.y", timed=True) as t:
        sum(range(1000))
    assert t.seconds > 0.0

    def f():
        return 1

    assert telemetry.carry(f) is f
    assert telemetry.spans() == []


def test_roots_and_requests_in_a_session(tmp_path):
    telemetry.clear()
    _start_trace(tmp_path)
    try:
        with telemetry.span("a.root", root=True) as a:
            with telemetry.span("a.child"):
                pass
            with telemetry.span("b.root", root=True):
                with telemetry.span("b.child", n=3) as b:
                    b.count(m=4)
        assert a.seconds > 0.0
    finally:
        jax.profiler.stop_trace()
    recs = {r.name: r for r in telemetry.spans()}
    telemetry.clear()
    assert set(recs) == {"a.root", "a.child", "b.root", "b.child"}
    assert recs["a.child"].parent == recs["a.root"].span_id
    assert recs["a.child"].request == recs["a.root"].request
    assert recs["b.root"].parent == recs["a.root"].span_id
    assert recs["b.root"].request != recs["a.root"].request
    assert recs["b.child"].request == recs["b.root"].request
    assert recs["b.child"].counts == {"n": 3, "m": 4}


def test_buffer_is_bounded_and_counts_what_it_drops():
    buf = telemetry._Buffer(2)
    rec = telemetry.SpanRecord("a", 0, 1, "t", 1, None, None, {})
    for _ in range(5):
        buf.add(rec)
    assert len(buf.records) == 2 and buf.dropped == 3


def test_plan_sweep_counts_the_padded_points_of_the_dispatch(tmp_path):
    """``padded`` is the batch bucket times the points bucket: three
    programs of 40, 50 and 70 points sweep as 4 rows of 128."""
    from repro.core.clustering import sweep_cluster_stack

    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((n, 4)).astype(np.float32) for n in (40, 50, 70)]
    telemetry.clear()
    _start_trace(tmp_path)
    try:
        sweep_cluster_stack(xs, k_max=4, iters=3)
    finally:
        jax.profiler.stop_trace()
    sweep, = [r for r in telemetry.spans() if r.name == "plan.sweep"]
    telemetry.clear()
    assert sweep.counts == {"points": 160, "padded": 4 * 128, "k_max": 4}
