"""CPU tests of the per-layer metrics read from the program's own spans
(``repro.telemetry``): on synthetic span lists, without the recorder, and
on real tiny windows recorded under a profiler session.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""

from __future__ import annotations

import sys

import pytest

from benchmarks.chip import harness
from benchmarks.chip.test_bench import tiny_bench

harness.ensure_paths()

from repro import telemetry  # noqa: E402

#: the metrics that read program spans, with the cell each is read in
SPAN_METRICS = {
    "ingest_share.plan": "suite-plan",
    "stage_share.plan": "suite-plan",
    "plan_host_share.plan": "suite-plan",
    "dead_step_share.fit": "zoo-fit",
    "stage_share.fit": "zoo-fit",
}


def _rec(i, name, start_s, end_s, **counts):
    return telemetry.SpanRecord(name, int(start_s * 1e9), int(end_s * 1e9),
                                "MainThread", i, None, 1, counts)


#: a 10 s window: 1 s of ingest, 0.5 + 0.25 s of embed staging (the stage
#: span around them is not read), 0.2 + 0.1 + 0.2 s of host planning (the
#: sweep is not read), 0.3 + 0.1 s of fit packing and chunk packing (the
#: stage span around the chunk's packing and its key derivation is not
#: read, nor the keys), and chunks of 32 steps of which 32 and 8 are live
SYNTHETIC = [
    _rec(1, "ingest.build", 0.0, 0.6), _rec(2, "ingest.build", 1.0, 1.4),
    _rec(3, "embed.stage", 2.0, 2.8), _rec(4, "embed.pack", 2.0, 2.5),
    _rec(5, "embed.upload", 2.5, 2.75), _rec(6, "plan.seed", 3.0, 3.2),
    _rec(7, "plan.sweep", 3.2, 4.2), _rec(8, "plan.select", 4.2, 4.3),
    _rec(9, "plan.build", 4.3, 4.5), _rec(10, "fit.plan_epoch", 5.0, 5.3),
    _rec(11, "fit.stage", 5.3, 5.45), _rec(14, "fit.pack", 5.3, 5.4),
    _rec(15, "fit.keys", 5.4, 5.45),
    _rec(12, "fit.chunk", 5.4, 5.5, computed=32, live=32),
    _rec(13, "fit.chunk", 5.5, 5.6, computed=32, live=8),
]
WINDOW = {"window": {"elapsed_s": 10.0}}


@pytest.mark.parametrize("metric,want", [
    ("ingest_share.plan", 10.0), ("stage_share.plan", 7.5),
    ("plan_host_share.plan", 5.0), ("stage_share.fit", 4.0),
    ("dead_step_share.fit", 37.5),
])
def test_reading_of_a_synthetic_window(monkeypatch, metric, want):
    monkeypatch.setattr(telemetry, "spans", lambda: list(SYNTHETIC))
    assert harness.load_reader(metric)(WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_no_spans_no_reading(monkeypatch, metric):
    monkeypatch.setattr(telemetry, "spans", lambda: [])
    assert harness.load_reader(metric)(WINDOW) is None


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_overflowed_buffer_gives_no_reading(monkeypatch, metric):
    """Spans dropped by a full buffer would read too low: no reading."""
    monkeypatch.setattr(telemetry, "spans", lambda: list(SYNTHETIC))
    monkeypatch.setattr(telemetry, "dropped", lambda: 1)
    assert harness.load_reader(metric)(WINDOW) is None


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_program_without_the_recorder_gives_no_reading(monkeypatch, metric):
    """A program that predates the recorder reads nothing, and does not
    raise."""
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert harness.load_reader(metric)(WINDOW) is None


def test_spans_of_other_layers_read_zero(monkeypatch):
    recs = [_rec(1, "gcl.prepare", 0.0, 1.0)]
    monkeypatch.setattr(telemetry, "spans", lambda: recs)
    assert harness.load_reader("ingest_share.plan")(WINDOW) == 0.0
    assert harness.load_reader("dead_step_share.fit")(WINDOW) is None


def test_span_metrics_listed_for_their_cells():
    bench = harness.bench_file()
    for metric, cell in SPAN_METRICS.items():
        names = [m["name"] for m in harness.load_cell(cell, bench).per_layer]
        assert metric in names, (metric, cell)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("bench"))


def _traced_window(bench, cell, tmp_path, seconds=0.2):
    """Set-up outside, then the window inside a profiler session, as a
    ``--trace 1`` run does; returns (generator, window, view)."""
    import jax

    generator = harness.load_generator(
        harness.load_cell(cell, bench).traffic["generator"])(
        cell=harness.load_cell(cell, bench), seed=7, spans=harness.Spans())
    generator.setup(warm=False)
    telemetry.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        window = generator.run_window(seconds)
    finally:
        jax.profiler.stop_trace()
    return generator, window, {"window": window}


def test_fit_window_reads_the_schedule_dead_share(bench, tmp_path):
    """The window's ``fit.chunk`` spans account for the fit's schedule,
    counted offline from the seed (``fit_loop.schedule_chunks``): each fit
    computes its live steps and passes the schedule's padding through the
    branch (Σ ``skipped``), so ``dead_step_share.fit`` reads 0."""
    from benchmarks.chip.generators import fit_loop

    gen, window, view = _traced_window(bench, "zoo-fit", tmp_path)
    try:
        cfg = gen.cfg
        sizes = fit_loop.packed_sizes(gen.graphs, cfg)
        steps = cfg["train"]["steps"]
        slots = [sum(c for _, c in fit_loop.schedule_chunks(sizes, s, cfg))
                 * fit_loop._chunk_len(steps, cfg) for s in gen.fit_seeds]
        assert len(slots) == window["attempted"] - window["failed"] >= 1
        assert sum(slots) > len(slots) * steps  # padding to pass
        recs = [r.counts for r in telemetry.spans() if r.name == "fit.chunk"]
        assert sum(c["live"] for c in recs) == len(slots) * steps
        assert sum(c["computed"] for c in recs) == len(slots) * steps
        assert sum(c["skipped"] for c in recs) == \
            sum(slots) - len(slots) * steps
        assert harness.load_reader("dead_step_share.fit")(view) == 0.0
        assert 0.0 < harness.load_reader("stage_share.fit")(view) < 100.0
    finally:
        telemetry.clear()


def test_fit_gap_names_are_spans_the_program_records(bench, tmp_path):
    """The spans that name zoo-fit's idle gaps (``span_names``, handed to
    ``reduce_trace``) are spans a traced fit records, each with time."""
    from benchmarks.chip.generators import fit_loop

    _, window, _ = _traced_window(bench, "zoo-fit", tmp_path)
    try:
        took = {}
        for r in telemetry.spans():
            took[r.name] = took.get(r.name, 0) + r.end_ns - r.start_ns
        for name in fit_loop.Generator.span_names:
            assert took.get(name, 0) > 0, name
    finally:
        telemetry.clear()


def test_plan_window_readings_are_shares(bench, tmp_path):
    _, window, view = _traced_window(bench, "suite-plan", tmp_path)
    try:
        assert window["attempted"] >= 3
        for metric in ("ingest_share.plan", "stage_share.plan",
                       "plan_host_share.plan"):
            assert 0.0 < harness.load_reader(metric)(view) < 100.0, metric
    finally:
        telemetry.clear()
