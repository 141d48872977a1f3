"""CPU tests of the cell ``dsv3-plan`` and of the per-layer metric
``sweep_pad_share.plan``.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""

from __future__ import annotations

import copy
import dataclasses
import json
import os
import sys

import pytest

from benchmarks.chip import harness, run
from benchmarks.chip.test_bench import cpu_gate

harness.ensure_paths()

from repro import telemetry  # noqa: E402


def tiny_bench(tmp_path) -> dict:
    """BENCHMARK.json with DeepSeek-V3's configuration cut to a CPU-sized
    run: its trace window to one warp of 48 instructions; a short
    K-sweep."""
    out = copy.deepcopy(harness.bench_file())
    for c in out["configs"]:
        cfg = harness.load_json(os.path.join(harness.REPO, c["file"]))
        if c["name"] != "dsv3-decode":
            continue
        cfg["trace_caps"] = [1, 48]
        cfg["sweep"].update(k_max=6, iters=4)
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    return out


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("bench"))


def _generator(bench, cell, seed=7):
    c = harness.load_cell(cell, bench)
    return harness.load_generator(c.traffic["generator"])(
        cell=c, seed=seed, spans=harness.Spans())


# -- kernel_mismatches ---------------------------------------------------------


def test_published_decode_stream_has_no_kernel_mismatch(bench):
    gen = _generator(bench, "dsv3-plan")
    from repro.tracing.programs import get_program

    gen.programs = [get_program(n) for n in gen.cfg["programs"]]
    assert gen.kernel_mismatches() == 0


@pytest.mark.parametrize("role,key", [
    ("L7_experts_down", "n"), ("L1_mla_attention", "kv_len"),
    ("MTP0_eh_proj", "m")])
def test_kernel_mismatches_counts_a_changed_width(bench, role, key):
    """One launch of the four steps with one width changed: one product
    differs (two for the fused attention, whose scores and value product
    both read the context)."""
    from repro.tracing.programs import get_program

    gen = _generator(bench, "dsv3-plan")
    prog = get_program(gen.cfg["programs"][0])
    kernels = list(prog.kernels)
    i = next(j for j, k in enumerate(kernels) if k.name == role)
    kernels[i] = dataclasses.replace(
        kernels[i], params=dict(kernels[i].params,
                                **{key: kernels[i].params[key] // 2}))
    gen.programs = [dataclasses.replace(prog, kernels=kernels)]
    assert gen.kernel_mismatches() == (2 if key == "kv_len" else 1)


# -- the cells, end to end on the CPU ------------------------------------------


def test_sound_run_is_correct(bench):
    result, checks = run.run_cell("dsv3-plan", 3, 0.5, False, bench=bench,
                                  gate=cpu_gate)
    assert result["correct"], result
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "plan_invocations_per_s"}
    assert result["metrics"]["plan_invocations_per_s"]["value"] > 0
    want = {"emb_gap", "sweep_gap", "plan_mismatches", "kernel_mismatches"}
    assert set(result["checks"]) == want
    assert {c.name for c in checks} == want


# -- the per-layer readers -----------------------------------------------------


def _rec(i, name, **counts):
    return telemetry.SpanRecord(name, i, i + 1, "t", i, None, 1, counts)


SYNTHETIC = [
    _rec(1, "plan.sweep", points=900, padded=1024, k_max=48),
    _rec(2, "plan.sweep", points=100, padded=4 * 256, k_max=48),
    _rec(5, "plan.seed"),
]


def test_reading_of_recorded_spans(monkeypatch):
    monkeypatch.setattr(telemetry, "spans", lambda: list(SYNTHETIC))
    assert harness.load_reader("sweep_pad_share.plan")({}) == \
        pytest.approx(100.0 * (1 - 1000 / 2048))


def test_no_spans_no_reading(monkeypatch):
    monkeypatch.setattr(telemetry, "spans", lambda: [_rec(1, "plan.seed")])
    assert harness.load_reader("sweep_pad_share.plan")({}) is None
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert harness.load_reader("sweep_pad_share.plan")({}) is None


def test_sweep_spans_without_the_padded_count_read_nothing(monkeypatch):
    """A program that records ``plan.sweep`` without ``padded`` (before the
    count came) gives no reading, rather than a wrong one."""
    monkeypatch.setattr(telemetry, "spans", lambda: [
        _rec(1, "plan.sweep", points=9, k_max=48)])
    assert harness.load_reader("sweep_pad_share.plan")({}) is None


def test_new_metric_listed_for_its_cells():
    bench = harness.bench_file()
    for cell in ("suite-plan", "dsv3-plan"):
        names = [m["name"] for m in harness.load_cell(cell, bench).per_layer]
        assert "sweep_pad_share.plan" in names, cell


def test_traced_window_reads_the_program_span_metrics(bench, tmp_path):
    """A window of the cell inside a profiler session, as a ``--trace 1``
    run records it, gives the program-span readings as shares."""
    import jax

    gen = _generator(bench, "dsv3-plan")
    gen.setup(warm=False)
    telemetry.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        window = gen.run_window(0.5)
        gen.free()
    finally:
        jax.profiler.stop_trace()
    try:
        view = {"window": window}
        for metric in ("sweep_pad_share.plan", "plan_host_share.plan"):
            value = harness.load_reader(metric)(view)
            assert value is not None and 0.0 <= value <= 100.0, metric
    finally:
        telemetry.clear()
