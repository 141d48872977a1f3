"""CPU tests of the trace reduction (on a small trace recorded on a TPU v5e)
and of the operation counts.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

``testdata/small_trace.xplane.pb``: inside a ``bench.window`` annotation,
5 ms asleep, span ``prepare`` with three runs of a jitted 512 x 512 matmul,
10 ms asleep, span ``plan`` with one jitted ``sin(x).sum()``, 5 ms asleep.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np
import pytest

from benchmarks.chip import counts, harness, reference, trace_reduce

harness.ensure_paths()
SMALL = os.path.join(harness.BENCH_DIR, "testdata", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return trace_reduce.reduce_trace(SMALL, span_names=("prepare", "plan"))


def test_window_and_busy(small):
    assert small["devices"] == 1
    assert 0.020 < small["window_s"] < 0.040
    assert 0 < small["busy_s"] < 0.001 * small["window_s"] * 10
    assert small["busy_s"] == pytest.approx(sum(small["module_s"].values()))


def test_modules_by_name(small):
    # the two jitted lambdas share a module name; program ids are dropped
    assert set(small["module_s"]) == {"jit__lambda"}
    assert small["device_ops"] == [["jit__lambda", small["module_s"]["jit__lambda"]]]


def test_idle_gaps_named_by_host_span(small):
    gaps = small["idle_gaps"]
    lengths = [g[1] for g in gaps]
    assert lengths == sorted(lengths, reverse=True)
    # the 10 ms sleep between the spans is the longest gap, outside both
    assert gaps[0][0] == trace_reduce.OUTSIDE and gaps[0][1] > 0.009
    assert {g[0] for g in gaps} <= {"prepare", "plan", trace_reduce.OUTSIDE}
    assert sum(lengths) + small["busy_s"] == pytest.approx(small["window_s"])


def test_module_base():
    assert trace_reduce.module_base("jit_chunk(123456)") == "jit_chunk"
    assert trace_reduce.module_base("jit__sweep_core") == "jit__sweep_core"


# -- counts -----------------------------------------------------------------------


def _graphs(name="lud", n=24):
    from repro.core.graphs import build_kernel_graph
    from repro.tracing.programs import get_program

    prog = get_program(name)
    return [build_kernel_graph(k.trace(2, 96)) for k in prog.kernels[:n]]


def _real_sizes(batch):
    return int(np.sum(batch["node_mask"])), int(np.sum(batch["edge_mask"]))


@pytest.mark.parametrize("kw", [dict(bucket=False), dict(bucket=True),
                                dict(bucket=True, pad_graphs_to=64)])
def test_encoder_count_ignores_padding(kw):
    from repro.core.batching import pack_graphs

    rc = harness.load_json(os.path.join(
        harness.BENCH_DIR, "configs", "paper-suite.json"))["rgcn"]
    graphs = _graphs()
    nodes = sum(g.n_nodes for g in graphs)
    edges = sum(g.n_edges for g in graphs)
    batch, _ = pack_graphs(graphs, **kw)
    assert _real_sizes(batch) == (nodes, edges)
    assert counts.encoder_flops(*_real_sizes(batch), rc) == \
        counts.encoder_flops(nodes, edges, rc)


def test_suite_counts_ignore_use_pallas():
    """The window's counts come from the programs' graphs and the sweep
    sizes, never from which implementation the method runs."""
    from benchmarks.chip.generators import closed_suite

    cell = harness.load_cell("suite-plan")
    cell = dataclasses.replace(cell, config=dict(
        cell.config, programs=["3mm", "backprop", "lud"]))
    out = []
    for over in (None, {"use_pallas": True}):
        drv = closed_suite.Generator(cell, seed=1, spans=harness.Spans(),
                                  rgcn_overrides=over)
        drv.setup(warm=False)
        assert drv.method.cfg.rgcn.use_pallas == bool(over)
        drv.done = [(i, None, None) for i in range(3)]
        out.append(drv.layer_inputs())
    assert out[0] == out[1]
    assert out[0]["sweep_flops"] > 0 and out[0]["encode_flops"] > 0


def test_sweep_count():
    # backprop's 2 points are clustered on the host: no device work
    assert counts.sweep_flops(2, 256, 48, 50, 1200) == 0.0
    n, d = 255, 256
    f = counts.sweep_flops(n, d, 48, 50, 1200)
    ks = np.arange(2, 49)
    lloyd = np.sum(2.0 * n * ks * d * 51 + n * d * 50)
    assert f == pytest.approx(lloyd + 2.0 * n * n * d + 2.0 * n * n * len(ks))


def test_train_step_count_is_three_forwards():
    rc = harness.load_json(os.path.join(
        harness.BENCH_DIR, "configs", "zoo-decode.json"))["rgcn"]
    sizes = [(2500, 4200)] * 16
    fwd = counts.encoder_flops(40000, 67200, rc) + counts.projection_flops(16, rc)
    assert counts.train_step_flops(sizes, rc) > 3 * 2 * fwd


def test_reference_pack_matches_program_pack():
    """The reference's flat layout is the packer's, position for position
    (the random masks of the fit are drawn over it)."""
    from repro.core.batching import pack_graphs

    graphs = _graphs("cfd", 16)
    got, _ = pack_graphs(graphs, max_nodes_per_graph=4096,
                         max_edges_per_graph=8192)
    from benchmarks.chip.program import graph_dict

    ref = reference.pack([reference.truncate(graph_dict(g), 4096, 8192)
                          for g in graphs], 4)
    for k, v in ref.items():
        np.testing.assert_array_equal(got[k], v, err_msg=k)


@pytest.fixture(scope="module")
def zoo():
    """The zoo-decode configuration and its pool's packed sizes."""
    from benchmarks.chip.generators import fit_loop

    cfg = harness.load_json(os.path.join(
        harness.BENCH_DIR, "configs", "zoo-decode.json"))
    return cfg, fit_loop.packed_sizes(fit_loop.build_pool(cfg), cfg)


def _stated_work(cfg: dict, work: dict) -> dict:
    return {k: cfg["schedule"][k] for k in work}


def test_schedule_seeds_share_their_device_work(zoo):
    """Every fit seed the zoo-decode configuration lists does the work the
    configuration states (``fit_loop.schedule_work``: scan chunks and live
    steps at each packed shape, the held-out batch's shape), an unlisted
    seed does not, and set-up's warm fit reaches every shape of them, at
    the fit's chunk length, in fewer chunks than a fit."""
    from benchmarks.chip.generators import fit_loop

    cfg, sizes = zoo
    want = fit_loop.schedule_work(sizes, cfg["schedule"]["seeds"][0], cfg)
    assert want == _stated_work(cfg, want)
    assert sum(n for _, n in want["live"]) == cfg["train"]["steps"]
    for seed in cfg["schedule"]["seeds"]:
        assert fit_loop.schedule_work(sizes, seed, cfg) == want, seed
    unlisted = next(s for s in range(20) if s not in cfg["schedule"]["seeds"])
    assert fit_loop.schedule_work(sizes, unlisted, cfg) != want
    w = fit_loop.warm_seed(sizes, cfg)
    n = fit_loop.warm_steps(sizes, w, cfg)
    warm = fit_loop.schedule_chunks(
        sizes, w, dict(cfg, train=dict(cfg["train"], steps=n)))
    assert [k for k, _ in warm] == [k for k, _ in want["chunks"]]
    assert fit_loop._chunk_len(n, cfg) == cfg["train"]["scan_chunk"]
    assert sum(c for _, c in warm) < sum(c for _, c in want["chunks"])


def test_schedule_seeds_follow_from_the_rule(zoo):
    """The listed seeds are exactly those that the configuration's rule
    (``fit_loop.derive_schedule``) picks from its search range."""
    from benchmarks.chip.generators import fit_loop

    cfg, sizes = zoo
    lo, hi = cfg["schedule"]["search"]
    work, seeds = fit_loop.derive_schedule(sizes, cfg, range(lo, hi))
    assert seeds == cfg["schedule"]["seeds"]
    assert work == _stated_work(cfg, work)
