"""Parallel trace->graph ingestion: engine determinism, graph store,
vectorized-tracer parity, and the model-zoo Program namespace
(DESIGN.md §13).

The load-bearing invariants:
- `IngestEngine` output is BIT-identical to sequential ingestion at any
  (workers, depth) — FIFO collection + keyed RNG, no shared mutable state
  (hypothesis sweeps the configuration space);
- the vectorized `trace_kernel` replays the loop oracle's exact RNG
  stream: every array of every warp matches `trace_kernel_loop` bit for
  bit, per template, including divergent control flow;
- a `GraphStore` entry round-trips exactly, a corrupt entry is rejected
  and re-traced (never served), and trace caps are part of the key so a
  cached graph cannot be replayed across differing trace windows;
- `model:<config>[:phase]` programs resolve from PROGRAMS and stream
  end-to-end through `embed_stream`.
"""

import numpy as np
import pytest

from repro.ingest import (
    GraphStore, IngestConfig, IngestEngine, kernel_graph_key,
)
from repro.tracing.programs import Program, get_program
from repro.tracing.templates import TEMPLATES, make_kernel
from repro.tracing.tracer import trace_kernel, trace_kernel_loop

# one valid parameter set per template (templates have no defaults)
TEMPLATE_PARAMS = {
    "gemm": {"M": 128, "N": 64, "K": 32},
    "elementwise": {"n": 4096},
    "reduction": {"n": 8192},
    "stencil": {"nx": 256, "ny": 8},
    "softmax": {"rows": 64, "cols": 128},
    "conv": {"c": 8, "hw": 32, "k": 16},
    "traversal": {"nodes": 512},   # divergent branches (mask bits vary)
    "gemv": {"n": 256, "m": 64},
    "mla_attention": {"heads": 4, "q_len": 1, "kv_len": 256, "qk_dim": 72,
                      "v_dim": 64, "kv_heads": 1},
    "grouped_topk": {"rows": 4, "experts": 64, "groups": 8,
                     "topk_group": 4, "top_k": 8},
}

_TRACE_FIELDS = ("opcode", "pc", "mask", "dest", "src",
                 "mem_width", "mem_addr", "vstats")
_GRAPH_FIELDS = ("node_type", "token", "pc_norm", "vstats", "warp_id",
                 "edge_src", "edge_dst", "edge_type")


def _mixed_program(n=10, seed=3):
    """Small program cycling through templates, with duplicate
    invocations (exercises the dedup memo) and per-kernel seeds."""
    names = sorted(TEMPLATE_PARAMS)
    ks = []
    for i in range(n):
        t = names[i % len(names)]
        ks.append(make_kernel(f"k{i}", t, TEMPLATE_PARAMS[t], i,
                              seed=seed + (i % 3)))
    return Program("ingest-test", ks)


def _assert_graphs_equal(a, b):
    for f in _GRAPH_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype and x.shape == y.shape, f
        assert np.array_equal(x, y), f"graph field {f} differs"
    assert a.n_warps == b.n_warps


# ---------------------------------------------------------------------------
# vectorized tracer vs the loop oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("template", sorted(TEMPLATE_PARAMS))
def test_trace_kernel_matches_loop_oracle(template):
    inv = make_kernel("k", template, TEMPLATE_PARAMS[template], 0, seed=11)
    for caps in ((2, 96), (4, 192), (1, 24)):
        fast = inv.trace(*caps)
        slow = inv.trace(*caps, loop=True)
        assert len(fast) == len(slow) == caps[0]
        for wf, ws in zip(fast, slow):
            for f in _TRACE_FIELDS:
                x, y = getattr(wf, f), getattr(ws, f)
                assert x.dtype == y.dtype, (f, caps)
                assert np.array_equal(x, y), \
                    f"{template} caps={caps} field {f} diverges from oracle"


def test_trace_default_caps_resolve_from_config():
    from repro.config import DEFAULT_CAP_INSTR, DEFAULT_CAP_WARPS

    inv = make_kernel("k", "gemm", TEMPLATE_PARAMS["gemm"], 0, seed=5)
    traces = inv.trace()   # no caps anywhere -> repo-wide defaults
    assert len(traces) == DEFAULT_CAP_WARPS
    assert all(len(w.opcode) <= DEFAULT_CAP_INSTR for w in traces)


def test_all_templates_covered():
    assert set(TEMPLATE_PARAMS) == set(TEMPLATES.names())


# ---------------------------------------------------------------------------
# engine determinism (hypothesis over the config space)
# ---------------------------------------------------------------------------


def _ingest(program, workers, depth=2, store=None):
    eng = IngestEngine(IngestConfig(workers=workers, depth=depth,
                                    cache=store is not None), store)
    return list(eng.iter_graphs(program)), eng


def test_parallel_matches_sequential_basic():
    prog = _mixed_program(12)
    ref, _ = _ingest(prog, workers=0)
    par, eng = _ingest(prog, workers=3)
    assert len(par) == len(ref) == 12
    for a, b in zip(par, ref):
        _assert_graphs_equal(a, b)
    assert eng.stats["kernels"] == 12
    # duplicates collapse in the memo: fewer traces than invocations
    assert eng.stats["traced"] + eng.stats["memo_hits"] >= 12


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(workers=st.integers(0, 4), depth=st.integers(1, 3),
           n=st.integers(1, 14), seed=st.integers(0, 50))
    def test_parallel_matches_sequential_property(workers, depth, n, seed):
        prog = _mixed_program(n, seed=seed)
        ref, _ = _ingest(prog, workers=0)
        par, _ = _ingest(prog, workers=workers, depth=depth)
        assert len(par) == len(ref) == n
        for a, b in zip(par, ref):
            _assert_graphs_equal(a, b)
except ImportError:  # hypothesis is a dev-only dep (requirements-dev.txt)
    pass


def test_engine_matches_iter_kernel_graphs():
    """The engine is a drop-in for the core sequential path."""
    from repro.core.graphs import iter_kernel_graphs

    prog = _mixed_program(6)
    ref = list(iter_kernel_graphs(prog))
    par, _ = _ingest(prog, workers=2)
    for a, b in zip(par, ref):
        _assert_graphs_equal(a, b)


# ---------------------------------------------------------------------------
# graph store
# ---------------------------------------------------------------------------


def test_graph_store_round_trip(tmp_path):
    from repro.core.graphs import build_kernel_graph

    inv = make_kernel("k", "gemm", TEMPLATE_PARAMS["gemm"], 0, seed=9)
    g = build_kernel_graph(inv.trace(2, 96))
    store = GraphStore(str(tmp_path))
    key = kernel_graph_key(inv, 2, 96)
    store.save_kernel(key, g)
    assert store.has_kernel(key)
    loaded = store.load_kernel(key)
    assert loaded is not None
    _assert_graphs_equal(loaded, g)
    assert store.stats["writes"] == 1 and store.stats["hits"] == 1


def test_graph_store_miss_returns_none(tmp_path):
    store = GraphStore(str(tmp_path))
    assert store.load_kernel("0" * 20) is None
    assert store.stats["misses"] == 1


def test_caps_are_part_of_the_cache_key():
    inv = make_kernel("k", "gemm", TEMPLATE_PARAMS["gemm"], 0, seed=9)
    keys = {kernel_graph_key(inv, *caps)
            for caps in ((2, 96), (2, 64), (4, 96))}
    assert len(keys) == 3, "trace caps must derive distinct cache keys"
    # same trace identity at the same caps -> same key (name/seq excluded:
    # duplicate invocations share one entry)
    other = make_kernel("other-name", "gemm", TEMPLATE_PARAMS["gemm"], 77,
                        seed=9)
    assert kernel_graph_key(other, 2, 96) == kernel_graph_key(inv, 2, 96)


def test_corrupted_entry_rejected_and_retraced(tmp_path):
    prog = _mixed_program(8)
    store = GraphStore(str(tmp_path))
    cold, eng_cold = _ingest(prog, workers=0, store=store)
    n_unique = eng_cold.stats["traced"]
    assert n_unique > 0

    # flip bytes inside one on-disk entry
    victim = next((tmp_path / "kernels").rglob("*.npz"))
    blob = bytearray(victim.read_bytes())
    blob[100:120] = b"\xff" * 20
    victim.write_bytes(bytes(blob))

    rewarm, eng = _ingest(prog, workers=2, store=store)
    for a, b in zip(rewarm, cold):
        _assert_graphs_equal(a, b)     # corruption never changes output
    assert eng.stats["corrupt"] == 1
    assert eng.stats["traced"] == 1    # only the victim re-traced
    # the overwrite healed the store: fully warm now
    warm, eng2 = _ingest(prog, workers=0, store=store)
    assert eng2.stats["traced"] == 0
    for a, b in zip(warm, cold):
        _assert_graphs_equal(a, b)


def test_warm_run_retraces_nothing(tmp_path):
    prog = _mixed_program(10)
    store = GraphStore(str(tmp_path))
    cold, eng_cold = _ingest(prog, workers=2, store=store)
    assert eng_cold.stats["traced"] > 0
    assert store.warm(prog, 2, 96)     # manifest published on full drain

    warm, eng = _ingest(prog, workers=2, store=store)
    assert eng.stats["traced"] == 0, "warm GraphStore run must not re-trace"
    assert eng.stats["store_hits"] + eng.stats["memo_hits"] == 10
    for a, b in zip(warm, cold):
        _assert_graphs_equal(a, b)
    # a different trace window is a different cache universe
    _, eng3 = _ingest(prog, workers=0, store=store)
    assert eng3.stats["traced"] == 0
    eng4 = IngestEngine(IngestConfig(workers=0, cache=True), store)
    list(eng4.iter_graphs(prog, cap_warps=2, cap_instr=64))
    assert eng4.stats["traced"] > 0


def test_partial_drain_publishes_no_manifest(tmp_path):
    prog = _mixed_program(8)
    store = GraphStore(str(tmp_path))
    eng = IngestEngine(IngestConfig(workers=2), store)
    it = eng.iter_graphs(prog)
    next(it); next(it)
    it.close()
    assert not store.warm(prog, 2, 96)


# ---------------------------------------------------------------------------
# model-zoo Program namespace
# ---------------------------------------------------------------------------


def test_model_zoo_programs_resolve():
    from repro.workloads import zoo_names

    names = zoo_names()
    assert len(names) >= 6
    for name in names:
        assert name.startswith("model:")
    for name in ("model:llama3.2-3b:prefill", "model:mamba2-780m:decode",
                 "model:dbrx-132b:prefill"):
        prog = get_program(name)
        assert len(prog) > 0
        assert prog.trace_caps is not None      # 10-100x trace window
        assert "modelzoo" in prog.fingerprint_extra


def test_model_zoo_graphs_are_model_scale():
    from repro.core.graphs import build_kernel_graph

    prog = get_program("model:llama3.2-3b:prefill")
    small = make_kernel("k", "gemm", TEMPLATE_PARAMS["gemm"], 0, seed=1)
    g_small = build_kernel_graph(small.trace())  # repo-default window
    g_zoo = build_kernel_graph(prog.kernels[0].trace(*prog.trace_caps))
    assert g_zoo.n_nodes >= 10 * g_small.n_nodes


def test_model_program_streams_through_embed(tmp_path):
    """A (truncated) model program flows end-to-end: parallel ingestion ->
    stream_pack -> train_stream -> embed_stream, warm run re-traces 0."""
    from repro.core.rgcn import RGCNConfig
    from repro.core.sampler import GCLSampler, GCLSamplerConfig
    from repro.core.train import GCLTrainConfig

    full = get_program("model:llama3.2-3b:decode")
    prog = Program(full.name, full.kernels[:6],
                   fingerprint_extra=full.fingerprint_extra,
                   trace_caps=(2, 64))   # keep the unit test cheap
    cfg = GCLSamplerConfig(
        train=GCLTrainConfig(steps=8, batch_size=4, scan_chunk=4),
        rgcn=RGCNConfig(),
        ingest=IngestConfig(workers=2),
    )
    s = GCLSampler(cfg)
    s.attach_graph_store(GraphStore(str(tmp_path)))
    s.train_stream(s.iter_graphs(prog), n_total=len(prog))
    emb = s.embed_stream(s.iter_graphs(prog))
    assert emb.shape[0] == len(prog)
    assert np.isfinite(emb).all()
    warm = GCLSampler(cfg)
    warm.attach_graph_store(GraphStore(str(tmp_path)))
    list(warm.iter_graphs(prog))
    assert warm.ingest.stats["traced"] == 0


# ---------------------------------------------------------------------------
# streaming front door routes through the engine
# ---------------------------------------------------------------------------


def test_iter_program_graphs_engine_route():
    from repro.workloads.streaming import iter_program_graphs

    prog = _mixed_program(5)
    eng = IngestEngine(IngestConfig(workers=2))
    ref = list(iter_program_graphs(prog))
    par = list(iter_program_graphs(prog, engine=eng))
    assert eng.stats["kernels"] == 5
    for a, b in zip(par, ref):
        _assert_graphs_equal(a, b)
