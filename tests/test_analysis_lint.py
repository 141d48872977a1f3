"""The linter linted: fixture-driven unit tests for rules R1–R5, the
baseline workflow, and the runtime sanitizers (recompile guard + NaN
tripwire).  See DESIGN.md §10."""

import dataclasses
from pathlib import Path

import numpy as np
import pytest

from repro.analysis.lint import (
    BASELINE_PATH, diff_baseline, lint_paths, load_baseline,
)
from repro.analysis.sanitize import (
    NonFiniteError, RecompileError, check_finite, nan_tripwire,
    recompile_guard,
)
from repro.core import clustering
from repro.core.clustering import sweep_cluster_stack, warm_sweep

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "lint"


def rules_of(path) -> set:
    return {f.rule for f in lint_paths([path])}


# ---------------------------------------------------------------------------
# static rules: every rule catches its known-bad and passes its known-good
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule,bad,good", [
    ("R1", "r1_bad.py", "r1_good.py"),
    ("R2", "r2_bad.py", "r2_good.py"),
    ("R3", "r3_bad.py", "r3_good.py"),
    ("R1", "r1_shardmap_bad.py", "r1_shardmap_good.py"),
    ("R1", "r1_prefetch_bad.py", "r1_prefetch_good.py"),
    ("R1", "r1_ingest_bad.py", "r1_ingest_good.py"),
    ("R3", "r3_shardmap_bad.py", "r3_shardmap_good.py"),
    ("R4", "r4_bad.py", "r4_good.py"),
    ("R5", "r5_bad.py", "r5_good.py"),
])
def test_rule_fixture_pair(rule, bad, good):
    assert rule in rules_of(FIXTURES / bad), f"{rule} missed {bad}"
    assert not lint_paths([FIXTURES / good]), f"false positive in {good}"


def test_r5_kernel_matmul_accumulator():
    bad = FIXTURES / "kernels" / "r5_matmul_bad" / "kernel.py"
    good = FIXTURES / "kernels" / "r5_matmul_good" / "kernel.py"
    findings = lint_paths([bad])
    assert any(f.rule == "R5" and "preferred_element_type" in f.message
               for f in findings)
    assert not lint_paths([good])


def test_fused_kernel_entries_registered_in_callgraph():
    """The rgcn_fused entry points are pinned trace entries through the
    explicit KERNEL_ENTRIES registry, independent of decorator detection —
    R1/R5 must keep looking inside the fused encode front-end."""
    import ast

    from repro.analysis.callgraph import (
        KERNEL_ENTRIES, ModuleIndex, build_graph,
    )
    from repro.analysis.lint import module_name_for

    fused = {fid for fid in KERNEL_ENTRIES if ".rgcn_fused." in fid}
    assert len(fused) == 3
    indexes = []
    for rel in ("src/repro/kernels/rgcn_fused/kernel.py",
                "src/repro/kernels/rgcn_fused/ops.py"):
        path = REPO / rel
        tree = ast.parse(path.read_text(), filename=str(path))
        indexes.append(ModuleIndex(str(path), module_name_for(path), tree))
    funcs = build_graph(indexes)
    for fid in fused:
        assert fid in funcs, f"registered kernel entry {fid} not found"
        assert funcs[fid].traced_entry and funcs[fid].traced


def test_ingest_entries_registered_in_callgraph():
    """The trace->graph ingestion roots are pinned HOST entries through the
    INGEST_ENTRIES registry: they exist in the graph, the pool.submit hop
    links the worker body as a real call edge, and none of them is
    reachable from a jit/scan/vmap trace (R1 would flag that)."""
    import ast

    from repro.analysis.callgraph import (
        INGEST_ENTRIES, ModuleIndex, build_graph,
    )
    from repro.analysis.lint import module_name_for

    assert len(INGEST_ENTRIES) >= 4
    indexes = []
    for rel in ("src/repro/ingest/engine.py",
                "src/repro/tracing/tracer.py"):
        path = REPO / rel
        tree = ast.parse(path.read_text(), filename=str(path))
        indexes.append(ModuleIndex(str(path), module_name_for(path), tree))
    funcs = build_graph(indexes)
    for fid in INGEST_ENTRIES:
        assert fid in funcs, f"registered ingest entry {fid} not found"
        assert funcs[fid].host_entry
        assert not funcs[fid].traced, f"{fid} must stay host-side"
    # the executor hop is a call edge: iter_graphs -> _build_one via submit
    it = funcs["repro.ingest.engine:IngestEngine.iter_graphs"]
    assert "repro.ingest.engine:IngestEngine._build_one" in it.calls


def test_r1_flags_both_traced_and_dispatch_loop_sites():
    findings = [f for f in lint_paths([FIXTURES / "r1_bad.py"])
                if f.rule == "R1"]
    symbols = {f.symbol for f in findings}
    assert "traced_sync" in symbols          # R1a inside the jitted fn
    assert "dispatch_loop" in symbols        # R1b on the engine output
    assert len(findings) >= 3


def test_r1_shard_map_bodies_are_traced():
    """Both spellings mark the wrapped body traced: the
    ``from jax import shard_map`` import AND the ``jax.shard_map``
    attribute (each fixture body syncs, so each must be flagged)."""
    findings = [f for f in lint_paths([FIXTURES / "r1_shardmap_bad.py"])
                if f.rule == "R1"]
    symbols = {f.symbol for f in findings}
    assert "psum_mean.body" in symbols      # from-import spelling
    assert "scaled.body2" in symbols        # jax.shard_map alias
    assert len(findings) == 3


def test_r2_distinguishes_loop_from_per_call():
    messages = [f.message for f in lint_paths([FIXTURES / "r2_bad.py"])
                if f.rule == "R2"]
    assert any("inside a loop" in m for m in messages)
    assert any("per call" in m for m in messages)


def test_r3_flags_literal_and_reuse():
    messages = [f.message for f in lint_paths([FIXTURES / "r3_bad.py"])
                if f.rule == "R3"]
    assert any("hard-coded" in m for m in messages)
    assert any("reused" in m for m in messages)


def test_waiver_comment_suppresses_rule():
    # r1_good's dispatch loop is the SAME shape as r1_bad's — only the
    # inline waiver separates them
    assert not [f for f in lint_paths([FIXTURES / "r1_good.py"])
                if f.symbol == "waived_dispatch_loop"]


# ---------------------------------------------------------------------------
# repo-wide run vs the checked-in baseline
# ---------------------------------------------------------------------------


def test_repo_run_matches_baseline_exactly(monkeypatch):
    monkeypatch.chdir(REPO)   # baseline keys are repo-relative paths
    findings = lint_paths(["src/repro"])
    baseline = load_baseline(BASELINE_PATH)
    new, accepted, stale = diff_baseline(findings, baseline)
    assert not new, "new lint findings:\n" + "\n".join(
        f.render() for f in new)
    assert not stale, "stale baseline entries (fixed? remove them):\n" \
        + "\n".join(stale)
    assert len(accepted) == sum(baseline.values())


def test_baseline_diff_detects_new_and_stale():
    findings = lint_paths([FIXTURES / "r3_bad.py"])
    assert findings
    baseline = load_baseline(BASELINE_PATH)  # src/repro keys: all stale here
    new, accepted, stale = diff_baseline(findings, baseline)
    assert len(new) == len(findings) and not accepted
    assert set(stale) == set(baseline)


# ---------------------------------------------------------------------------
# runtime sanitizers
# ---------------------------------------------------------------------------

# a (points, dim, k_max, iters) combo no other test warms — the executable
# cache and build counters are process-wide
_COLD = dict(d=7, k_max=5, iters=11)


def test_recompile_guard_passes_on_warm_path():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(20, 8)).astype(np.float32)
    warm_sweep(1, x.shape[0], x.shape[1], k_max=6, iters=9)
    with recompile_guard(label="warm sweep") as guard:
        sweep_cluster_stack([x], k_max=6, iters=9)
    assert guard.builds == 0


def test_recompile_guard_trips_when_warmup_skipped():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(20, _COLD["d"])).astype(np.float32)
    with pytest.raises(RecompileError, match="exceed the budget"):
        with recompile_guard(label="cold sweep"):
            sweep_cluster_stack([x], k_max=_COLD["k_max"],
                                iters=_COLD["iters"])
    # and the stats the guard reported match the engine counters' story
    with recompile_guard(label="now warm") as guard:
        sweep_cluster_stack([x], k_max=_COLD["k_max"], iters=_COLD["iters"])
    assert guard.builds == 0
    assert clustering.ENGINE_STATS["builds"] > 0


def test_check_finite_walks_nested_containers_and_dataclasses():
    @dataclasses.dataclass
    class Box:
        w: np.ndarray
        meta: dict

    ok = Box(w=np.ones(3, np.float32), meta={"loss": 0.5, "n": 7})
    check_finite(ok)   # no raise
    bad = Box(w=np.array([1.0, np.nan], np.float32), meta={})
    with pytest.raises(NonFiniteError, match=r"\.w"):
        check_finite(bad)
    with pytest.raises(NonFiniteError, match="loss"):
        check_finite({"loss": float("inf")})
    # integer arrays are never "non-finite"
    check_finite({"labels": np.array([1, 2, 3])})


def test_nan_tripwire_wraps_callables():
    @nan_tripwire
    def good():
        return {"w": np.zeros(2, np.float32)}

    assert good()["w"].shape == (2,)

    bad = nan_tripwire(lambda: np.array([np.inf], np.float32), name="plan")
    with pytest.raises(NonFiniteError, match="plan"):
        bad()


def test_plan_service_sanitize_isolates_nonfinite_plans():
    from repro.serving.service import PlanService

    with PlanService(max_batch=2, sanitize=True) as svc:
        poisoned = svc._sanitize_plan({"weights": np.array([np.nan])})
        assert isinstance(poisoned, NonFiniteError)
        clean = {"weights": np.array([0.5, 0.5])}
        assert svc._sanitize_plan(clean) is clean
        err = RuntimeError("upstream")   # existing failures pass through
        assert svc._sanitize_plan(err) is err
    assert svc.stats()["sanitize_trips"] == 1
