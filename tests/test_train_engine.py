"""Compiled training engine (core/train.py, DESIGN.md §4-§6): scan-vs-shim
parity, bit-exact interrupt/resume, eval-mode validation loss, host-sync
accounting, precision policy, loss scaling, and packed-batch sharding."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.core import rgcn as rgcn_mod
from repro.core.batching import pack_graphs, plan_epoch
from repro.core.graphs import build_kernel_graph
from repro.core.precision import Policy, get_policy
from repro.core.rgcn import RGCNConfig
from repro.core.train import (
    ContrastiveTrainer, FitInterrupted, GCLTrainConfig, METRIC_KEYS,
    packed_loss,
)
from repro.distributed.sharding import MeshRules, constrain_batch
from repro.optim import adamw_init
from repro.tracing.templates import make_kernel


def _graphs(n=6, cap=48):
    ks = [
        make_kernel(f"k{i}", "gemm",
                    {"M": 128 * (i % 3 + 1), "N": 128, "K": 128}, i, seed=i)
        for i in range(n)
    ]
    return [build_kernel_graph(k.trace(cap_warps=2, cap_instr=cap)) for k in ks]


GRAPHS = _graphs()


def _tc(**kw):
    base = dict(steps=8, batch_size=4, scan_chunk=4, log_every=50)
    base.update(kw)
    return GCLTrainConfig(**base)


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


# ---------------------------------------------------------------------------
# engine parity + host-sync accounting
# ---------------------------------------------------------------------------


def test_scan_engine_matches_python_shim():
    """Same seed -> the compiled scan engine and the per-step shim must
    produce the same loss trajectory and parameters (they share the loss;
    only execution differs)."""
    p_scan, i_scan = ContrastiveTrainer(
        RGCNConfig(), _tc(engine="scan")).fit(GRAPHS)
    p_py, i_py = ContrastiveTrainer(
        RGCNConfig(), _tc(engine="python")).fit(GRAPHS)

    l_scan = np.array([h["loss"] for h in i_scan["history"]])
    l_py = np.array([h["loss"] for h in i_py["history"]])
    assert len(l_scan) == len(l_py) == 8
    np.testing.assert_allclose(l_scan, l_py, atol=1e-5, rtol=1e-5)
    for a, b in zip(_leaves(p_scan), _leaves(p_py)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
    # every metric key present in both histories
    assert set(i_scan["history"][0]) == set(METRIC_KEYS)
    assert set(i_py["history"][0]) == set(METRIC_KEYS)
    # eval-mode validation ran in both engines
    assert "val_loss" in i_scan and "val_loss" in i_py
    assert np.isclose(i_scan["val_loss"], i_py["val_loss"], atol=1e-5)


def test_prefetch_fit_bit_exact_vs_inline_staging():
    """Double-buffered host->device staging (GCLTrainConfig.prefetch) rides
    a background thread but stages the SAME arrays in the SAME order with
    the SAME fold_in keys — the trajectory must be bit-exact vs inline
    staging, and the overlap accounting must be reported."""
    p_pre, i_pre = ContrastiveTrainer(
        RGCNConfig(), _tc(engine="scan", prefetch=True)).fit(GRAPHS)
    p_off, i_off = ContrastiveTrainer(
        RGCNConfig(), _tc(engine="scan", prefetch=False)).fit(GRAPHS)

    for a, b in zip(_leaves(p_pre), _leaves(p_off)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    l_pre = [h["loss"] for h in i_pre["history"]]
    l_off = [h["loss"] for h in i_off["history"]]
    np.testing.assert_array_equal(l_pre, l_off)

    assert i_pre["prefetch"] is True and i_off["prefetch"] is False
    assert i_pre["prefetch_stage_s"] > 0
    assert 0.0 <= i_pre["prefetch_overlap"] <= 1.0
    # inline staging by definition overlaps nothing
    assert i_off["prefetch_overlap"] == 0.0


def test_scan_host_syncs_bounded_by_log_every():
    """The engine's selling point: metrics cross to the host only at
    log_every boundaries (+ the final flush and the val pull), not per
    step — the shim syncs every step."""
    _, info = ContrastiveTrainer(
        RGCNConfig(), _tc(engine="scan", log_every=4)).fit(GRAPHS)
    windows = -(-8 // 4)  # ceil(steps / log_every)
    assert info["host_syncs"] <= windows + 2  # + final flush + val
    _, info_py = ContrastiveTrainer(
        RGCNConfig(), _tc(engine="python")).fit(GRAPHS)
    assert info_py["host_syncs"] >= 8  # one per step (+ val)
    assert info["engine"] == "scan" and info_py["engine"] == "python"


def test_dead_steps_match_a_schedule_without_padding():
    """6 steps in chunks of 4 leave dead steps; chunks of 1 leave none.
    The branch that passes a dead step through must leave the trajectory
    exactly that of the unpadded fit (bit-exact on the CPU)."""
    p_pad, i_pad = ContrastiveTrainer(
        RGCNConfig(), _tc(steps=6, scan_chunk=4)).fit(GRAPHS)
    p_one, i_one = ContrastiveTrainer(
        RGCNConfig(), _tc(steps=6, scan_chunk=1)).fit(GRAPHS)
    assert i_pad["skipped_steps"] > 0 and i_one["skipped_steps"] == 0
    assert i_pad["skipped_steps"] == \
        i_pad["scan_chunks"] * i_pad["chunk_len"] - 6
    for a, b in zip(_leaves(p_pad), _leaves(p_one)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert i_pad["history"] == i_one["history"]
    assert i_pad["val_loss"] == i_one["val_loss"]


def _chunk_args(live):
    """(state, stacked rows, keys, live) for one chunk of len(live) steps,
    every row the same packed batch."""
    tr = ContrastiveTrainer(RGCNConfig(), _tc())
    state = adamw_init(rgcn_mod.init_rgcn(jax.random.PRNGKey(0), tr.rc),
                       tr._opt)
    packed, _ = pack_graphs(GRAPHS[:4])
    stacked = {k: jnp.asarray(np.stack([v] * len(live)))
               for k, v in packed.items()}
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(0), i))(
        jnp.arange(len(live)))
    return tr._engine().scan, (state, stacked, keys, jnp.asarray(live))


def test_all_dead_chunk_passes_state_through():
    """A chunk with no live step returns the carried state unchanged and a
    zero metrics row per step."""
    scan, (state, stacked, keys, live) = _chunk_args([False, False])
    before = [np.asarray(x).copy() for x in _leaves(state)]
    out, ys = scan(state, stacked, keys, live)   # donates `state`
    for a, b in zip(before, _leaves(out)):
        assert np.array_equal(a, np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ys),
                                  np.zeros((2, len(METRIC_KEYS))))


def _primitives(eqns) -> set:
    """Names of every primitive in ``eqns`` and their sub-jaxprs."""
    out = set()
    for e in eqns:
        out.add(e.primitive.name)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out |= _primitives(sub.eqns)
    return out


def test_chunk_branches_past_dead_steps():
    """The scan body is one conditional on `live`: the step's products sit
    in one branch only, and none runs outside it, so a dead step computes
    nothing (a compute-then-discard body would fail this)."""
    scan, args = _chunk_args([True, False])
    assert "stablehlo.case" in scan.lower(*args).as_text()
    jaxpr = jax.make_jaxpr(scan)(*args).jaxpr
    scan_eqn, = [e for e in jaxpr.eqns[0].params["jaxpr"].jaxpr.eqns
                 if e.primitive.name == "scan"]
    body = scan_eqn.params["jaxpr"].jaxpr
    conds = [e for e in body.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    outside = [e for e in body.eqns if e is not conds[0]]
    assert "dot_general" not in _primitives(outside)
    branches = [_primitives(b.jaxpr.eqns)
                for b in conds[0].params["branches"]]
    assert sorted("dot_general" in b for b in branches) == [False, True]


def test_epoch_plan_covers_steps_in_order():
    sel = np.array([[0, 1, 2, 3], [2, 3, 4, 5], [0, 0, 1, 1], [4, 5, 0, 1]])
    plan = plan_epoch(GRAPHS, sel)
    assert plan.n_steps == 4
    covered = []
    for seg in plan.segments:
        assert seg.stop > seg.start
        assert all(v.shape[0] == len(seg) for v in seg.batches.values())
        covered.extend(range(seg.start, seg.stop))
    assert covered == [0, 1, 2, 3]
    # stacked rows reproduce a fresh per-step pack exactly
    seg0 = plan.segments[0]
    packed, _ = pack_graphs([GRAPHS[i] for i in sel[seg0.start]])
    for k, v in packed.items():
        np.testing.assert_array_equal(seg0.batches[k][0], v)


# ---------------------------------------------------------------------------
# interrupt / resume
# ---------------------------------------------------------------------------


def test_interrupt_resume_bit_exact(tmp_path):
    """A fit interrupted at step k and resumed must reproduce the
    uninterrupted run's params AND history bit-exactly (chunks are masked
    per step, so the resume boundary cannot change the math)."""
    tc = _tc(steps=12, checkpoint_every=4)
    p_full, i_full = ContrastiveTrainer(RGCNConfig(), tc).fit(GRAPHS)

    ck = str(tmp_path / "ck")
    with pytest.raises(FitInterrupted):
        ContrastiveTrainer(RGCNConfig(), tc).fit(
            GRAPHS, checkpoint_dir=ck, interrupt_after=8)
    assert CheckpointManager(ck).latest_step() == 8

    p_res, i_res = ContrastiveTrainer(RGCNConfig(), tc).fit(
        GRAPHS, checkpoint_dir=ck)
    assert i_res["resumed_from"] == 8
    for a, b in zip(_leaves(p_full), _leaves(p_res)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert [h["loss"] for h in i_full["history"]] == \
        [h["loss"] for h in i_res["history"]]
    assert i_full["val_loss"] == i_res["val_loss"]


def test_resume_refuses_foreign_seed(tmp_path):
    ck = str(tmp_path / "ck")
    tc = _tc(steps=12, checkpoint_every=4)
    with pytest.raises(FitInterrupted):
        ContrastiveTrainer(RGCNConfig(), tc).fit(
            GRAPHS, checkpoint_dir=ck, interrupt_after=4)
    with pytest.raises(ValueError, match="different seed"):
        ContrastiveTrainer(RGCNConfig(), _tc(steps=12, checkpoint_every=4,
                                             seed=1)).fit(
            GRAPHS, checkpoint_dir=ck)


def test_python_engine_rejects_checkpointing(tmp_path):
    with pytest.raises(ValueError, match="scan"):
        ContrastiveTrainer(RGCNConfig(), _tc(engine="python")).fit(
            GRAPHS, checkpoint_dir=str(tmp_path / "ck"))


def test_restore_tree_round_trip(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"))
    tree = {
        "state": {"params": {"layers": [np.arange(4.0), np.ones((2, 3))]}},
        "cursor": np.int64(7),
        "hist": {"loss": np.array([1.0, 0.5], np.float32)},
    }
    mgr.save(7, tree, blocking=True)
    got, step = mgr.restore_tree()
    assert step == 7
    assert int(got["cursor"]) == 7
    np.testing.assert_array_equal(got["state"]["params"]["layers"][0],
                                  tree["state"]["params"]["layers"][0])
    np.testing.assert_array_equal(got["state"]["params"]["layers"][1],
                                  tree["state"]["params"]["layers"][1])
    np.testing.assert_array_equal(got["hist"]["loss"], tree["hist"]["loss"])


def test_gcl_prepare_resumes_and_store_replays(tmp_path):
    """Store-level resume protocol: an interrupted gcl prepare() resumes
    from the last checkpoint instead of refitting, produces the SAME
    encoder as an uninterrupted fit, and a later run() replays the stored
    artifact outright."""
    from repro.core.sampler import GCLSampler, GCLSamplerConfig
    from repro.sampling import ArtifactStore, get_method
    from repro.tracing.programs import get_program

    prog = get_program("3mm")
    cfg = GCLSamplerConfig(
        cap_instr=48,
        train=_tc(checkpoint_every=4))
    kw = dict(cfg=cfg)
    store = ArtifactStore(str(tmp_path / "store"))

    m1 = get_method("gcl", **kw)
    m1.attach_store(store)
    ckdir = m1._fit_checkpoint_dir(prog)
    assert ckdir is not None and ckdir.startswith(store.root)

    # simulate the killed prepare(): identical sampler config, same
    # checkpoint dir, interrupted mid-fit
    crashed = GCLSampler(m1.cfg)
    graphs = crashed.build_graphs(prog)
    with pytest.raises(FitInterrupted):
        crashed.trainer.fit(graphs, checkpoint_dir=ckdir, interrupt_after=4)

    plan, art = m1.run(prog, store=store)
    assert art.meta["train"]["resumed_from"] == 4

    # resumed encoder == uninterrupted encoder (fresh store => its own
    # checkpoint dir is empty, so this fit runs start-to-finish)
    m2 = get_method("gcl", **kw)
    _, art2 = m2.run(prog, store=ArtifactStore(str(tmp_path / "store2")))
    assert art2.meta["train"]["resumed_from"] == 0
    for a, b in zip(_leaves(art.payload["params"]),
                    _leaves(art2.payload["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # a fresh method replays the resumed artifact from the store (no refit)
    m3 = get_method("gcl", **kw)
    m3.attach_store(store)
    assert store.has("gcl", m3.artifact_key(prog))
    _, art3 = m3.run(prog, store=store)
    assert art3.meta["train"]["resumed_from"] == 4  # the stored fit's meta
    assert m3.sampler.params is not None            # encoder adopted


# ---------------------------------------------------------------------------
# eval-mode validation loss
# ---------------------------------------------------------------------------


def test_eval_loss_is_deterministic_and_dropout_free():
    """The val block advertises "no dropout/noise, fixed augs": eval mode
    must be a pure function of (params, batch, key) and differ from the
    stochastic train-mode loss."""
    rc = RGCNConfig()
    params = rgcn_mod.init_rgcn(jax.random.PRNGKey(0), rc)
    packed, _ = pack_graphs(GRAPHS[:4])
    batch = {k: jnp.asarray(v) for k, v in packed.items()}
    key = jax.random.PRNGKey(123)

    e1, m1 = packed_loss(params, rc, 0.05, batch, key, train=False)
    e2, m2 = packed_loss(params, rc, 0.05, batch, key, train=False)
    assert float(e1) == float(e2)
    assert float(m1["nce_acc"]) == float(m2["nce_acc"])

    t1, _ = packed_loss(params, rc, 0.05, batch, key, train=True)
    assert float(t1) != float(e1)  # dropout + noise + gated augs active


# ---------------------------------------------------------------------------
# precision policy + loss scaling
# ---------------------------------------------------------------------------


def test_bf16_policy_encodes_close_to_f32():
    rc32 = RGCNConfig()
    rc16 = RGCNConfig(policy=get_policy("bf16"))
    params = rgcn_mod.init_rgcn(jax.random.PRNGKey(1), rc32)
    packed, _ = pack_graphs(GRAPHS[:4])
    batch = {k: jnp.asarray(v) for k, v in packed.items()}
    z32 = np.asarray(rgcn_mod.encode_packed(params, rc32, batch))
    z16 = np.asarray(rgcn_mod.encode_packed(params, rc16, batch))
    assert z16.dtype == np.float32  # readout is upcast
    assert np.all(np.isfinite(z16))
    # bf16 has ~3 decimal digits; embeddings must stay close in direction
    cos = np.sum(z32 * z16, -1) / (
        np.linalg.norm(z32, axis=-1) * np.linalg.norm(z16, axis=-1) + 1e-9)
    assert np.all(cos > 0.99)


def test_pow2_loss_scale_is_bit_neutral():
    """Scaling the loss by a power of two and unscaling the grads inside
    AdamW is exact in f32 — the trajectory must be identical to scale=1."""
    rc_scaled = RGCNConfig(policy=Policy(loss_scale=256.0))
    p0, i0 = ContrastiveTrainer(RGCNConfig(), _tc(steps=4)).fit(GRAPHS)
    p1, i1 = ContrastiveTrainer(rc_scaled, _tc(steps=4)).fit(GRAPHS)
    l0 = [h["loss"] for h in i0["history"]]
    l1 = [h["loss"] for h in i1["history"]]
    np.testing.assert_allclose(l0, l1, atol=0, rtol=0)
    for a, b in zip(_leaves(p0), _leaves(p1)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # grad_norm is reported UNSCALED
    assert np.isclose(i0["history"][0]["grad_norm"],
                      i1["history"][0]["grad_norm"], rtol=1e-6)


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------


def test_constrain_batch_no_rules_is_identity():
    packed, _ = pack_graphs(GRAPHS[:2])
    batch = {k: jnp.asarray(v) for k, v in packed.items()}
    out = constrain_batch(batch)
    assert out is batch or all(out[k] is batch[k] for k in batch)


def test_scan_engine_under_mesh_rules_matches_unsharded():
    """A 1x1 mesh makes every sharding constraint a layout no-op, so the
    scanned fit under MeshRules must reproduce the unsharded fit."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    rules = MeshRules(mesh=mesh)
    p_plain, i_plain = ContrastiveTrainer(
        RGCNConfig(), _tc(steps=4)).fit(GRAPHS)
    p_mesh, i_mesh = ContrastiveTrainer(
        RGCNConfig(), _tc(steps=4), mesh_rules=rules).fit(GRAPHS)
    np.testing.assert_allclose(
        [h["loss"] for h in i_plain["history"]],
        [h["loss"] for h in i_mesh["history"]], atol=1e-6)
    for a, b in zip(_leaves(p_plain), _leaves(p_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
