"""Multi-device scale-out of the train + plan engines (DESIGN.md §11).

Every test here runs on a SIMULATED mesh: the `scaleout` marker requires
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in the environment
before pytest launches (the scaleout-smoke CI job sets it); on an unforced
interpreter the whole module auto-skips (see conftest).

Covered:
- sharded training matches the single-device trajectory within float
  tolerance, and is BIT-exact across refits at a fixed device count;
- checkpoint interrupt/resume stays bit-exact on a sharded mesh;
- the sharded PlanEngine dispatch returns labels/K identical to the
  sequential reference, with ZERO recompiles on the second dispatch
  (device-count-aware executable-cache keys);
- error-feedback int8 gradient compression: the shard_map collective
  tracks the exact f32 mean, and the value-level path
  (``tc.opt.grad_compress``) still converges under sharding;
- the benchmark artifact gates (>=3x modelled steps/s and plans/s at 8
  devices, 0 warm recompiles) via a slow subprocess smoke.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.rgcn import RGCNConfig
from repro.core.train import ContrastiveTrainer, FitInterrupted, GCLTrainConfig
from repro.launch.mesh import make_data_mesh
from repro.sampling.engine import PlanEngine
from repro.tracing.templates import make_kernel

pytestmark = pytest.mark.scaleout


def _graphs(n=8, cap=48):
    from repro.core.graphs import build_kernel_graph

    ks = [make_kernel(f"k{i}", "gemm",
                      {"M": 128 * (i % 3 + 1), "N": 128, "K": 128}, i, seed=i)
          for i in range(n)]
    return [build_kernel_graph(k.trace(cap_warps=2, cap_instr=cap))
            for k in ks]


GRAPHS = _graphs()


def _tc(**kw):
    base = dict(steps=8, batch_size=4, scan_chunk=4, log_every=50)
    base.update(kw)
    return GCLTrainConfig(**base)


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _losses(info):
    return np.array([h["loss"] for h in info["history"]])


# ---------------------------------------------------------------------------
# training: sharded-vs-single parity, fixed-width determinism, resume
# ---------------------------------------------------------------------------


def test_sharded_fit_matches_single_device():
    """The 8-wide data-parallel fit must track the single-device trajectory
    within float tolerance (same math, different reduction order)."""
    p1, i1 = ContrastiveTrainer(RGCNConfig(), _tc()).fit(GRAPHS)
    p8, i8 = ContrastiveTrainer(
        RGCNConfig(), _tc(), mesh_rules=make_data_mesh(8)).fit(GRAPHS)
    assert i8["data_shards"] == 8 and i1["data_shards"] == 1
    np.testing.assert_allclose(_losses(i1), _losses(i8),
                               atol=5e-5, rtol=5e-5)
    for a, b in zip(_leaves(p1), _leaves(p8)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)
    assert np.isclose(i1["val_loss"], i8["val_loss"], atol=5e-5)


def test_fixed_device_count_refit_bit_exact():
    """f32 determinism holds AT a fixed mesh width: two fits on the same
    8-wide mesh produce bit-identical parameters."""
    rules = make_data_mesh(8)
    p_a, _ = ContrastiveTrainer(RGCNConfig(), _tc(),
                                mesh_rules=rules).fit(GRAPHS)
    p_b, _ = ContrastiveTrainer(RGCNConfig(), _tc(),
                                mesh_rules=rules).fit(GRAPHS)
    for a, b in zip(_leaves(p_a), _leaves(p_b)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_sharded_checkpoint_resume_bit_exact(tmp_path):
    """Interrupt + resume on the 8-wide mesh == the uninterrupted sharded
    fit, bit for bit (checkpoints are device-layout-agnostic host arrays,
    so the resume protocol is untouched by sharding)."""
    rules = make_data_mesh(8)
    tc = _tc(steps=8, checkpoint_every=4)
    ck = str(tmp_path / "ck")
    with pytest.raises(FitInterrupted):
        ContrastiveTrainer(RGCNConfig(), tc, mesh_rules=rules).fit(
            GRAPHS, checkpoint_dir=ck, interrupt_after=4)
    p_res, i_res = ContrastiveTrainer(RGCNConfig(), tc,
                                      mesh_rules=rules).fit(
        GRAPHS, checkpoint_dir=ck)
    assert i_res["resumed_from"] >= 4
    p_full, i_full = ContrastiveTrainer(RGCNConfig(), tc,
                                        mesh_rules=rules).fit(GRAPHS)
    np.testing.assert_array_equal(_losses(i_res), _losses(i_full))
    for a, b in zip(_leaves(p_res), _leaves(p_full)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# gradient compression under sharding
# ---------------------------------------------------------------------------


def test_compressed_psum_mean_tracks_exact():
    """The error-feedback int8 collective must agree with the exact f32
    psum mean within the int8 quantization grid (amax/127 per tensor),
    and its residual must be exactly what went uncommunicated."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.optim.grad_compress import compressed_psum_mean, psum_mean

    mesh = make_data_mesh(8).mesh
    rng = np.random.default_rng(0)
    grads = {"w": jnp.asarray(rng.normal(size=(8, 16, 4)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(8, 7)), jnp.float32)}
    err = jax.tree_util.tree_map(jnp.zeros_like, grads)
    spec = jax.tree_util.tree_map(lambda _: P("data"), grads)

    exact = jax.jit(shard_map(
        lambda g: psum_mean(g, "data"), mesh=mesh,
        in_specs=(spec,), out_specs=spec))(grads)
    approx, new_err = jax.jit(shard_map(
        lambda g, e: compressed_psum_mean(g, e, "data"), mesh=mesh,
        in_specs=(spec, spec), out_specs=(spec, spec)))(grads, err)

    for k in grads:
        a, b = np.asarray(exact[k]), np.asarray(approx[k])
        grid = np.abs(np.asarray(grads[k])).max() / 127.0
        assert np.abs(a - b).max() <= grid + 1e-6
        # error feedback: residual == local grad minus what was sent
        assert np.isfinite(np.asarray(new_err[k])).all()
        assert np.abs(np.asarray(new_err[k])).max() <= grid + 1e-6


def test_grad_compress_convergence_sharded():
    """Value-level EF-int8 (tc.opt.grad_compress) under the 8-wide mesh:
    training still converges to the same neighborhood as uncompressed —
    final loss within 15% — and the compression state survives the fit."""
    import dataclasses

    tc_off = _tc(steps=12)
    tc_on = dataclasses.replace(
        tc_off, opt=dataclasses.replace(tc_off.opt, grad_compress=True))
    rules = make_data_mesh(8)
    _, i_off = ContrastiveTrainer(RGCNConfig(), tc_off,
                                  mesh_rules=rules).fit(GRAPHS)
    _, i_on = ContrastiveTrainer(RGCNConfig(), tc_on,
                                 mesh_rules=rules).fit(GRAPHS)
    l_off, l_on = _losses(i_off), _losses(i_on)
    assert l_on[-1] <= l_on[0]  # it trains
    assert abs(l_on[-1] - l_off[-1]) <= 0.15 * abs(l_off[-1])


# ---------------------------------------------------------------------------
# plan engine: sharded dispatch parity + zero-recompile warm path
# ---------------------------------------------------------------------------


def _embs(n=16, dim=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(40 + 3 * i, dim)).astype(np.float32)
            for i in range(n)]


def test_plan_engine_sharded_matches_sequential():
    """Labels and chosen K from the sharded sweep dispatch must equal the
    sequential reference exactly — sharding the program axis cannot change
    any program's math."""
    embs = _embs()
    sharded = PlanEngine(k_max=6, iters=8, max_batch=2,
                         data_devices=8).cluster_many(embs)
    reference = PlanEngine(k_max=6, iters=8,
                           engine="sequential").cluster_many(embs)
    for (lab, info), (lab_r, info_r) in zip(sharded, reference):
        np.testing.assert_array_equal(np.asarray(lab), np.asarray(lab_r))
        assert info["k"] == info_r["k"]


def test_sharded_dispatch_scales_chunk_cap():
    """One sharded dispatch serves data_devices x max_batch programs (16
    same-bucket programs, cap = 2 x 8)."""
    rng = np.random.default_rng(2)
    embs = [rng.normal(size=(40 + i, 8)).astype(np.float32)
            for i in range(16)]  # all in the 64-points bucket
    eng = PlanEngine(k_max=6, iters=8, max_batch=2, data_devices=8)
    eng.cluster_many(embs)
    assert eng.stats["dispatches"] == 1
    assert eng.engine_stats()["data_shards"] == 8


def test_zero_recompiles_on_second_sharded_dispatch():
    """The executable-cache key is device-count-aware, so the warm sharded
    path never re-lowers: the 2nd identical dispatch adds 0 builds."""
    from repro.core.clustering import engine_stats

    embs = _embs(n=8, dim=8, seed=3)
    eng = PlanEngine(k_max=6, iters=8, max_batch=1, data_devices=8)
    eng.cluster_many(embs)
    builds0 = engine_stats()["builds"]
    eng.cluster_many(embs)
    assert engine_stats()["builds"] - builds0 == 0


def test_warmup_covers_sharded_dispatch():
    """warm_sweep warms the SAME (sharded) key cluster_many later serves
    from — a warmed engine compiles nothing at dispatch time."""
    from repro.core.clustering import engine_stats

    embs = _embs(n=8, dim=7, seed=5)
    eng = PlanEngine(k_max=5, iters=8, max_batch=1, data_devices=8)
    eng.warmup([(64, 7)], batch_sizes=[8])
    builds0 = engine_stats()["builds"]
    eng.cluster_many(embs)
    assert engine_stats()["builds"] - builds0 == 0


# ---------------------------------------------------------------------------
# benchmark gates (slow: re-runs the bench in a subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_bench_scaleout_gates(tmp_path):
    """The committed acceptance gates: >=3x modelled steps/s and plans/s at
    8 simulated devices vs 1, 0 recompiles on the warm sharded path, and a
    real collective-bytes win from gradient compression."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env.pop("XLA_FLAGS", None)  # the bench pins its own device count
    subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_scaleout", "--smoke",
         "--devices", "1,8"],
        check=True, env=env, cwd=repo, timeout=560)
    with open(os.path.join(repo, "BENCH_scaleout.json")) as f:
        doc = json.load(f)
    h = doc["headline"]
    assert h["train_modelled_speedup"] >= 3.0
    assert h["plan_modelled_speedup"] >= 3.0
    assert h["warm_recompiles"] == 0
    assert h["grad_compress_bytes_reduction"] >= 1.5
    # wall-clock floors: simulated devices share the physical cores, so we
    # only require the sharded path not to collapse (no-regression floor)
    t, p = doc["train"], doc["plan"]
    assert t["8"]["steps_per_s_wall"] >= 0.2 * t["1"]["steps_per_s_wall"]
    assert p["8"]["plans_per_s_wall"] >= 0.2 * p["1"]["plans_per_s_wall"]
