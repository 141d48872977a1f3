"""Scale-out benchmark: train + plan engines across a simulated device mesh.

Runs STANDALONE in its own process (``python -m benchmarks.bench_scaleout``)
because ``--xla_force_host_platform_device_count`` must be set before jax
initializes.  It is a simulated-CPU-mesh benchmark of modelled FLOPs; its
counterpart on real chips is ``python chip_smoke.py --chips 4``.

Reported per device count (1..N simulated host devices):

- **train**: measured steps/s of the compiled scan engine on a
  data-parallel mesh, plus the MODELLED scaling — per-device FLOPs of the
  compiled sharded scan from XLA ``cost_analysis`` (under SPMD
  partitioning cost_analysis is per-device, the same methodology as
  ``repro.launch.dryrun``), with per-device collective bytes from the
  partitioned HLO;
- **plan**: measured plans/s of the sharded K-sweep dispatch (one dispatch
  serves N_devices x max_batch programs), modelled per-program-per-device
  FLOPs scaling, and the warm-path recompile count (MUST be 0: the
  executable-cache key is device-count-aware);
- **grad compression**: per-device collective bytes of the data-parallel
  gradient exchange over the REAL model's parameter tree — exact f32
  ``psum_mean`` vs error-feedback int8 ``compressed_psum_mean`` (int16
  reduce payload), both lowered under shard_map.

Why modelled speedup is the headline: simulated host devices share the
machine's physical cores, so wall-clock on a 1-core CI runner CANNOT show
parallel speedup — per-device compute from the partitioned executable is
the hardware-independent scaling signal (deterministic, stable in CI).
Wall-clock numbers are still reported and gated as no-regression floors.

Results go to ``benchmarks/results/scaleout.json`` AND a repo-root
``BENCH_scaleout.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_PATH = os.path.join(REPO_ROOT, "BENCH_scaleout.json")
FORCE_FLAG = "--xla_force_host_platform_device_count"


# ---------------------------------------------------------------------------
# jax is imported lazily, AFTER main() pins XLA_FLAGS
# ---------------------------------------------------------------------------


def _cost(compiled) -> dict:
    """Per-device flops + collective bytes of a compiled executable."""
    from repro.launch.roofline import collective_bytes_from_hlo

    ca = compiled.cost_analysis() or {}
    coll = collective_bytes_from_hlo(compiled.as_text())
    return {"flops_per_device": float(ca.get("flops") or 0.0),
            "coll_bytes_per_device": float(coll["per_device_bytes"])}


def _train_graphs(n=12, cap=48):
    from repro.core.graphs import build_kernel_graph
    from repro.tracing.templates import make_kernel

    ks = [make_kernel(f"k{i}", "gemm",
                      {"M": 128 * (i % 3 + 1), "N": 128, "K": 128}, i, seed=i)
          for i in range(n)]
    return [build_kernel_graph(k.trace(cap_warps=2, cap_instr=cap))
            for k in ks]


def _lower_scan(trainer, graphs, rules):
    """Lower + compile the REAL engine scan on representative sharded
    inputs — the same staging path ``_fit_scan`` runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import rgcn as rgcn_mod
    from repro.core.batching import (
        MAX_EDGES_PER_MICROBATCH, MAX_NODES_PER_MICROBATCH, bucket_size,
        plan_epoch,
    )
    from repro.distributed.sharding import shard_batch_put
    from repro.optim import adamw_init

    tc = trainer.tc
    rng = np.random.default_rng(tc.seed)
    bs = min(tc.batch_size, len(graphs))
    selections = np.stack([rng.choice(len(graphs), size=bs)
                           for _ in range(tc.steps)])
    plan = plan_epoch(graphs, selections,
                      max_nodes_per_graph=MAX_NODES_PER_MICROBATCH,
                      max_edges_per_graph=MAX_EDGES_PER_MICROBATCH)
    chunk_len = min(tc.scan_chunk, bucket_size(max(plan.n_steps, 1), 1))
    seg = plan.segments[0]
    rows_np = {f: arr[:chunk_len] for f, arr in seg.batches.items()}
    stacked = shard_batch_put(rows_np, rules, leading=1)
    key = jax.random.PRNGKey(tc.seed)
    base_key, k_init = jax.random.split(key)
    params = rgcn_mod.init_rgcn(k_init, trainer.rc)
    state = adamw_init(params, trainer._opt)
    keys = jax.vmap(lambda i: jax.random.fold_in(base_key, i))(
        jnp.arange(chunk_len))
    live = jnp.ones((chunk_len,), bool)
    eng = trainer._engine()
    return eng.scan.lower(state, stacked, keys, live).compile()


def _bench_train(ndevs, steps, batch_size) -> dict:
    from repro.core.rgcn import RGCNConfig
    from repro.core.train import ContrastiveTrainer, GCLTrainConfig
    from repro.launch.mesh import make_data_mesh

    graphs = _train_graphs(n=max(12, batch_size + 4))
    tc = GCLTrainConfig(steps=steps, batch_size=batch_size,
                        scan_chunk=8, log_every=1000)
    out = {}
    for ndev in ndevs:
        rules = make_data_mesh(ndev) if ndev > 1 else None
        trainer = ContrastiveTrainer(RGCNConfig(), tc, mesh_rules=rules)
        trainer.fit(graphs)            # warm: compiles land here
        t0 = time.perf_counter()
        _, info = trainer.fit(graphs)
        wall = time.perf_counter() - t0
        rec = _cost(_lower_scan(trainer, graphs, rules))
        rec.update(steps_per_s_wall=steps / wall,
                   data_shards=info["data_shards"])
        out[str(ndev)] = rec
        print(f"[scaleout] train ndev={ndev}: "
              f"{rec['steps_per_s_wall']:.2f} steps/s wall, "
              f"{rec['flops_per_device']:.3g} flops/dev", flush=True)
    base = out[str(ndevs[0])]["flops_per_device"]
    for ndev in ndevs:
        out[str(ndev)]["modelled_speedup"] = (
            base / max(out[str(ndev)]["flops_per_device"], 1.0))
    return out


def _bench_grad_compress(ndev) -> dict:
    """Per-device collective bytes of the DP gradient exchange on the real
    parameter tree: exact f32 psum_mean vs error-feedback int8 (int16
    reduce payload)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import rgcn as rgcn_mod
    from repro.core.rgcn import RGCNConfig
    from repro.launch.mesh import make_data_mesh
    from repro.launch.roofline import collective_bytes_from_hlo
    from repro.optim.grad_compress import compressed_psum_mean, psum_mean

    params = rgcn_mod.init_rgcn(jax.random.PRNGKey(0), RGCNConfig())
    mesh = make_data_mesh(ndev).mesh
    rep = jax.tree_util.tree_map(lambda _: P(), params)

    def f32(grads):
        return psum_mean(grads, "data")

    def int8(grads, err):
        return compressed_psum_mean(grads, err, "data")

    err = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    low_f32 = jax.jit(shard_map(f32, mesh=mesh, in_specs=(rep,),
                                out_specs=rep)).lower(params)
    low_i8 = jax.jit(shard_map(int8, mesh=mesh, in_specs=(rep, rep),
                               out_specs=(rep, rep))).lower(params, err)
    b_f32 = collective_bytes_from_hlo(
        low_f32.compile().as_text())["per_device_bytes"]
    b_i8 = collective_bytes_from_hlo(
        low_i8.compile().as_text())["per_device_bytes"]
    # numerics sanity: compressed mean tracks the exact mean
    g_ref = jax.jit(shard_map(f32, mesh=mesh, in_specs=(rep,),
                              out_specs=rep))(params)
    g_cmp, _ = jax.jit(shard_map(int8, mesh=mesh, in_specs=(rep, rep),
                                 out_specs=(rep, rep)))(params, err)
    errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            / (float(jnp.max(jnp.abs(a))) + 1e-12)
            for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                            jax.tree_util.tree_leaves(g_cmp))]
    return {"devices": ndev,
            "f32_coll_bytes_per_device": float(b_f32),
            "int8_coll_bytes_per_device": float(b_i8),
            "bytes_reduction": float(b_f32) / max(float(b_i8), 1.0),
            "max_rel_quant_err": max(errs)}


def _bench_plan(ndevs, n_programs, points, dim, max_batch) -> dict:
    import numpy as np

    from repro.core.clustering import (
        _effective_shards, _round_sil_block, _shard_args, _sweep_fn,
        bucket_points, engine_stats,
    )
    from repro.sampling.engine import PlanEngine

    rng = np.random.default_rng(0)
    embs = [rng.normal(size=(points - (i % 4), dim)).astype(np.float32)
            for i in range(n_programs)]
    out = {}
    for ndev in ndevs:
        eng = PlanEngine(k_max=8, iters=10, max_batch=max_batch,
                         data_devices=ndev)
        eng.cluster_many(embs)         # warm: compiles land here
        b0 = engine_stats()["builds"]
        t0 = time.perf_counter()
        eng.cluster_many(embs)
        wall = time.perf_counter() - t0
        recompiles = engine_stats()["builds"] - b0

        # modelled: per-program per-device flops of ONE full dispatch
        # (ndev x max_batch programs), from the cached sharded executable
        b_total = max_batch * ndev
        n_pad = bucket_points(points)
        shards = _effective_shards(b_total, ndev)
        fn = _sweep_fn(b_total, n_pad, dim, 8, 10, False,
                       _round_sil_block(n_pad, 512), shards)
        args = (np.zeros((b_total, n_pad, dim), np.float32),
                np.zeros((b_total, n_pad), bool),
                np.zeros((b_total, 8), np.int32),
                np.zeros((b_total, n_pad), bool))
        if shards > 1:
            args = _shard_args(args, shards)
        cost = _cost(fn.lower(*args).compile())
        rec = {
            "plans_per_s_wall": n_programs / wall,
            "warm_recompiles": int(recompiles),
            "dispatches": eng.stats["dispatches"],
            "flops_per_program_per_device":
                cost["flops_per_device"] / b_total,
            "coll_bytes_per_device": cost["coll_bytes_per_device"],
            "data_shards": shards,
        }
        out[str(ndev)] = rec
        print(f"[scaleout] plan ndev={ndev}: "
              f"{rec['plans_per_s_wall']:.1f} plans/s wall, "
              f"{rec['warm_recompiles']} warm recompiles", flush=True)
    base = out[str(ndevs[0])]["flops_per_program_per_device"]
    for ndev in ndevs:
        out[str(ndev)]["modelled_speedup"] = (
            base / max(out[str(ndev)]["flops_per_program_per_device"], 1.0))
    return out


def _bench(ndevs, fast: bool) -> dict:
    import jax

    steps = 8 if fast else 32
    doc = {
        "device_counts": list(ndevs),
        "backend_devices": jax.device_count(),
        "fast": fast,
        "train": _bench_train(ndevs, steps=steps,
                              batch_size=8 if fast else 16),
        "plan": _bench_plan(ndevs, n_programs=32 if fast else 128,
                            points=64, dim=16, max_batch=4 if fast else 8),
        "grad_compress": _bench_grad_compress(max(ndevs)),
    }
    top = str(max(ndevs))
    doc["headline"] = {
        "train_modelled_speedup": doc["train"][top]["modelled_speedup"],
        "plan_modelled_speedup": doc["plan"][top]["modelled_speedup"],
        "warm_recompiles": max(v["warm_recompiles"]
                               for v in doc["plan"].values()),
        "grad_compress_bytes_reduction":
            doc["grad_compress"]["bytes_reduction"],
    }
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma list of simulated device counts")
    args = ap.parse_args()
    ndevs = sorted({int(d) for d in args.devices.split(",")})
    if args.smoke:
        ndevs = [d for d in ndevs if d in (min(ndevs), max(ndevs))]

    # the forced-host-device flag only works BEFORE jax initializes
    if "jax" in sys.modules:
        import jax

        if jax.device_count() < max(ndevs):
            raise SystemExit(
                f"jax already initialized with {jax.device_count()} "
                f"device(s); run this module in a fresh process")
    else:
        os.environ["XLA_FLAGS"] = " ".join(
            p for p in [os.environ.get("XLA_FLAGS", ""),
                        f"{FORCE_FLAG}={max(ndevs)}"] if p)

    doc = _bench(ndevs, fast=args.smoke)

    from benchmarks.common import save_results

    save_results("scaleout", doc)
    with open(BENCH_PATH, "w") as f:
        json.dump(doc, f, indent=1)
    h = doc["headline"]
    print(f"[scaleout] modelled @ {max(ndevs)} devices: "
          f"train {h['train_modelled_speedup']:.2f}x, "
          f"plan {h['plan_modelled_speedup']:.2f}x, "
          f"warm recompiles {h['warm_recompiles']}, "
          f"grad-compress bytes {h['grad_compress_bytes_reduction']:.2f}x "
          f"-> {BENCH_PATH}", flush=True)


if __name__ == "__main__":
    main()
