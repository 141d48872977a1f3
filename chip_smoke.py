"""On-chip smoke run of the GCL-Sampler main path, in one process.

    python chip_smoke.py             # phases A-C on one TPU chip
    python chip_smoke.py --chips 4   # the sharded fit + plan dispatch only

Phase A fits the contrastive RGCN encoder on ``cfd`` (the paper's largest
program, 2,425 invocations, streaming ingestion), embeds it, plans it
through the compiled K-sweep and evaluates the plan (eq. 5 error, eq. 6
speedup).  The plain reference is the sequential ``select_k_and_cluster``
on the same embeddings: K and labels must be identical.

Phase B serves plans from a warmed ``PlanService``: ``submit`` requests at
real size (cfd, lud) and one tenant ``submit_program`` (nw) that replays
phase A's encoder from the artifact store.  Every future resolves, every
plan equals the sequential reference, and the warm pool builds nothing.

Phase C runs the Pallas kernels compiled: one encode micro-batch through
``rgcn_fused`` and phase A's sweep through ``kmeans_assign`` /
``silhouette_sums``, each against the jnp path on the same chip.

``--chips 4`` fits on a 4-device data mesh against one device (per-step
losses within ``SHARDED_LOSS_RTOL``) and runs the sharded plan dispatch
(``data_devices=4``) against ``data_devices=1`` (identical K and labels).

The device gate runs first: without a TPU the script exits non-zero and
prints no result.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Earlier lines carry compile/wall seconds and cache counts; they are
informational, not benchmarks.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

#: rgcn_fused vs its jnp oracle, f32: the tolerance of
#: tests/test_kernel_parity.py (atol = rtol = 1e-4)
ENCODE_TOL = 1e-4
#: sharded vs one-device fit, relative per-step InfoNCE loss.  The mesh
#: sums per-device partial gradients in another order, and the TPU's
#: default f32 matmul rounds its operands to bf16 (8-bit mantissa): a
#: last-bit difference that flips one operand's rounding moves a product
#: by up to 2^-8 (0.4%), and the Adam steps carry it forward.  1e-2 allows
#: a few such roundings; a real sharding bug (a wrong or lost shard, a
#: missing cross-device reduction) moves the loss by far more.
SHARDED_LOSS_RTOL = 1e-2


@dataclass(frozen=True)
class Sizes:
    """Program and model sizes of a run.  The defaults are the real ones:
    the paper's RGCN widths (64, 128, 128, 256), 2 bases, default trace
    caps, k_max 48 and 50 Lloyd steps."""
    program: str = "cfd"
    serve_programs: tuple = ("nw", "lud")   # (submit_program, submit)
    steps: int = 20
    batch_size: int = 16
    k_max: int = 48
    iters: int = 50
    cap_instr: int | None = None
    encode_graphs: int = 400                # graphs scanned for phase C
    shard_program: str = "nw"
    shard_steps: int = 8
    shard_requests: int = 8


class Checks:
    """Collects pass/fail lines; a run passes only if every check did."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"[check] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)
        return ok


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_gate(min_count: int = 1) -> dict:
    """Refuse to run anywhere but on a TPU with compiled Pallas kernels."""
    import jax

    from repro.kernels import default_interpret

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev['platform']} devices")
    if default_interpret():
        raise SystemExit("Pallas would run in interpret mode")
    if dev["count"] < min_count:
        raise SystemExit(f"need {min_count} TPU devices, found {dev['count']}")
    return dev


class CacheCounter:
    """Persistent compilation cache hits/misses, from JAX's own events."""

    def __init__(self):
        import jax

        self.counts = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.counts["misses"] += 1


def _same_plan(check: Checks, name: str, lab, info, ref_lab, ref_info):
    import numpy as np

    ok_k = info["k"] == ref_info["k"]
    ok_lab = np.array_equal(np.asarray(lab), np.asarray(ref_lab))
    check(ok_k and ok_lab,
          f"{name}: K={info['k']} (reference {ref_info['k']}), labels "
          f"{'identical' if ok_lab else 'DIFFER'}")


def _reference(emb, seed: int, sz: Sizes):
    from repro.core.clustering import select_k_and_cluster

    return select_k_and_cluster(emb, k_max=sz.k_max, seed=seed,
                                iters=sz.iters)


def phase_a(root: str, sz: Sizes, check: Checks) -> dict:
    """Fit + embed + plan + evaluate through the gcl method on one program."""
    import numpy as np

    from repro.sampling import ArtifactStore, PlanEngine, evaluate, get_method
    from repro.tracing.programs import get_program

    prog = get_program(sz.program)
    store = ArtifactStore(os.path.join(root, "artifacts"), cache=True)
    method = get_method("gcl", steps=sz.steps, batch_size=sz.batch_size,
                        k_max=sz.k_max, cap_instr=sz.cap_instr)
    t0 = time.perf_counter()
    plan, art = method.run(prog, store=store)
    log(f"A fit+embed+plan {sz.program} ({len(prog)} invocations, "
        f"streaming={art.meta['streaming']}): "
        f"{time.perf_counter() - t0:.1f}s wall incl. compiles; "
        f"train {art.timings['train_s']:.1f}s, "
        f"embed {art.timings['embed_s']:.1f}s, "
        f"val_loss {art.meta['train'].get('val_loss', float('nan')):.4f}")
    emb = np.asarray(art.payload["embeddings"])
    check(emb.shape == (len(prog), method.cfg.rgcn.dims[-1])
          and bool(np.isfinite(emb).all()),
          f"A embeddings {emb.shape} finite")

    seed = method.cfg.train.seed
    engine = PlanEngine(k_max=sz.k_max, iters=sz.iters, seed=seed)
    t0 = time.perf_counter()
    lab, info = engine.cluster_many([emb], errors="raise")[0]
    log(f"A PlanEngine sweep: {time.perf_counter() - t0:.2f}s wall "
        f"(cached executable), stats {engine.stats['dispatches']} dispatch")
    check(engine.stats["fallback_dispatches"] == 0,
          f"A fallback_dispatches={engine.stats['fallback_dispatches']}")
    t0 = time.perf_counter()
    ref_lab, ref_info = _reference(emb, seed, sz)
    log(f"A sequential reference: {time.perf_counter() - t0:.1f}s wall")
    _same_plan(check, "A sweep vs sequential", lab, info, ref_lab, ref_info)
    _same_plan(check, "A method plan vs sequential", plan.labels,
               {"k": plan.num_clusters}, ref_lab, ref_info)

    ev = evaluate(plan, prog, "P1")
    log(f"A plan {sz.program}: K={plan.num_clusters} "
        f"eq5_cycles_error_pct={ev.error_pct['cycles']!r} "
        f"eq6_speedup={ev.speedup!r}")
    check(np.isfinite(ev.error_pct["cycles"]) and ev.speedup >= 1.0,
          "A eq.5 finite and eq.6 >= 1")
    return {"store": store, "method": method, "emb": emb, "seed": seed,
            "labels": lab, "info": info, "ref": (ref_lab, ref_info)}


def phase_b(a: dict, sz: Sizes, check: Checks) -> None:
    """Served path: warm pool, real-size submits, a replaying tenant."""
    import numpy as np

    from repro.core.clustering import bucket_points, engine_stats
    from repro.sampling import PlanRequest, get_method
    from repro.serving import PlanService
    from repro.tracing.programs import get_program

    store, method, seed = a["store"], a["method"], a["seed"]
    tenant_name, submit_name = sz.serve_programs
    big = get_program(sz.program)
    small = get_program(submit_name)
    tenant_prog = get_program(tenant_name)
    small_emb = np.asarray(
        method.run_prepare(small, store).payload["embeddings"])

    def request(prog, emb, s):
        seqs = np.array([k.seq for k in prog.kernels])
        return PlanRequest(emb, seqs, "GCL-Sampler", seed=s)

    requests = [request(big, a["emb"], seed), request(small, small_emb, seed),
                request(small, small_emb, seed + 1)]
    d = a["emb"].shape[1]
    buckets = sorted({(bucket_points(len(p)), d)
                      for p in (big, small, tenant_prog)})
    with PlanService(max_batch=2, max_delay_ms=20.0, k_max=sz.k_max,
                     iters=sz.iters, seed=seed) as svc:
        t0 = time.perf_counter()
        built = svc.warmup(buckets)
        log(f"B warm pool: {built} executables for buckets {buckets} in "
            f"{time.perf_counter() - t0:.1f}s")
        builds0 = engine_stats()["builds"]
        t0 = time.perf_counter()
        futs = [svc.submit(r) for r in requests]
        # a second tenant: adopts phase A's encoder from the store, then
        # embeds its own program with it — no refit
        tenant = get_method("gcl", steps=sz.steps, batch_size=sz.batch_size,
                            k_max=sz.k_max, cap_instr=sz.cap_instr)
        tenant.run_prepare(big, store)
        futs.append(svc.submit_program(tenant, tenant_prog, store=store))
        plans = []
        for f in futs:
            try:
                plans.append(f.result(timeout=600))
            except Exception as e:  # report every future, then fail
                check(False, f"B future raised {type(e).__name__}: {e}")
                plans.append(None)
        log(f"B served {len(futs)} plans in {time.perf_counter() - t0:.1f}s "
            f"wall")
        warm_builds = engine_stats()["builds"] - builds0
        stats = svc.stats()
    log(f"B builds while warm: {warm_builds}; flushes "
        f"{stats['flush_causes']}; mean batch {stats['mean_batch']}")
    check(warm_builds == 0, f"B builds while warm = {warm_builds}")
    check(stats["engine"]["fallback_dispatches"] == 0,
          f"B fallback_dispatches="
          f"{stats['engine']['fallback_dispatches']}")
    check(stats["failed"] == 0, f"B failed futures = {stats['failed']}")

    tenant_art = store.load("gcl", tenant.artifact_key(tenant_prog))
    replayed = (tenant_art is not None
                and tenant_art.meta.get("encoder_reused") is True
                and "train" not in tenant_art.meta)
    check(replayed, f"B submit_program({tenant_name}) replayed the "
                    f"encoder (no refit)")
    embs = [a["emb"], small_emb, small_emb,
            None if tenant_art is None
            else np.asarray(tenant_art.payload["embeddings"])]
    seeds = [seed, seed, seed + 1, seed]
    names = [sz.program, submit_name, f"{submit_name} seed+1", tenant_name]
    for name, plan, emb, s in zip(names, plans, embs, seeds):
        if plan is None or emb is None:
            continue
        ref_lab, ref_info = (a["ref"] if emb is a["emb"] and s == seed
                             else _reference(emb, s, sz))
        _same_plan(check, f"B served {name}", plan.labels,
                   {"k": plan.num_clusters}, ref_lab, ref_info)


def phase_c(a: dict, sz: Sizes, check: Checks) -> None:
    """The Pallas kernels, compiled, against the jnp path on the chip."""
    import dataclasses
    import itertools

    import jax
    import numpy as np

    from repro.core import rgcn as rgcn_mod
    from repro.core.batching import (
        MAX_EDGES_PER_MICROBATCH, MAX_NODES_PER_MICROBATCH, bucket_size,
        pack_graphs, plan_microbatches,
    )
    from repro.sampling import PlanEngine
    from repro.tracing.programs import get_program

    method = a["method"]
    rc = method.cfg.rgcn
    rc_pallas = dataclasses.replace(rc, use_pallas=True)
    graphs = list(itertools.islice(
        method.sampler.iter_graphs(get_program(sz.program)),
        sz.encode_graphs))
    bins = plan_microbatches(graphs)
    sel = max(bins, key=lambda b: sum(graphs[i].n_nodes for i in b))
    batch, _ = pack_graphs(
        [graphs[i] for i in sel], pad_graphs_to=bucket_size(len(sel), 8),
        max_nodes_per_graph=MAX_NODES_PER_MICROBATCH,
        max_edges_per_graph=MAX_EDGES_PER_MICROBATCH)
    batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
    params = method.sampler.params

    def encode(cfg):
        fn = jax.jit(lambda p, b: rgcn_mod.encode_packed(p, cfg, b))
        t0 = time.perf_counter()
        z = np.asarray(fn(params, batch))
        return z, time.perf_counter() - t0

    log(f"C encode micro-batch: {len(sel)} graphs, "
        f"{batch['node_mask'].shape[0]} node slots, "
        f"{batch['edge_src'].shape[0]} edge slots")
    for precision in ("default", "highest"):
        with jax.default_matmul_precision(precision):
            z_ref, t_ref = encode(rc)
            z_pal, t_pal = encode(rc_pallas)
        diff = float(np.max(np.abs(z_pal - z_ref)))
        within = bool(np.all(np.abs(z_pal - z_ref)
                             <= ENCODE_TOL + ENCODE_TOL * np.abs(z_ref)))
        log(f"C encode matmul precision={precision}: max|pallas - jnp| = "
            f"{diff!r} (jnp {t_ref:.1f}s, pallas {t_pal:.1f}s incl. "
            f"compile)")
        if precision == "highest":
            # the parity tolerance is for f32 arithmetic, which the CPU
            # tests get by default and the TPU only at 'highest'
            check(within and bool(np.isfinite(z_pal).all()),
                  f"C rgcn_fused encode within {ENCODE_TOL} of jnp")

    def sweep(use_pallas):
        engine = PlanEngine(k_max=sz.k_max, iters=sz.iters, seed=a["seed"],
                            use_pallas=use_pallas)
        t0 = time.perf_counter()
        lab, info = engine.cluster_many([a["emb"]], errors="raise")[0]
        return (lab, info, time.perf_counter() - t0,
                engine.stats["fallback_dispatches"])

    for precision in ("default", "highest"):
        with jax.default_matmul_precision(precision):
            lab_j, info_j, t_j, fb_j = sweep(False)
            lab_p, info_p, t_p, fb_p = sweep(True)
        check(fb_j + fb_p == 0,
              f"C fallback_dispatches={fb_j + fb_p} at {precision} precision")
        same = (info_j["k"] == info_p["k"]
                and np.array_equal(np.asarray(lab_j), np.asarray(lab_p)))
        log(f"C sweep matmul precision={precision}: Pallas K={info_p['k']}, "
            f"jnp K={info_j['k']}, labels "
            f"{'identical' if same else 'DIFFER'} (jnp {t_j:.1f}s, "
            f"Pallas {t_p:.1f}s incl. compile)")
        if precision == "highest":
            _same_plan(check, "C Pallas sweep vs jnp sweep", lab_p, info_p,
                       lab_j, info_j)


def phase_sharded(root: str, sz: Sizes, check: Checks, n_dev: int) -> None:
    """Sharded fit and sharded plan dispatch against one device."""
    import jax
    import numpy as np

    from repro.core.batching import pack_graphs
    from repro.core.clustering import _shard_args
    from repro.core.rgcn import RGCNConfig
    from repro.core.sampler import GCLSampler, GCLSamplerConfig
    from repro.core.train import ContrastiveTrainer, GCLTrainConfig
    from repro.distributed.sharding import shard_batch_put
    from repro.launch.mesh import make_data_mesh
    from repro.sampling import PlanEngine
    from repro.tracing.programs import get_program

    sampler = GCLSampler(GCLSamplerConfig(cap_instr=sz.cap_instr))
    graphs = sampler.build_graphs(get_program(sz.shard_program))
    graphs = graphs[:sampler.cfg.train_subsample]
    tc = GCLTrainConfig(steps=sz.shard_steps, batch_size=sz.batch_size)
    rc = RGCNConfig()
    rules = make_data_mesh(n_dev)
    t0 = time.perf_counter()
    p1, i1 = ContrastiveTrainer(rc, tc).fit(graphs)
    t1 = time.perf_counter()
    pn, i_n = ContrastiveTrainer(rc, tc, mesh_rules=rules).fit(graphs)
    t2 = time.perf_counter()
    l1 = np.array([h["loss"] for h in i1["history"]])
    ln = np.array([h["loss"] for h in i_n["history"]])
    rel = float(np.max(np.abs(ln - l1) / np.abs(l1)))
    log(f"S fit {sz.shard_program}: 1 device {t1 - t0:.1f}s, {n_dev} "
        f"devices {t2 - t1:.1f}s (incl. compiles); losses 1-dev "
        f"{l1.round(4).tolist()} vs {n_dev}-dev {ln.round(4).tolist()}")
    check(i_n["data_shards"] == n_dev and len(l1) == len(ln)
          and rel <= SHARDED_LOSS_RTOL,
          f"S sharded fit losses within rtol {SHARDED_LOSS_RTOL} "
          f"(max rel diff {rel!r})")
    staged = shard_batch_put(pack_graphs(graphs[:sz.batch_size])[0], rules)
    placed = sorted((s.index[0].start or 0, s.device.id)
                    for s in staged["node_mask"].addressable_shards)
    log(f"S fit batch shards (first node, device id): {placed}")
    param_devs = sorted({d.id for leaf in jax.tree_util.tree_leaves(pn)
                         for d in leaf.sharding.device_set})
    log(f"S sharded fit params live on devices {param_devs}")
    check(len({dev for _, dev in placed}) == n_dev,
          f"S fit batch spread over {n_dev} distinct devices")

    emb = sampler.trainer.embed(p1, graphs)
    embs = [emb] * sz.shard_requests
    seeds = list(range(sz.shard_requests))
    eng_n = PlanEngine(k_max=sz.k_max, iters=sz.iters, max_batch=2,
                       data_devices=n_dev)
    eng_1 = PlanEngine(k_max=sz.k_max, iters=sz.iters,
                       max_batch=sz.shard_requests, data_devices=1)
    t0 = time.perf_counter()
    sharded = eng_n.cluster_many(embs, seeds, errors="raise")
    t1 = time.perf_counter()
    single = eng_1.cluster_many(embs, seeds, errors="raise")
    t2 = time.perf_counter()
    log(f"S plan dispatch {sz.shard_requests} programs: {n_dev} devices "
        f"{t1 - t0:.1f}s, 1 device {t2 - t1:.1f}s (incl. compiles); "
        f"shards {eng_n.engine_stats()['data_shards']}")
    for i, ((lab, info), (lab1, info1)) in enumerate(zip(sharded, single)):
        _same_plan(check, f"S sharded plan {i}", lab, info, lab1, info1)
    check(eng_n.stats["dispatches"] == 1
          and eng_n.stats["fallback_dispatches"] == 0,
          f"S one sharded dispatch, no fallback ({eng_n.stats})")
    stacked = np.zeros((sz.shard_requests,) + emb.shape, np.float32)
    (arg,) = _shard_args((stacked,), n_dev)
    placed = sorted((s.index[0].start or 0, s.device.id)
                    for s in arg.addressable_shards)
    log(f"S plan program-axis shards (first program, device id): {placed}")
    check(len({dev for _, dev in placed}) == n_dev,
          f"S plan programs spread over {n_dev} distinct devices")


def run(chips: int, sz: Sizes = Sizes()) -> dict:
    """Gate, then the phases; returns the device record of a passing run
    and raises on any failed check."""
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = device_gate(chips)
    cache = CacheCounter()
    log(f"compile cache dir {cache_dir}")
    check = Checks()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        if chips > 1:
            phase_sharded(root, sz, check, chips)
        else:
            a = phase_a(root, sz, check)
            phase_b(a, sz, check)
            phase_c(a, sz, check)
    log(f"total {time.perf_counter() - t0:.1f}s; compile cache "
        f"{cache.counts}")
    if check.failed:
        raise RuntimeError(f"{len(check.failed)} check(s) failed: "
                           f"{check.failed}")
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded fit + plan dispatch")
    args = ap.parse_args(argv)
    try:
        dev = run(args.chips)
    except (Exception, SystemExit) as e:  # no result line, non-zero exit
        if not isinstance(e, SystemExit):
            traceback.print_exc()
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
