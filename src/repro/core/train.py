"""Distributed contrastive trainer for the RGCN encoder (paper §3.3, §4).

Training config mirrors the paper: AdamW, lr 7e-4 with cosine annealing,
temperature tau=0.05, 80/20 train/validation split of the program's kernels.

Batching: graphs are PACKED (core/batching.py) — one flat node/edge array per
batch with segment ids, padded to power-of-two size buckets, so jit
recompilation is bounded by the bucket count and no kernel pays for the
batch-wide max size.  The dense `pad_batch` path is kept as `embed_dense`
for parity tests and the batching benchmark baseline.

Engine (DESIGN.md §4): the default ``engine='scan'`` pre-packs the whole
epoch on the host (`core.batching.plan_epoch`), stages each same-bucket
segment to the device once, and drives training with fixed-length
`jax.lax.scan` chunks — donated `TrainState`, fold-in per-step RNG, per-step
metrics accumulated on device and pulled to the host only at ``log_every``
boundaries.  Compiled chunk executables are shared process-wide (keyed on
the model/optimizer config), so repeated fits pay zero recompiles.  Host
pack/upload staging for chunk i+1 (and the next embed micro-batch) is
double-buffered behind the device's work on chunk i (`_OneAhead`,
DESIGN.md §12) — pure pipelining, bit-exact vs ``prefetch=False``.  The
pre-engine per-step Python loop survives as ``engine='python'``, a parity
shim for tests and the benchmark baseline: it packs, uploads and syncs every
step and re-jits per fit, exactly like the seed trainer.

Resume (DESIGN.md §6): with ``checkpoint_dir`` the scan engine snapshots
(TrainState, base RNG key, metrics history, step cursor) every
``checkpoint_every`` steps through `repro.checkpoint.CheckpointManager`; an
interrupted fit restarted with the same config replays the deterministic
epoch plan and continues from the cursor BIT-EXACTLY (a chunk's padded and
already-done steps are passed through by a branch, so chunk boundaries never
change the math).

Distribution: batches shard over the mesh's batch axes (the packed
node/edge/graph axes carry the 'batch' logical name — see
`distributed.sharding.constrain_batch`); the InfoNCE logits matrix
z1 @ z2^T makes GSPMD all-gather the projected embeddings — global
negatives across data shards (SimCLR-at-scale adaptation, DESIGN.md §3).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.checkpoint.manager import CheckpointManager
from repro.config import TrainConfig
from repro.core import rgcn as rgcn_mod
from repro.core.augment import augment_view, augment_view_packed
from repro.core.batching import (
    MAX_EDGES_PER_MICROBATCH, MAX_NODES_PER_MICROBATCH, bucket_key,
    bucket_size, graph_content_hash, pack_graphs, plan_epoch,
    plan_microbatches, stream_bins,
)
from repro.core.contrastive import info_nce
from repro.core.graphs import KernelGraph, pad_batch
from repro.core.rgcn import RGCNConfig
from repro.distributed.fault import DeviceLost, Watchdog
from repro.distributed.sharding import (
    MeshRules, constrain_batch, set_mesh_rules, shard_batch_put,
)
from repro.optim import TrainState, adamw_init, apply_gradients

#: fixed metric layout of a training step (the scan emits them as one
#: (chunk, len(METRIC_KEYS)) device array; checkpoints store one column per key)
METRIC_KEYS = ("loss", "nce_acc", "pos_sim", "neg_sim", "lr", "grad_norm")


class FitInterrupted(RuntimeError):
    """Raised by ``fit(interrupt_after=k)`` right after the checkpoint at the
    first chunk boundary >= k — the hook tests/CI use to simulate a killed
    training job without killing the process."""


#: what ``_OneAhead._next`` returns for an exhausted source
_END = object()


class _OneAhead:
    """One-slot host->device staging pipeline (DESIGN.md §12).

    Wraps an iterable of work items and a ``stage`` callable (host pack +
    ``device_put``); iterating yields ``(item, staged)`` pairs where item
    i+1's staging runs on a single background thread WHILE the caller
    consumes item i — jax dispatch is async, so the device crunches chunk i
    while the host packs chunk i+1.  Items are staged strictly in order on
    one worker, so the staged arrays, their order, and any rng-key
    derivation are identical to inline staging: pure pipelining, bit-exact
    trajectories.  Staged batches are never donated (only TrainState is),
    so a prefetched buffer can never be invalidated by the running chunk.

    ``enabled=False`` degrades to inline staging (the parity baseline);
    ``stage_s`` (host seconds spent staging) and ``wait_s`` (main-thread
    seconds blocked waiting for a stage) quantify the overlap:
    ``overlap_fraction = 1 - wait_s / stage_s``.  Each stage is a
    ``<span>.stage`` span, each wait a ``<span>.wait`` span, and each pull
    from ``items`` (a lazy source's own work, such as an ingest pass) a
    ``<span>.next`` span (repro.telemetry); the staging thread carries
    the caller's span context.

    ``depth=k`` keeps up to k staged items queued ahead of the consumer
    (still ONE worker thread, so items stage strictly in submission order
    and bit-exactness is preserved); the default k=1 is the PR 9
    behaviour, while the ingestion pipeline runs deeper so a slow trace
    upstream can't starve the device (DESIGN.md §13).  Peak staged
    residency is bounded by ``depth + 1``.
    """

    def __init__(self, stage, items, *, span: str, enabled: bool = True,
                 depth: int = 1):
        self._stage = stage
        self._items = items
        self._stage_span = f"{span}.stage"
        self._wait_span = f"{span}.wait"
        self._next_span = f"{span}.next"
        self.enabled = bool(enabled)
        self.depth = max(1, int(depth))
        self.stage_s = 0.0
        self.wait_s = 0.0

    def _timed_stage(self, item):
        with telemetry.span(self._stage_span, timed=True) as s:
            staged = self._stage(item)
        self.stage_s += s.seconds
        return staged

    def _next(self, it):
        """The next item of ``it``, or ``_END`` when it is exhausted."""
        with telemetry.span(self._next_span):
            return next(it, _END)

    @property
    def overlap_fraction(self) -> float:
        if self.stage_s <= 0.0:
            return 0.0
        return max(0.0, 1.0 - self.wait_s / self.stage_s)

    def __iter__(self):
        it = iter(self._items)
        if not self.enabled:
            # inline staging: all staging time is wait time
            while (item := self._next(it)) is not _END:
                staged = self._timed_stage(item)
                self.wait_s = self.stage_s
                yield item, staged
            return
        from concurrent.futures import ThreadPoolExecutor

        pool = ThreadPoolExecutor(max_workers=1,
                                  thread_name_prefix="stage-prefetch")
        try:
            def task():
                item = self._next(it)
                if item is _END:
                    return None
                return item, self._timed_stage(item)

            from collections import deque

            q = deque(pool.submit(telemetry.carry(task))
                      for _ in range(self.depth))
            while True:
                with telemetry.span(self._wait_span, timed=True) as w:
                    res = q.popleft().result()
                self.wait_s += w.seconds
                if res is None:
                    return
                # refill the look-ahead window
                q.append(pool.submit(telemetry.carry(task)))
                yield res
        finally:
            pool.shutdown(wait=True)


@dataclass(frozen=True)
class GCLTrainConfig:
    steps: int = 120
    batch_size: int = 16
    tau: float = 0.05
    val_fraction: float = 0.2
    log_every: int = 50
    seed: int = 0
    #: 'scan' = compiled device-resident epochs (default);
    #: 'python' = the pre-engine per-step loop, kept as a parity shim
    engine: str = "scan"
    #: scan chunk length (fixed per fit: chunks shorter than this are padded
    #: with dead steps that a branch passes through at no step compute, so
    #: ONE executable per bucket serves any step count).  Effective length
    #: is min(scan_chunk, next_pow2(steps)).
    scan_chunk: int = 32
    #: snapshot (state, rng, history, cursor) every N steps (0 = off;
    #: scan engine only) — cadence is rounded up to chunk boundaries
    checkpoint_every: int = 0
    #: validation eval key = fold_in(PRNGKey(seed), eval_fold): seed-derived
    #: and deterministic, disjoint from the per-step fold_in(base_key, i)
    #: stream (was a hard-coded PRNGKey(123) before the linter's R3)
    eval_fold: int = 123
    #: double-buffered host->device staging (DESIGN.md §12): while the device
    #: runs scan chunk i / embed micro-batch i, a background thread packs and
    #: `device_put`s i+1.  Pure pipelining — the staged arrays, their order,
    #: and the fold-in key stream are identical, so trajectories are
    #: bit-exact vs ``prefetch=False`` (asserted by tests/test_train_engine).
    prefetch: bool = True
    #: staged look-ahead window (k slots on ONE worker — order and bits
    #: unchanged).  >1 lets a deep trace->pack->device pipeline ride out
    #: jittery upstream ingestion (DESIGN.md §13).
    prefetch_depth: int = 1
    opt: TrainConfig = field(
        default_factory=lambda: TrainConfig(
            learning_rate=7e-4, weight_decay=0.01, warmup_steps=20,
            total_steps=120, schedule="cosine", grad_clip=1.0,
        )
    )


# ---------------------------------------------------------------------------
# Loss (shared by both engines so they cannot diverge mathematically)
# ---------------------------------------------------------------------------


def packed_loss(params, rc: RGCNConfig, tau: float, batch, rng, *,
                train: bool = True):
    """Packed-batch InfoNCE.  The graph axis is exact (G == batch size), so
    the logits matrix never sees padding graphs.

    ``train=True``: stochastic augs + feature-noise gates, dropout on.
    ``train=False`` (validation): augmentations drawn from the CALLER'S rng
    (pass a fixed key for deterministic "fixed augs"), no feature noise, no
    dropout — the eval-mode path `fit` uses for ``val_loss``/``val_acc``.
    """
    if train:
        r1, r2, rp1, rp2 = jax.random.split(rng, 4)
        v1, noise1 = augment_view_packed(r1, batch)
        v2, noise2 = augment_view_packed(r2, batch)
        z1 = rgcn_mod.encode_packed(params, rc, v1, rng=r1, train=True,
                                    noise_gate=noise1)
        z2 = rgcn_mod.encode_packed(params, rc, v2, rng=r2, train=True,
                                    noise_gate=noise2)
        p1 = rgcn_mod.project(params, rc, z1, rng=rp1, train=True)
        p2 = rgcn_mod.project(params, rc, z2, rng=rp2, train=True)
    else:
        r1, r2 = jax.random.split(rng)
        v1, _ = augment_view_packed(r1, batch)
        v2, _ = augment_view_packed(r2, batch)
        z1 = rgcn_mod.encode_packed(params, rc, v1)
        z2 = rgcn_mod.encode_packed(params, rc, v2)
        p1 = rgcn_mod.project(params, rc, z1)
        p2 = rgcn_mod.project(params, rc, z2)
    return info_nce(p1, p2, tau)


class EngineFns(NamedTuple):
    """Compiled training-engine entry points (one cache entry per
    (RGCNConfig, TrainConfig, tau, MeshRules) — shared across trainer
    instances and fits, so refits never recompile)."""
    scan: callable     # jit (state, stacked batch, keys, live) -> (state, ys)
    step: callable     # UNJITTED single step (the python shim jits per fit)
    eval_loss: callable  # jit (params, batch, rng) -> (loss, metrics)


@functools.lru_cache(maxsize=64)
def _engine_fns(rc: RGCNConfig, opt: TrainConfig, tau: float,
                rules: Optional[MeshRules]) -> EngineFns:
    scale = rc.policy.loss_scale

    def step(state: TrainState, batch, rng):
        batch = constrain_batch(batch, rules)

        def lossf(p):
            loss, metrics = packed_loss(p, rc, tau, batch, rng, train=True)
            # loss-scale hook (precision policy): differentiate the scaled
            # loss; adamw_update unscales via opt.loss_scale.  scale == 1.0
            # multiplies by exactly 1.0 — bit-neutral.
            return loss * scale, (loss, metrics)

        (_, (loss, metrics)), grads = jax.value_and_grad(
            lossf, has_aux=True)(state.params)
        state, opt_metrics = apply_gradients(state, grads, opt)
        return state, dict(metrics, loss=loss, **opt_metrics)

    def chunk(state: TrainState, stacked, keys, live):
        """One fixed-length scan segment.  `live` flags the real steps; a
        padded / already-done step takes the other branch of a
        ``lax.cond``, which passes the state through and emits a zero
        metrics row (dropped on the host), so it costs no step compute.
        A live step is the same step on the same rows with the same key
        wherever it sits in a chunk, which makes chunk boundaries — and
        hence resume points — bit-neutral."""

        def body(st, xs):
            batch, k, lv = xs

            def run(st):
                st, m = step(st, batch, k)
                return st, jnp.stack([m[x] for x in METRIC_KEYS])

            def skip(st):
                return st, jnp.zeros(len(METRIC_KEYS), jnp.float32)

            return jax.lax.cond(lv, run, skip, st)

        return jax.lax.scan(body, state, (stacked, keys, live))

    return EngineFns(
        scan=jax.jit(chunk, donate_argnums=(0,)),
        step=step,
        eval_loss=jax.jit(
            lambda p, b, r: packed_loss(p, rc, tau, b, r, train=False)),
    )


class ContrastiveTrainer:
    def __init__(self, rc: RGCNConfig, tc: GCLTrainConfig,
                 mesh_rules: Optional[MeshRules] = None):
        self.rc = rc
        self.tc = tc
        self.mesh_rules = mesh_rules
        self._embed_fn = None          # packed jit'd encode
        self._embed_fn_dense = None    # dense-path jit cache (per max_warps)
        self._embed_cache: dict[str, np.ndarray] = {}
        self._embed_cache_fp: Optional[str] = None
        # LRU-evicted above this many entries: cache hits move the entry to
        # the dict's insertion-order tail, so eviction pops the least
        # recently USED key, not merely the oldest inserted
        self.embed_cache_max = 65536
        self.embed_stats: dict = {}

    # -- loss ---------------------------------------------------------------
    @property
    def _opt(self) -> TrainConfig:
        """Optimizer config with the precision policy's loss scale threaded
        through.  The policy is the ONE source of truth for this trainer —
        a conflicting explicit `opt.loss_scale` is rejected rather than
        silently overridden."""
        if self.tc.opt.loss_scale == self.rc.policy.loss_scale:
            return self.tc.opt
        if self.tc.opt.loss_scale != 1.0:
            raise ValueError(
                f"conflicting loss scales: TrainConfig.loss_scale="
                f"{self.tc.opt.loss_scale} vs policy.loss_scale="
                f"{self.rc.policy.loss_scale}; set it on the precision "
                f"policy (RGCNConfig.policy) only")
        return dataclasses.replace(
            self.tc.opt, loss_scale=self.rc.policy.loss_scale)

    def _engine(self) -> EngineFns:
        return _engine_fns(self.rc, self._opt, self.tc.tau, self.mesh_rules)

    def _loss(self, params, batch, max_warps, rng):
        """Dense-batch InfoNCE (kept for parity tests / benchmarks)."""
        r1, r2, rp1, rp2 = jax.random.split(rng, 4)
        v1, noise1 = augment_view(r1, batch)
        v2, noise2 = augment_view(r2, batch)
        z1 = rgcn_mod.encode(params, self.rc, v1, max_warps, rng=r1,
                             train=True, noise_gate=noise1)
        z2 = rgcn_mod.encode(params, self.rc, v2, max_warps, rng=r2,
                             train=True, noise_gate=noise2)
        p1 = rgcn_mod.project(params, self.rc, z1, rng=rp1, train=True)
        p2 = rgcn_mod.project(params, self.rc, z2, rng=rp2, train=True)
        return info_nce(p1, p2, self.tc.tau)

    def _loss_packed(self, params, batch, rng, *, train=True):
        """Back-compat wrapper over the module-level `packed_loss`."""
        return packed_loss(params, self.rc, self.tc.tau, batch, rng,
                           train=train)

    def _make_step(self, max_warps=None):
        """Seed-faithful per-fit jit of one training step (the python shim's
        executable; `max_warps` is accepted for old callers and ignored).
        A FRESH closure is built per call — like the seed trainer, every fit
        re-traces and re-compiles (jax would otherwise reuse the executable
        cached on the shared engine callable, which is exactly the
        amortization the scan engine claims and the baseline must not get)."""
        raw = self._engine().step

        def step(state, batch, rng):
            return raw(state, batch, rng)

        # lint: allow[R2] parity shim re-jits per fit by design (see above)
        return jax.jit(step, donate_argnums=(0,))

    # -- data ---------------------------------------------------------------
    @staticmethod
    def prepad(graphs: list[KernelGraph], pad_to=None):
        """Dense-batch compatibility shim (see core/graphs.pad_batch)."""
        batch, max_warps = pad_batch(graphs, *(pad_to or (None, None, None)))
        return batch, max_warps

    # -- fit -----------------------------------------------------------------
    def fit(self, graphs: list[KernelGraph], verbose=False, *,
            checkpoint_dir: Optional[str] = None, resume: bool = True,
            interrupt_after: Optional[int] = None,
            fault_hook: Optional[callable] = None,
            watchdog: Optional[Watchdog] = None):
        """Train on an 80/20 split of the program's kernels; returns
        (params, info).

        ``checkpoint_dir`` (scan engine only) enables the resume protocol:
        snapshots every ``tc.checkpoint_every`` steps; when the directory
        already holds a snapshot and ``resume`` is True, training continues
        from its cursor instead of refitting.  ``interrupt_after=k`` raises
        :class:`FitInterrupted` after the checkpoint at the first chunk
        boundary >= k (test/CI hook).

        Scale-out fault protocol (scan engine only, DESIGN.md §11):
        ``fault_hook(done_step)`` runs at every chunk boundary and may raise
        :class:`repro.distributed.fault.DeviceLost` (injection hook for
        fault tests and real lost-participant detectors); a ``watchdog``
        brackets each chunk with step_start/step_end and converts a fired
        straggler SLO into DeviceLost at the SAME boundary.  Either way the
        engine checkpoints at the boundary before re-raising, so
        :func:`fit_resilient` can shrink the mesh and resume — losing at
        most the current chunk, never the fit.
        """
        with telemetry.span("train.fit", root=True):
            return self._fit(graphs, verbose, checkpoint_dir, resume,
                             interrupt_after, fault_hook, watchdog)

    def _fit(self, graphs, verbose, checkpoint_dir, resume,
             interrupt_after, fault_hook, watchdog):
        tc, rc = self.tc, self.rc
        rng_np = np.random.default_rng(tc.seed)
        n = len(graphs)
        perm = rng_np.permutation(n)
        n_val = max(1, int(n * tc.val_fraction)) if n >= 5 else 0
        train_idx = perm[n_val:] if n_val else perm
        val_idx = perm[:n_val]

        key = jax.random.PRNGKey(tc.seed)
        base_key, k_init = jax.random.split(key)
        params = rgcn_mod.init_rgcn(k_init, rc)
        state = adamw_init(params, self._opt)

        # the whole epoch's batch selections, drawn up front with the SAME
        # rng stream the per-step loop used — deterministic given the seed,
        # which is what makes the resume replay exact
        bs = min(tc.batch_size, len(train_idx))
        selections = np.stack([
            train_idx[rng_np.choice(len(train_idx), size=bs,
                                    replace=len(train_idx) < bs)]
            for _ in range(tc.steps)
        ]) if tc.steps else np.zeros((0, bs), np.int64)

        # per-graph caps bound each graph's footprint (and the bucket blowup
        # a pathological graph would cause); with use_pallas the WHOLE batch
        # (~batch_size * graph size) must additionally fit the flat kernel's
        # VMEM budget — size tc.batch_size accordingly (see rgcn_spmm_flat)
        caps = dict(
            max_nodes_per_graph=MAX_NODES_PER_MICROBATCH,
            max_edges_per_graph=MAX_EDGES_PER_MICROBATCH,
        )

        ctx = set_mesh_rules(self.mesh_rules) if self.mesh_rules else None
        if ctx:
            ctx.__enter__()
        try:
            if tc.engine == "python":
                if checkpoint_dir is not None:
                    raise ValueError(
                        "checkpointing requires engine='scan' (the python "
                        "path is a parity shim)")
                if fault_hook is not None or watchdog is not None:
                    raise ValueError(
                        "the fault protocol (fault_hook/watchdog) requires "
                        "engine='scan' — degradation resumes from chunk-"
                        "boundary checkpoints the python shim never writes")
                state, info = self._fit_python(
                    graphs, selections, state, base_key, caps, verbose)
            elif tc.engine == "scan":
                state, info = self._fit_scan(
                    graphs, selections, state, base_key, caps, verbose,
                    checkpoint_dir=checkpoint_dir, resume=resume,
                    interrupt_after=interrupt_after,
                    fault_hook=fault_hook, watchdog=watchdog)
            else:
                raise ValueError(f"unknown engine {tc.engine!r}")

            # validation InfoNCE — eval mode: no dropout, no feature noise,
            # augmentations drawn from a seed-derived key (deterministic)
            trunc_nodes = info["trunc_nodes"]
            if n_val:
                packed, vmeta = pack_graphs(
                    [graphs[i] for i in val_idx], **caps)
                trunc_nodes += int(vmeta.trunc_nodes.sum())
                vb = {k: jnp.asarray(v) for k, v in packed.items()}
                eval_key = jax.random.fold_in(
                    jax.random.PRNGKey(tc.seed), tc.eval_fold)
                loss, m = self._engine().eval_loss(
                    state.params, vb, eval_key)
                info["val_loss"] = float(loss)
                info["val_acc"] = float(m["nce_acc"])
                info["host_syncs"] += 1
        finally:
            if ctx:
                ctx.__exit__(None, None, None)

        if trunc_nodes:
            import warnings

            warnings.warn(
                f"training packed {trunc_nodes} node(s) over the per-graph "
                f"budget; graphs were truncated (see batching caps)",
                stacklevel=3,  # the caller of fit
            )
        info["trunc_nodes"] = trunc_nodes
        return state.params, info

    # lint: allow[R1] engine="python" parity shim syncs per step by design
    def _fit_python(self, graphs, selections, state, base_key, caps, verbose):
        """The pre-engine per-step loop, preserved as a parity shim and the
        per-step benchmark baseline: packs on the host, uploads, and blocks
        on a device->host metrics sync EVERY step, and re-jits per fit
        (exactly the seed trainer's behavior).  Shares `packed_loss` with the
        scan engine so the two can only differ in execution, not math."""
        tc = self.tc
        step_fn = self._make_step()
        history = []
        bucket_keys = set()
        trunc_nodes = 0
        t0 = time.time()
        for step in range(len(selections)):
            packed, meta = pack_graphs(
                [graphs[i] for i in selections[step]], **caps)
            trunc_nodes += int(meta.trunc_nodes.sum())
            bucket_keys.add(bucket_key(packed))
            batch = {k: jnp.asarray(v) for k, v in packed.items()}
            k_step = jax.random.fold_in(base_key, step)
            state, metrics = step_fn(state, batch, k_step)
            if verbose and (step % tc.log_every == 0 or step == tc.steps - 1):
                m = {k: float(v) for k, v in metrics.items()}
                print(
                    f"  step {step:4d} loss={m['loss']:.4f} "
                    f"acc={m['nce_acc']:.3f} lr={m['lr']:.2e} "
                    f"({time.time() - t0:.1f}s)"
                )
            history.append({k: float(v) for k, v in metrics.items()})
        info = {
            "history": history,
            "bucket_keys": sorted(bucket_keys),
            "step_compiles": _jit_cache_size(step_fn),
            "trunc_nodes": trunc_nodes,
            "engine": "python",
            "host_syncs": len(history),
            "resumed_from": 0,
            "checkpoint_saves": 0,
        }
        return state, info

    def _fit_scan(self, graphs, selections, state, base_key, caps, verbose,
                  *, checkpoint_dir, resume, interrupt_after,
                  fault_hook=None, watchdog=None):
        """Compiled engine: pre-packed epoch plan, per-segment device
        staging (sharded over the mesh's batch axes under MeshRules),
        fixed-length scan chunks whose dead steps are branched past,
        log_every-gated host syncs, chunk-boundary checkpoints.  With
        ``tc.prefetch`` the host side of chunk i+1 (row slicing +
        shard_batch_put + key derivation) rides a background thread behind
        chunk i's async dispatch (_OneAhead) — bit-exact either way."""
        tc = self.tc
        eng = self._engine()
        wd_fired0 = watchdog.fired if watchdog is not None else 0
        with telemetry.span("fit.plan_epoch", steps=len(selections)):
            plan = plan_epoch(graphs, selections, **caps)
        steps = plan.n_steps
        chunk_len = min(tc.scan_chunk, bucket_size(max(steps, 1), 1))

        mgr = CheckpointManager(checkpoint_dir) if checkpoint_dir else None
        start_step = 0
        history: list[dict] = []
        if mgr is not None and resume and mgr.latest_step() is not None:
            state, history, start_step = self._restore_fit(mgr, base_key)

        host_syncs = 0
        saves = 0
        last_save = start_step
        next_log = ((start_step // tc.log_every) + 1) * tc.log_every
        pending: list[tuple] = []   # (ys device array, live bool mask)
        n_chunks = 0
        skipped = 0
        t0 = time.time()

        def flush():
            """Pull all buffered per-step metrics to the host in ONE sync."""
            nonlocal host_syncs
            if not pending:
                return
            host_syncs += 1
            for ys, live in pending:
                vals = np.asarray(ys)
                for j in np.nonzero(live)[0]:
                    history.append(
                        {k: float(vals[j, i])
                         for i, k in enumerate(METRIC_KEYS)})
            pending.clear()
            if verbose and history:
                m = history[-1]
                print(
                    f"  step {len(history) - 1:4d} loss={m['loss']:.4f} "
                    f"acc={m['nce_acc']:.3f} lr={m['lr']:.2e} "
                    f"({time.time() - t0:.1f}s)"
                )

        def chunk_descs():
            for seg in plan.segments:
                for lo in range(seg.start, seg.stop, chunk_len):
                    hi = min(lo + chunk_len, seg.stop)
                    if hi <= start_step:
                        continue
                    yield (seg, lo, hi)

        def stage_chunk(desc):
            """Host side of one chunk: slice + edge-pad the segment rows,
            shard/upload them, and derive the fold-in key stream.  Runs on
            the prefetch thread — deterministic in (desc, base_key), so
            overlap cannot change the math."""
            seg, lo, hi = desc
            r0, r1 = lo - seg.start, hi - seg.start
            with telemetry.span("fit.pack"):
                rows_np = {}
                for f, arr in seg.batches.items():
                    rows = arr[r0:r1]
                    if len(rows) < chunk_len:  # edge-pad dead tail steps
                        pad = np.repeat(rows[-1:], chunk_len - len(rows),
                                        axis=0)
                        rows = np.concatenate([rows, pad], axis=0)
                    rows_np[f] = rows
                # multi-device staging: each device receives only its own
                # shard of the batch axes (leading scan-steps axis stays
                # replicated); plain upload on a 1-device data axis
                stacked = shard_batch_put(rows_np, self.mesh_rules,
                                          leading=1)
            abs_idx = np.arange(lo, lo + chunk_len)
            live = (abs_idx < hi) & (abs_idx >= start_step)
            # eager device ops, which can wait behind the running chunk
            with telemetry.span("fit.keys"):
                keys = jax.vmap(
                    lambda i: jax.random.fold_in(base_key, i)
                )(jnp.asarray(abs_idx))
            return stacked, keys, live

        pipe = _OneAhead(stage_chunk, chunk_descs(), span="fit",
                         enabled=tc.prefetch, depth=tc.prefetch_depth)
        for (_, _, hi), (stacked, keys, live) in pipe:
            n_chunks += 1
            n_live = int(live.sum())
            skipped += chunk_len - n_live
            if watchdog is not None:
                watchdog.step_start()
            # the dispatch only: the chunk runs on after the span closes;
            # the device runs the step body for the live steps alone
            with telemetry.span("fit.chunk", computed=n_live, live=n_live,
                                skipped=chunk_len - n_live):
                state, ys = eng.scan(state, stacked, keys,
                                     jnp.asarray(live))
            pending.append((ys, live))
            if watchdog is not None:
                # SLO timing needs REAL chunk completion — an opt-in
                # sync per chunk, only when a watchdog is armed
                # lint: allow[R1] watchdog SLO measurement is a deliberate per-chunk sync
                jax.block_until_ready(ys)
                watchdog.step_end()

            done = hi
            if done >= next_log or done == steps:
                flush()
                next_log = ((done // tc.log_every) + 1) * tc.log_every
            due = (mgr is not None and tc.checkpoint_every > 0
                   and done - last_save >= tc.checkpoint_every)
            interrupt = (interrupt_after is not None
                         and done >= interrupt_after)
            if due or (interrupt and mgr is not None):
                flush()
                self._save_fit(mgr, state, base_key, history, done)
                last_save = done
                saves += 1
            if interrupt:
                if mgr is not None:
                    mgr.wait()
                raise FitInterrupted(
                    f"fit interrupted at step {done} "
                    f"(interrupt_after={interrupt_after})")
            # fault boundary: a lost/straggling participant surfaces
            # HERE (never mid-chunk) — checkpoint, then let the caller
            # degrade (see fit_resilient)
            lost = None
            if fault_hook is not None:
                try:
                    fault_hook(done)
                except DeviceLost as e:
                    lost = e
            if (lost is None and watchdog is not None
                    and watchdog.fired > wd_fired0):
                lost = DeviceLost(
                    f"chunk ending at step {done} exceeded the "
                    f"watchdog SLO (straggling participant)")
            if lost is not None:
                flush()
                if mgr is not None:
                    if done > last_save:
                        self._save_fit(mgr, state, base_key, history,
                                       done)
                        last_save = done
                        saves += 1
                    mgr.wait()
                raise lost
        flush()

        info = {
            "history": history,
            "bucket_keys": list(plan.bucket_keys),
            "step_compiles": _jit_cache_size(eng.scan),
            "trunc_nodes": plan.trunc_nodes,
            "engine": "scan",
            "host_syncs": host_syncs,
            "resumed_from": start_step,
            "checkpoint_saves": saves,
            "scan_chunks": n_chunks,
            "chunk_len": chunk_len,
            "skipped_steps": skipped,
            "prefetch": pipe.enabled,
            "prefetch_stage_s": pipe.stage_s,
            "prefetch_wait_s": pipe.wait_s,
            "prefetch_overlap": pipe.overlap_fraction,
            "data_shards": (self.mesh_rules.fsdp_size
                            if self.mesh_rules else 1),
        }
        return state, info

    # -- resume protocol -----------------------------------------------------
    @staticmethod
    def _save_fit(mgr: CheckpointManager, state: TrainState, base_key,
                  history: list[dict], cursor: int):
        tree = {
            "state": {
                "step": state.step, "params": state.params,
                "mu": state.mu, "nu": state.nu,
                **({"compress_err": state.compress_err}
                   if state.compress_err is not None else {}),
            },
            "rng": np.asarray(base_key),
            "history": {
                k: np.asarray([h[k] for h in history], np.float32)
                for k in METRIC_KEYS
            },
            "cursor": np.int64(cursor),
        }
        mgr.save(cursor, tree)

    def _restore_fit(self, mgr: CheckpointManager, base_key):
        """Rebuild (TrainState, history, cursor) from the latest snapshot;
        refuses checkpoints from a different seed (the epoch plan would not
        replay)."""
        tree, ck_step = mgr.restore_tree()
        if not np.array_equal(np.asarray(tree["rng"]),
                              np.asarray(base_key)):
            raise ValueError(
                f"checkpoint in {mgr.directory} was written with a "
                f"different seed; pass resume=False to refit")
        sd = tree["state"]
        state = TrainState(
            step=jnp.asarray(sd["step"]),
            params=jax.tree_util.tree_map(jnp.asarray, sd["params"]),
            mu=jax.tree_util.tree_map(jnp.asarray, sd["mu"]),
            nu=jax.tree_util.tree_map(jnp.asarray, sd["nu"]),
            compress_err=(
                jax.tree_util.tree_map(jnp.asarray, sd["compress_err"])
                if "compress_err" in sd else None),
        )
        cursor = int(tree["cursor"])
        hist = tree["history"]
        history = [
            {k: float(hist[k][i]) for k in METRIC_KEYS}
            for i in range(cursor)
        ]
        return state, history, cursor

    # -- inference ----------------------------------------------------------
    def _embed_setup(self, params, n_cap, e_cap):
        """Shared embed prologue: the content cache is valid only for the
        (params, truncation caps) it was built with; the packed encode fn
        is jit'd once."""
        fp = f"{_params_fingerprint(params)}:{n_cap}:{e_cap}"
        if fp != self._embed_cache_fp:
            self._embed_cache.clear()
            self._embed_cache_fp = fp
        if self._embed_fn is None:
            self._embed_fn = jax.jit(
                lambda p, b: rgcn_mod.encode_packed(p, self.rc, b)
            )
        return self._embed_fn

    def _stage_bin(self, bin_graphs, n_cap, e_cap):
        """Pack + upload one micro-batch (the host half of an encode; runs
        on the prefetch thread).  Per-graph caps: a single graph larger
        than the budget is truncated (with accounting) instead of silently
        blowing the bucket past the Pallas kernel's VMEM budget.
        Returns (device batch, PackMeta, bucket key)."""
        with telemetry.span("embed.pack", graphs=len(bin_graphs)) as s:
            packed, meta = pack_graphs(
                bin_graphs,
                pad_graphs_to=bucket_size(len(bin_graphs), 8),
                max_nodes_per_graph=n_cap, max_edges_per_graph=e_cap,
            )
            s.count(real_nodes=int(meta.node_off[-1]),
                    padded_nodes=len(packed["token"]))
        with telemetry.span("embed.upload"):
            batch = {k: jnp.asarray(v) for k, v in packed.items()}
        return batch, meta, bucket_key(packed)

    def _embed_finish(self, label, hashes, fn, stats):
        """Shared embed epilogue: assemble rows from the cache, warn on
        truncation, LRU-evict, publish `self.embed_stats`."""
        if stats["trunc_nodes"] or stats["trunc_edges"]:
            import warnings

            warnings.warn(
                f"{label} truncated {stats['trunc_nodes']} node(s) / "
                f"{stats['trunc_edges']} edge(s) over the micro-batch "
                f"budget; embeddings for the affected graphs are computed "
                f"on truncated graphs",
                stacklevel=3,
            )
        out = np.stack([self._embed_cache[h] for h in hashes]) if hashes \
            else np.zeros((0, self.rc.dims[-1]), np.float32)
        # LRU eviction: hits were moved to the insertion-order tail when
        # looked up, so the dict's first key is the least recently used
        while len(self._embed_cache) > self.embed_cache_max:
            self._embed_cache.pop(next(iter(self._embed_cache)))
        self.embed_stats = {
            "graphs": len(hashes),
            "compiles": _jit_cache_size(fn),
            **stats,
        }
        return out

    def embed(self, params, graphs: list[KernelGraph], batch_size=64,
              max_nodes=None, max_edges=None) -> np.ndarray:
        """256-d kernel embeddings for all graphs (paper §3.4 uses z_k, not
        the projection head output).

        Micro-batched pass over size buckets with a content-hash embedding
        cache: repeated kernel invocations (identical traces) are encoded
        once; micro-batches are size-sorted so jit retraces stay bounded by
        the bucket count.  Stats land in `self.embed_stats`.
        """
        n_cap = max_nodes or MAX_NODES_PER_MICROBATCH
        e_cap = max_edges or MAX_EDGES_PER_MICROBATCH
        fn = self._embed_setup(params, n_cap, e_cap)

        n = len(graphs)
        hashes = [graph_content_hash(g) for g in graphs]
        todo: list[int] = []
        scheduled: set[str] = set()
        for i, hsh in enumerate(hashes):
            if hsh in self._embed_cache:
                # LRU touch: move the hit to the insertion-order tail so
                # hot entries survive eviction pressure
                self._embed_cache[hsh] = self._embed_cache.pop(hsh)
            elif hsh not in scheduled:
                scheduled.add(hsh)
                todo.append(i)

        bucket_keys = set()
        trunc_nodes = trunc_edges = 0
        bins = plan_microbatches(
            [graphs[i] for i in todo],
            max_nodes=n_cap, max_edges=e_cap, max_graphs=batch_size,
        )

        def stage(bin_idx):
            sel = [todo[j] for j in bin_idx]
            return sel, self._stage_bin(
                [graphs[i] for i in sel], n_cap, e_cap)

        pipe = _OneAhead(stage, bins, span="embed", enabled=self.tc.prefetch,
                         depth=self.tc.prefetch_depth)
        for _, (sel, (batch, meta, bkey)) in pipe:
            with telemetry.span("embed.encode", graphs=len(sel)):
                z = np.asarray(fn(params, batch))
            trunc_nodes += int(meta.trunc_nodes.sum())
            trunc_edges += int(meta.trunc_edges.sum())
            bucket_keys.add(bkey)
            for k, i in enumerate(sel):
                self._embed_cache[hashes[i]] = z[k]

        return self._embed_finish("embed", hashes, fn, {
            "cache_hits": n - len(todo),
            "encoded": len(todo),
            "microbatches": len(bins),
            "bucket_keys": sorted(bucket_keys),
            "trunc_nodes": trunc_nodes,
            "trunc_edges": trunc_edges,
            "prefetch": pipe.enabled,
            "prefetch_stage_s": pipe.stage_s,
            "prefetch_wait_s": pipe.wait_s,
            "prefetch_overlap": pipe.overlap_fraction,
        })

    def embed_stream(self, params, graphs, batch_size=64, max_nodes=None,
                     max_edges=None) -> np.ndarray:
        """Streaming-iterator variant of `embed`: consumes ANY iterable of
        KernelGraphs (e.g. `repro.workloads.iter_program_graphs`, which
        traces lazily) holding at most one micro-batch of graphs resident
        inside the binner — plus, with ``tc.prefetch``, ONE staged
        micro-batch riding the background upload thread (so peak residency
        is bounded by two micro-batches, never the stream length).

        Unlike `embed`, no global size-sort is possible (the stream is
        consumed in arrival order), so distinct bucket keys may be slightly
        higher; the content-hash cache and pow-2 buckets still apply.
        Peak residency lands in `self.embed_stats` (the bound asserted by
        tests/test_workloads.py).
        """
        n_cap = max_nodes or MAX_NODES_PER_MICROBATCH
        e_cap = max_edges or MAX_EDGES_PER_MICROBATCH
        fn = self._embed_setup(params, n_cap, e_cap)

        order: list[str] = []          # content hash per input position
        scheduled: set[str] = set()
        cache_hits = 0

        def pending():
            nonlocal cache_hits
            for g in graphs:
                h = graph_content_hash(g)
                order.append(h)
                if h in self._embed_cache:
                    # LRU touch (see embed): hot entries survive eviction
                    self._embed_cache[h] = self._embed_cache.pop(h)
                    cache_hits += 1
                    continue
                if h in scheduled:
                    cache_hits += 1
                    continue
                scheduled.add(h)
                yield (h, g)

        bucket_keys = set()
        trunc_nodes = trunc_edges = 0
        stream_stats: dict = {}

        def stage(bin_items):
            return self._stage_bin([g for _, g in bin_items], n_cap, e_cap)

        pipe = _OneAhead(
            stage,
            stream_bins(
                pending(), lambda hg: (hg[1].n_nodes, hg[1].n_edges),
                max_nodes=n_cap, max_edges=e_cap, max_graphs=batch_size,
                stats=stream_stats),
            span="embed", enabled=self.tc.prefetch,
        )
        for bin_items, (batch, meta, bkey) in pipe:
            with telemetry.span("embed.encode", graphs=len(bin_items)):
                z = np.asarray(fn(params, batch))
            trunc_nodes += int(meta.trunc_nodes.sum())
            trunc_edges += int(meta.trunc_edges.sum())
            bucket_keys.add(bkey)
            for k, (h, _) in enumerate(bin_items):
                self._embed_cache[h] = z[k]

        return self._embed_finish("embed_stream", order, fn, {
            "cache_hits": cache_hits,
            "encoded": len(scheduled),
            "microbatches": stream_stats.pop("bins", 0),
            "bucket_keys": sorted(bucket_keys),
            "trunc_nodes": trunc_nodes,
            "trunc_edges": trunc_edges,
            "streaming": True,
            "prefetch": pipe.enabled,
            "prefetch_stage_s": pipe.stage_s,
            "prefetch_wait_s": pipe.wait_s,
            "prefetch_overlap": pipe.overlap_fraction,
            **stream_stats,
        })

    def embed_dense(self, params, graphs: list[KernelGraph], batch_size=64,
                    pad_shapes=None) -> np.ndarray:
        """Dense `pad_batch` embed path — the pre-packing baseline, kept for
        parity tests and benchmarks/bench_batching.py."""
        full, max_warps = self.prepad(graphs, pad_shapes)
        full = {k: np.asarray(v) for k, v in full.items()}
        n = len(graphs)
        if self._embed_fn_dense is None:
            self._embed_fn_dense = {}
        if max_warps not in self._embed_fn_dense:
            self._embed_fn_dense[max_warps] = jax.jit(
                lambda p, b, mw=max_warps: rgcn_mod.encode(p, self.rc, b, mw),
            )
        fn = self._embed_fn_dense[max_warps]
        outs = []
        for i in range(0, n, batch_size):
            sel = slice(i, min(i + batch_size, n))
            batch = {k: jnp.asarray(v[sel]) for k, v in full.items()}
            outs.append(np.asarray(fn(params, batch)))
        return np.concatenate(outs, axis=0)


def fit_resilient(rc: RGCNConfig, tc: GCLTrainConfig,
                  graphs: list[KernelGraph], *, checkpoint_dir: str,
                  device_counts: Optional[list] = None,
                  fault_hook: Optional[callable] = None,
                  watchdog: Optional[Watchdog] = None,
                  mesh_axes: tuple = ("data", "model"),
                  verbose: bool = False):
    """Degrade-don't-abort scale-out driver (DESIGN.md §11).

    Fits on a data-parallel mesh of ``device_counts[0]`` devices; when a
    participant is lost or straggles (the fit raises
    :class:`repro.distributed.fault.DeviceLost` from its fault boundary,
    AFTER checkpointing), the mesh SHRINKS to the next width and training
    resumes from that checkpoint instead of aborting.  ``device_counts``
    defaults to halving widths down to 1 (e.g. 8, 4, 2, 1).

    Returns ``(params, info)`` from the surviving fit, with
    ``info["degradations"]`` recording each shrink and
    ``info["data_shards"]`` the width that finished.  Raises DeviceLost
    only when every width — including the single-device floor — failed.
    """
    from repro.launch.mesh import make_data_mesh

    if not checkpoint_dir:
        raise ValueError("fit_resilient requires a checkpoint_dir — "
                         "degradation resumes from checkpoints")
    if device_counts is None:
        n = jax.device_count()
        device_counts = []
        while n >= 1:
            device_counts.append(n)
            n //= 2
    degradations: list[dict] = []
    last: Optional[DeviceLost] = None
    for i, ndev in enumerate(device_counts):
        rules = make_data_mesh(ndev, axes=mesh_axes)
        trainer = ContrastiveTrainer(rc, tc, mesh_rules=rules)
        try:
            params, info = trainer.fit(
                graphs, verbose, checkpoint_dir=checkpoint_dir,
                resume=True, fault_hook=fault_hook, watchdog=watchdog)
            info["degradations"] = degradations
            info["data_shards"] = ndev
            return params, info
        except DeviceLost as e:
            last = e
            nxt = device_counts[i + 1] if i + 1 < len(device_counts) else None
            degradations.append({"from_devices": ndev, "to_devices": nxt,
                                 "error": str(e)})
            if verbose:
                print(f"[fit_resilient] {e} — degrading "
                      f"{ndev} -> {nxt} devices", flush=True)
    raise DeviceLost(
        f"training failed at every mesh width {device_counts} "
        f"(last: {last})") from last


def _jit_cache_size(fn) -> int:
    try:
        return int(fn._cache_size())
    except Exception:
        return -1


def _params_fingerprint(params) -> str:
    """Cheap content fingerprint of a param pytree (embedding cache is only
    valid for the params it was computed with).  Every leaf contributes — a
    prefix of its bytes is enough to catch any realistic update."""
    h = hashlib.blake2b(digest_size=8)
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(leaf).tobytes()[:4096])
    return h.hexdigest()
