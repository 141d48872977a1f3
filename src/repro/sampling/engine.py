"""PlanEngine — batched, compiled plan serving for every clustering method.

The paper's serving path (§3.4: embeddings -> silhouette K-Means ->
representatives) used to run one program at a time through a host-bound
Python loop over candidate Ks.  The engine instead:

- buckets plan requests by embedding-matrix size (PR 1-style power-of-two
  points buckets, exact feature dim) so nearby program sizes share one
  executable;
- dispatches MANY programs per compiled K-sweep
  (:func:`repro.core.clustering.sweep_cluster_stack`): all candidate Ks of
  all programs in a bucket chunk evaluated in a single device trace;
- falls back to the same host paths as the sequential reference for
  trivial/tiny programs, so results are identical request-for-request.

Executables are cached process-wide in :mod:`repro.core.clustering`
(`ENGINE_STATS`), so a PlanEngine is cheap to construct — methods make one
per plan call with their own (k_max, seed, use_pallas) and still share
compiled sweeps.

Serving hooks (DESIGN.md §9; consumed by :mod:`repro.serving`):

- :meth:`PlanEngine.warmup` pre-builds the executables for an expected
  bucket set, taking cold-start compiles off the serving path;
- ``cluster_many(..., on_chunk=...)`` surfaces results per dispatched
  chunk, which ``plan_many`` uses to overlap host-side plan building with
  the next chunk's device dispatch;
- ``errors="isolate"`` turns a poison request into an Exception entry in
  the result list instead of killing the whole batch; every program a
  failed sweep dispatch re-runs through the sequential reference is
  counted in ``stats["fallback_dispatches"]``, so a sweep that fails on
  the device never passes for a served one;
- ``record_timings`` stamps per-request dispatch telemetry into the plan
  ``extra`` so a server can account batch occupancy and service time.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field, replace
from typing import Callable, Optional

import jax
import numpy as np

from repro import telemetry
from repro.core.clustering import (
    bucket_batch, bucket_points, engine_stats, select_k_and_cluster,
    sweep_cluster_stack, warm_sweep,
)
from repro.distributed.fault import DeviceLost
from repro.sampling.base import plan_from_labels
from repro.sim.simulate import SamplingPlan


@dataclass(frozen=True)
class PlanEngineConfig:
    """Clustering knobs (mirrors `select_k_and_cluster`) + engine policy."""
    k_max: int = 48
    seed: int = 0
    sil_floor: float = 0.20
    tie_tol: float = 0.02
    tiny_n: int = 4
    sil_cap: int = 1200
    iters: int = 50
    use_pallas: bool = False     # fused kmeans_assign / silhouette kernels
    init: str = "host"           # 'host' numpy kmeans++ | 'device' fold-in
    engine: str = "sweep"        # 'sweep' | 'sequential' (parity reference)
    max_batch: int = 8           # programs per compiled dispatch PER DEVICE
    record_timings: bool = False  # stamp per-request dispatch telemetry
    overlap_plan_build: bool = True  # build plans while the next chunk runs
    #: program-axis device count for sharded dispatches: one dispatch then
    #: serves data_devices x max_batch programs.  0 = every device the
    #: backend exposes; 1 = single-device (the pre-scale-out behavior)
    data_devices: int = 0


@dataclass
class PlanRequest:
    """One program's plan inputs: kernel embeddings + invocation seqs."""
    embeddings: np.ndarray
    seqs: np.ndarray
    method: str = ""
    seed: Optional[int] = None   # overrides the engine seed per request
    extra: dict = field(default_factory=dict)


def normalize_embeddings(x) -> np.ndarray:
    """Engine-wide input normalization: float32, 2-D.  1-D vectors are a
    single scalar feature per point -> (n, 1); scalars/ragged inputs raise
    the numpy conversion error (isolated per request under
    ``errors="isolate"``)."""
    x = np.asarray(x, np.float32)
    if x.ndim == 1:
        x = x[:, None]
    if x.ndim != 2:
        raise ValueError(f"embeddings must be (n, d) or (n,), got {x.shape}")
    return x


def bucket_key(x) -> tuple[int, int]:
    """The ``(points-bucket, dim)`` grouping key for one request — the
    sweep's own padding unit, shared by PlanEngine and the serving
    batcher so both agree on which requests coalesce."""
    x = normalize_embeddings(x)
    return (bucket_points(len(x)), x.shape[1])


class PlanEngine:
    def __init__(self, cfg: Optional[PlanEngineConfig] = None, **overrides):
        cfg = cfg or PlanEngineConfig()
        self.cfg = replace(cfg, **overrides) if overrides else cfg
        #: per-instance serving counters (process-wide compile counters
        #: live in repro.core.clustering.ENGINE_STATS)
        self.stats = self._fresh_stats()
        #: program-axis shard width for sweep dispatches.  Starts at the
        #: configured device count and only ever SHRINKS (halves) when a
        #: dispatch raises DeviceLost — degrade, don't abort.
        self._data_shards = max(1, self.cfg.data_devices or jax.device_count())
        #: scale-out fault injection point: called before every compiled
        #: dispatch; raise DeviceLost from it to exercise the degradation
        #: path (halve shards, retry the same chunk)
        self.fault_hook: Optional[Callable[[], None]] = None

    @staticmethod
    def _fresh_stats() -> dict:
        return {"programs": 0, "dispatches": 0, "errors": 0,
                "warmed_executables": 0, "degraded_dispatches": 0,
                "fallback_dispatches": 0, "bucket_hist": []}

    def reset_stats(self) -> None:
        """Zero the INSTANCE counters (long-lived servers window their
        telemetry with this).  Process-wide compile counters — shared by
        every engine — stay put; see
        :func:`repro.core.clustering.reset_engine_stats`."""
        self.stats = self._fresh_stats()

    def _bump_bucket(self, key: tuple[int, int], n: int) -> None:
        """bucket_hist entries are structured
        ``{"points_bucket": p, "dim": d, "count": n}`` (JSON-ready — no
        stringified tuple keys)."""
        for entry in self.stats["bucket_hist"]:
            if (entry["points_bucket"], entry["dim"]) == key:
                entry["count"] += n
                return
        self.stats["bucket_hist"].append(
            {"points_bucket": key[0], "dim": key[1], "count": n})

    # -- warm pool -----------------------------------------------------------
    def warmup(self, buckets, batch_sizes: Optional[list] = None) -> int:
        """Pre-build the compiled sweeps for an expected bucket set, taking
        cold-start compiles OFF the serving path.

        ``buckets``: iterable of ``(points, dim)`` pairs or
        ``{"points_bucket": p, "dim": d}`` dicts; points are rounded up to
        their power-of-two bucket.  ``batch_sizes`` defaults to every
        power-of-two chunk size the engine can dispatch (1..max_batch;
        just 1 under ``use_pallas``, which never batches).  Returns the
        number of NEW executables built — 0 means the pool was already
        warm."""
        c = self.cfg
        if batch_sizes is None:
            if c.use_pallas:
                batch_sizes = [1]
            else:
                batch_sizes, b = [], 1
                while b <= bucket_batch(max(1, c.max_batch)):
                    batch_sizes.append(b)
                    b <<= 1
        built = 0
        for bucket in buckets:
            if isinstance(bucket, dict):
                points, dim = bucket["points_bucket"], bucket["dim"]
            else:
                points, dim = bucket
            for b in batch_sizes:
                built += warm_sweep(
                    int(b), int(points), int(dim), k_max=c.k_max,
                    iters=c.iters, use_pallas=c.use_pallas, init=c.init,
                    data_shards=self._data_shards)
        self.stats["warmed_executables"] += built
        return built

    # -- clustering ---------------------------------------------------------
    def _cluster_kwargs(self) -> dict:
        c = self.cfg
        return dict(k_max=c.k_max, sil_floor=c.sil_floor, tie_tol=c.tie_tol,
                    tiny_n=c.tiny_n, sil_cap=c.sil_cap, iters=c.iters,
                    use_pallas=c.use_pallas, init=c.init)

    def _dispatch_chunk(self, xs: list, seeds: list):
        """One compiled sweep dispatch, with scale-out degradation: a
        DeviceLost — raised by the injected ``fault_hook`` or the sharded
        dispatch itself — halves the program-axis shard width and retries
        the SAME chunk, so a lost/straggling participant shrinks
        throughput instead of dropping requests.  Requests are only at a
        chunk boundary here (nothing is half-served), matching the
        training engine's checkpoint-boundary contract."""
        while True:
            try:
                if self.fault_hook is not None:
                    self.fault_hook()
                return sweep_cluster_stack(
                    xs, seed=seeds, data_shards=self._data_shards,
                    **self._cluster_kwargs())
            except DeviceLost:
                if self._data_shards <= 1:
                    raise
                self._data_shards //= 2
                self.stats["degraded_dispatches"] += 1

    def _stamp(self, results: list, key, chunk: int, dispatch_s: float):
        """record_timings hook: dispatch telemetry on every info dict (flows
        into plan.extra), so a server can account occupancy + service."""
        for r in results:
            if isinstance(r, Exception):
                continue
            r[1]["serve"] = {
                "points_bucket": key[0], "dim": key[1], "batch": chunk,
                "dispatch_s": dispatch_s,
            }

    def cluster_many(self, embs: list, seeds: Optional[list] = None,
                     errors: str = "raise",
                     on_chunk: Optional[Callable] = None):
        """Cluster many programs' embeddings; returns aligned
        [(labels, info)].  Requests are grouped by (points-bucket, dim) —
        the sweep's OWN padding unit, so grouped programs share both the
        executable and the padded shape — and chunked to `max_batch`
        programs per compiled dispatch.

        ``errors="isolate"``: a failing request becomes an Exception entry
        (the chunk retries its siblings one-by-one through the sequential
        reference, so one poison request never drops a batch; each re-run
        counts in ``stats["fallback_dispatches"]``).
        ``on_chunk(indices, results)`` fires after every dispatched chunk —
        the overlap hook ``plan_many`` builds plans on."""
        if errors not in ("raise", "isolate"):
            raise ValueError(f"errors must be 'raise'|'isolate': {errors!r}")
        out: list = [None] * len(embs)
        if not embs:
            return out
        seeds = ([self.cfg.seed] * len(embs) if seeds is None
                 else [self.cfg.seed if s is None else s for s in seeds])
        norm: list = [None] * len(embs)
        for i, x in enumerate(embs):
            try:
                norm[i] = normalize_embeddings(x)
            except Exception as e:
                if errors == "raise":
                    raise
                out[i] = e
                self.stats["errors"] += 1
        live = [i for i in range(len(embs)) if norm[i] is not None]

        if self.cfg.engine == "sequential":
            for i in live:
                t0 = time.perf_counter()
                try:
                    res = select_k_and_cluster(norm[i], seed=seeds[i],
                                               **self._cluster_kwargs())
                except Exception as e:
                    if errors == "raise":
                        raise
                    res = e
                    self.stats["errors"] += 1
                if self.cfg.record_timings:
                    self._stamp([res], bucket_key(norm[i]), 1,
                                time.perf_counter() - t0)
                out[i] = res
                self.stats["dispatches"] += 1
                if on_chunk is not None:
                    on_chunk([i], [res])
            self.stats["programs"] += len(embs)
            return out

        groups: dict[tuple, list[int]] = {}
        for i in live:
            groups.setdefault(
                (bucket_points(len(norm[i])), norm[i].shape[1]), []).append(i)
        # use_pallas sweeps stay unbatched: pallas_call inside vmap leans on
        # batching rules we don't exercise elsewhere — the cached executable
        # is still shared across programs.  Sharded dispatches scale the cap
        # by the mesh width: one dispatch serves data_shards x max_batch
        # programs, each device sweeping its own max_batch slice.
        cap = (1 if self.cfg.use_pallas
               else max(1, self.cfg.max_batch) * max(1, self._data_shards))
        for key, idxs in sorted(groups.items()):
            self._bump_bucket(key, len(idxs))
            for lo in range(0, len(idxs), cap):
                chunk = idxs[lo:lo + cap]
                t0 = time.perf_counter()
                try:
                    res = self._dispatch_chunk(
                        [norm[i] for i in chunk],
                        [seeds[i] for i in chunk])
                except Exception:
                    if errors == "raise":
                        raise
                    # err-isolated dispatch: retry one-by-one through the
                    # sequential reference so siblings still get served
                    res = []
                    for i in chunk:
                        self.stats["fallback_dispatches"] += 1
                        try:
                            res.append(select_k_and_cluster(
                                norm[i], seed=seeds[i],
                                **self._cluster_kwargs()))
                        except Exception as e:
                            res.append(e)
                            self.stats["errors"] += 1
                if self.cfg.record_timings:
                    self._stamp(res, key, len(chunk),
                                time.perf_counter() - t0)
                for i, r in zip(chunk, res):
                    out[i] = r
                self.stats["dispatches"] += 1
                if on_chunk is not None:
                    on_chunk(chunk, res)
        self.stats["programs"] += len(embs)
        return out

    def cluster(self, emb: np.ndarray, seed: Optional[int] = None):
        return self.cluster_many([emb], [seed])[0]

    # -- plans --------------------------------------------------------------
    def plan_many(self, requests: list[PlanRequest],
                  errors: str = "raise") -> list:
        """Serve MANY programs' SamplingPlans per compiled dispatch.

        Host-side plan building (`plan_from_labels`) is OVERLAPPED with the
        next chunk's device dispatch on a worker thread
        (``cfg.overlap_plan_build``) — the representative scan for chunk i
        runs while chunk i+1 is on the device, so the dispatch queue never
        blocks on it.  With ``errors="isolate"`` failed requests come back
        as Exception entries, aligned with their request."""
        if not requests:
            return []
        plans: list = [None] * len(requests)

        def build(idxs, results):
            for i, r in zip(idxs, results):
                if isinstance(r, Exception):
                    plans[i] = r
                    continue
                labels, info = r
                req = requests[i]
                try:
                    with telemetry.span("plan.build"):
                        plans[i] = plan_from_labels(
                            labels, req.seqs, req.method,
                            extra=dict(info, **req.extra))
                except Exception as e:
                    if errors == "raise":
                        raise
                    self.stats["errors"] += 1
                    plans[i] = e

        embs = [r.embeddings for r in requests]
        seeds = [r.seed for r in requests]
        if self.cfg.overlap_plan_build:
            with ThreadPoolExecutor(max_workers=1) as pool:
                futs = []
                results = self.cluster_many(
                    embs, seeds, errors=errors,
                    on_chunk=lambda idxs, res: futs.append(
                        pool.submit(telemetry.carry(build), idxs, res)))
                for f in futs:
                    f.result()
            # normalization failures never reach a chunk — pick the
            # isolated Exception entries up from the aligned result list
            for i, r in enumerate(results):
                if plans[i] is None:
                    build([i], [r])
        else:
            results = self.cluster_many(embs, seeds, errors=errors)
            build(range(len(requests)), results)
        return plans

    def plan(self, embeddings: np.ndarray, seqs: np.ndarray, method: str = "",
             seed: Optional[int] = None, extra: Optional[dict] = None
             ) -> SamplingPlan:
        return self.plan_many([PlanRequest(embeddings, seqs, method,
                                           seed=seed, extra=extra or {})])[0]

    def engine_stats(self) -> dict:
        """Instance counters + the process-wide compile counters (the
        process-wide dispatch counter keeps its own key so it never shadows
        this instance's)."""
        g = engine_stats()
        return dict(self.stats, builds=g["builds"],
                    cache_entries=g["cache_entries"],
                    process_dispatches=g["dispatches"],
                    data_shards=self._data_shards)
