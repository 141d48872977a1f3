"""GCL-Sampler end-to-end pipeline (paper Fig. 2):

  program -> NVBit-like traces -> HRGs -> RGCN contrastive training ->
  kernel embeddings z_k -> K-Means (silhouette K) -> representatives
  (first invocation per cluster) -> SamplingPlan.

This class is the MODEL behind the registered ``gcl`` sampling method;
prefer the unified API (``repro.sampling.get_method("gcl")``) for new code.
``plan_from_labels`` lives in ``repro.sampling`` (shared by all methods)
and is re-exported here for backward compatibility; the K-selection /
clustering stage routes through the compiled planning engine
(``repro.sampling.PlanEngine`` over the swept K-Means in
``core/clustering.py`` — DESIGN.md §8), with the sequential
``select_k_and_cluster`` loop kept as its parity reference.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Optional

import numpy as np

from repro.core.graphs import KernelGraph
from repro.core.rgcn import RGCNConfig
from repro.core.train import ContrastiveTrainer, GCLTrainConfig
from repro.sampling.base import plan_from_labels  # noqa: F401  (compat shim)
from repro.sim.simulate import SamplingPlan
from repro.tracing.programs import Program

if TYPE_CHECKING:  # layering: ingest imports core, so core types it lazily
    from repro.ingest.engine import IngestConfig


def _default_ingest():
    # lazy: repro.ingest sits ABOVE core in the layering (it imports
    # core.graphs), so core must not import it at module load time
    from repro.ingest.engine import IngestConfig

    return IngestConfig()


@dataclass(frozen=True)
class GCLSamplerConfig:
    #: trace window; None = resolve per program (its `trace_caps`, else the
    #: repo defaults in repro.config) — model-zoo programs carry their own
    cap_warps: Optional[int] = None
    cap_instr: Optional[int] = None
    k_max: int = 48
    rgcn: RGCNConfig = field(default_factory=RGCNConfig)
    train: GCLTrainConfig = field(default_factory=GCLTrainConfig)
    train_subsample: int = 400   # cap on kernels used for contrastive training
    #: trace->graph ingestion (workers/depth/cache) — never affects results,
    #: only how fast graphs arrive (excluded from artifact content keys)
    ingest: "IngestConfig" = field(default_factory=_default_ingest)


class GCLSampler:
    def __init__(self, cfg: Optional[GCLSamplerConfig] = None):
        self.cfg = cfg or GCLSamplerConfig()
        self.trainer = ContrastiveTrainer(self.cfg.rgcn, self.cfg.train)
        from repro.ingest.engine import IngestEngine

        self.ingest = IngestEngine(self.cfg.ingest)
        self.params = None

    # -- stages --------------------------------------------------------------
    def attach_graph_store(self, graph_store) -> None:
        """Back the ingestion engine with an on-disk `GraphStore`: warm runs
        then skip tracing entirely (repro.sampling wires this from the
        ArtifactStore's run directory)."""
        self.ingest.store = graph_store

    def build_graphs(self, program: Program) -> list[KernelGraph]:
        return list(self.iter_graphs(program))

    def iter_graphs(self, program: Program):
        """Lazy per-invocation trace + graph build through the ingestion
        engine (parallel workers, dedup memo, optional graph cache) —
        deterministic program order, bounded peak residency."""
        c = self.cfg
        return self.ingest.iter_graphs(program, c.cap_warps, c.cap_instr)

    def train_stream(self, graphs_iter, n_total=None, verbose=False,
                     checkpoint_dir=None, resume=True):
        """Fit on a bounded subset of a graph ITERATOR without materializing
        it.  When `n_total` is known (the Program case: one graph per
        invocation), the subset is the SAME `rng.choice` draw as the
        materialized `train(build_graphs(...))` path — streaming and
        materialized ingestion then train the identical encoder.  Without
        `n_total`, falls back to reservoir sampling (same cap, different
        subset).  Either way at most `train_subsample` graphs are retained.
        `checkpoint_dir`/`resume` thread through to the trainer's resume
        protocol (core/train.py, DESIGN.md §6).
        """
        cap = self.cfg.train_subsample
        rng = np.random.default_rng(self.cfg.train.seed)
        kw = dict(verbose=verbose, checkpoint_dir=checkpoint_dir,
                  resume=resume)
        if n_total is not None:
            if n_total <= cap:
                return self.train(list(graphs_iter), **kw)
            # replicate train()'s selection exactly (indices AND order)
            sel = rng.choice(n_total, cap, replace=False)
            want = set(int(i) for i in sel)
            picked = {i: g for i, g in enumerate(graphs_iter) if i in want}
            # train() sees len == cap <= train_subsample: no re-subsampling
            return self.train([picked[int(i)] for i in sel], **kw)
        buf: list[KernelGraph] = []
        for i, g in enumerate(graphs_iter):
            if len(buf) < cap:
                buf.append(g)
            else:
                j = int(rng.integers(0, i + 1))
                if j < cap:
                    buf[j] = g
        return self.train(buf, **kw)

    def train(self, graphs: list[KernelGraph], verbose=False,
              checkpoint_dir=None, resume=True):
        rng = np.random.default_rng(self.cfg.train.seed)
        if len(graphs) > self.cfg.train_subsample:
            sel = rng.choice(len(graphs), self.cfg.train_subsample, replace=False)
            train_graphs = [graphs[i] for i in sel]
        else:
            train_graphs = graphs
        self.params, info = self.trainer.fit(
            train_graphs, verbose=verbose, checkpoint_dir=checkpoint_dir,
            resume=resume)
        return info

    def embed(self, graphs: list[KernelGraph]) -> np.ndarray:
        """Streaming packed-bucketed embed with a content-hash cache:
        repeated kernel invocations are encoded once (see trainer.embed)."""
        if self.params is None:
            raise RuntimeError(
                "GCLSampler has no trained encoder: call train(graphs) (or "
                "the end-to-end fit(program)) before embed(), or adopt "
                "pretrained params via repro.sampling's ArtifactStore replay"
            )
        return self.trainer.embed(self.params, graphs)

    def embed_stream(self, graphs_iter) -> np.ndarray:
        """Streaming `embed` over a graph iterator (see trainer.embed_stream);
        peak resident graphs bounded by one micro-batch budget."""
        if self.params is None:
            raise RuntimeError(
                "GCLSampler has no trained encoder: call train/train_stream "
                "before embed_stream(), or adopt pretrained params via "
                "repro.sampling's ArtifactStore replay"
            )
        return self.trainer.embed_stream(self.params, graphs_iter)

    def plan_engine(self):
        """The compiled planning engine configured for this sampler:
        `k_max`/seed from the config, `use_pallas` threaded through from
        `RGCNConfig` (the same switch that picks the rgcn_spmm kernel now
        also picks the fused kmeans_assign / silhouette kernels)."""
        from repro.sampling.engine import PlanEngine

        return PlanEngine(k_max=self.cfg.k_max, seed=self.cfg.train.seed,
                          use_pallas=self.cfg.rgcn.use_pallas)

    def cluster(self, embeddings: np.ndarray, seqs: np.ndarray) -> SamplingPlan:
        return self.plan_engine().plan(embeddings, seqs, "GCL-Sampler")

    # -- end-to-end ------------------------------------------------------------
    def fit(self, program: Program, verbose=False) -> SamplingPlan:
        """End-to-end streaming fit: graphs are traced lazily per pass
        (`iter_graphs`), trained via `train_stream` (same subset draw as the
        materialized path) and embedded via `embed_stream`, so peak graph
        residency stays bounded by one micro-batch instead of 2x the
        program (PR 3's guarantee, previously bypassed here)."""
        t0 = time.perf_counter()
        train_info = self.train_stream(self.iter_graphs(program),
                                       n_total=len(program), verbose=verbose)
        t2 = time.perf_counter()
        emb = self.embed_stream(self.iter_graphs(program))
        t3 = time.perf_counter()
        seqs = np.array([k.seq for k in program.kernels])
        plan = self.cluster(emb, seqs)
        plan.extra.update(
            train=train_info,
            embed=dict(self.trainer.embed_stats),
            timings={
                "train_s": t2 - t0,  # includes the lazy trace->graph pass
                "embed_s": t3 - t2, "cluster_s": time.perf_counter() - t3,
            },
        )
        return plan
