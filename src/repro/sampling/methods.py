"""The four paper methods behind one protocol.

| id          | display name | prepare() artifact            | plan()                          |
|-------------|--------------|-------------------------------|---------------------------------|
| `gcl`       | GCL-Sampler  | trained RGCN params + z_k     | silhouette K-Means on z_k       |
| `pka`       | PKA          | 12-d profiled feature matrix  | silhouette K-Means on features  |
| `sieve`     | Sieve        | name/CoV strata + CTA counts  | max-CTA representative          |
| `stem_root` | STEM+ROOT    | profiled execution times      | STEM strata + ROOT multi-rep    |

Every method is constructible through ``repro.sampling.get_method(id,
**overrides)`` with identical `prepare`/`plan`/`run` signatures, making the
full method x program x platform sweep (``repro.launch.sample``) a plain
loop over registry ids.
"""

from __future__ import annotations

import time
from dataclasses import asdict, replace
from typing import Optional

import numpy as np

from repro import telemetry
from repro.core.baselines.pka import pka_features
from repro.core.baselines.sieve import sieve_partition
from repro.core.baselines.stem_root import stem_root_partition, stem_root_times
from repro.core.sampler import GCLSampler, GCLSamplerConfig
from repro.sampling.base import (
    Artifacts, SamplingMethod, config_hash, plan_from_labels,
)
from repro.sampling.engine import PlanEngine, PlanRequest
from repro.sampling.registry import register_method
from repro.sampling.store import program_fingerprint
from repro.sim.simulate import SamplingPlan
from repro.tracing.programs import Program


def _seqs(program: Program) -> np.ndarray:
    return np.array([k.seq for k in program.kernels])


def _artifacts(method: SamplingMethod, program: Program, payload: dict,
               timings: dict, meta: Optional[dict] = None,
               provenance: str = "") -> Artifacts:
    return Artifacts(
        method=method.id, program=program_fingerprint(program),
        config_hash=config_hash(method.config()), payload=payload,
        timings=timings, meta=meta or {}, provenance=provenance,
    )


@register_method
class GCLMethod(SamplingMethod):
    """The paper's contribution, wrapping :class:`GCLSampler`.

    The trained encoder lives on the instance: the first ``prepare`` fits
    the RGCN contrastively, subsequent programs (or replayed artifacts via
    ``adopt``) reuse it and only pay for graph building + embedding.
    """

    id = "gcl"
    display_name = "GCL-Sampler"

    #: auto-streaming threshold: programs with at least this many
    #: invocations use the bounded-memory trace->graph path by default
    STREAM_THRESHOLD = 512

    def __init__(self, cfg: Optional[GCLSamplerConfig] = None, *,
                 steps: Optional[int] = None,
                 batch_size: Optional[int] = None,
                 cap_instr: Optional[int] = None,
                 k_max: Optional[int] = None,
                 seed: Optional[int] = None,
                 streaming: Optional[bool] = None,
                 engine: Optional[str] = None,
                 checkpoint_every: Optional[int] = None,
                 ingest_workers: Optional[int] = None,
                 graph_cache: Optional[bool] = None,
                 resume: bool = True):
        #: None = auto (stream iff len(program) >= STREAM_THRESHOLD);
        #: True/False force the streaming / materialized ingestion path
        self.streaming = streaming
        #: False = ignore existing fit checkpoints and refit from scratch
        self.resume = resume
        cfg = cfg or GCLSamplerConfig()
        train_kw = {k: v for k, v in
                    [("steps", steps), ("batch_size", batch_size),
                     ("seed", seed), ("engine", engine),
                     ("checkpoint_every", checkpoint_every)]
                    if v is not None}
        cfg_kw = {k: v for k, v in
                  [("cap_instr", cap_instr), ("k_max", k_max)]
                  if v is not None}
        if train_kw:
            cfg_kw["train"] = replace(cfg.train, **train_kw)
        ingest_kw = {k: v for k, v in
                     [("workers", ingest_workers), ("cache", graph_cache)]
                     if v is not None}
        if ingest_kw:
            cfg_kw["ingest"] = replace(cfg.ingest, **ingest_kw)
        self.cfg = replace(cfg, **cfg_kw) if cfg_kw else cfg
        self.sampler = GCLSampler(self.cfg)
        self._trained_on: Optional[str] = None  # program fp of the fit
        self._store = None                      # set by attach_store / run

    def config(self) -> dict:
        """JSON-safe config hashed into the artifact content key.  The
        checkpoint cadence and the ingest config are EXCLUDED: cadence
        changes when snapshots are taken and ingest changes how fast graphs
        arrive (workers/depth/cache) — neither ever changes the fitted
        encoder or the embeddings (ingestion is bit-identical at any worker
        count), so runs differing only there must share artifacts."""
        cfg = asdict(self.cfg)
        cfg["train"].pop("checkpoint_every", None)
        cfg.pop("ingest", None)
        return dict(cfg, streaming=self.streaming)

    def attach_store(self, store) -> None:
        """Remember the store so ``prepare`` can place fit checkpoints under
        ``store.checkpoint_dir`` (an interrupted prepare then resumes from
        the last snapshot instead of refitting), and back the sampler's
        ingestion engine with the run's on-disk graph cache — warm runs
        (and `PlanService.submit_program` tenants) skip tracing entirely."""
        self._store = store
        if self.cfg.ingest.cache and hasattr(store, "graph_store"):
            self.sampler.attach_graph_store(store.graph_store())

    def _fit_checkpoint_dir(self, program: Program) -> Optional[str]:
        if self._store is None or self.cfg.train.checkpoint_every <= 0:
            return None
        # artifact_key is the single source of truth for content keys; a
        # fit only happens with no adopted encoder, so the provenance
        # suffix is empty and this equals the artifact's own key
        return self._store.checkpoint_dir(self.id, self.artifact_key(program))

    def _use_streaming(self, program: Program) -> bool:
        if self.streaming is not None:
            return self.streaming
        return len(program) >= self.STREAM_THRESHOLD

    def _encoder_provenance(self, program_fp: str) -> str:
        """Non-empty when the encoder was fit on a DIFFERENT program: the
        artifact content then depends on that program too, so it must be
        part of the content key (keeps replayed results independent of
        store history / grid order)."""
        if self._trained_on and self._trained_on != program_fp:
            return f"enc-{self._trained_on}"
        return ""

    def artifact_key(self, program: Program) -> str:
        base = super().artifact_key(program)
        prov = self._encoder_provenance(program_fingerprint(program))
        return f"{base}-{prov}" if prov else base

    def prepare(self, program: Program) -> Artifacts:
        with telemetry.span("gcl.prepare", root=True,
                            invocations=len(program)):
            return self._prepare(program)

    def _prepare(self, program: Program) -> Artifacts:
        stream = self._use_streaming(program)
        t0 = time.perf_counter()
        graphs = None if stream else self.sampler.build_graphs(program)
        t1 = time.perf_counter()
        meta: dict = {"streaming": stream}
        if self.sampler.params is None:
            ckpt = dict(checkpoint_dir=self._fit_checkpoint_dir(program),
                        resume=self.resume)
            if stream:
                # n_total makes the training subset identical to the
                # materialized path: streaming changes memory, not results
                info = self.sampler.train_stream(
                    self.sampler.iter_graphs(program),
                    n_total=len(program), **ckpt)
            else:
                info = self.sampler.train(graphs, **ckpt)
            self._trained_on = program_fingerprint(program)
            meta["train"] = {
                k: info[k] for k in
                ("val_loss", "val_acc", "trunc_nodes", "step_compiles",
                 "engine", "resumed_from", "checkpoint_saves", "host_syncs")
                if k in info
            }
        else:
            meta["encoder_reused"] = True
        meta["trained_on"] = self._trained_on
        t2 = time.perf_counter()
        if stream:
            # second lazy pass: graphs flow through pack/encode one
            # micro-batch at a time (bounded peak residency; the
            # content-hash cache de-dupes repeated invocations)
            emb = self.sampler.embed_stream(self.sampler.iter_graphs(program))
            meta["embed"] = {
                k: v for k, v in self.sampler.trainer.embed_stats.items()
                if k in ("cache_hits", "encoded", "microbatches",
                         "peak_resident_graphs", "peak_resident_nodes")
            }
        else:
            emb = self.sampler.embed(graphs)
        t3 = time.perf_counter()
        ing = self.sampler.ingest
        meta["ingest"] = {
            "workers": self.cfg.ingest.workers,
            "kernels": ing.stats["kernels"], "traced": ing.stats["traced"],
            "memo_hits": ing.stats["memo_hits"],
            "store_hits": ing.stats["store_hits"],
            "corrupt": ing.stats["corrupt"],
            "overlap_fraction": round(ing.overlap_fraction, 4),
        }
        payload = {
            "params": self.sampler.params,
            "embeddings": emb,
            "seqs": _seqs(program),
        }
        timings = {"graphs_s": t1 - t0, "train_s": t2 - t1,
                   "embed_s": t3 - t2}
        return _artifacts(
            self, program, payload, timings, meta,
            provenance=self._encoder_provenance(program_fingerprint(program)))

    def plan(self, program: Program, artifacts: Artifacts) -> SamplingPlan:
        return self.plan_batch([(program, artifacts)])[0]

    def plan_request(self, program: Program,
                     artifacts: Artifacts) -> PlanRequest:
        """The engine-ready request ``plan`` serves (repro.serving): same
        embeddings/seqs/seed, artifact timings + meta riding in ``extra``."""
        return PlanRequest(
            np.asarray(artifacts.payload["embeddings"]),
            np.asarray(artifacts.payload["seqs"]), self.display_name,
            seed=self.cfg.train.seed,
            extra=dict(artifacts.meta, timings=dict(artifacts.timings)))

    def plan_batch(self, items: list) -> list[SamplingPlan]:
        """All programs of the batch through the compiled planning engine:
        one multi-K sweep dispatch per embedding-size bucket, `use_pallas`
        threaded through from the RGCN config."""
        with telemetry.span("gcl.plan", root=True, timed=True,
                            programs=len(items)) as s:
            engine = self.sampler.plan_engine()
            plans = engine.plan_many([
                PlanRequest(np.asarray(a.payload["embeddings"]),
                            np.asarray(a.payload["seqs"]), self.display_name)
                for _, a in items])
        cluster_s = s.seconds / max(len(items), 1)
        for (_, artifacts), plan in zip(items, plans):
            plan.extra["timings"] = dict(artifacts.timings,
                                         cluster_s=cluster_s)
            plan.extra.update(artifacts.meta)
        return plans

    def adopt(self, artifacts: Artifacts) -> None:
        params = artifacts.payload.get("params")
        if params is not None:
            self.sampler.params = params
            self._trained_on = artifacts.meta.get("trained_on",
                                                  artifacts.program)


@register_method
class PKAMethod(SamplingMethod):
    id = "pka"
    display_name = "PKA"

    def __init__(self, platform: str = "P1", k_max: int = 48, seed: int = 0):
        self.platform = platform
        self.k_max = k_max
        self.seed = seed

    def config(self) -> dict:
        return {"platform": self.platform, "k_max": self.k_max,
                "seed": self.seed}

    def prepare(self, program: Program) -> Artifacts:
        t0 = time.perf_counter()
        x = pka_features(program, self.platform)
        return _artifacts(self, program, {"features": x},
                          {"features_s": time.perf_counter() - t0})

    def plan(self, program: Program, artifacts: Artifacts) -> SamplingPlan:
        return self.plan_batch([(program, artifacts)])[0]

    def plan_request(self, program: Program,
                     artifacts: Artifacts) -> PlanRequest:
        return PlanRequest(
            np.asarray(artifacts.payload["features"]), _seqs(program),
            self.display_name, seed=self.seed,
            extra={"timings": dict(artifacts.timings)})

    def plan_batch(self, items: list) -> list[SamplingPlan]:
        t0 = time.perf_counter()
        engine = PlanEngine(k_max=self.k_max, seed=self.seed)
        plans = engine.plan_many([
            PlanRequest(np.asarray(a.payload["features"]), _seqs(p),
                        self.display_name)
            for p, a in items])
        cluster_s = (time.perf_counter() - t0) / max(len(items), 1)
        for (_, artifacts), plan in zip(items, plans):
            plan.extra["timings"] = dict(artifacts.timings,
                                         cluster_s=cluster_s)
        return plans


@register_method
class SieveMethod(SamplingMethod):
    id = "sieve"
    display_name = "Sieve"

    def __init__(self, platform: str = "P1"):
        self.platform = platform

    def config(self) -> dict:
        return {"platform": self.platform}

    def prepare(self, program: Program) -> Artifacts:
        t0 = time.perf_counter()
        labels, ctas = sieve_partition(program, self.platform)
        return _artifacts(self, program, {"labels": labels, "priority": ctas},
                          {"partition_s": time.perf_counter() - t0})

    def plan(self, program: Program, artifacts: Artifacts) -> SamplingPlan:
        plan = plan_from_labels(
            np.asarray(artifacts.payload["labels"]), _seqs(program),
            self.display_name,
            priority=np.asarray(artifacts.payload["priority"]))
        plan.extra["timings"] = dict(artifacts.timings)
        return plan


@register_method
class StemRootMethod(SamplingMethod):
    id = "stem_root"
    display_name = "STEM+ROOT"

    def __init__(self, platform: str = "P1", eps: float = 0.25):
        self.platform = platform
        self.eps = eps

    def config(self) -> dict:
        return {"platform": self.platform, "eps": self.eps}

    def prepare(self, program: Program) -> Artifacts:
        t0 = time.perf_counter()
        times = stem_root_times(program, self.platform)
        return _artifacts(self, program, {"times": times},
                          {"profile_s": time.perf_counter() - t0})

    def plan(self, program: Program, artifacts: Artifacts) -> SamplingPlan:
        names = [k.name for k in program.kernels]
        labels, rep_selector = stem_root_partition(
            np.asarray(artifacts.payload["times"]), names, self.eps)
        plan = plan_from_labels(labels, _seqs(program), self.display_name,
                                rep_selector=rep_selector)
        plan.extra["timings"] = dict(artifacts.timings)
        return plan
