"""Closed loop over a program suite: whole passes over the suite, each in
a seeded shuffled order; each program goes through the sampler's prepare (ingest, encode)
and plan (K-sweep, representatives) before the next starts.

Traffic keys: ``generator`` ("closed_suite").  The comparison covers every
program of the suite, each by its first result in the window.

Nothing from an earlier program is replayed: there is no artifact or graph
store, and the embedding cache and the ingest dedup memo are emptied before
every program, so dedup works within a program only, as a user's single
call gets it.  The encoder's weights are made from the seed by the
benchmark and adopted by the method; nothing is fitted.
"""

from __future__ import annotations

import time
from contextlib import nullcontext

import numpy as np

from benchmarks.chip import counts, harness, reference
from benchmarks.chip.harness import compared
from benchmarks.chip.program import graph_dict, program_seed, rgcn_config


class Generator:
    span_names = ("prepare", "plan")

    def __init__(self, cell, seed: int, spans, rgcn_overrides=None):
        self.cell, self.cfg, self.seed, self.spans = (
            cell, cell.config, seed, spans)
        self.rgcn_overrides = rgcn_overrides
        self.done: list = []       # (program index, embeddings, plan)
        self.elapsed = None
        self._graphs: dict = {}    # program index -> (graphs, inv)

    # -- set-up ---------------------------------------------------------------
    def setup(self, warm: bool = True, pool=None) -> None:
        import jax

        from repro.core.sampler import GCLSamplerConfig
        from repro.core.train import GCLTrainConfig
        from repro.ingest.engine import IngestConfig
        from repro.sampling.methods import GCLMethod
        from repro.tracing.programs import get_program

        cfg = self.cfg
        self.programs = [get_program(n) for n in cfg["programs"]]
        cw, ci = cfg["trace_caps"]
        sweep = cfg["sweep"]
        scfg = GCLSamplerConfig(
            cap_warps=cw, cap_instr=ci, k_max=sweep["k_max"],
            rgcn=rgcn_config(cfg, self.rgcn_overrides),
            train=GCLTrainConfig(seed=program_seed(self.seed)),
            ingest=IngestConfig(cache=False))
        self.method = GCLMethod(scfg)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed % 2**32),
                                 self.seed >> 32)
        init = jax.jit(lambda k: reference.init_encoder(k, cfg["rgcn"],
                                                        cfg["vocab"]))
        self.params = jax.block_until_ready(init(key))
        self.method.sampler.params = self.params
        if warm:  # the window's shapes are the suite's: one pass warms them
            for prog in self.programs:
                self._one(prog)

    def _one(self, prog, plan: bool = True):
        sampler = self.method.sampler
        sampler.trainer._embed_cache.clear()
        sampler.ingest._memo.clear()
        with self.spans("prepare"):
            art = self.method.run_prepare(prog)
        if not plan:
            return art, None
        with self.spans("plan"), self._precision():
            return art, self.method.plan_batch([(prog, art)])[0]

    def _precision(self):
        """The K-sweep's matrix-product precision (``sweep.matmul_precision``;
        without it XLA's default stands)."""
        import jax

        p = self.cfg["sweep"].get("matmul_precision")
        return jax.default_matmul_precision(p) if p else nullcontext()

    # -- window ---------------------------------------------------------------
    def run_window(self, seconds: float) -> dict:
        """Whole suite passes, each in a fresh seeded order, until
        ``seconds`` have passed; the pass that straddles the end completes,
        so every seed does the same work in another order."""
        from repro.core.clustering import engine_stats

        rng = np.random.default_rng([self.seed, 1])
        attempted = failed = invocations = 0
        self.errors: list = []
        builds0 = engine_stats()["builds"]
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            for i in rng.permutation(len(self.programs)):
                i = int(i)
                attempted += 1
                try:
                    art, plan = self._one(self.programs[i])
                except Exception as e:  # counted; the run is not correct
                    failed += 1
                    self.errors.append(f"{self.programs[i].name}: {e!r}")
                    continue
                # embeddings are kept for each program's first plan only:
                # those are the ones the comparison re-encodes
                emb = (None if any(j == i for j, _, _ in self.done)
                       else np.asarray(art.payload["embeddings"]))
                self.done.append((i, emb, plan))
                invocations += len(self.programs[i])
        self.elapsed = time.perf_counter() - t0
        self.sweep_builds = engine_stats()["builds"] - builds0
        self.invocations = invocations
        return {"attempted": attempted, "failed": failed,
                "elapsed_s": self.elapsed, "invocations": invocations}

    def end_to_end(self) -> dict:
        return {"plan_invocations_per_s": self.invocations / self.elapsed}

    def notes(self) -> list:
        out = [f"error {e}" for e in self.errors]
        out.append(f"sweep executables built in the window: "
                   f"{self.sweep_builds}")
        ing = self.method.sampler.ingest.stats
        out.append(f"ingest (whole run): kernels {ing['kernels']} traced "
                   f"{ing['traced']} memo_hits {ing['memo_hits']} "
                   f"store_hits {ing['store_hits']}")
        out.append(f"embed (last program): "
                   f"{self.method.sampler.trainer.embed_stats}")
        seen = {}
        for i, _, plan in self.done:
            seen.setdefault(i, plan)
        for i, plan in sorted(seen.items()):
            out.append(f"plan {self.programs[i].name}: K={plan.num_clusters} "
                       f"mode={plan.extra.get('mode')} "
                       f"sil={plan.extra.get('sil')!r}")
        return out

    # -- the programs' distinct graphs -----------------------------------------
    def _points(self, i: int) -> tuple:
        """Program ``i``'s distinct graphs, truncated to the packer's caps,
        and for each invocation the index of its graph.  The tracer draws
        a trace from the kernel's template, parameters and seed alone, so
        invocations that share those share a graph."""
        from repro.core.graphs import build_kernel_graph

        if i not in self._graphs:
            cw, ci = self.cfg["trace_caps"]
            caps = self.cfg["pack_caps"]
            index, graphs, inv = {}, [], []
            for k in self.programs[i].kernels:
                key = f"{k.template}|{sorted(k.params.items())}|{k.seed}"
                if key not in index:
                    index[key] = len(graphs)
                    graphs.append(reference.truncate(
                        graph_dict(build_kernel_graph(k.trace(cw, ci))),
                        caps["max_nodes_per_graph"],
                        caps["max_edges_per_graph"]))
                inv.append(index[key])
            self._graphs[i] = (graphs, np.array(inv))
        return self._graphs[i]

    # -- per-layer inputs -------------------------------------------------------
    def layer_inputs(self) -> dict:
        """Operations and bytes of the window's work: the encoder over each
        program's distinct graphs, the K-sweep over each program."""
        cfg = self.cfg
        sw = cfg["sweep"]
        d = cfg["rgcn"]["dims"][-1]
        per_prog = {}
        for i in {i for i, _, _ in self.done}:
            graphs, _ = self._points(i)
            nodes = sum(len(g["token"]) for g in graphs)
            edges = sum(len(g["edge_src"]) for g in graphs)
            n = len(self.programs[i])
            per_prog[i] = {
                "encode_flops": counts.encoder_flops(nodes, edges, cfg["rgcn"]),
                "encode_bytes": counts.encoder_bytes(nodes, edges, len(graphs),
                                                     cfg["rgcn"], cfg["vocab"]),
                "sweep_flops": counts.sweep_flops(n, d, sw["k_max"],
                                                  sw["iters"], sw["sil_cap"],
                                                  sw["tiny_n"]),
                "sweep_bytes": counts.sweep_bytes(n, d, sw["k_max"],
                                                  sw["tiny_n"])}
        total = {k: 0.0 for k in ("encode_flops", "encode_bytes",
                                  "sweep_flops", "sweep_bytes")}
        for i, _, _ in self.done:
            for k in total:
                total[k] += per_prog[i][k]
        return total

    def free(self) -> None:
        self.method = None
        self.params_host = reference.params_f64(self.params)
        self.params = None

    # -- comparison -------------------------------------------------------------
    def check(self) -> list:
        """Each program's first result of the window against the float64
        reference on the same graphs: the embeddings of every invocation
        (``emb_gap``); the float64 silhouette of the plan's labels against
        the reference sweep's (``sweep_gap``); the plan's silhouette at its
        K against the float64 silhouette of its labels (``sil_gap``).
        Besides, every plan's representatives and K against the plan rules
        applied to its own labels and scores (``plan_mismatches``).  The
        limits file names the numbers compared; the others are kept in
        ``observed`` and printed."""
        sw = self.cfg["sweep"]
        R = self.cfg["rgcn"]["num_relations"]
        mismatches = sum(plan_mismatches(plan, self.programs[i], sw)
                         for i, _, plan in self.done)
        first = {i: (emb, plan) for i, emb, plan in self.done
                 if emb is not None}
        self.ref, self.ref_sweep = {}, {}
        for i in sorted(first):
            graphs, inv = self._points(i)
            u = np.stack([reference.encode_graph(self.params_host, g, R)
                          for g in graphs])
            self.ref[i] = (u, inv)
            self.ref_sweep[i] = reference.sweep(u, inv,
                                                program_seed(self.seed), sw)
        got = self._gaps({i: (emb, plan.labels, plan.extra)
                          for i, (emb, plan) in first.items()}, log=True)
        got["plan_mismatches"] = float(mismatches)
        checks, self.observed = compared(self.cell.limits, got)
        return checks

    def _gaps(self, outputs: dict, log: bool = False) -> dict:
        """``emb_gap``, ``sweep_gap`` and ``sil_gap`` of ``outputs``
        (program -> embeddings, labels, plan extra) against the reference.
        ``emb_gap``: the widest gap of any element over the median distinct
        point's largest reference element.  ``sweep_gap``: the most by which
        the float64 silhouette of a program's labels falls short of the
        reference sweep's choice (0 for K=1).  Programs of at most
        ``tiny_n`` invocations take the host rule and no sweep."""
        sw = self.cfg["sweep"]
        seed = program_seed(self.seed)
        emb_gap = sweep_gap = sil_gap = 0.0
        scales = float(np.median(np.concatenate(
            [np.max(np.abs(u), axis=1) for u, _ in self.ref.values()])))
        for i, (emb, labels, extra) in sorted(outputs.items()):
            u, inv = self.ref[i]
            emb_gap = max(emb_gap, float(np.max(np.abs(emb - u[inv]))) / scales)
            ref = self.ref_sweep[i]
            if ref is None:
                continue
            ref_q = ref[1]["sil"] if ref[1]["mode"] == "silhouette" else 0.0
            q = reference.silhouette_of(u, inv, labels, seed, sw["sil_cap"])
            sweep_gap = max(sweep_gap, ref_q - q)
            if extra.get("mode") == "silhouette":
                sil_gap = max(sil_gap, abs(extra["sil"] - q))
            if log:
                harness.log(
                    f"sweep {self.programs[i].name}: n {len(inv)} points "
                    f"{len(u)}; K {int(np.max(labels)) + 1} "
                    f"{extra.get('mode')} sil {extra.get('sil')!r}, float64 "
                    f"sil of its labels {q!r}; reference K {ref[1]['k']} "
                    f"{ref[1]['mode']} sil {ref[1]['sil']!r}")
        return {"emb_gap": emb_gap, "sweep_gap": sweep_gap,
                "sil_gap": sil_gap}

    def reference_gap(self, mode: str) -> dict:
        """The numbers ``check`` compares, with the reference computed with
        its products in ``mode`` (see ``reference._mm``; "<encoder>+<sweep>"
        gives the two their own) put in the program's place (the control's
        readings)."""
        R = self.cfg["rgcn"]["num_relations"]
        seed = program_seed(self.seed)
        enc_mode, _, sweep_mode = mode.partition("+")
        outputs = {}
        for i, (_, inv) in self.ref.items():
            graphs, _ = self._points(i)
            z = np.stack([reference.encode_graph(self.params_host, g, R,
                                                 enc_mode or None)
                          for g in graphs])
            res = reference.sweep(z, inv, seed, self.cfg["sweep"],
                                  sweep_mode or enc_mode or None)
            labels, extra = res if res else (np.zeros(len(inv), int), {})
            outputs[i] = (z[inv], labels, extra)
        return self._gaps(outputs)


def plan_mismatches(plan, program, sw: dict) -> int:
    """Departures of one plan from the plan rules applied to its own
    labels and scores: each cluster's representative is its first
    invocation, K is the rule's choice from the per-K silhouettes (or 1
    below the floor)."""
    seqs = np.array([k.seq for k in program.kernels])
    ref_reps = reference.representatives(plan.labels, seqs)
    got = {int(c): sorted(int(r) for r in v) for c, v in plan.reps.items()}
    out = sum(got.get(c) != [r] for c, r in ref_reps.items())
    out += len(set(got) - set(ref_reps))
    mode = plan.extra.get("mode")
    if mode == "silhouette":
        k_ref = reference.choose_k(plan.extra["scores"], sw["sil_floor"],
                                   sw["tie_tol"])
        out += not (k_ref is not None
                    and plan.extra["sil"] == plan.extra["scores"][k_ref]
                    and plan.num_clusters <= k_ref)
    elif mode == "weak->K=1":
        out += not (plan.extra["sil"] < sw["sil_floor"]
                    and plan.num_clusters == 1)
    return int(out)
