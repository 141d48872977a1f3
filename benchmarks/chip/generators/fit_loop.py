"""Closed loop of whole contrastive fits: ``ContrastiveTrainer.fit`` (the
scan engine) runs back to back on one pool of kernel graphs built in
set-up, each fit from the seed's initialisation.

Traffic keys: ``generator`` ("fit_loop").  The configuration gives the
programs, the trace window, the pool (how many graphs, drawn with which
sample seed from all the programs' invocations), the encoder widths and
the training settings.

Each fit's own seed is one of the configuration's ``schedule.seeds``, all
of which do the same device work on different data (``schedule_work``):
the window's first fit takes the one the run's seed picks, and each later
fit the next in the list, so that every run spreads its fits over the
same schedules.  Set-up builds the pool and runs the first steps of one
listed fit, the one that reaches every packed shape in the fewest steps
(``warm_seed``, ``warm_steps``): the listed fits share their shapes, so
that compiles every executable the window runs, and nothing compiles
inside it.
"""

from __future__ import annotations

import dataclasses
import json
import time

import numpy as np

from benchmarks.chip import counts, reference
from benchmarks.chip.harness import Check, compared
from benchmarks.chip.program import graph_dict, program_seed, rgcn_config


def train_config(cfg: dict, seed: int):
    from repro.config import TrainConfig
    from repro.core.train import GCLTrainConfig

    t = cfg["train"]
    return GCLTrainConfig(
        steps=t["steps"], batch_size=t["batch_size"], tau=t["tau"],
        val_fraction=t["val_fraction"], seed=seed, scan_chunk=t["scan_chunk"],
        opt=TrainConfig(learning_rate=t["learning_rate"],
                        weight_decay=t["weight_decay"], beta1=t["beta1"],
                        beta2=t["beta2"], eps=t["eps"],
                        warmup_steps=t["warmup_steps"],
                        total_steps=t["total_steps"], schedule=t["schedule"],
                        grad_clip=t["grad_clip"]))


def build_pool(cfg: dict) -> list:
    """The configuration's pool of graphs: ``pool.graphs`` invocations drawn
    without replacement, by ``pool.sample_seed``, from all the programs."""
    from repro.core.graphs import build_kernel_graph
    from repro.tracing.programs import get_program

    progs = [get_program(n) for n in cfg["programs"]]
    pairs = [(pi, ki) for pi, p in enumerate(progs) for ki in range(len(p))]
    rng = np.random.default_rng(cfg["pool"]["sample_seed"])
    pick = rng.choice(len(pairs), cfg["pool"]["graphs"], replace=False)
    cw, ci = cfg["trace_caps"]
    return [build_kernel_graph(progs[pairs[j][0]].kernels[pairs[j][1]]
                               .trace(cw, ci)) for j in pick]


def packed_sizes(graphs: list, cfg: dict) -> list:
    """(nodes, edges, warps) of each graph as the packer holds it."""
    caps = cfg["pack_caps"]
    out = []
    for g in graphs:
        t = reference.truncate(graph_dict(g), caps["max_nodes_per_graph"],
                               caps["max_edges_per_graph"])
        out.append((len(t["token"]), len(t["edge_src"]), int(g.n_warps)))
    return out


def _packed_shapes(sizes: list, batches) -> list:
    """(nodes, edges, warps) buckets of each batch of graph indices packed
    as one.  ``sizes``: (nodes, edges, warps) of each graph after
    truncation."""
    tot = np.asarray(sizes)[np.asarray(batches)].sum(axis=1)
    return [(reference.pow2_at_least(n, reference.NODE_FLOOR),
             reference.pow2_at_least(max(e, 1), reference.EDGE_FLOOR),
             reference.pow2_at_least(max(w, 1), reference.WARP_FLOOR))
            for n, e, w in tot.tolist()]


def _split(sizes: list, seed: int, cfg: dict) -> tuple:
    """(training, held-out, per-step selections) of the fit from ``seed``."""
    t = cfg["train"]
    return reference.split_and_selections(
        len(sizes), t["steps"], t["batch_size"], t["val_fraction"], seed)


def _step_shapes(sizes: list, seed: int, cfg: dict) -> list:
    """The packed shape of each step of the fit's schedule from ``seed``."""
    return _packed_shapes(sizes, _split(sizes, seed, cfg)[2])


def _chunk_len(steps: int, cfg: dict) -> int:
    return min(cfg["train"]["scan_chunk"], reference.pow2_at_least(steps, 1))


def warm_steps(sizes: list, seed: int, cfg: dict) -> int:
    """The fewest leading steps of the fit's schedule that reach every
    packed shape it uses, with scan chunks of the whole fit's length: a fit
    of that many steps compiles every executable the whole fit runs."""
    keys = _step_shapes(sizes, seed, cfg)
    n = next(i + 1 for i in range(len(keys))
             if set(keys[:i + 1]) == set(keys))
    while _chunk_len(n, cfg) != _chunk_len(len(keys), cfg):
        n += 1
    return n


def warm_seed(sizes: list, cfg: dict) -> int:
    """The listed schedule seed whose fit reaches every packed shape in the
    fewest leading steps (the first such in the list)."""
    seeds = cfg["schedule"]["seeds"]
    return min(seeds, key=lambda s: warm_steps(sizes, s, cfg))


def schedule_chunks(sizes: list, seed: int, cfg: dict) -> list:
    """The device work of a fit's schedule from ``seed``: how many scan
    chunks run at each packed shape.  The trainer groups consecutive steps
    of one shape into segments and pads each segment's last chunk to the
    chunk length, so the order of the shapes sets the work."""
    return _chunk_counts(_step_shapes(sizes, seed, cfg), cfg)


def _chunk_counts(keys: list, cfg: dict) -> list:
    chunk = _chunk_len(len(keys), cfg)
    out: dict = {}
    i = 0
    while i < len(keys):
        j = i
        while j < len(keys) and keys[j] == keys[i]:
            j += 1
        out[keys[i]] = out.get(keys[i], 0) + -(-(j - i) // chunk)
        i = j
    return sorted([list(k), v] for k, v in out.items())


def schedule_work(sizes: list, seed: int, cfg: dict) -> dict:
    """All the device work of a fit that its seed sets, as the
    configuration's ``schedule`` states it: the scan chunks at each packed
    shape (``schedule_chunks``), the live steps at each, and the packed
    shape of the held-out batch that each fit's eval runs on."""
    _, held_out, sel = _split(sizes, seed, cfg)
    keys = _packed_shapes(sizes, sel)
    live: dict = {}
    for k in keys:
        live[k] = live.get(k, 0) + 1
    return {"chunks": _chunk_counts(keys, cfg),
            "live": sorted([list(k), v] for k, v in live.items()),
            "eval": list(_packed_shapes(sizes, [held_out])[0])}


def derive_schedule(sizes: list, cfg: dict, seeds) -> tuple:
    """The commonest ``schedule_work`` among ``seeds`` and the seeds that
    share it, in order; a tie goes to the fewer scan chunks, then to the
    work seen first."""
    groups: dict = {}
    for seed in seeds:
        w = schedule_work(sizes, seed, cfg)
        groups.setdefault(json.dumps(w, sort_keys=True), []).append(seed)
    best = max(groups.items(), key=lambda kv: (
        len(kv[1]), -sum(c for _, c in json.loads(kv[0])["chunks"])))
    return json.loads(best[0]), best[1]


class Generator:
    #: what names the window's idle gaps: the program's own host work in a
    #: fit (``repro.telemetry``).  A gap outside them is the held-out
    #: eval's packing, the next fit's initialisation or the loop itself.
    span_names = ("fit.plan_epoch", "fit.wait", "fit.pack", "fit.keys")

    def __init__(self, cell, seed: int, spans, rgcn_overrides=None):
        self.cell, self.cfg, self.seed, self.spans = (
            cell, cell.config, seed, spans)
        self.rgcn_overrides = rgcn_overrides
        self.first = None          # (params, info) of the window's first fit
        self.fit_seeds: list = []  # schedule seed of each completed fit

    def setup(self, warm: bool = True, pool: list | None = None) -> None:
        """``pool``: graphs already built for this configuration (the
        control readings build them once for many seeds)."""
        from repro.core.train import ContrastiveTrainer

        self.graphs = pool if pool is not None else build_pool(self.cfg)
        self.rc = rgcn_config(self.cfg, self.rgcn_overrides)
        seeds = self.cfg["schedule"]["seeds"]
        start = program_seed(self.seed) % len(seeds)
        self.tcs = [train_config(self.cfg, s)
                    for s in seeds[start:] + seeds[:start]]
        self.tc = self.tcs[0]      # the window's first fit, the one checked
        self.trainer_cls = ContrastiveTrainer
        if warm:
            sizes = packed_sizes(self.graphs, self.cfg)
            w = warm_seed(sizes, self.cfg)
            self.trainer_cls(self.rc, dataclasses.replace(
                train_config(self.cfg, w),
                steps=warm_steps(sizes, w, self.cfg))).fit(self.graphs)

    def run_window(self, seconds: float) -> dict:
        attempted = failed = steps = 0
        self.errors: list = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            tc = self.tcs[attempted % len(self.tcs)]
            attempted += 1
            try:
                with self.spans("fit"):
                    params, info = self.trainer_cls(self.rc, tc).fit(
                        self.graphs)
            except Exception as e:  # counted, and the run is not correct
                failed += 1
                self.errors.append(repr(e))
                continue
            if self.first is None and tc is self.tc:
                self.first = (params, info)
            self.fit_seeds.append(tc.seed)
            steps += len(info["history"])
        self.elapsed = time.perf_counter() - t0
        self.steps = steps
        return {"attempted": attempted, "failed": failed,
                "elapsed_s": self.elapsed, "steps": steps}

    def end_to_end(self) -> dict:
        return {"fit_steps_per_s": self.steps / self.elapsed}

    def notes(self) -> list:
        out = [f"error {e}" for e in self.errors]
        if self.first is not None:
            info = self.first[1]
            out.append(
                f"fit: steps {len(info['history'])} trunc_nodes "
                f"{info['trunc_nodes']} step_compiles {info['step_compiles']} "
                f"host_syncs {info['host_syncs']} scan_chunks "
                f"{info.get('scan_chunks')} of {info.get('chunk_len')} steps "
                f"bucket_keys {info['bucket_keys']} val_loss "
                f"{info.get('val_loss')!r}")
            out.append("fit: losses of steps 0-4 "
                       f"{[h['loss'] for h in info['history'][:5]]}")
        return out

    def _sizes(self) -> list:
        caps = self.cfg["pack_caps"]
        return [(min(g.n_nodes, caps["max_nodes_per_graph"]),
                 len(reference.truncate(graph_dict(g),
                                        caps["max_nodes_per_graph"],
                                        caps["max_edges_per_graph"])
                     ["edge_src"]))
                for g in self.graphs]

    def layer_inputs(self) -> dict:
        """Operations of the window's fits: every step's batch (both views,
        forward and backward) and the held-out forward of each fit."""
        rc = self.cfg["rgcn"]
        sizes = self._sizes()

        def per_fit(seed):
            _, val_idx, sel = _split(sizes, seed, self.cfg)
            vn = sum(sizes[j][0] for j in val_idx)
            ve = sum(sizes[j][1] for j in val_idx)
            return (sum(counts.train_step_flops([sizes[j] for j in s], rc)
                        for s in sel)
                    + 2 * (counts.encoder_flops(vn, ve, rc)
                           + counts.projection_flops(len(val_idx), rc)))

        flops = {s: per_fit(s) for s in set(self.fit_seeds)}
        return {"work_flops": sum(flops[s] for s in self.fit_seeds),
                "elapsed_s": self.elapsed}

    def free(self) -> None:
        if self.first is not None:
            import jax

            self.first = (jax.tree_util.tree_map(np.asarray, self.first[0]),
                          self.first[1])

    def check(self) -> list:
        """The window's first fit against the plain reference run of the
        same schedule (``fit_gaps``).  The limits file names the numbers
        compared; the others are kept in ``observed`` and printed."""
        lim = self.cell.limits
        self.observed = {}
        if self.first is None:
            return [Check("fits_completed", 0.0, -1.0)]
        params, info = self.first
        caps = self.cfg["pack_caps"]
        graphs = [reference.truncate(graph_dict(g), caps["max_nodes_per_graph"],
                                     caps["max_edges_per_graph"])
                  for g in self.graphs]
        ref = reference.TrainReference(self.cfg)
        self.ref_fit = ref.fit(graphs, self.tc.seed)
        self.ref_graphs = graphs
        checks, self.observed = compared(
            lim, fit_gaps(params, info, *self.ref_fit))
        return checks

    def reference_gap(self, operand_dtype: str) -> dict:
        """The numbers ``check`` compares, with the reference computed with
        its matrix-product operands in ``operand_dtype`` put in the
        program's place (the control's readings)."""
        p0, p_low, losses, gnorms, _ = reference.TrainReference(
            self.cfg, operand_dtype).fit(self.ref_graphs, self.tc.seed)
        info = {"history": [{"loss": a, "grad_norm": b}
                            for a, b in zip(losses, gnorms)]}
        return fit_gaps(p_low, info, *self.ref_fit)


def fit_gaps(params, info, p0, p_ref, losses, gnorms, g0) -> dict:
    """The numbers a fit is compared by: the widest relative gap of the
    losses of steps 0-2; the relative gap of step 0's gradient norm; each
    leaf's gap between the program's and the reference's norm of its change
    over the fit, against the larger of that leaf's reference change and
    the median leaf's, at the median leaf (``param_change_gap``) and at the
    worst (``param_change_worst_leaf``)."""
    import jax

    hist = info["history"]
    loss_gap = max(abs(hist[i]["loss"] - losses[i]) / abs(losses[i])
                   for i in range(3))
    gnorm_gap = abs(hist[0]["grad_norm"] - gnorms[0]) / gnorms[0]
    flat = jax.tree_util.tree_flatten_with_path
    leaves_p = {jax.tree_util.keystr(k): np.asarray(v, np.float64)
                for k, v in flat(params)[0]}
    leaves_0 = {jax.tree_util.keystr(k): np.asarray(v, np.float64)
                for k, v in flat(p0)[0]}
    leaves_r = {jax.tree_util.keystr(k): np.asarray(v, np.float64)
                for k, v in flat(p_ref)[0]}
    grads = {jax.tree_util.keystr(k): float(np.linalg.norm(np.asarray(v)))
             for k, v in flat(g0)[0]}
    g_med = float(np.median(list(grads.values())))
    # leaves whose gradient is nought to rounding move by round-off alone
    kept = [k for k in leaves_r if grads[k] >= 1e-3 * g_med]
    moved_p = {k: float(np.linalg.norm(leaves_p[k] - leaves_0[k])) for k in kept}
    moved_r = {k: float(np.linalg.norm(leaves_r[k] - leaves_0[k])) for k in kept}
    med = float(np.median(list(moved_r.values())))
    gaps = [abs(moved_p[k] - moved_r[k]) / max(moved_r[k], med) for k in kept]
    return {"loss_gap": loss_gap, "grad_norm_gap": gnorm_gap,
            "param_change_gap": float(np.median(gaps)),
            "param_change_worst_leaf": max(gaps)}
