"""Plain references for the benchmark's `correct`.

Written from the published description and the configuration files alone:
nothing here imports the system under test or takes weights it made.

- ``encode_graph``: the RGCN encoder forward (basis decomposition, per
  relation degree normalisation, self loop, LayerNorm, ReLU, warp-mean then
  graph-mean readout) on ONE unpadded graph, in float64 numpy.
- ``sweep``: the K-sweep (kmeans++ seeding, Lloyd, mean silhouette on the
  configuration's deterministic subsample, the K rule) in float64 numpy;
  ``silhouette_of``: the float64 silhouette of any labelling.
- ``representatives`` / ``choose_k``: the plan rules (first invocation of
  each cluster; largest silhouette, smaller K within the tie tolerance,
  K=1 below the floor).
- ``TrainReference``: the contrastive fit (two augmented views, InfoNCE,
  AdamW with warm-up and cosine decay, global-norm clipping) in plain
  ``jax.numpy`` at 'highest' matmul precision, on batches packed in the
  same flat layout and drawing the same random masks from the same keys.

A graph is a dict of numpy arrays: node_type, token, pc_norm, vstats,
warp_id (per node), edge_src, edge_dst, edge_type (per edge), n_warps.
"""

from __future__ import annotations

import numpy as np

# ---------------------------------------------------------------------------
# encoder forward, float64, one graph
# ---------------------------------------------------------------------------


def truncate(g: dict, max_nodes: int, max_edges: int) -> dict:
    """The packer's per-graph caps: nodes past ``max_nodes`` go, with every
    edge touching them, then edges past ``max_edges`` (in edge order)."""
    n = min(len(g["token"]), max_nodes)
    src, dst, et = g["edge_src"], g["edge_dst"], g["edge_type"]
    if n < len(g["token"]):
        keep = (src < n) & (dst < n)
        src, dst, et = src[keep], dst[keep], et[keep]
    src, dst, et = src[:max_edges], dst[:max_edges], et[:max_edges]
    out = {k: g[k][:n] for k in ("node_type", "token", "pc_norm", "vstats",
                                 "warp_id")}
    out.update(edge_src=src, edge_dst=dst, edge_type=et,
               n_warps=g["n_warps"])
    return out


def _positional(pc_norm, dim):
    half = dim // 2
    freqs = np.exp(np.arange(half) * (-np.log(10_000.0) / half))
    ang = pc_norm[:, None] * 1000.0 * freqs
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=1)


def node_features(p: dict, g: dict) -> np.ndarray:
    """64-d input features: instruction = opcode embedding + positional code
    of the normalised PC; variable = 32-d kind embedding ++ 8 signed-sqrt
    value statistics, zero padded; pseudo = 16-d kind embedding, padded."""
    tok = g["token"].astype(np.int64)
    nt = g["node_type"]
    e_i, e_v, e_p = p["embed_instr"], p["embed_var"], p["embed_pseudo"]
    n = len(tok)
    instr = e_i[np.clip(tok, 0, len(e_i) - 1)] + _positional(
        g["pc_norm"].astype(np.float64), e_i.shape[1])
    vs = g["vstats"].astype(np.float64)
    var = np.concatenate([e_v[np.clip(tok, 0, len(e_v) - 1)],
                          np.sign(vs) * np.sqrt(np.abs(vs)) * 0.3,
                          np.zeros((n, 64 - e_v.shape[1] - vs.shape[1]))],
                         axis=1)
    pse = np.concatenate([e_p[np.clip(tok, 0, len(e_p) - 1)],
                          np.zeros((n, 64 - e_p.shape[1]))], axis=1)
    return np.where((nt == 0)[:, None], instr,
                    np.where((nt == 1)[:, None], pse, var))


#: the three-pass product: each operand is a bfloat16 high part plus a
#: bfloat16 low part, and the low-by-low term is dropped
BF16X3 = "bfloat16x3"


def _round(x, dtype: str) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x).astype(np.float32).astype(
        getattr(ml_dtypes, dtype)).astype(np.float64)


def _rounder(operand_dtype):
    """Rounds matrix-product operands to ``operand_dtype`` (an ml_dtypes
    name such as "bfloat16" or "float8_e4m3fn") and back; None keeps
    float64; ``BF16X3`` keeps what the three-pass product sees of one
    operand that is multiplied by an exact one (a one-hot)."""
    if operand_dtype is None:
        return lambda x: x
    if operand_dtype == BF16X3:
        def split(x):
            hi = _round(x, "bfloat16")
            return hi + _round(np.asarray(x, np.float64) - hi, "bfloat16")
        return split
    return lambda x: _round(x, operand_dtype)


def _mm(a, b, mode: str | None = None) -> np.ndarray:
    """``a @ b`` as a matrix unit computes it with operands in ``mode``."""
    if mode != BF16X3:
        r = _rounder(mode)
        return r(a) @ r(b)
    a_hi, b_hi = _round(a, "bfloat16"), _round(b, "bfloat16")
    a_lo = _round(np.asarray(a, np.float64) - a_hi, "bfloat16")
    b_lo = _round(np.asarray(b, np.float64) - b_hi, "bfloat16")
    return a_hi @ b_hi + a_hi @ b_lo + a_lo @ b_hi


def encode_graph(p: dict, g: dict, num_relations: int,
                 operand_dtype: str | None = None) -> np.ndarray:
    """Kernel embedding z_k of one graph (eval mode: no dropout, no noise).
    ``p`` holds float64 numpy leaves.  ``operand_dtype`` (see ``_mm``) sets
    how the two matrix products of each layer are taken (a lower-precision
    reference for the control)."""
    h = node_features(p, g)
    n = len(h)
    src = g["edge_src"].astype(np.int64)
    dst = g["edge_dst"].astype(np.int64)
    et = g["edge_type"].astype(np.int64)
    deg = np.zeros(n * num_relations)
    np.add.at(deg, dst * num_relations + et, 1.0)
    norm = 1.0 / np.maximum(deg[dst * num_relations + et], 1.0)
    for lp in p["layers"]:
        w = lp["comb"][et] * norm[:, None]                     # (E, nb)
        s = np.zeros((n,) + lp["basis"].shape[:2])            # (N, nb, D)
        np.add.at(s, dst, w[:, :, None] * h[src][:, None, :])
        nb, din, dout = lp["basis"].shape
        out = (_mm(s.reshape(n, nb * din), lp["basis"].reshape(nb * din, dout),
                   operand_dtype)
               + _mm(h, lp["w0"], operand_dtype) + lp["b"])
        mu = out.mean(1, keepdims=True)
        var = out.var(1, keepdims=True)
        out = (out - mu) / np.sqrt(var + 1e-5) * lp["ln_scale"] + lp["ln_bias"]
        h = np.maximum(out, 0.0)
    wid = g["warp_id"].astype(np.int64)
    nw = int(g["n_warps"])
    sums = np.zeros((nw, h.shape[1]))
    np.add.at(sums, wid, h)
    cnt = np.bincount(wid, minlength=nw).astype(np.float64)
    live = cnt > 0
    return (sums[live] / cnt[live, None]).mean(0)


def params_f64(params) -> dict:
    """Host float64 copy of an encoder parameter tree."""
    if isinstance(params, dict):
        return {k: params_f64(v) for k, v in params.items()}
    if isinstance(params, (list, tuple)):
        return [params_f64(v) for v in params]
    return np.asarray(params, np.float64)


# ---------------------------------------------------------------------------
# plan rules
# ---------------------------------------------------------------------------


def representatives(labels, seqs) -> dict:
    """Cluster -> the member with the smallest invocation sequence number."""
    labels, seqs = np.asarray(labels), np.asarray(seqs)
    reps = {}
    for c in np.unique(labels):
        members = np.nonzero(labels == c)[0]
        reps[int(c)] = int(members[np.argmin(seqs[members])])
    return reps


# ---------------------------------------------------------------------------
# K-sweep, float64
#
# A program's points are given as distinct points ``u`` (m, d) and, for each
# of its n invocations, the index of its point (``inv``): identical
# invocations have identical embeddings, so every sum over points is a sum
# over distinct points weighted by how many invocations share each.
# ---------------------------------------------------------------------------


def _sqdist(a, c, mode=None) -> np.ndarray:
    """Squared distances in Gram form, |a|^2 - 2 a.c + |c|^2, clipped at 0:
    the norms exact, the cross product as ``_mm`` takes it."""
    a2 = np.sum(a * a, axis=1)
    c2 = np.sum(c * c, axis=1)
    return np.maximum(a2[:, None] - 2.0 * _mm(a, c.T, mode) + c2[None, :], 0.0)


def subsample(n: int, seed: int, sil_cap: int) -> np.ndarray:
    """The points a silhouette is taken on: all, or above ``sil_cap`` the
    ``numpy.random.default_rng(seed).choice(n, sil_cap, replace=False)``
    draw."""
    if n > sil_cap:
        return np.random.default_rng(seed).choice(n, sil_cap, replace=False)
    return np.arange(n)


def _silhouette(dist, lab, w, mode=None):
    """Mean silhouette of weighted groups of identical points: ``dist``
    (g, g) between the groups' points, ``lab`` (g,) the group's cluster,
    ``w`` (g,) its points.  A point alone in its cluster scores 0; None
    where fewer than two clusters are present."""
    _, lab = np.unique(lab, return_inverse=True)
    if lab.max(initial=0) < 1:
        return None
    g = np.arange(len(lab))
    onehot = np.zeros((len(lab), lab.max() + 1))
    onehot[g, lab] = w
    sums = _rounder(mode)(dist) @ onehot       # distance to each cluster
    cnt = onehot.sum(0)
    own = cnt[lab]
    a = sums[g, lab] / np.maximum(own - 1, 1)
    other = sums / cnt[None, :]
    other[g, lab] = np.inf
    b = other.min(axis=1)
    s = np.where(own > 1, (b - a) / np.maximum(np.maximum(a, b), 1e-12), 0.0)
    return float(np.sum(w * s) / np.sum(w))


def silhouette_of(u, inv, labels, seed: int, sil_cap: int) -> float:
    """Float64 mean silhouette of any labelling of the points ``u[inv]``
    on the configuration's subsample; 0 where it holds one cluster."""
    u = np.asarray(u, np.float64)
    sub = subsample(len(inv), seed, sil_cap)
    pairs, w = np.unique(np.stack([np.asarray(inv)[sub],
                                   np.asarray(labels)[sub]], 1),
                         axis=0, return_counts=True)
    pts = u[pairs[:, 0]]
    s = _silhouette(np.sqrt(_sqdist(pts, pts)), pairs[:, 1],
                    w.astype(np.float64))
    return 0.0 if s is None else s


def _kmeanspp(u, inv, k: int, seed: int) -> np.ndarray:
    """kmeans++ seeding (D^2 sampling with ``default_rng(seed)``), from
    exact differences; a uniform draw once every point is taken."""
    rng = np.random.default_rng(seed)
    n = len(inv)
    idx = [int(rng.integers(n))]
    du = np.sum((u - u[inv[idx[0]]]) ** 2, axis=1)
    for _ in range(1, k):
        d = du[inv]
        tot = d.sum()
        if not np.isfinite(tot) or tot <= 1e-20:
            nxt = int(rng.integers(n))
        else:
            nxt = int(rng.choice(n, p=d / tot))
        idx.append(nxt)
        du = np.minimum(du, np.sum((u - u[inv[nxt]]) ** 2, axis=1))
    return np.array(idx)


def _lloyd(u, w, cent, iters: int, mode=None) -> np.ndarray:
    """``iters`` Lloyd steps from ``cent`` (an empty cluster keeps its
    centroid), then the final assignment; ties go to the lower centroid."""
    xu = _rounder(mode)(u)
    k = len(cent)
    for _ in range(iters):
        lab = np.argmin(_sqdist(u, cent, mode), axis=1)
        sums = np.zeros_like(cent)
        np.add.at(sums, lab, w[:, None] * xu)
        cnt = np.bincount(lab, weights=w, minlength=k)
        cent = np.where(cnt[:, None] > 0, sums / np.maximum(cnt, 1)[:, None],
                        cent)
    return np.argmin(_sqdist(u, cent, mode), axis=1)


def sweep(u, inv, seed: int, sw: dict, mode: str | None = None):
    """The paper's K-selection over the points ``u[inv]``: for every K in
    2..min(k_max, n-1), ``iters`` Lloyd steps from the first K kmeans++
    seeds, scored by the mean silhouette on the subsample; a K whose
    subsample holds fewer than two clusters is not scored.  Returns
    (labels (n,), info) with info's k, sil, mode and scores, as the rule
    ``choose_k`` picks; None for a program of at most ``tiny_n`` points,
    which no sweep serves.  ``mode`` (see ``_mm``) sets how the distance
    and centroid products are taken (the control's lower precision)."""
    u = np.asarray(u, np.float64)
    inv = np.asarray(inv)
    n, m = len(inv), len(u)
    if n <= max(1, sw["tiny_n"]):
        return None
    w = np.bincount(inv, minlength=m).astype(np.float64)
    ws = np.bincount(inv[subsample(n, seed, sw["sil_cap"])],
                     minlength=m).astype(np.float64)
    live = ws > 0
    k_up = min(sw["k_max"], n - 1)
    init = _kmeanspp(u, inv, k_up, seed)
    dist = np.sqrt(_sqdist(u[live], u[live], mode))
    scores, labs = {}, {}
    for k in range(2, k_up + 1):
        lab = _lloyd(u, w, u[inv[init[:k]]], sw["iters"], mode)
        s = _silhouette(dist, lab[live], ws[live], mode)
        if s is not None:
            scores[k], labs[k] = s, lab
    if not scores:
        return np.zeros(n, int), {"k": 1, "sil": 0.0, "mode": "degenerate"}
    k = choose_k(scores, sw["sil_floor"], sw["tie_tol"])
    if k is None:
        return np.zeros(n, int), {"k": 1, "sil": max(scores.values()),
                                  "mode": "weak->K=1", "scores": scores}
    _, lab = np.unique(labs[k][inv], return_inverse=True)
    return lab, {"k": int(lab.max()) + 1, "sil": scores[k],
                 "mode": "silhouette", "scores": scores}


def choose_k(scores: dict, sil_floor: float, tie_tol: float):
    """Largest silhouette wins, the smallest K within ``tie_tol`` of it;
    None (K=1) when the best is under ``sil_floor``."""
    best = max(scores.values())
    if best < sil_floor:
        return None
    return min(k for k, s in scores.items() if s >= best - tie_tol)


# ---------------------------------------------------------------------------
# contrastive fit, plain jax.numpy
# ---------------------------------------------------------------------------

NODE_FLOOR, EDGE_FLOOR, WARP_FLOOR = 256, 512, 4
NODE_DROP, EDGE_DROP = 0.15, 0.15
#: the six augmentation strategies: one or two of {node drop, edge drop,
#: feature noise}
COMBOS = ((1, 0, 0), (0, 1, 0), (0, 0, 1), (1, 1, 0), (1, 0, 1), (0, 1, 1))


def pow2_at_least(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def pack(graphs: list, num_relations: int) -> dict:
    """Flat layout: graphs' nodes back to back, node axis padded to a power
    of two (floor 256), edges shifted into it and stably sorted by
    destination (floor 512), warps offset per graph (floor 4).  The graph
    axis is exact.  Graphs must already be truncated."""
    nn = [len(g["token"]) for g in graphs]
    ne = [len(g["edge_src"]) for g in graphs]
    nw = [int(g["n_warps"]) for g in graphs]
    no, eo, wo = (np.concatenate([[0], np.cumsum(x)]) for x in (nn, ne, nw))
    P = pow2_at_least(int(no[-1]), NODE_FLOOR)
    Q = pow2_at_least(max(int(eo[-1]), 1), EDGE_FLOOR)
    W = pow2_at_least(max(int(wo[-1]), 1), WARP_FLOOR)
    b = {k: np.zeros(P, np.int32) for k in
         ("node_type", "token", "graph_id", "warp_seg")}
    b.update(pc_norm=np.zeros(P, np.float32), vstats=np.zeros((P, 8), np.float32),
             node_mask=np.zeros(P, np.float32), edge_mask=np.zeros(Q, np.float32),
             warp_graph=np.zeros(W, np.int32))
    for k in ("edge_src", "edge_dst", "edge_type", "edge_graph"):
        b[k] = np.zeros(Q, np.int32)
    for gi, g in enumerate(graphs):
        sl = slice(no[gi], no[gi + 1])
        b["node_type"][sl] = g["node_type"]
        b["token"][sl] = g["token"]
        b["pc_norm"][sl] = g["pc_norm"]
        b["vstats"][sl] = g["vstats"]
        b["graph_id"][sl] = gi
        b["warp_seg"][sl] = g["warp_id"].astype(np.int32) + wo[gi]
        b["node_mask"][sl] = 1.0
        el = slice(eo[gi], eo[gi + 1])
        b["edge_src"][el] = g["edge_src"] + no[gi]
        b["edge_dst"][el] = g["edge_dst"] + no[gi]
        b["edge_type"][el] = g["edge_type"]
        b["edge_graph"][el] = gi
        b["edge_mask"][el] = 1.0
        b["warp_graph"][wo[gi]:wo[gi + 1]] = gi
    used = int(eo[-1])
    order = np.argsort(b["edge_dst"][:used], kind="stable")
    for k in ("edge_src", "edge_dst", "edge_type", "edge_graph", "edge_mask"):
        b[k][:used] = b[k][:used][order]
    b["graph_mask"] = np.ones(len(graphs), np.float32)
    return b


def split_and_selections(n: int, steps: int, batch_size: int,
                         val_fraction: float, seed: int):
    """The fit's data schedule: one seeded permutation, the first fifth held
    out, then one draw of ``batch_size`` training graphs per step."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    n_val = max(1, int(n * val_fraction)) if n >= 5 else 0
    train_idx = perm[n_val:] if n_val else perm
    bs = min(batch_size, len(train_idx))
    sel = [train_idx[rng.choice(len(train_idx), size=bs,
                                replace=len(train_idx) < bs)]
           for _ in range(steps)]
    return train_idx, perm[:n_val], sel


def init_encoder(key, rgcn: dict, vocab: dict):
    """Encoder + projection head from one key: normal draws in a fixed key
    order, scaled by 1/sqrt(fan-in) (embeddings by 0.1); biases 0,
    LayerNorm scale 1."""
    import jax
    import jax.numpy as jnp

    dims = rgcn["dims"]
    ks = iter(jax.random.split(key, 4 * len(dims) + 8))
    n = jax.random.normal
    p = {"embed_instr": n(next(ks), (vocab["opcodes"], 64)) * 0.1,
         "embed_var": n(next(ks), (vocab["var_kinds"], 32)) * 0.1,
         "embed_pseudo": n(next(ks), (vocab["pseudo_kinds"], 16)) * 0.1,
         "layers": []}
    nb, R = rgcn["num_bases"], rgcn["num_relations"]
    for din, dout in zip(dims[:-1], dims[1:]):
        p["layers"].append({
            "basis": n(next(ks), (nb, din, dout)) / np.sqrt(din),
            "comb": n(next(ks), (R, nb)) / np.sqrt(nb),
            "w0": n(next(ks), (din, dout)) / np.sqrt(din),
            "b": jnp.zeros((dout,)), "ln_scale": jnp.ones((dout,)),
            "ln_bias": jnp.zeros((dout,))})
    ph, po = rgcn["proj_hidden"], rgcn["proj_out"]
    p["proj"] = {"w1": n(next(ks), (dims[-1], ph)) / np.sqrt(dims[-1]),
                 "b1": jnp.zeros((ph,)),
                 "w2": n(next(ks), (ph, po)) / np.sqrt(ph),
                 "b2": jnp.zeros((po,))}
    return p


class TrainReference:
    """The fit's first steps and whole trajectory, in plain jax.numpy."""

    def __init__(self, cfg: dict, operand_dtype: str | None = None):
        """``operand_dtype`` (a jax.numpy dtype name) rounds the operands of
        every matrix product, forward and backward: the lower-precision
        reference of the control."""
        import jax

        self.jax = jax
        self.cfg = cfg
        if operand_dtype is None:
            self.r = lambda x: x
        else:
            dt = getattr(jax.numpy, operand_dtype)
            self.r = lambda x: x.astype(dt).astype(jax.numpy.float32)
        self.rc = cfg["rgcn"]
        self.tr = cfg["train"]
        self._step = jax.jit(self._step_impl)

    # -- parameters ---------------------------------------------------------
    def init(self, seed: int):
        """The fit's initial parameters: the second half of the seed's key."""
        jax = self.jax
        _, k_init = jax.random.split(jax.random.PRNGKey(seed))
        return init_encoder(k_init, self.rc, self.cfg["vocab"])

    # -- loss ---------------------------------------------------------------
    def _augment(self, key, b):
        jax = self.jax
        jnp = jax.numpy
        P, Q = b["node_mask"].shape[0], b["edge_mask"].shape[0]
        G = b["graph_mask"].shape[0]
        k_combo, k_node, k_edge = jax.random.split(key, 3)
        flags = jnp.asarray(COMBOS, jnp.float32)[
            jax.random.randint(k_combo, (G,), 0, len(COMBOS))]
        node_keep = jax.random.bernoulli(k_node, 1 - NODE_DROP, (P,))
        node_keep = jnp.where(flags[b["graph_id"], 0] > 0, node_keep, True)
        edge_keep = jax.random.bernoulli(k_edge, 1 - EDGE_DROP, (Q,))
        edge_keep = jnp.where(flags[b["edge_graph"], 1] > 0, edge_keep, True)
        node_mask = b["node_mask"] * node_keep
        edge_mask = (b["edge_mask"] * edge_keep * node_mask[b["edge_src"]]
                     * node_mask[b["edge_dst"]])
        return node_mask, edge_mask, flags[:, 2]

    def _encode(self, p, b, key, node_mask, edge_mask, gate):
        jax = self.jax
        jnp = jax.numpy
        rc = self.rc
        R = rc["num_relations"]
        P = node_mask.shape[0]
        keys = jax.random.split(key, len(rc["dims"]))
        tok = b["token"]
        instr = p["embed_instr"][jnp.clip(tok, 0, p["embed_instr"].shape[0] - 1)]
        half = 32
        freqs = jnp.exp(jnp.arange(half) * (-np.log(10_000.0) / half))
        ang = b["pc_norm"][:, None] * 1000.0 * freqs
        instr = instr + jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], 1)
        vs = b["vstats"]
        var = jnp.concatenate(
            [p["embed_var"][jnp.clip(tok, 0, p["embed_var"].shape[0] - 1)],
             jnp.sign(vs) * jnp.sqrt(jnp.abs(vs)) * 0.3,
             jnp.zeros((P, 24))], 1)
        pse = jnp.concatenate(
            [p["embed_pseudo"][jnp.clip(tok, 0, p["embed_pseudo"].shape[0] - 1)],
             jnp.zeros((P, 48))], 1)
        nt = b["node_type"][:, None]
        h = jnp.where(nt == 0, instr, jnp.where(nt == 1, pse, var))
        h = h * node_mask[:, None]
        noise = rc["feat_noise_sigma"] * jax.random.normal(keys[-1], h.shape)
        h = (h + noise * gate[b["graph_id"]][:, None]) * node_mask[:, None]
        src, dst, et = b["edge_src"], b["edge_dst"], b["edge_type"]
        seg = dst * R + et
        deg = jax.ops.segment_sum(edge_mask, seg, num_segments=P * R)
        wnorm = edge_mask / jnp.maximum(deg[seg], 1.0)
        n_layers = len(p["layers"])
        for li, lp in enumerate(p["layers"]):
            w = lp["comb"][et] * wnorm[:, None]
            s = jax.ops.segment_sum(h[src][:, None, :] * w[..., None], dst,
                                    num_segments=P)
            r = self.r
            out = (jnp.einsum("nkd,kdo->no", r(s), r(lp["basis"]))
                   + r(h) @ r(lp["w0"]) + lp["b"])
            mu = out.mean(-1, keepdims=True)
            sig = out.var(-1, keepdims=True)
            out = ((out - mu) * jax.lax.rsqrt(sig + 1e-5) * lp["ln_scale"]
                   + lp["ln_bias"])
            out = jax.nn.relu(out)
            if li < n_layers - 1 and rc["dropout"] > 0:
                keep = jax.random.bernoulli(keys[li], 1 - rc["dropout"],
                                            out.shape)
                out = out * keep / (1 - rc["dropout"])
            h = out * node_mask[:, None]
        W = b["warp_graph"].shape[0]
        G = b["graph_mask"].shape[0]
        wsum = jax.ops.segment_sum(h * node_mask[:, None], b["warp_seg"],
                                   num_segments=W)
        wcnt = jax.ops.segment_sum(node_mask, b["warp_seg"], num_segments=W)
        valid = (wcnt > 0).astype(h.dtype)
        wmean = wsum / jnp.maximum(wcnt, 1.0)[:, None]
        gsum = jax.ops.segment_sum(wmean * valid[:, None], b["warp_graph"],
                                   num_segments=G)
        gcnt = jax.ops.segment_sum(valid, b["warp_graph"], num_segments=G)
        return gsum / jnp.maximum(gcnt, 1.0)[:, None]

    def _project(self, p, z, key):
        jax = self.jax
        r = self.r
        h = jax.nn.relu(r(z) @ r(p["proj"]["w1"]) + p["proj"]["b1"])
        d = self.rc["dropout"]
        if d > 0:
            h = h * jax.random.bernoulli(key, 1 - d, h.shape) / (1 - d)
        return r(h) @ r(p["proj"]["w2"]) + p["proj"]["b2"]

    def _loss(self, p, b, key):
        jax = self.jax
        jnp = jax.numpy
        k1, k2, kp1, kp2 = jax.random.split(key, 4)
        zs = []
        for kv, kp in ((k1, kp1), (k2, kp2)):
            nm, em, gate = self._augment(kv, b)
            zs.append(self._project(p, self._encode(p, b, kv, nm, em, gate),
                                    kp))
        z1, z2 = (z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True),
                                  1e-8) for z in zs)
        S = self.r(z1) @ self.r(z2).T / self.tr["tau"]

        def ce(S):
            return -jnp.mean(jnp.diag(jax.nn.log_softmax(S, axis=-1)))

        return 0.5 * (ce(S) + ce(S.T))

    # -- optimiser ----------------------------------------------------------
    def _lr(self, step):
        jnp = self.jax.numpy
        t = self.tr
        warm = jnp.minimum(step / max(t["warmup_steps"], 1), 1.0)
        frac = jnp.clip((step - t["warmup_steps"])
                        / max(t["total_steps"] - t["warmup_steps"], 1), 0, 1)
        cos = 0.01 + 0.99 * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        return t["learning_rate"] * warm * cos

    def _step_impl(self, state, b, key):
        jax = self.jax
        jnp = jax.numpy
        t = self.tr
        p, mu, nu, step = state
        loss, g = jax.value_and_grad(self._loss)(p, b, key)
        leaves = jax.tree_util.tree_leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
        clip = jnp.minimum(1.0, t["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        step = step + 1
        lr = self._lr(step.astype(jnp.float32))
        b1, b2 = t["beta1"], t["beta2"]
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)
        tm = jax.tree_util.tree_map
        mu = tm(lambda m, x: b1 * m + (1 - b1) * x * clip, mu, g)
        nu = tm(lambda v, x: b2 * v + (1 - b2) * (x * clip) ** 2, nu, g)
        p = tm(lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + t["eps"])
                                         + t["weight_decay"] * w), p, mu, nu)
        return (p, mu, nu, step), loss, gnorm, g

    def fit(self, graphs: list, seed: int, steps: int | None = None,
            batch_transform=None):
        """Runs the fit's schedule from ``seed``; returns (init params,
        final params, per-step losses, per-step gradient norms, the first
        step's gradient).  ``batch_transform`` edits each packed batch (the
        control's planted faults)."""
        jax = self.jax
        t = self.tr
        steps = t["steps"] if steps is None else steps
        _, _, sel = split_and_selections(len(graphs), t["steps"],
                                         t["batch_size"], t["val_fraction"],
                                         seed)
        base_key, _ = jax.random.split(jax.random.PRNGKey(seed))
        R = self.rc["num_relations"]
        with jax.default_matmul_precision("highest"):
            p0 = self.init(seed)
            zeros = jax.tree_util.tree_map(jax.numpy.zeros_like, p0)
            state = (p0, zeros, zeros, jax.numpy.zeros((), jax.numpy.int32))
            losses, gnorms, g0 = [], [], None
            for i in range(steps):
                b = pack([graphs[j] for j in sel[i]], R)
                if batch_transform is not None:
                    b = batch_transform(b)
                b = {k: jax.numpy.asarray(v) for k, v in b.items()}
                state, loss, gnorm, g = self._step(
                    state, b, jax.random.fold_in(base_key, i))
                losses.append(float(loss))
                gnorms.append(float(gnorm))
                if i == 0:
                    g0 = g
        return p0, state[0], losses, gnorms, g0
