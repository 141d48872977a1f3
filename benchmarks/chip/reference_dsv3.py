"""Plain float32 reference of one DeepSeek-V3 decode step, and the matrix
products it computes.

DeepSeek-V3 (arXiv 2412.19437 §2.1-2.2; the published config.json, whose
key names the configuration ``c`` uses): token embedding; 61 decoder layers
of latent attention (MLA) followed by a dense SwiGLU in the first
``first_k_dense_replace`` layers and a mixture of experts in the rest
(``n_routed_experts`` routed experts, ``num_experts_per_tok`` of them chosen
per token by group-limited sigmoid routing with a bias correction, beside
``n_shared_experts`` always-on experts); the final norm and the head; then
``num_nextn_predict_layers`` multi-token-prediction (MTP) modules.  Batch 1,
one new token at position ``ctx - 1`` attending over ``ctx`` positions.

Straightforward ``jax.numpy`` in float32 with every product at
``jax.default_matmul_precision("highest")``; no kernels, no batching.
Imports nothing of the program under test.  ``products`` lists the step's
matrix products in order from ``jax.make_jaxpr`` of ``decode_step`` on
shapes alone, so the published widths cost no memory; ``stream_products``
reads the same list off a launch stream's kernel parameters, for the
comparison that counts ``kernel_mismatches``.

Departures from the report and the published inference code, none of which
changes the mathematics:

- MLA in decode is the absorbed form (``W_UK`` folded into each head's
  query, attention over the shared latent rows, ``W_UV`` per head), as a
  serving engine computes it; ``mla_naive`` is the report's form, and the
  CPU tests hold the two equal.  Both weights come from the published
  ``kv_b_proj``.
- The YaRN attention factor (``0.1 * mscale_all_dim * ln(factor) + 1``) is
  folded into the softmax scale, squared, as DeepSeek's inference code
  does; with ``mscale`` equal to ``mscale_all_dim`` the rotary tables carry
  no factor.  RoPE takes the Hugging Face form: the rotary dimensions are
  de-interleaved, then rotated by halves.
- Each SwiGLU takes its gate and up projections as one product over their
  concatenated weights.
- A token's routed experts run as one batched product over its
  ``num_experts_per_tok`` gathered experts (batch = tokens x experts).
- Groups left out by the router are masked with -inf (DeepSeek's inference
  code), not 0.
- The MTP module embeds the token the main head ranks first (the step's
  prediction) with the shared embedding, and its shared head uses the main
  head's weights.
"""

from __future__ import annotations

import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def dims(c: dict) -> dict:
    """The widths the forward uses, by short name."""
    return dict(
        D=c["hidden_size"], H=c["num_attention_heads"], V=c["vocab_size"],
        F=c["intermediate_size"], Fe=c["moe_intermediate_size"],
        E=c["n_routed_experts"], k=c["num_experts_per_tok"],
        ns=c["n_shared_experts"], ql=c["q_lora_rank"], r=c["kv_lora_rank"],
        dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"],
        dv=c["v_head_dim"], L=c["num_hidden_layers"],
        dense=c["first_k_dense_replace"], mtp=c["num_nextn_predict_layers"])


# -- parameters ----------------------------------------------------------------
def _layer_shapes(c: dict, routed: bool) -> dict:
    d = dims(c)
    D, H, r, dr = d["D"], d["H"], d["r"], d["dr"]
    out = {
        "input_norm": (D,), "post_norm": (D,),
        "q_a": (D, d["ql"]), "q_a_norm": (d["ql"],),
        "q_b": (d["ql"], H * (d["dn"] + dr)),
        "kv_a": (D, r + dr), "kv_a_norm": (r,),
        "kv_b": (r, H * (d["dn"] + d["dv"])), "o": (H * d["dv"], D)}
    if routed:
        Fs = d["Fe"] * d["ns"]
        out.update(router=(D, d["E"]), bias=(d["E"],),
                   experts_gate_up=(d["E"], D, 2 * d["Fe"]),
                   experts_down=(d["E"], d["Fe"], D),
                   shared_gate_up=(D, 2 * Fs), shared_down=(Fs, D))
    else:
        out.update(gate_up=(D, 2 * d["F"]), down=(d["F"], D))
    return out


def param_shapes(c: dict) -> dict:
    """Every weight of the model, as ``jax.ShapeDtypeStruct``s."""
    d = dims(c)
    D = d["D"]
    tree = {
        "embed": (d["V"], D), "final_norm": (D,), "head": (D, d["V"]),
        "layers": [_layer_shapes(c, i >= d["dense"]) for i in range(d["L"])],
        "mtp": [dict(enorm=(D,), hnorm=(D,), eh_proj=(2 * D, D),
                     shared_head_norm=(D,), layer=_layer_shapes(c, True))
                for _ in range(d["mtp"])]}
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32), tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def init_params(key, c: dict) -> dict:
    """Seeded random weights: norms near 1, the rest normal over the square
    root of the fan-in, the router's bias correction normal / 10."""
    shapes = param_shapes(c)
    leaves, tree = jax.tree.flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, s), kk in zip(leaves, keys):
        name = str(path[-1])
        z = jax.random.normal(kk, s.shape, jnp.float32)
        if "norm" in name:
            out.append(1.0 + 0.1 * z)
        elif "bias" in name:
            out.append(0.1 * z)
        else:
            out.append(z / math.sqrt(s.shape[-2] if len(s.shape) > 1 else 1))
    return jax.tree.unflatten(tree, out)


def cache_shapes(c: dict, ctx: int) -> list:
    """The latent cache of every attention (main layers, then MTP): the
    ``ctx - 1`` earlier positions' rows of ``kv_lora_rank`` +
    ``qk_rope_head_dim``."""
    d = dims(c)
    row = d["r"] + d["dr"]
    return [jax.ShapeDtypeStruct((ctx - 1, row), jnp.float32)
            for _ in range(d["L"] + d["mtp"])]


# -- pieces ----------------------------------------------------------------------
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_inv_freq(c: dict) -> np.ndarray:
    """YaRN's rotary frequencies: interpolated by ``factor`` below the
    ``beta_slow`` correction dimension, extrapolated above ``beta_fast``,
    a linear ramp between."""
    rs, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    pos_freqs = base ** (np.arange(0, dim, 2) / dim)

    def corr(n_rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (n_rot * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extrapolate = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (1.0 / (rs["factor"] * pos_freqs) * (1 - extrapolate)
            + 1.0 / pos_freqs * extrapolate)


def softmax_scale(c: dict) -> float:
    rs = c["rope_scaling"]
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    if rs["factor"] > 1:
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return scale


def apply_rope(x, pos, c: dict):
    """Rotate the last axis of ``x`` (..., T, [heads,] dr) at positions
    ``pos`` (T,)."""
    freqs = np.asarray(pos, np.float64)[:, None] * yarn_inv_freq(c)[None, :]
    emb = np.concatenate([freqs, freqs], -1)
    shape = (len(pos),) + (1,) * (x.ndim - 2) + (emb.shape[-1],)
    cos = jnp.asarray(np.cos(emb).reshape(shape), jnp.float32)
    sin = jnp.asarray(np.sin(emb).reshape(shape), jnp.float32)
    dr = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (dr // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (dr,))
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _queries_and_row(p, c, x, pos):
    """Each head's (nope, rotated rope) query and the tokens' latent rows
    (normed ``c_kv``, rotated shared ``k_pe``)."""
    d = dims(c)
    T, eps = x.shape[0], c["rms_norm_eps"]
    q = rms_norm(x @ p["q_a"], p["q_a_norm"], eps) @ p["q_b"]
    q = q.reshape(T, d["H"], d["dn"] + d["dr"])
    kv = x @ p["kv_a"]
    c_kv = rms_norm(kv[:, : d["r"]], p["kv_a_norm"], eps)
    k_pe = apply_rope(kv[:, d["r"]:], pos, c)
    q_pe = apply_rope(q[..., d["dn"]:], pos, c)
    return q[..., : d["dn"]], q_pe, jnp.concatenate([c_kv, k_pe], -1)


def mla_absorbed(p, c, x, cache, pos):
    """Decode attention of the tokens ``x`` (T, D) at positions ``pos``,
    after ``cache`` (earlier latent rows): every head reads the shared
    latent rows.  Returns the output and the grown cache."""
    d = dims(c)
    T, H, r, dn, dv = x.shape[0], d["H"], d["r"], d["dn"], d["dv"]
    q_nope, q_pe, row = _queries_and_row(p, c, x, pos)
    cache = jnp.concatenate([cache, row], 0)
    w = p["kv_b"].reshape(r, H, dn + dv)
    w_uk = jnp.transpose(w[..., :dn], (1, 2, 0))          # (H, dn, r)
    w_uv = jnp.transpose(w[..., dn:], (1, 0, 2))          # (H, r, dv)
    q_lat = jnp.matmul(jnp.swapaxes(q_nope, 0, 1), w_uk)  # (H, T, r)
    q_full = jnp.concatenate([jnp.swapaxes(q_lat, 0, 1), q_pe], -1)
    S = cache.shape[0]
    scores = (q_full.reshape(T * H, r + d["dr"]) @ cache.T) * softmax_scale(c)
    causal = np.arange(S)[None, :] <= np.asarray(pos)[:, None]   # (T, S)
    scores = jnp.where(np.repeat(causal, H, 0), scores, -jnp.inf)
    o_lat = jax.nn.softmax(scores, -1) @ cache[:, :r]      # (T*H, r)
    o = jnp.matmul(jnp.swapaxes(o_lat.reshape(T, H, r), 0, 1), w_uv)
    return jnp.swapaxes(o, 0, 1).reshape(T, H * dv) @ p["o"], cache


def mla_naive(p, c, x, pos):
    """The report's MLA over the tokens ``x`` (T, D) at positions ``pos``,
    causal: ``kv_b_proj`` up-projects each latent row to every head's key
    and value.  Returns the output and the tokens' latent rows."""
    d = dims(c)
    T, H, r, dn, dv = x.shape[0], d["H"], d["r"], d["dn"], d["dv"]
    q_nope, q_pe, rows = _queries_and_row(p, c, x, pos)
    kvb = (rows[:, :r] @ p["kv_b"]).reshape(T, H, dn + dv)
    k_pe = jnp.broadcast_to(rows[:, None, r:], (T, H, d["dr"]))
    k = jnp.concatenate([kvb[..., :dn], k_pe], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    scores = jnp.matmul(jnp.swapaxes(q, 0, 1), jnp.transpose(k, (1, 2, 0)))
    scores = scores * softmax_scale(c)
    causal = np.asarray(pos)[None, :] <= np.asarray(pos)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.matmul(jax.nn.softmax(scores, -1),
                   jnp.swapaxes(kvb[..., dn:], 0, 1))      # (H, T, dv)
    return jnp.swapaxes(o, 0, 1).reshape(T, H * dv) @ p["o"], rows


def swiglu(x, gate_up, down):
    h = x @ gate_up
    half = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :half]) * h[..., half:]) @ down


def route(c: dict, logits, bias):
    """Group-limited routing (``noaux_tc``): sigmoid scores; biased scores
    choose; each group scores the sum of its two best; the best
    ``topk_group`` groups survive; ``num_experts_per_tok`` experts are
    chosen inside them; their unbiased scores, normalised and scaled, are
    the weights.  Returns (expert ids, weights), each (T, top_k)."""
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias
    T, E = scores.shape
    G = c["n_group"]
    best2 = jax.lax.top_k(choice.reshape(T, G, E // G), 2)[0].sum(-1)
    _, groups = jax.lax.top_k(best2, c["topk_group"])
    keep = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], groups].set(True)
    keep = jnp.repeat(keep, E // G, axis=1)
    _, idx = jax.lax.top_k(jnp.where(keep, choice, -jnp.inf),
                           c["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, 1)
    if c["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * c["routed_scaling_factor"]


def moe(p, c, x):
    """Routed experts (the chosen ones only) plus the shared experts."""
    d = dims(c)
    T, D, Fe, k = x.shape[0], d["D"], d["Fe"], d["k"]
    idx, w = route(c, x @ p["router"], p["bias"])
    xk = jnp.broadcast_to(x[:, None, None, :], (T, k, 1, D)).reshape(T * k, 1, D)
    h = jnp.matmul(xk, p["experts_gate_up"][idx].reshape(T * k, D, 2 * Fe))
    a = jax.nn.silu(h[..., :Fe]) * h[..., Fe:]
    y = jnp.matmul(a, p["experts_down"][idx].reshape(T * k, Fe, D))
    y = (y.reshape(T, k, D) * w[..., None]).sum(1)
    return y + swiglu(x, p["shared_gate_up"], p["shared_down"])


def decoder_layer(p, c, x, cache, pos, routed):
    eps = c["rms_norm_eps"]
    a, cache = mla_absorbed(p, c, rms_norm(x, p["input_norm"], eps), cache, pos)
    x = x + a
    h = rms_norm(x, p["post_norm"], eps)
    f = moe(p, c, h) if routed else swiglu(h, p["gate_up"], p["down"])
    return x + f, cache


def decode_step(params, token, caches, *, c: dict, ctx: int):
    """One decode step of the token id ``token`` at position ``ctx - 1``:
    the main model's logits, then the MTP modules' logits for the token
    after the one the main head ranks first, and the grown caches."""
    d = dims(c)
    eps = c["rms_norm_eps"]
    pos = np.array([ctx - 1])
    new = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][token][None, :]
        for i, p in enumerate(params["layers"]):
            routed = i >= d["dense"]
            with jax.named_scope("moe" if routed else "dense"):
                x, cache = decoder_layer(p, c, x, caches[i], pos, routed)
            new.append(cache)
        with jax.named_scope("head"):
            logits = rms_norm(x, params["final_norm"], eps) @ params["head"]
        h, nxt = x, jnp.argmax(logits[0])
        mtp_logits = []
        for j, m in enumerate(params["mtp"]):
            with jax.named_scope("mtp"):
                e = params["embed"][nxt][None, :]
                h = jnp.concatenate([rms_norm(e, m["enorm"], eps),
                                     rms_norm(h, m["hnorm"], eps)], -1)
                h = h @ m["eh_proj"]
                h, cache = decoder_layer(m["layer"], c, h,
                                         caches[d["L"] + j], pos, True)
                out = rms_norm(h, m["shared_head_norm"], eps) @ params["head"]
            new.append(cache)
            mtp_logits.append(out)
            nxt = jnp.argmax(out[0])
    return logits, mtp_logits, new


# -- the step's matrix products ---------------------------------------------------
def _walk(jaxpr, out: list) -> None:
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            (lc, rc), (lb, rb) = e.params["dimension_numbers"]
            ls, rs = e.invars[0].aval.shape, e.invars[1].aval.shape
            lfree = [i for i in range(len(ls)) if i not in lc and i not in lb]
            rfree = [i for i in range(len(rs)) if i not in rc and i not in rb]
            kind = str(e.source_info.name_stack).split("/")[0]
            out.append((kind, int(np.prod([ls[i] for i in lb])),
                        int(np.prod([ls[i] for i in lfree])),
                        int(np.prod([rs[i] for i in rfree])),
                        int(np.prod([ls[i] for i in lc]))))
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                _walk(sub, out)


def products(c: dict, ctx: int) -> list:
    """The decode step's matrix products in order, each ``(kind, batch, M,
    N, K)``: kind is the layer kind ("dense", "moe", "head", "mtp"), batch
    the heads or experts of a batched product.  Read from the jaxpr of
    ``decode_step`` on shapes alone."""
    fn = partial(decode_step, c=c, ctx=ctx)
    jaxpr = jax.make_jaxpr(fn)(param_shapes(c),
                               jax.ShapeDtypeStruct((), jnp.int32),
                               cache_shapes(c, ctx))
    out: list = []
    _walk(jaxpr.jaxpr, out)
    return out


_LAYER = re.compile(r"^L(\d+)_")


def stream_kind(name: str, first_dense: int) -> str:
    """A launch's layer kind, from its name."""
    if name.startswith("lm_head"):
        return "head"
    if name.startswith("MTP"):
        return "mtp"
    m = _LAYER.match(name)
    if m:
        return "dense" if int(m.group(1)) < first_dense else "moe"
    return "other"


def stream_products(kernels, c: dict) -> list:
    """The matrix products a launch stream computes, ``(kind, batch, M, N,
    K)`` in launch order, from each ``(name, template, params)``: a
    matvec (``gemv``: n, m; one row per batch element) or GEMM (``gemm``:
    M, N, K), ``batch`` products where given; a fused attention
    (``mla_attention``) gives its scores and its value product, over one
    shared latent row per position (``kv_heads`` 1: every head's query
    rows against the rows) or per head.  Other launches compute none."""
    out = []
    first = c["first_k_dense_replace"]
    for name, template, p in kernels:
        kind = stream_kind(name, first)
        b = p.get("batch", 1)
        if template == "gemv":
            out.append((kind, b, 1, p["n"], p["m"]))
        elif template == "gemm":
            out.append((kind, b, p["M"], p["N"], p["K"]))
        elif template == "mla_attention":
            H, q, kv = p["heads"], p["q_len"], p["kv_len"]
            if p["kv_heads"] == 1:
                out += [(kind, 1, q * H, kv, p["qk_dim"]),
                        (kind, 1, q * H, p["v_dim"], kv)]
            else:
                out += [(kind, H, q, kv, p["qk_dim"]),
                        (kind, H, q, p["v_dim"], kv)]
    return out


def mismatches(got: list, want: list) -> int:
    """Products that differ position by position, plus the ones either
    list lacks."""
    return (sum(a != b for a, b in zip(got, want))
            + abs(len(got) - len(want)))
