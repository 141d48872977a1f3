"""Kernel template library: parameterized SASS-like loop bodies + analytic
whole-grid statistics.  Templates cover the behavioral space of the paper's
benchmark suites (PolyBench / Rodinia / Tango / LLM inference kernels)."""

from __future__ import annotations

from collections import Counter


from repro.tracing.tracer import BodyInstr as I
from repro.tracing.tracer import KernelInvocation, make_stats
from repro.utils.registry import Registry

TEMPLATES: Registry = Registry("kernel template")


def _count_classes(body):
    from repro.tracing.isa import OPCODES

    return Counter(OPCODES[i.op][0] for i in body)


# ---------------------------------------------------------------------------
# GEMM (tiled, smem double-buffered flavor)
# ---------------------------------------------------------------------------


def gemm_body(params):
    fp16 = params.get("fp16", False)
    mma = "HMMA" if fp16 else "FFMA"
    # row-major leading dimensions are visible in the address stream of real
    # SASS traces: A advances by lda=K*4 per k-tile row crossing, B by ldb=N*4
    # per k-step — so matrix shape is trace-discoverable (not just grid size).
    lda = max(128, params["K"] * 4)
    ldb = max(128, params["N"] * 4)
    body = [
        I("LDG", (10,), (2,), mem={"kind": "load", "width": 16, "stride_iter": lda, "base": 0x10000000, "pattern": "coalesced"}),
        I("LDG", (11,), (3,), mem={"kind": "load", "width": 16, "stride_iter": ldb, "base": 0x20000000, "pattern": "coalesced"}),
        I("STS", (), (10,)),
        I("STS", (), (11,)),
        I("BAR", (), ()),
        I("LDS", (12,), ()),
        I("LDS", (13,), ()),
    ]
    for r in range(8):
        body.append(I(mma, (20 + r,), (12, 13, 20 + r)))
    body += [I("BAR", (), ()), I("IADD3", (2,), (2,)), I("ISETP", (), (2,)), I("BRA", (), ())]
    M, N, K = params["M"], params["N"], params["K"]
    n_iter = max(1, K // 32)
    return body, n_iter, {"warps_per_cta": 8}


def gemm_stats(params, platform):
    # ``batch``: independent products of this shape in one launch (a
    # batched or grouped GEMM over heads or experts)
    M, N, K = params["M"], params["N"], params["K"]
    b = params.get("batch", 1)
    fp16 = params.get("fp16", False)
    body, n_iter, _ = gemm_body(params)
    ctas = max(1, (M // 64) * (N // 64)) * b
    elt = 2 if fp16 else 4
    # tiled-GEMM traffic: A rereads once per 128-wide N tile, B per M tile
    tile = 128
    bytes_acc = elt * (
        M * K * max(1, N // tile) + K * N * max(1, M // tile) + M * N
    ) * b
    ws = (M * K + K * N + M * N) * elt * b
    return make_stats(
        body_class_counts=_count_classes(body), n_iter=n_iter, ctas=ctas,
        threads_per_cta=256, flops_total=2.0 * M * N * K * b,
        bytes_accessed=max(bytes_acc, ws), working_set=ws,
        pattern="coalesced", regs=96 if fp16 else 64, smem=32768, ilp=4.0,
    )


TEMPLATES.add("gemm", (gemm_body, gemm_stats))


# ---------------------------------------------------------------------------
# Elementwise / memcpy-like streams
# ---------------------------------------------------------------------------


def elementwise_body(params):
    nops = params.get("nops", 2)
    ops = params.get("ops", ["FMUL", "FADD"])
    body = [I("LDG", (10,), (2,), mem={"kind": "load", "width": 16, "stride_iter": 4096, "base": 0x30000000, "pattern": "coalesced"})]
    prev = 10
    for i in range(nops):
        op = ops[i % len(ops)]
        body.append(I(op, (11 + i,), (prev,)))
        prev = 11 + i
    body += [
        I("STG", (), (prev,), mem={"kind": "store", "width": 16, "stride_iter": 4096, "base": 0x40000000, "pattern": "coalesced"}),
        I("IADD3", (2,), (2,)),
        I("BRA", (), ()),
    ]
    n = params["n"]
    n_iter = max(1, n // (256 * 4 * max(1, params.get("grid_cap", 4096))))
    return body, max(n_iter, params.get("iters", 4)), {"warps_per_cta": 8}


def elementwise_stats(params, platform):
    n = params["n"]
    nops = params.get("nops", 2)
    body, n_iter, _ = elementwise_body(params)
    ctas = min(max(1, n // (256 * 4)), params.get("grid_cap", 4096))
    return make_stats(
        body_class_counts=_count_classes(body), n_iter=n_iter, ctas=ctas,
        threads_per_cta=256, flops_total=float(n) * nops,
        bytes_accessed=8.0 * n, working_set=8.0 * n,
        pattern="coalesced", regs=24, ilp=6.0,
    )


TEMPLATES.add("elementwise", (elementwise_body, elementwise_stats))


# ---------------------------------------------------------------------------
# Reduction (shuffle tree)
# ---------------------------------------------------------------------------


def reduction_body(params):
    body = [
        I("LDG", (10,), (2,), mem={"kind": "load", "width": 16, "stride_iter": 4096, "base": 0x50000000, "pattern": "coalesced"}),
        I("FADD", (11,), (10, 11)),
        I("IADD3", (2,), (2,)),
        I("BRA", (), ()),
    ]
    tail = []
    for s in range(5):
        tail += [I("SHFL", (12,), (11,)), I("FADD", (11,), (11, 12))]
    tail += [I("BAR", (), ()), I("STG", (), (11,), mem={"kind": "store", "width": 4, "stride_iter": 4, "base": 0x60000000, "pattern": "coalesced"})]
    n = params["n"]
    n_iter = max(2, min(64, n // (256 * 1024)))
    return body + tail, n_iter, {"warps_per_cta": 8}


def reduction_stats(params, platform):
    n = params["n"]
    body, n_iter, _ = reduction_body(params)
    ctas = max(1, min(n // (256 * 16), 2048))
    return make_stats(
        body_class_counts=_count_classes(body), n_iter=n_iter, ctas=ctas,
        threads_per_cta=256, flops_total=float(n),
        bytes_accessed=4.0 * n, working_set=4.0 * n,
        pattern="coalesced", regs=16, ilp=2.0,
    )


TEMPLATES.add("reduction", (reduction_body, reduction_stats))


# ---------------------------------------------------------------------------
# Stencil (structured neighbors, L1-friendly)
# ---------------------------------------------------------------------------


def stencil_body(params):
    pts = params.get("pts", 5)
    stride = params.get("stride", 512)  # small stride -> line reuse in trace
    body = []
    for p in range(pts):
        body.append(I("LDG", (10 + p,), (2,), mem={"kind": "load", "width": 4, "stride_iter": stride, "base": 0x70000000 + 4096 * p, "pattern": params.get("pattern", "strided")}))
    acc = 30
    body.append(I("FMUL", (acc,), (10,)))
    for p in range(1, pts):
        body.append(I("FFMA", (acc,), (10 + p, acc)))
    body += [
        I("STG", (), (acc,), mem={"kind": "store", "width": 4, "stride_iter": stride, "base": 0x80000000, "pattern": "coalesced"}),
        I("IADD3", (2,), (2,)),
        I("ISETP", (), (2,)),
        I("BRA", (), ()),
    ]
    return body, max(2, params.get("iters", 8)), {"warps_per_cta": 8}


def stencil_stats(params, platform):
    nx, ny = params["nx"], params["ny"]
    pts = params.get("pts", 5)
    body, n_iter, _ = stencil_body(params)
    ctas = max(1, (nx * ny) // (256 * n_iter))
    reuse = params.get("reuse", 1.0)  # spatial-locality factor
    return make_stats(
        body_class_counts=_count_classes(body), n_iter=n_iter, ctas=ctas,
        threads_per_cta=256, flops_total=2.0 * nx * ny * pts,
        bytes_accessed=4.0 * nx * ny * pts,
        working_set=4.0 * nx * ny * pts / max(reuse, 1.0),
        pattern=params.get("pattern", "strided"), regs=40,
        ilp=params.get("ilp", 3.0),
    )


TEMPLATES.add("stencil", (stencil_body, stencil_stats))


# ---------------------------------------------------------------------------
# Softmax / normalization rows (SFU-heavy)
# ---------------------------------------------------------------------------


def softmax_body(params):
    body = [
        I("LDG", (10,), (2,), mem={"kind": "load", "width": 16, "stride_iter": 2048, "base": 0x90000000, "pattern": "coalesced"}),
        I("FADD", (11,), (10, 11)),
        I("SHFL", (12,), (11,)),
        I("FADD", (11,), (11, 12)),
        I("MUFU", (13,), (10,)),
        I("FADD", (14,), (13, 14)),
        I("SHFL", (15,), (14,)),
        I("FADD", (14,), (14, 15)),
        I("MUFU", (16,), (14,)),
        I("FMUL", (17,), (13, 16)),
        I("STG", (), (17,), mem={"kind": "store", "width": 16, "stride_iter": 2048, "base": 0xA0000000, "pattern": "coalesced"}),
        I("IADD3", (2,), (2,)),
        I("BRA", (), ()),
    ]
    cols = params["cols"]
    n_iter = max(1, cols // (32 * 4))
    return body, n_iter, {"warps_per_cta": 4}


def softmax_stats(params, platform):
    rows, cols = params["rows"], params["cols"]
    body, n_iter, _ = softmax_body(params)
    ctas = max(1, rows // 4)
    return make_stats(
        body_class_counts=_count_classes(body), n_iter=n_iter, ctas=ctas,
        threads_per_cta=128, flops_total=6.0 * rows * cols,
        bytes_accessed=8.0 * rows * cols, working_set=8.0 * rows * cols,
        pattern="coalesced", regs=32, ilp=2.5,
    )


TEMPLATES.add("softmax", (softmax_body, softmax_stats))


# ---------------------------------------------------------------------------
# Convolution (implicit GEMM; platform-sensitive algorithm selection!)
# ---------------------------------------------------------------------------


def conv_body(params):
    algo = params.get("algo", "implicit_gemm")
    if algo == "cudnn_heuristic":
        algo = "implicit_gemm"  # traces are collected on P1 (paper setup)
    if algo == "winograd":
        # transform-heavy: more ALU, fewer loads
        body = [
            I("LDG", (10,), (2,), mem={"kind": "load", "width": 16, "stride_iter": 256, "base": 0xB0000000, "pattern": "strided"}),
            I("FADD", (11,), (10,)), I("FMUL", (12,), (11,)),
            I("FADD", (13,), (12,)), I("FMUL", (14,), (13,)),
        ]
        for r in range(4):
            body.append(I("FFMA", (20 + r,), (14, 20 + r)))
        body += [I("STG", (), (20,), mem={"kind": "store", "width": 16, "stride_iter": 256, "base": 0xC0000000, "pattern": "coalesced"}),
                 I("IADD3", (2,), (2,)), I("BRA", (), ())]
    else:
        body = [
            I("LDG", (10,), (2,), mem={"kind": "load", "width": 16, "stride_iter": 512, "base": 0xB0000000, "pattern": "strided"}),
            I("LDG", (11,), (3,), mem={"kind": "load", "width": 16, "stride_iter": 0, "base": 0xB8000000, "pattern": "coalesced"}),
            I("STS", (), (10,)), I("BAR", (), ()), I("LDS", (12,), ()),
        ]
        for r in range(6):
            body.append(I("FFMA", (20 + r,), (11, 12, 20 + r)))
        body += [I("BAR", (), ()),
                 I("STG", (), (20,), mem={"kind": "store", "width": 16, "stride_iter": 512, "base": 0xC0000000, "pattern": "coalesced"}),
                 I("IADD3", (2,), (2,)), I("BRA", (), ())]
    c, k, r = params["c"], params["k"], params.get("r", 3)
    n_iter = max(1, (c * r * r) // 32)
    return body, n_iter, {"warps_per_cta": 8}


def conv_stats(params, platform):
    c, hw, k, r = params["c"], params["hw"], params["k"], params.get("r", 3)
    algo = params.get("algo", "implicit_gemm")
    if algo == "cudnn_heuristic":
        # the library picks the algorithm per GPU generation at runtime
        # (the paper's phi-2 / PKA profiling quirk, §5.2): clustering done on
        # P1 sees implicit-gemm behavior; P2/P3 ground truth runs winograd.
        algo = "implicit_gemm" if platform == "P1" else "winograd"
    p = dict(params)
    p["algo"] = algo
    body, n_iter, _ = conv_body(p)
    ctas = params.get("ctas", max(1, (hw * hw * k) // (64 * 64)))
    flops = 2.0 * hw * hw * k * c * r * r
    if algo == "winograd":
        flops *= 0.45  # winograd reduces multiplies
    bytes_acc = 4.0 * (hw * hw * c * 3 + k * c * r * r)
    # winograd: long transform dependency chains -> low ILP (the perf
    # difference instruction-count signatures cannot see)
    ilp = 1.0 if algo == "winograd" else 4.0
    # the output buffer scales with the launched grid (64x64 tile per CTA)
    ws = 4.0 * (hw * hw * c + k * c * r * r) + 4.0 * ctas * 64 * 64
    return make_stats(
        body_class_counts=_count_classes(body), n_iter=n_iter, ctas=ctas,
        threads_per_cta=256, flops_total=flops,
        bytes_accessed=max(bytes_acc, ws), working_set=ws,
        pattern="strided", regs=80, smem=24576, ilp=ilp,
    )


TEMPLATES.add("conv", (conv_body, conv_stats))


# ---------------------------------------------------------------------------
# Graph traversal (irregular, divergent, atomic)
# ---------------------------------------------------------------------------


def traversal_body(params):
    body = [
        I("LDG", (10,), (2,), mem={"kind": "load", "width": 4, "stride_iter": 4, "base": 0xD0000000, "pattern": "coalesced"}),
        I("ISETP", (), (10,)),
        I("BRA", (), ()),
        I("LDG", (11,), (10,), mem={"kind": "load", "width": 4, "stride_iter": 8192, "base": 0xD8000000, "pattern": "random"}),
        I("LDG", (12,), (11,), mem={"kind": "load", "width": 4, "stride_iter": 16384, "base": 0xE0000000, "pattern": "random"}),
        I("IADD3", (13,), (11, 12)),
        I("ISETP", (), (13,)),
        I("RED", (), (13,), mem={"kind": "store", "width": 4, "stride_iter": 8192, "base": 0xE8000000, "pattern": "random"}),
        I("IADD3", (2,), (2,)),
        I("BRA", (), ()),
    ]
    deg = params.get("degree", 8)
    return body, max(1, deg), {"warps_per_cta": 8, "divergence": params.get("divergence", 0.4)}


def traversal_stats(params, platform):
    nodes, deg = params["nodes"], params.get("degree", 8)
    frontier = params.get("frontier", nodes)
    body, n_iter, _ = traversal_body(params)
    ctas = max(1, frontier // 256)
    return make_stats(
        body_class_counts=_count_classes(body), n_iter=n_iter, ctas=ctas,
        threads_per_cta=256, flops_total=0.0,
        bytes_accessed=4.0 * frontier * deg * 3,
        working_set=4.0 * nodes,
        pattern="random", regs=24, ilp=1.2,
        divergence=params.get("divergence", 0.4),
    )


TEMPLATES.add("traversal", (traversal_body, traversal_stats))


# ---------------------------------------------------------------------------
# GEMV (memory-bound matvec — LLM decode kernels)
# ---------------------------------------------------------------------------


def gemv_body(params):
    # acc_regs=1 -> serial FFMA dependency chain (latency-bound);
    # acc_regs=2 -> independent accumulators (ILP).  Identical opcode MIX and
    # COUNT either way — the difference lives in the register SSA structure,
    # which HRGs capture and hand-crafted mixes cannot.
    serial = params.get("acc_regs", 2) == 1
    lda = max(128, params["m"] * 4)  # matvec row stride = m*4
    body = [
        I("LDG", (10,), (2,), mem={"kind": "load", "width": 16, "stride_iter": lda, "base": 0xF0000000, "pattern": "coalesced"}),
        I("LDG", (11,), (3,), mem={"kind": "load", "width": 16, "stride_iter": 64, "base": 0xF8000000, "pattern": "coalesced"}),
        I("FFMA", (20,), (10, 11, 20)),
        I("FFMA", (20,) if serial else (21,), (10, 11, 20) if serial else (10, 11, 21)),
        I("IADD3", (2,), (2,)),
        I("BRA", (), ()),
    ]
    tail = [I("SHFL", (22,), (20,)), I("FADD", (20,), (20, 22)),
            I("STG", (), (20,), mem={"kind": "store", "width": 4, "stride_iter": 4, "base": 0xFC000000, "pattern": "coalesced"})]
    n, m = params["n"], params["m"]
    n_iter = max(1, m // (32 * 8))
    return body + tail, n_iter, {"warps_per_cta": 8}


def gemv_stats(params, platform):
    # ``batch``: independent matvecs of this shape in one launch (per head,
    # or per routed expert of a decode token)
    n, m = params["n"], params["m"]
    b = params.get("batch", 1)
    body, n_iter, _ = gemv_body(params)
    ctas = max(1, n // 64) * b
    ilp = 1.0 if params.get("acc_regs", 2) == 1 else 6.0
    return make_stats(
        body_class_counts=_count_classes(body), n_iter=n_iter, ctas=ctas,
        threads_per_cta=256, flops_total=2.0 * n * m * b,
        bytes_accessed=(4.0 * n * m + 8.0 * m) * b, working_set=4.0 * n * m * b,
        pattern="coalesced", regs=32, ilp=ilp,
    )


TEMPLATES.add("gemv", (gemv_body, gemv_stats))


# ---------------------------------------------------------------------------
# Fused attention over cached rows (FlashAttention / FlashMLA style)
# ---------------------------------------------------------------------------


def mla_attention_body(params):
    """Scores, online softmax and the value product of ``heads`` query heads
    in one loop over blocks of ``kv_len`` cached positions.  With
    ``kv_heads == 1`` every head reads ONE shared latent row per position
    (MLA's absorbed decode: the keys are the whole ``qk_dim``-wide row, the
    values its first ``v_dim`` columns), so the row is loaded once into
    shared memory and read twice; with ``kv_heads == heads`` (MLA's naive
    form) each head streams its own key and value rows."""
    shared = params["kv_heads"] == 1
    body = [I("LDG", (10,), (2,), mem={"kind": "load", "width": 16, "stride_iter": params["qk_dim"] * 4, "base": 0x10000000, "pattern": "coalesced"})]
    if not shared:
        body.append(I("LDG", (11,), (3,), mem={"kind": "load", "width": 16, "stride_iter": params["v_dim"] * 4, "base": 0x20000000, "pattern": "coalesced"}))
    body += [I("STS", (), (10,))]
    if not shared:
        body.append(I("STS", (), (11,)))
    body += [I("BAR", (), ()), I("LDS", (12,), ())]
    for r in range(4 if shared else 2):
        body.append(I("HMMA", (20 + r,), (12, 4, 20 + r)))
    body += [
        I("FMUL", (14,), (20,)),
        I("FSETP", (), (14, 15)),
        I("MUFU", (16,), (14,)),
        I("FADD", (17,), (16, 17)),
        I("SHFL", (18,), (17,)),
        I("LDS", (13,), ()),
    ]
    for r in range(4 if shared else 2):
        body.append(I("HMMA", (24 + r,), (16, 13, 24 + r)))
    body += [I("BAR", (), ()), I("IADD3", (2,), (2,)), I("ISETP", (), (2,)), I("BRA", (), ())]
    return body, max(1, params["kv_len"] // 64), {"warps_per_cta": 8}


def mla_attention_stats(params, platform):
    H, q, kv = params["heads"], params["q_len"], params["kv_len"]
    qk, v, kvh = params["qk_dim"], params["v_dim"], params["kv_heads"]
    body, n_iter, _ = mla_attention_body(params)
    causal = 0.5 if q == kv and q > 1 else 1.0
    # the shared latent row holds the values too
    kv_bytes = 4.0 * kvh * kv * (qk if kvh == 1 else qk + v)
    q_bytes = 4.0 * H * q * (qk + v)
    # one CTA per 64 query rows; a single-query decode splits the cache
    # into 1,024-position chunks (split-KV) to fill the machine
    ctas = max(1, H * q // 64) * (max(1, kv // 1024) if q == 1 else 1)
    return make_stats(
        body_class_counts=_count_classes(body), n_iter=n_iter, ctas=ctas,
        threads_per_cta=256, flops_total=2.0 * H * q * kv * (qk + v) * causal,
        bytes_accessed=kv_bytes + q_bytes, working_set=kv_bytes + q_bytes,
        pattern="coalesced", regs=128, smem=65536, ilp=4.0,
    )


TEMPLATES.add("mla_attention", (mla_attention_body, mla_attention_stats))


# ---------------------------------------------------------------------------
# Group-limited top-k routing (two selection stages)
# ---------------------------------------------------------------------------


def grouped_topk_body(params):
    """DeepSeek-V3 routing of ``rows`` tokens over ``experts``: sigmoid of
    the router logits plus a bias correction; each of ``groups`` groups
    scores the sum of its two best biased scores; warp-shuffle arg-max
    rounds keep the best ``topk_group`` groups, then choose ``top_k``
    experts inside them; the chosen unbiased scores are normalised and
    scaled."""
    body = [
        I("LDG", (10,), (2,), mem={"kind": "load", "width": 16, "stride_iter": params["experts"] * 4, "base": 0x90000000, "pattern": "coalesced"}),
        I("LDG", (11,), (3,), mem={"kind": "load", "width": 16, "stride_iter": 0, "base": 0xA0000000, "pattern": "coalesced"}),
        I("FMUL", (12,), (10,)),
        I("MUFU", (13,), (12,)),
        I("FADD", (13,), (13,)),
        I("MUFU", (14,), (13,)),
        I("FADD", (15,), (14, 11)),
        # stage 1: each group's two best biased scores
        I("SHFL", (16,), (15,)),
        I("FSETP", (), (15, 16)),
        I("SHFL", (17,), (16,)),
        I("FSETP", (), (16, 17)),
        I("FADD", (18,), (16, 17)),
        I("STS", (), (18,)),
        I("BAR", (), ()),
        I("LDS", (19,), ()),
        # stage 2: the best groups, then the experts inside them
        I("SHFL", (20,), (19,)),
        I("FSETP", (), (19, 20)),
        I("LOP3", (21,), (21,)),
        I("SHFL", (22,), (15,)),
        I("FSETP", (), (15, 22)),
        I("FADD", (23,), (14, 23)),
        I("MUFU", (24,), (23,)),
        I("FMUL", (25,), (14, 24)),
        I("STG", (), (25,), mem={"kind": "store", "width": 4, "stride_iter": params["top_k"] * 4, "base": 0xB0000000, "pattern": "coalesced"}),
        I("STG", (), (21,), mem={"kind": "store", "width": 4, "stride_iter": params["top_k"] * 4, "base": 0xC0000000, "pattern": "coalesced"}),
        I("IADD3", (2,), (2,)),
        I("BRA", (), ()),
    ]
    # one pass of the loop per selection round
    return body, params["topk_group"] + params["top_k"], {"warps_per_cta": 4}


def grouped_topk_stats(params, platform):
    rows, E, k = params["rows"], params["experts"], params["top_k"]
    body, n_iter, _ = grouped_topk_body(params)
    return make_stats(
        body_class_counts=_count_classes(body), n_iter=n_iter,
        ctas=max(1, rows // 4), threads_per_cta=128,
        flops_total=8.0 * rows * E,
        bytes_accessed=4.0 * (rows * E + E + 2 * rows * k),
        working_set=4.0 * (rows * E + E + 2 * rows * k),
        pattern="coalesced", regs=40, ilp=2.0,
    )


TEMPLATES.add("grouped_topk", (grouped_topk_body, grouped_topk_stats))


def make_kernel(name, template, params, seq, seed) -> KernelInvocation:
    body_fn, stats_fn = TEMPLATES.get(template)
    return KernelInvocation(
        name=name, template=template, params=params, seq=seq, seed=seed,
        body_fn=body_fn, stats_fn=stats_fn,
    )
