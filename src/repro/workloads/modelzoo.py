"""The in-repo model zoo as a trace-pack workload suite.

``model:<arch_id>[:phase]`` derives a `Program` from an assigned
architecture config (`repro.configs.ARCHS`) by walking its per-layer specs
(attention / mamba mixers, dense / MoE FFNs) through the same library-kernel
stream builder the paper's LLM workloads use — but at REAL phase shapes and
with a 10-100x larger trace window than the scenario families:

    phase     shapes                       trace window (cap_warps, cap_instr)
    prefill   seq 2048, full-layer gemms   (4, 2048)   -> ~42x default graphs
    decode    4 steps against a 4096 ctx   (4, 1024)   -> ~21x default graphs

Architectures that only the trace path builds (`repro.configs.TRACE_ARCHS`,
DeepSeek-V3) take `latent_kernels`: latent attention, leading dense layers,
group-limited routed experts beside a shared expert, and the MTP module.

The window rides on `Program.trace_caps` (resolved by
``repro.config.resolve_trace_caps``) and is ALSO folded into
``fingerprint_extra``, so artifacts and cached graphs for one window can
never be replayed at another.  This is the ROADMAP's "real-model trace pack"
item — the SimNet/NPS-style real-workload stress test for the ingestion
engine (DESIGN.md §13).
"""

from __future__ import annotations

from repro.tracing.programs import (
    Program, _lm_layer_kernels, _mla_layer_kernels, mm_kernel,
)
from repro.tracing.templates import make_kernel

#: default model-zoo grid: one dense-attention, one pure-SSM, one MoE arch
MODEL_ZOO = ("llama3.2-3b", "mamba2-780m", "dbrx-132b")
PHASES = ("prefill", "decode")

#: per-phase trace window — the "10-100x larger graphs" knob
PHASE_CAPS = {"prefill": (4, 2048), "decode": (4, 1024)}
#: prefill sequence length / decode KV-context length
PHASE_SEQ = {"prefill": 2048, "decode": 4096}
#: decode emits several steps (real decode is many small identical launches
#: — the ingest engine's dedup memo is what makes this cheap)
DECODE_STEPS = 4


def zoo_names(archs=MODEL_ZOO, phases=PHASES) -> list[str]:
    return [f"model:{a}:{p}" for a in archs for p in phases]


def model_program(name: str) -> Program:
    """Build ``model:<arch_id>[:phase]`` (phase defaults to prefill)."""
    parts = name.split(":")
    if len(parts) not in (2, 3) or parts[0] != "model":
        raise KeyError(f"bad model program name {name!r} "
                       "(want model:<arch_id>[:phase])")
    arch_id = parts[1]
    phase = parts[2] if len(parts) == 3 else "prefill"
    if phase not in PHASES:
        raise KeyError(f"unknown phase {phase!r} (want one of {PHASES})")

    from repro.configs import TRACE_ARCHS, get_arch

    seq_len = PHASE_SEQ[phase]
    decode = phase == "decode"
    steps = DECODE_STEPS if decode else 1
    seed = 211 if decode else 199
    if arch_id in TRACE_ARCHS:
        ks = latent_kernels(TRACE_ARCHS.get(arch_id), seq_len, decode,
                            steps, seed)
    else:
        ks = _spec_kernels(get_arch(arch_id), seq_len, decode, steps, seed)
    for i, k in enumerate(ks):
        k.seq = i

    caps = PHASE_CAPS[phase]
    full_name = f"model:{arch_id}:{phase}"
    return Program(
        full_name, ks,
        fingerprint_extra=f"modelzoo|{arch_id}|{phase}"
                          f"|cw{caps[0]}ci{caps[1]}",
        trace_caps=caps,
    )


def _spec_kernels(cfg, seq_len, decode, steps, seed) -> list:
    """Launch stream of a `ModelConfig`: its per-layer specs, then the
    head, once per step."""
    from repro.config import FFN_MOE, MIXER_MAMBA2

    ks = []
    s = 0
    for _step in range(steps):
        for layer in range(cfg.num_layers):
            spec = cfg.layer_specs()[layer % cfg.block_size]
            moe = (
                {"experts": cfg.num_experts, "top_k": cfg.top_k}
                if spec.ffn == FFN_MOE else None
            )
            mamba = (
                {"d_inner": cfg.d_inner}
                if spec.mixer == MIXER_MAMBA2 else None
            )
            lk, s = _lm_layer_kernels(
                f"L{layer}", cfg.d_model, cfg.d_ff, max(cfg.num_heads, 1),
                seq_len, decode, s, seed=seed, moe=moe, mamba=mamba,
            )
            ks.extend(lk)
        ks.append(
            make_head_kernel(cfg, seq_len, decode, s, seed))
        s += 1
    return ks


def latent_kernels(cfg, ctx, decode, steps=1, seed=211) -> list:
    """Launch stream of ``steps`` forward steps of a `LatentMoEConfig`
    model at batch 1 (``ctx``: the decode context, or the prefill length):
    the token embedding; the main layers, ``first_dense`` dense and then
    routed (`_mla_layer_kernels`); the final norm and the head;
    then each MTP module (arXiv 2412.19437 §2.2): the embedding of the token
    the head predicted, its norm and the norm of the main model's last
    hidden state, ``eh_proj`` of the two concatenated, one routed layer, and
    the shared head (a norm and the main head's product).  The MTP module
    follows the head because its input is the head's prediction."""
    ks: list = []
    s = 0
    T = 1 if decode else ctx
    D, V = cfg.d_model, cfg.vocab_size

    def add(kernel):
        nonlocal s
        ks.append(kernel)
        s += 1

    def layer(prefix, routed):
        nonlocal s
        lk, s = _mla_layer_kernels(prefix, cfg, ctx, decode, s, seed,
                                   routed=routed)
        ks.extend(lk)

    for _step in range(steps):
        add(make_kernel("embedding_lookup", "elementwise",
                        {"n": T * D, "nops": 1, "iters": 2}, s, seed))
        for i in range(cfg.num_layers):
            layer(f"L{i}", routed=i >= cfg.first_dense)
        add(make_kernel("final_rmsnorm", "softmax", {"rows": T, "cols": D},
                        s, seed))
        add(mm_kernel("lm_head_logits", V, D, decode, s, seed, m=T))
        for depth in range(cfg.mtp_depth):
            p = f"MTP{depth}"
            add(make_kernel(f"{p}_embedding_lookup", "elementwise",
                            {"n": T * D, "nops": 1, "iters": 2}, s, seed))
            for role in ("enorm", "hnorm"):
                add(make_kernel(f"{p}_{role}", "softmax",
                                {"rows": T, "cols": D}, s, seed))
            add(mm_kernel(f"{p}_eh_proj", D, 2 * D, decode, s, seed, m=T))
            layer(p, routed=True)
            add(make_kernel(f"{p}_shared_head_rmsnorm", "softmax",
                            {"rows": T, "cols": D}, s, seed))
            add(mm_kernel(f"{p}_shared_head_logits", V, D, decode, s, seed,
                          m=T))
    return ks


def make_head_kernel(cfg, seq_len, decode, seq, seed):
    if decode:
        return make_kernel("lm_head_logits", "gemv",
                           {"n": cfg.vocab_size, "m": cfg.d_model},
                           seq, seed)
    return make_kernel("lm_head_logits", "gemm",
                       {"M": max(seq_len, 64), "N": cfg.vocab_size,
                        "K": cfg.d_model}, seq, seed)
