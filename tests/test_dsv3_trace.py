"""DeepSeek-V3 in the model-zoo trace path, against the plain reference
(``benchmarks/chip/reference_dsv3.py``), and the kernel streams of every
program that was there before it, pinned."""

import dataclasses
import hashlib
import itertools
import json
import os

import jax
import numpy as np
import pytest

from benchmarks.chip import reference_dsv3 as R
from repro.config import LatentMoEConfig
from repro.configs import TRACE_ARCHS, list_archs
from repro.tracing.programs import PAPER_PROGRAMS, get_program
from repro.workloads.modelzoo import DECODE_STEPS, PHASE_SEQ, latent_kernels

CONFIG_FILE = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                           "chip", "configs", "dsv3-decode.json")

#: a DeepSeek-V3-shaped model at a small size: 2 dense + 2 routed layers,
#: 16 experts in 4 groups (2 may be chosen), top-4, 1 shared, 1 MTP module
SMALL = {
    "hidden_size": 64, "num_attention_heads": 4, "vocab_size": 96,
    "intermediate_size": 96, "moe_intermediate_size": 16,
    "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "num_hidden_layers": 4, "first_k_dense_replace": 2,
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
}


def published() -> dict:
    with open(CONFIG_FILE) as f:
        return json.load(f)


def trace_config(c: dict) -> LatentMoEConfig:
    """The trace path's description of the reference's configuration."""
    return LatentMoEConfig(
        arch_id="small", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        vocab_size=c["vocab_size"], d_ff=c["intermediate_size"],
        first_dense=c["first_k_dense_replace"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        num_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        expert_d_ff=c["moe_intermediate_size"],
        num_shared_experts=c["n_shared_experts"], n_group=c["n_group"],
        topk_group=c["topk_group"], mtp_depth=c["num_nextn_predict_layers"])


def test_published_config_is_the_trace_paths():
    assert trace_config(published()) == dataclasses.replace(
        TRACE_ARCHS.get("deepseek-v3"), arch_id="small")


def test_absorbed_mla_decode_equals_the_naive_form():
    """Decode through the latent cache (``W_UK`` folded into the query,
    ``W_UV`` after the attention) against the report's form over the whole
    sequence, at its last position.  The two reassociate the same float32
    sums (q·(c W_UK) against (q W_UK^T)·c), so they agree to float32
    rounding over sums of at most a few hundred terms: 1e-4 of the output's
    largest element.  A wrong absorption, RoPE on the wrong part or a
    stale cache row is off by O(1)."""
    c = SMALL
    p = R.init_params(jax.random.PRNGKey(0), c)["layers"][0]
    T = 12
    x = jax.random.normal(jax.random.PRNGKey(1), (T, c["hidden_size"]))
    pos = np.arange(T)
    with jax.default_matmul_precision("highest"):
        full, rows = R.mla_naive(p, c, x, pos)
        last, cache = R.mla_absorbed(p, c, x[-1:], rows[:-1], pos[-1:])
    scale = float(np.max(np.abs(full)))
    assert scale > 0.1
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(full[-1]),
                               rtol=0, atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(cache), np.asarray(rows),
                               rtol=0, atol=1e-6)


def _brute_force_route(c, logits, bias):
    """Per token, by sorting: each group's score is the sum of its two best
    biased scores; the best ``topk_group`` groups; the best
    ``num_experts_per_tok`` biased scores inside them; weights from the
    unbiased scores, normalised and scaled."""
    E, G = c["n_routed_experts"], c["n_group"]
    per = E // G
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    choice = scores + bias
    ids, weights = [], []
    for t in range(len(logits)):
        gs = [sum(sorted(choice[t, g * per:(g + 1) * per])[-2:])
              for g in range(G)]
        groups = sorted(range(G), key=lambda g: -gs[g])[:c["topk_group"]]
        allowed = [e for g in groups for e in range(g * per, (g + 1) * per)]
        chosen = sorted(allowed, key=lambda e: -choice[t, e])[
            :c["num_experts_per_tok"]]
        w = scores[t, chosen]
        ids.append(chosen)
        weights.append(w / w.sum() * c["routed_scaling_factor"])
    return np.array(ids), np.array(weights)


@pytest.mark.parametrize("size", ["small", "published"])
def test_group_limited_routing_matches_a_brute_force_choice(size):
    c = SMALL if size == "small" else published()
    rng = np.random.default_rng(3)
    E = c["n_routed_experts"]
    logits = rng.standard_normal((16, E)).astype(np.float32)
    bias = (0.5 * rng.standard_normal(E)).astype(np.float32)
    idx, w = R.route(c, logits, bias)
    want_idx, want_w = _brute_force_route(c, logits, bias)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    # the limit binds: some token's best experts overall lie in a group
    # the router left out
    top = np.argsort(-(1 / (1 + np.exp(-logits)) + bias), axis=1)[
        :, :c["num_experts_per_tok"]]
    assert (np.sort(top, 1) != np.sort(want_idx, 1)).any()


def _step_products(c):
    ks = latent_kernels(trace_config(c), 64 if c is SMALL else
                        PHASE_SEQ["decode"], decode=True, steps=1)
    got = R.stream_products([(k.name, k.template, k.params) for k in ks], c)
    want = R.products(c, 64 if c is SMALL else PHASE_SEQ["decode"])
    return got, want


@pytest.fixture(scope="module")
def step_products():
    return {"small": _step_products(SMALL),
            "published": _step_products(published())}


@pytest.mark.parametrize("size,kind", list(itertools.product(
    ["small", "published"], ["dense", "moe", "mtp", "head"])))
def test_step_stream_matches_the_reference_products(step_products, size,
                                                    kind):
    """The matrix products one decode step's launches compute, read off
    their kernel parameters, equal the reference forward's, in order, for
    each layer kind."""
    got, want = step_products[size]
    sel_got = [p for p in got if p[0] == kind]
    sel_want = [p for p in want if p[0] == kind]
    assert sel_want and sel_got == sel_want
    assert R.mismatches(got, want) == 0


def test_decode_program_is_four_reference_steps():
    c = published()
    prog = get_program("model:deepseek-v3:decode")
    got = R.stream_products([(k.name, k.template, k.params)
                             for k in prog.kernels], c)
    want = R.products(c, PHASE_SEQ["decode"]) * DECODE_STEPS
    assert R.mismatches(got, want) == 0
    # 3 dense and 58 routed layers, the head and the MTP module per step
    assert len(prog) == DECODE_STEPS * (1 + 3 * 15 + 58 * 21 + 2 + 27)
    assert [k.seq for k in prog.kernels] == list(range(len(prog)))


def test_prefill_program_takes_the_naive_form():
    prog = get_program("model:deepseek-v3:prefill")
    names = [k.name for k in prog.kernels]
    assert "L0_kv_b_proj" in names and "L0_w_uk_absorb" not in names
    att = next(k for k in prog.kernels if k.name == "L0_mla_attention")
    assert att.params["kv_heads"] == 128 and att.params["q_len"] == 2048
    experts = next(k for k in prog.kernels if k.name == "L3_experts_gate_up")
    # the balanced share: 2,048 tokens x 8 experts over 256 experts
    assert experts.params == {"M": 64, "N": 4096, "K": 7168, "batch": 256}


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_deepseek_v3_plans_through_the_normal_path(phase):
    """get_program -> IngestEngine -> the gcl method -> PlanEngine, at a
    tiny trace window and encoder."""
    from repro.core.rgcn import RGCNConfig
    from repro.core.sampler import GCLSamplerConfig
    from repro.core.train import GCLTrainConfig
    from repro.ingest.engine import IngestConfig
    from repro.sampling import get_method

    prog = get_program(f"model:deepseek-v3:{phase}")
    method = get_method("gcl", cfg=GCLSamplerConfig(
        cap_warps=1, cap_instr=24, k_max=4,
        rgcn=RGCNConfig(dims=(64, 16), proj_hidden=16, proj_out=8),
        train=GCLTrainConfig(steps=2, batch_size=4, scan_chunk=2),
        ingest=IngestConfig(cache=False)))
    art = method.run_prepare(prog)
    plan, = method.plan_batch([(prog, art)])
    assert len(plan.labels) == len(prog)
    # the fit's pass and the embedding's each ingest every invocation
    assert method.sampler.ingest.stats["kernels"] == 2 * len(prog)
    assert 1 <= plan.num_clusters <= 4


def stream_digest(prog) -> str:
    """Every launch's template, parameters, seed, name and position, and
    the analytic statistics the timing model reads of it."""
    h = hashlib.sha256()
    for k in prog.kernels:
        st = k.stats("P1")
        h.update(repr((k.template, sorted(k.params.items()), k.seed, k.name,
                       k.seq, st.flops, st.bytes_accessed, st.working_set,
                       st.ctas, st.warp_instructions)).encode())
    return h.hexdigest()[:16]


#: (invocations, stream_digest) of every program before DeepSeek-V3 came
PINNED = {
    "nw": (255, "1782e2b78c9acfe3"),
    "lu": (2225, "60142aca20af38a2"),
    "3mm": (9, "812790a407b204a3"),
    "bfs": (26, "61cab6a5df112d5f"),
    "cfd": (2425, "fb9db66a57217642"),
    "lud": (120, "d668b333806b223f"),
    "backprop": (2, "a999ca8b0f35791e"),
    "AlexNet": (38, "022b5b87c5e9e000"),
    "qwen1.5": (676, "d506933850b5bdef"),
    "phi-2": (1125, "15821a539c89fe82"),
    "pythia": (845, "8c8a7cd9399139c2"),
    "model:dbrx-132b:prefill": (321, "06ba61592aa6d187"),
    "model:dbrx-132b:decode": (1284, "9e2d53eb3c0b1223"),
    "model:granite-3-2b:prefill": (281, "149653ebd44b233f"),
    "model:granite-3-2b:decode": (1124, "d2fb75259e56d550"),
    "model:grok-1-314b:prefill": (513, "09c0b1082e4a9df3"),
    "model:grok-1-314b:decode": (2052, "ce25360721d86d77"),
    "model:jamba-v0.1-52b:prefill": (241, "3b6dacc28be24e13"),
    "model:jamba-v0.1-52b:decode": (964, "535b7cf03cbc4708"),
    "model:llama3.2-3b:prefill": (197, "7a34c099be38a263"),
    "model:llama3.2-3b:decode": (788, "c303ac48a1f2d25e"),
    "model:mamba2-780m:prefill": (241, "d66953d4207fc34b"),
    "model:mamba2-780m:decode": (964, "ad0de2ae2aef2e41"),
    "model:musicgen-medium:prefill": (337, "a3764f8a38054930"),
    "model:musicgen-medium:decode": (1348, "82df9a05d9c9f212"),
    "model:paligemma-3b:prefill": (127, "fdfec0ab23dceffb"),
    "model:paligemma-3b:decode": (508, "47e68b62007d26ce"),
    "model:qwen2-72b:prefill": (561, "744de8f14ca97ffe"),
    "model:qwen2-72b:decode": (2244, "f47a02f1894b6540"),
    "model:yi-34b:prefill": (421, "96eff1af2f555a49"),
    "model:yi-34b:decode": (1684, "e8e08d8793ff8323"),
}


def test_pinned_programs_are_every_program_but_deepseek_v3():
    zoo = {f"model:{a}:{p}" for a in list_archs() for p in ("prefill",
                                                              "decode")}
    assert set(PINNED) == set(PAPER_PROGRAMS) | zoo
    assert len(PINNED) == 31


@pytest.mark.parametrize("name", sorted(PINNED))
def test_existing_kernel_streams_are_unchanged(name):
    prog = get_program(name)
    assert (len(prog), stream_digest(prog)) == PINNED[name]
