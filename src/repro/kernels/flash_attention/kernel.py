"""Pallas TPU kernel: GQA causal flash attention (FlashAttention-2 schedule).

Grid: (B, H, nQ, nK) — the innermost kv dimension streams KV blocks through
VMEM while fp32 running-max / running-sum / accumulator live in VMEM scratch
(they persist across the innermost grid steps; the output block's index_map
is constant in kv, so the block is revisited and written once at the end).

BlockSpecs (VMEM working set per step, bf16 inputs):
  q:   (1, block_q, 1, 1, hd)   — one query tile of one (b, head)
  k/v: (1, block_k, 1, hd)      — kv head = head // G (GQA sharing)
  o:   (1, block_q, 1, 1, hd)
  scratch: acc (block_q, hd) f32, m/l (block_q, 128) f32
With block_q = block_k = 512, hd = 128: ~1.1 MB << 16 MB VMEM; MXU matmul
dims (512x128x512) are 128-aligned.

Causality: kv blocks strictly above the diagonal are skipped via pl.when
(the FLOP savings the chunked-jnp fallback cannot express — see
EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, block_q, block_k, seq_len, num_kv_blocks):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(k_start <= q_start + block_q - 1)  # skip fully-masked kv blocks
    def _compute():
        q = q_ref[0, :, 0, 0, :].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)     # (bk, hd)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                      # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = l_scr[:, 0] * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[:, 0] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[:, 0], 1e-20)[:, None]
        o_ref[0, :, 0, 0, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret")
)
def flash_attention_fwd(q, k, v, *, scale, block_q=512, block_k=512,
                        interpret=False):
    B, S, K, G, hd = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    H = K * G

    grid = (B, H, nq, nk)
    q_spec = pl.BlockSpec(
        (1, block_q, 1, 1, hd), lambda b, h, qi, ki: (b, qi, h // G, h % G, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, block_k, 1, hd), lambda b, h, qi, ki: (b, ki, h // G, 0)
    )
    o_spec = pl.BlockSpec(
        (1, block_q, 1, 1, hd), lambda b, h, qi, ki: (b, qi, h // G, h % G, 0)
    )
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_len=S, num_kv_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
