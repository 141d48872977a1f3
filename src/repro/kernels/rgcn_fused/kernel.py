"""Pallas TPU kernel: fused RGCN message + degree-norm + scatter + basis.

Single-pass flat-edge kernel for the packed encode path (DESIGN.md §12).
Where rgcn_spmm materializes the pre-basis accumulator s: (P, nb*D) in HBM
and finishes with a dense einsum outside the kernel, this kernel contracts
each edge block against the basis INSIDE the pass (contract-then-scatter:
msg_e = sum_k coef[e,k]*wnorm[e] * (h[src_e] @ basis[k]) is linear, so the
per-block matmul against basisflat (nb*D, O) is exact) and accumulates
straight into the final (P, O) aggregate.  Only (P, O) ever touches HBM —
no (P, nb*D) round trip, and the degree normalizer arrives precomputed as
``wnorm`` (edge_mask * edge_norm from core/batching.pack_graphs) instead of
being re-derived by two extra segment-sums per layer.

Precision: h enters in the message dtype (bf16 under the low-precision
policy), so the gather matmul streams bf16 messages through the MXU; the
edge weights w = coef * wnorm and every post-gather intermediate stay f32
(exactly like rgcn_spmm, whose accumulator is f32 — no extra bf16
round-trips the unfused path doesn't have), every matmul pins
``preferred_element_type=jnp.float32``, and the (P, O) output block
accumulates in f32 — bf16 messages, f32 accumulate.

Grid: (nE,) — edge blocks stream through VMEM; h, basisflat and the (P, O)
output block use constant index_maps so Pallas keeps them VMEM-resident
across the whole pass.  block_e = 256 keeps the three matmuls
(256,P)x(P,D), (256,nb*D)x(nb*D,O), (P,256)x(256,O) 128-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES


def _rgcn_fused_flat_kernel(h_ref, src_ref, dst_ref, coef_ref, wnorm_ref,
                            basis_ref, out_ref, *, num_nodes, block_e, nb):
    ei = pl.program_id(0)

    @pl.when(ei == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    h = h_ref[...]                     # (P, D) message dtype
    src = src_ref[0]                   # (block_e,)
    dst = dst_ref[0]
    coef = coef_ref[...]               # (block_e, nb)
    wnorm = wnorm_ref[0]               # (block_e,) mask * 1/|N_r(dst)|
    basis = basis_ref[...]             # (nb*D, O)

    w = coef.astype(jnp.float32) * wnorm[:, None]           # (be, nb) f32

    iota_n = jax.lax.broadcasted_iota(jnp.int32, (block_e, num_nodes), 1)
    onehot_src = (iota_n == src[:, None]).astype(h.dtype)   # (be, P)
    onehot_dst = (iota_n == dst[:, None]).astype(jnp.float32)

    # a one-hot gather of bf16 messages is exact at the MXU's native
    # precision, the only one Mosaic accepts for bf16 operands (a global
    # 'highest' would otherwise reach this dot and be refused)
    gathered = jax.lax.dot_general(                         # (be, D) via MXU
        onehot_src, h, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.DEFAULT if h.dtype == jnp.bfloat16
                   else None),
    )
    D = h.shape[-1]
    weighted = (gathered[:, None, :] * w[:, :, None]).reshape(block_e, nb * D)
    msg = jax.lax.dot_general(                              # (be, O) via MXU
        weighted, basis.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    scat = jax.lax.dot_general(                             # (P, O) via MXU
        onehot_dst.T, msg, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    out_ref[...] += scat.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("num_nodes", "block_e", "interpret")
)
def rgcn_fused_flat_fwd(h, src, dst, coef, wnorm, basisflat, *, num_nodes,
                        block_e=256, interpret=False):
    """Fused flat forward: returns the FINAL per-node aggregate agg: (P, O)
    in f32.  h (P,D); src/dst (Q,) int32 (dst-sorted by core/batching.py so
    each block's scatter targets are near-contiguous); coef (Q,nb) =
    comb[etype]; wnorm (Q,) = edge_mask * edge_norm; basisflat (nb*D, O)."""
    (E,) = src.shape
    P, D = h.shape
    nb = coef.shape[-1]
    O = basisflat.shape[-1]
    if E == 0:  # empty edge list: aggregation is identically zero
        return jnp.zeros((P, O), jnp.float32)
    block_e = min(block_e, E)
    if E % block_e != 0:  # pad edges (wnorm=0 rows are no-ops)
        pad = block_e - E % block_e
        src = jnp.pad(src, (0, pad))
        dst = jnp.pad(dst, (0, pad))
        coef = jnp.pad(coef, ((0, pad), (0, 0)))
        wnorm = jnp.pad(wnorm, (0, pad))
        E = E + pad
    ne = E // block_e
    # TPU-friendly 2-D layout for the int32/f32 edge streams
    src2 = src.reshape(1, E)
    dst2 = dst.reshape(1, E)
    wnorm2 = wnorm.reshape(1, E)

    kernel = functools.partial(
        _rgcn_fused_flat_kernel, num_nodes=P, block_e=block_e, nb=nb
    )
    return pl.pallas_call(
        kernel,
        grid=(ne,),
        in_specs=[
            pl.BlockSpec((P, D), lambda e: (0, 0)),
            pl.BlockSpec((1, block_e), lambda e: (0, e)),
            pl.BlockSpec((1, block_e), lambda e: (0, e)),
            pl.BlockSpec((block_e, nb), lambda e: (e, 0)),
            pl.BlockSpec((1, block_e), lambda e: (0, e)),
            pl.BlockSpec((nb * D, O), lambda e: (0, 0)),
        ],
        out_specs=pl.BlockSpec((P, O), lambda e: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((P, O), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(h, src2, dst2, coef, wnorm2, basisflat)
