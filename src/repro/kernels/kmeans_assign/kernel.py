"""Pallas TPU kernel: blocked K-Means assignment (paper §3.4 at scale).

At framework scale the sampler clusters millions of kernel embeddings
(every invocation of every program in a fleet trace), so assignment is a
streaming (n x d) x (d x k) MXU matmul with a fused row argmin — no (n, k)
distance matrix ever hits HBM.

Grid: (n / block_n,).  BlockSpecs: x (block_n, d) streams; centroids (k, d)
stay resident (k <= a few hundred, d = 256: ~0.25 MB).  block_n = 512 keeps
the distance tile (512 x k) in VMEM and the matmul 128-aligned for d=256.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES


def _pad_rows(x, block_n):
    n = x.shape[0]
    if n % block_n:
        x = jnp.pad(x, ((0, block_n - n % block_n), (0, 0)))
    return x


def _kmeans_kernel(x_ref, c_ref, lab_ref, dist_ref):
    x = x_ref[...]                                  # (bn, d)
    c = c_ref[...]                                  # (k, d)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)      # (bn, 1)
    c2 = jnp.sum(c * c, axis=1)                     # (k,)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (bn, k)
    d = jnp.maximum(x2 - 2.0 * xc + c2[None, :], 0.0)
    lab_ref[...] = jnp.argmin(d, axis=1, keepdims=True).astype(jnp.int32)
    dist_ref[...] = jnp.min(d, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_fwd(x, cent, *, block_n=512, interpret=False):
    n, d = x.shape
    k = cent.shape[0]
    block_n = min(block_n, n)
    if n % block_n:
        pad = block_n - n % block_n
        x = jnp.pad(x, ((0, pad), (0, 0)))
    np_ = x.shape[0]
    grid = (np_ // block_n,)
    labels, dists = pl.pallas_call(
        _kmeans_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, cent)
    return labels[:n, 0], dists[:n, 0]


def _kmeans_fused_kernel(x_ref, c_ref, cm_ref, pm_ref,
                         lab_ref, dist_ref, sum_ref, cnt_ref):
    """Fused assign + masked min-dist + per-cluster sums/counts.

    One streaming pass produces everything a mask-aware Lloyd step needs:
    the (k, d) cluster sums and (k,) counts accumulate across the sequential
    grid (constant out index maps), so the (n, k) distance tile never leaves
    VMEM and no (n, k) one-hot hits HBM.
    """
    i = pl.program_id(0)
    x = x_ref[...]                                  # (bn, d)
    c = c_ref[...]                                  # (k, d)
    cmask = cm_ref[...]                             # (1, k)  1 = live centroid
    pmask = pm_ref[...]                             # (bn, 1) 1 = real point
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    d = jnp.maximum(x2 - 2.0 * xc + c2[None, :], 0.0)
    d = jnp.where(cmask > 0, d, jnp.inf)            # dead slots never win
    lab = jnp.argmin(d, axis=1, keepdims=True).astype(jnp.int32)  # (bn, 1)
    lab_ref[...] = lab
    dist_ref[...] = jnp.min(d, axis=1, keepdims=True) * pmask  # pad: 0 inertia
    k = c.shape[0]
    onehot = (lab == jax.lax.broadcasted_iota(jnp.int32, (1, k), 1))
    onehot = onehot.astype(jnp.float32) * pmask

    @pl.when(i == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    sum_ref[...] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                               # (k, d)
    cnt_ref[...] += jnp.sum(onehot, axis=0, keepdims=True)  # (1, k)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_fused_fwd(x, cent, cmask, pmask, *, block_n=512,
                            interpret=False):
    n, d = x.shape
    k = cent.shape[0]
    block_n = min(block_n, n)
    x = _pad_rows(x, block_n)
    np_ = x.shape[0]
    # 2-D (rows, 1) / (1, k) vector layouts: Mosaic and XLA agree on their
    # tiling at every n, where a 1-D (block_n,) block is refused on TPU
    pmask = jnp.pad(pmask, (0, np_ - n)).reshape(np_, 1)
    cmask = cmask.reshape(1, k)
    grid = (np_ // block_n,)
    labels, dists, sums, cnts = pl.pallas_call(
        _kmeans_fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=interpret,
    )(x, cent, cmask, pmask)
    return labels[:n, 0], dists[:n, 0], sums, cnts[0]


def _sil_sums_kernel(x_ref, xb_ref, oh_ref, sum_ref):
    """Blocked silhouette accumulator: sums[i, c] += sum_j d(i, j) oh[j, c]
    over one column block j.  The (n, bn) distance tile is consumed in VMEM —
    the full (n, n) matrix is never materialized."""
    j = pl.program_id(0)
    x = x_ref[...]                                  # (n, d)  resident
    xb = xb_ref[...]                                # (bn, d) streamed block
    oh = oh_ref[...]                                # (bn, k) masked one-hot
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    b2 = jnp.sum(xb * xb, axis=1)
    xb_t = jax.lax.dot_general(
        x, xb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    dist = jnp.sqrt(jnp.maximum(x2 - 2.0 * xb_t + b2[None, :], 0.0))

    @pl.when(j == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    sum_ref[...] += jax.lax.dot_general(
        dist, oh, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def silhouette_sums_fwd(x, onehot, *, block_n=512, interpret=False):
    """x (n, d), onehot (n, k) (already point-masked) ->
    sums (n, k): total euclidean distance from each point to each cluster."""
    n, d = x.shape
    k = onehot.shape[1]
    block_n = min(block_n, n)
    xb = _pad_rows(x, block_n)
    oh = _pad_rows(onehot, block_n)                 # padded rows are all-zero
    nb = xb.shape[0]
    grid = (nb // block_n,)
    sums = pl.pallas_call(
        _sil_sums_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, d), lambda j: (0, 0)),
            pl.BlockSpec((block_n, d), lambda j: (j, 0)),
            pl.BlockSpec((block_n, k), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((n, k), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, xb, oh)
    return sums
