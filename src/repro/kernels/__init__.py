"""Pallas TPU kernels for the framework's compute hot-spots.

flash_attention  GQA causal attention, online softmax, KV-block streaming
rgcn_spmm        RGCN message aggregation as MXU one-hot matmuls (TPU-native
                 adaptation of scatter-gather SpMM; DESIGN.md §3)
rgcn_fused       one-pass message+degree-norm+scatter+basis layer for the
                 packed encode path, plus the fused two-level readout
                 (DESIGN.md §12)
kmeans_assign    blocked K-Means assignment + fused Lloyd-step statistics +
                 blocked silhouette sums (planning engine; DESIGN.md §8)
ssd_scan         Mamba-2/SSD intra-chunk compute (per-chunk MXU matmuls)

Each kernel ships <name>/kernel.py (pl.pallas_call + BlockSpec),
<name>/ops.py (jit'd wrapper + custom_vjp fallback), <name>/ref.py
(pure-jnp oracle).  All are validated against their oracle in interpret
mode on CPU (tests/test_kernels_*.py); `interpret=False` targets real TPUs.
"""

from __future__ import annotations

import jax

#: scoped-VMEM budget for the kernels whose working set grows with the
#: packed node count or the points bucket (rgcn_fused, silhouette_sums).
#: The compiler's default scope is 16 MiB; at the main path's real sizes
#: (4096 nodes, D=128 -> O=256; 4096 points x 256-d) these kernels need
#: 18-30 MiB once f32 matmuls run at 'highest' precision, which splits
#: each operand into bf16 parts.  A TPU v5e core has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 64 * 2**20


def default_interpret() -> bool:
    """Backend-aware interpret default for every Pallas wrapper: interpret
    on CPU (where Mosaic cannot compile), compiled everywhere else.  Call
    sites that used to hardcode ``interpret=True`` now resolve through this
    so TPU/GPU runs hit the real kernels."""
    return jax.default_backend() == "cpu"
