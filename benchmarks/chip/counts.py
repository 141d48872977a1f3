"""Operations and compulsory HBM bytes of the work the path needs.

Counted from the real (unpadded) sizes only: nodes, edges and warps of each
graph, points of each program, the candidate K range, the Lloyd steps and
the silhouette subsample.  The same work gives the same count whichever
implementation does it (the jnp path or a Pallas kernel) and however the
buckets pad.  Bytes are the least any implementation must move: inputs read
once and outputs written once, in float32 / int32.

Conventions: a multiply-add is 2 operations; elementwise work is counted
where it is of the same order as the matrix work.
"""

from __future__ import annotations

import numpy as np

F32 = 4
#: per node inputs of a packed batch: node_type, token, pc_norm, 8 vstats,
#: graph id, warp segment, mask (4 bytes each)
NODE_INPUT_BYTES = 14 * F32
#: per edge inputs: src, dst, type, graph id, mask, degree normaliser
EDGE_INPUT_BYTES = 6 * F32


def param_count(rgcn: dict, vocab: dict) -> int:
    dims, nb, R = rgcn["dims"], rgcn["num_bases"], rgcn["num_relations"]
    n = 64 * vocab["opcodes"] + 32 * vocab["var_kinds"] + 16 * vocab["pseudo_kinds"]
    for din, dout in zip(dims[:-1], dims[1:]):
        n += nb * din * dout + R * nb + din * dout + 3 * dout
    ph, po = rgcn["proj_hidden"], rgcn["proj_out"]
    return n + dims[-1] * ph + ph + ph * po + po


def encoder_flops(nodes: int, edges: int, rgcn: dict) -> float:
    """Forward of the RGCN encoder over ``nodes`` / ``edges`` (summed over
    graphs): per layer the weighted message gather-sum over edges
    (2 * E * nb * din), the basis transform of the aggregate
    (2 * N * nb * din * dout), the self loop (2 * N * din * dout) and the
    LayerNorm/ReLU epilogue (about 8 * N * dout); then the readout."""
    dims, nb = rgcn["dims"], rgcn["num_bases"]
    f = 0.0
    for din, dout in zip(dims[:-1], dims[1:]):
        f += 2.0 * edges * nb * din
        f += 2.0 * nodes * nb * din * dout
        f += 2.0 * nodes * din * dout
        f += 8.0 * nodes * dout
    return f + 2.0 * nodes * dims[-1]


def encoder_bytes(nodes: int, edges: int, graphs: int, rgcn: dict,
                  vocab: dict) -> float:
    """Inputs once, parameters once, one embedding per graph out."""
    return (nodes * NODE_INPUT_BYTES + edges * EDGE_INPUT_BYTES
            + param_count(rgcn, vocab) * F32 + graphs * rgcn["dims"][-1] * F32)


def projection_flops(graphs: int, rgcn: dict) -> float:
    d, ph, po = rgcn["dims"][-1], rgcn["proj_hidden"], rgcn["proj_out"]
    return 2.0 * graphs * (d * ph + ph * po)


def train_step_flops(graph_sizes: list, rgcn: dict) -> float:
    """One contrastive step on a batch of graphs ``[(nodes, edges), ...]``:
    two augmented views through encoder and projection head, the B x B
    InfoNCE logits, and a backward pass at twice the forward."""
    nodes = sum(n for n, _ in graph_sizes)
    edges = sum(e for _, e in graph_sizes)
    B = len(graph_sizes)
    fwd = 2 * (encoder_flops(nodes, edges, rgcn) + projection_flops(B, rgcn))
    fwd += 2.0 * B * B * rgcn["proj_out"] + 6.0 * B * B
    return 3.0 * fwd


def sweep_flops(n: int, d: int, k_max: int, iters: int, sil_cap: int,
                tiny_n: int = 4) -> float:
    """K-sweep of one program of ``n`` points: for each candidate
    K = 2..min(k_max, n-1), ``iters`` Lloyd steps (assignment distances
    2*n*K*d plus the centroid sums n*d) and a final assignment; then the
    silhouette over m = min(n, sil_cap) points: the m x m distances once
    (2*m*m*d) and one m x m contraction per candidate (2*m*m).  Programs of
    at most ``tiny_n`` points are clustered on the host: 0."""
    if n <= tiny_n:
        return 0.0
    ks = np.arange(2, min(k_max, n - 1) + 1)
    lloyd = float(np.sum(2.0 * n * ks * d * (iters + 1) + n * d * iters))
    m = min(n, sil_cap)
    return lloyd + 2.0 * m * m * d + 2.0 * m * m * len(ks)


def sweep_bytes(n: int, d: int, k_max: int, tiny_n: int = 4) -> float:
    """Points in, one label per point and candidate plus the scores out."""
    if n <= tiny_n:
        return 0.0
    num_k = max(min(k_max, n - 1) - 1, 0)
    return n * d * F32 + num_k * (n + 1) * F32


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The chip's floor for that work: the larger of operations over peak
    FLOP/s and bytes over peak HBM bandwidth."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
