"""Logical-axis sharding policy engine (t5x-flavoured, divisibility-aware).

Every parameter and activation carries a tuple of *logical* dim names
(e.g. ``('embed', 'heads', 'head_dim')``).  A single policy maps logical
names to mesh axes:

- ``batch``       -> the batch axes (``('data',)`` or ``('pod','data')``)
- tensor-model parallelism: the FIRST name of the preference list present in
  the tuple whose dim can be sharded over the ``model`` axis gets it
  (uneven sharding allowed when dim >= axis size — GSPMD pads; dims smaller
  than the axis are skipped)
- FSDP (params only): the first *remaining* name whose dim is shardable gets
  the batch axes (ZeRO-3: params + optimizer moments sharded over DP)

The same engine drives parameter `in_shardings` and in-model
``with_sharding_constraint`` calls, so the whole policy lives in one place
and per-arch divisibility quirks (24 heads, 8 experts, vocab 49155, MQA)
resolve automatically with documented fallbacks.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Preference order for placing the tensor-parallel 'model' axis.
PARAM_MODEL_PREF = (
    "vocab", "ffn", "heads", "d_inner", "ssm_heads", "attn_hidden", "embed",
)
ACT_MODEL_PREF = (
    "vocab", "ffn", "heads", "d_inner", "ssm_heads", "cache_seq",
)
# Preference order for placing the FSDP axes on parameters.
FSDP_PREF = (
    "embed", "ffn", "vocab", "d_inner", "heads", "attn_hidden",
    "kv_hidden", "experts", "blocks",
)


@dataclass(frozen=True)
class MeshRules:
    mesh: Mesh
    batch_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    fsdp: bool = True
    # perf-iteration knobs (see EXPERIMENTS.md §Perf)
    act_model_pref: tuple[str, ...] = ACT_MODEL_PREF
    param_model_pref: tuple[str, ...] = PARAM_MODEL_PREF
    fsdp_pref: tuple[str, ...] = FSDP_PREF
    seq_shard: bool = False  # sequence parallelism on residual activations

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def fsdp_size(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n


_local = threading.local()


def current_rules() -> Optional[MeshRules]:
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def set_mesh_rules(rules: Optional[MeshRules]):
    prev = current_rules()
    _local.rules = rules
    try:
        yield rules
    finally:
        _local.rules = prev


def _shardable(dim: int, n: int, *, exact: bool) -> bool:
    """Can a dim of size `dim` be sharded n-ways?  pjit ARGUMENT shardings
    must divide exactly; with_sharding_constraint on activations tolerates
    uneven dims (GSPMD pads)."""
    if exact:
        return dim % n == 0
    return dim >= n


def spec_for(
    names: Sequence[Optional[str]],
    shape: Sequence[int],
    *,
    rules: MeshRules,
    is_param: bool,
) -> P:
    assert len(names) == len(shape), (names, shape)
    assign: list = [None] * len(names)

    # 1) batch axes on 'batch' (skip when the batch is too small to shard,
    # e.g. long_500k's global_batch=1 — it stays replicated over data)
    bsize = rules.fsdp_size
    for i, n in enumerate(names):
        if n == "batch" and shape[i] % bsize == 0:
            assign[i] = rules.batch_axes
            break  # a PartitionSpec may use each mesh axis at most once

    # 2) tensor-parallel 'model' placement
    pref = rules.param_model_pref if is_param else rules.act_model_pref
    msize = rules.model_size
    for cand in pref:
        placed = False
        for i, n in enumerate(names):
            if n == cand and assign[i] is None and _shardable(
                shape[i], msize, exact=is_param
            ):
                assign[i] = (rules.model_axis,)
                placed = True
                break
        if placed:
            break

    # 2b) optional sequence parallelism on activations
    if not is_param and rules.seq_shard:
        if not any(a == (rules.model_axis,) for a in assign):
            for i, n in enumerate(names):
                if n == "seq" and assign[i] is None and _shardable(
                    shape[i], msize, exact=False
                ):
                    assign[i] = (rules.model_axis,)
                    break

    # 3) FSDP placement on params — only when the batch axes are still free:
    # a dim already carrying them via rule 1 (e.g. a param with a literal
    # 'batch' dim) must not be duplicated onto a second dim, since a
    # PartitionSpec may use each mesh axis at most once
    if is_param and rules.fsdp and not any(
        a is not None and set(a) & set(rules.batch_axes) for a in assign
    ):
        fsize = rules.fsdp_size
        for cand in rules.fsdp_pref:
            placed = False
            for i, n in enumerate(names):
                if (
                    n == cand
                    and assign[i] is None
                    and shape[i] % fsize == 0  # keep FSDP even (gather layout)
                ):
                    assign[i] = rules.batch_axes
                    placed = True
                    break
            if placed:
                break

    return P(*[a if a is None else (a[0] if len(a) == 1 else a) for a in assign])


def sharding_for(names, shape, *, rules: MeshRules, is_param: bool) -> NamedSharding:
    return NamedSharding(rules.mesh, spec_for(names, shape, rules=rules, is_param=is_param))


def constrain(x, *names, rules: Optional[MeshRules] = None):
    """with_sharding_constraint using the active MeshRules (no-op otherwise).
    `rules` overrides the thread-local context (used by the compiled training
    engine, whose traces are cached per MeshRules — see core/train.py)."""
    rules = rules or current_rules()
    if rules is None:
        return x
    spec = spec_for(names, x.shape, rules=rules, is_param=False)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


#: logical axis names for every field of a packed graph batch
#: (core/batching.py layout).  The flat node/edge/warp/graph axes all carry
#: the 'batch' logical name: packed graphs are data-parallel — bucket sizes
#: are powers of two, so the axes divide evenly over the batch mesh axes.
PACKED_BATCH_AXES: dict[str, tuple] = {
    "node_type": ("batch",),
    "token": ("batch",),
    "pc_norm": ("batch",),
    "vstats": ("batch", None),
    "graph_id": ("batch",),
    "warp_seg": ("batch",),
    "node_mask": ("batch",),
    "edge_src": ("batch",),
    "edge_dst": ("batch",),
    "edge_type": ("batch",),
    "edge_graph": ("batch",),
    "edge_mask": ("batch",),
    "edge_norm": ("batch",),
    "warp_graph": ("batch",),
    "graph_mask": ("batch",),
    "trunc_nodes": ("batch",),
    "trunc_edges": ("batch",),
}


def constrain_batch(batch: dict, rules: Optional[MeshRules] = None) -> dict:
    """Constrain every packed-batch field to its PACKED_BATCH_AXES spec so
    the node/edge/graph axes stay data-parallel INSIDE a compiled scan step
    (GSPMD would otherwise be free to gather the whole epoch slice onto one
    shard).  No-op without active/explicit MeshRules."""
    rules = rules or current_rules()
    if rules is None:
        return batch
    return {
        k: constrain(v, *PACKED_BATCH_AXES[k], rules=rules)
        if k in PACKED_BATCH_AXES else v
        for k, v in batch.items()
    }


def batch_put_spec(field: str, shape: Sequence[int], rules: MeshRules,
                   *, leading: int = 0) -> P:
    """PartitionSpec for host->device staging of one packed-batch field.

    The first ``leading`` dims (e.g. the scan-steps axis of a stacked
    segment) stay replicated; the remaining dims follow PACKED_BATCH_AXES.
    Pad-or-skip fallback: a 'batch' dim that does not divide the data-axis
    size (non-pow2 graph counts, tiny buckets) stays REPLICATED instead of
    producing an invalid argument sharding — pjit argument shardings must
    divide exactly (see `_shardable`), unlike in-trace constraints."""
    axes = PACKED_BATCH_AXES.get(field, ())
    bsize = rules.fsdp_size
    spec: list = [None] * leading
    for i, ax in enumerate(axes):
        dim = shape[leading + i] if leading + i < len(shape) else 0
        if ax == "batch" and bsize > 1 and dim % bsize == 0:
            spec.append(rules.batch_axes if len(rules.batch_axes) > 1
                        else rules.batch_axes[0])
        else:
            spec.append(None)
    return P(*spec)


def shard_batch_put(batch: dict, rules: Optional[MeshRules] = None,
                    *, leading: int = 0) -> dict:
    """Stage a packed batch (host numpy arrays) onto the mesh with its
    PACKED_BATCH_AXES shardings — the multi-device counterpart of the
    single-device ``jnp.asarray`` upload.  Each device receives only its
    own batch shard instead of a full replica, so host->device bytes stay
    constant as the mesh grows.  No-op (plain upload) without rules or on
    a 1-device data axis."""
    import jax.numpy as jnp

    if rules is None:
        rules = current_rules()
    if rules is None or rules.fsdp_size <= 1:
        return {k: jnp.asarray(v) for k, v in batch.items()}
    out = {}
    for k, v in batch.items():
        spec = batch_put_spec(k, tuple(v.shape), rules, leading=leading)
        out[k] = jax.device_put(v, NamedSharding(rules.mesh, spec))
    return out


def param_shardings(param_axes, abstract_params, rules: MeshRules):
    """Pytree of NamedShardings from an axes-metadata tree (same structure)."""

    def _one(axes, leaf):
        return sharding_for(axes, leaf.shape, rules=rules, is_param=True)

    return jax.tree_util.tree_map(
        _one, param_axes, abstract_params,
        is_leaf=lambda a: isinstance(a, tuple) and all(isinstance(x, (str, type(None))) for x in a),
    )
