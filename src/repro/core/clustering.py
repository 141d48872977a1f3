"""K-Means + silhouette K-selection (paper §3.4).

TPU-native formulation: distances are dense matmuls (|x|^2 - 2xc^T + |c|^2);
Lloyd iterations are jit'd.  K selection maximizes the silhouette
coefficient, preferring the smaller K on near-ties; degenerate structure
(all kernels essentially identical -> max silhouette below threshold)
collapses to K=1, and tiny programs (n <= 4) fall back to distance-threshold
agglomeration (silhouette is uninformative over singletons).

Two implementations share the selection rule (DESIGN.md §8):

- the SEQUENTIAL reference (`select_k_and_cluster`): one jitted K-Means fit
  per candidate K plus an O(n^2) silhouette per candidate — up to ~2(k_max-1)
  dispatches and as many executables per embedding shape;
- the SWEPT engine (`select_k_and_cluster_swept` / `sweep_cluster_stack`):
  centroids padded to `k_max` with mask-aware Lloyd updates, every candidate
  K evaluated via `vmap`/`lax.scan` inside ONE executable, on-device
  kmeans++ init (fold-in RNG), and a blocked silhouette that never
  materializes the n x n distance matrix.  Executables are cached
  process-wide per (batch, bucket, d, k_max, ...) key — the second program
  in a bucket never recompiles (`ENGINE_STATS`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry

#: process-wide swept-engine instrumentation: `builds` counts compiled
#: executables (cache misses), `dispatches` counts engine invocations
ENGINE_STATS = {"builds": 0, "dispatches": 0}
_ENGINE_CACHE: dict[tuple, object] = {}

#: points-axis power-of-two bucket floor for the swept engine (embeddings
#: are padded per bucket so nearby program sizes share one executable)
POINT_FLOOR = 32


def _pairwise_sq(x, c):
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)
    return jnp.maximum(x2 - 2 * x @ c.T + c2[None], 0.0)


# ---------------------------------------------------------------------------
# sequential reference path (one fit per candidate K)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "iters", "use_pallas"))
def _kmeans_run(x, init_idx, k: int, iters: int = 50, use_pallas: bool = False):
    cent = x[init_idx]

    def assign(cent):
        if use_pallas:  # blocked MXU kernel (interpret resolves per backend)
            from repro.kernels.kmeans_assign.ops import kmeans_assign

            return kmeans_assign(x, cent)
        d = _pairwise_sq(x, cent)
        return jnp.argmin(d, axis=1), jnp.min(d, axis=1)

    def body(cent, _):
        lab, _ = assign(cent)
        onehot = jax.nn.one_hot(lab, k, dtype=x.dtype)
        sums = onehot.T @ x
        cnts = onehot.sum(0)[:, None]
        new = jnp.where(cnts > 0, sums / jnp.maximum(cnts, 1), cent)
        return new, None

    cent, _ = jax.lax.scan(body, cent, None, length=iters)
    lab, mind = assign(cent)
    inertia = jnp.sum(mind)
    return lab, cent, inertia


def _kmeanspp_init(x, k, seed):
    rng = np.random.default_rng(seed)
    n = x.shape[0]
    idx = [int(rng.integers(n))]
    d = np.sum((x - x[idx[0]]) ** 2, axis=1)
    for _ in range(1, k):
        tot = d.sum()
        if not np.isfinite(tot) or tot <= 1e-20:
            nxt = int(rng.integers(n))  # degenerate: all points coincide
        else:
            nxt = int(rng.choice(n, p=d / tot))
        idx.append(nxt)
        d = np.minimum(d, np.sum((x - x[nxt]) ** 2, axis=1))
    return np.array(idx)


def kmeans(x: np.ndarray, k: int, seed: int = 0, iters: int = 50,
           use_pallas: bool = False, init_idx=None):
    """Returns (labels (n,), centroids (k,d), inertia).  `init_idx`
    overrides the kmeans++ seeding (the device-init parity path)."""
    x = np.asarray(x, np.float32)
    if k >= len(x):
        return np.arange(len(x)), x.copy(), 0.0
    init = _kmeanspp_init(x, k, seed) if init_idx is None else init_idx[:k]
    lab, cent, inertia = _kmeans_run(jnp.asarray(x), jnp.asarray(init), k,
                                     iters, use_pallas)
    return np.asarray(lab), np.asarray(cent), float(inertia)


@jax.jit
def _silhouette_jit(x, lab_onehot):
    """Mean silhouette; clusters of size 1 contribute s=0."""
    d = jnp.sqrt(_pairwise_sq(x, x))
    cnt = lab_onehot.sum(0)  # (k,)
    sums = d @ lab_onehot    # (n,k) total distance to each cluster
    own_cnt = lab_onehot @ cnt  # (n,)
    own_sum = jnp.sum(sums * lab_onehot, axis=1)
    a = own_sum / jnp.maximum(own_cnt - 1, 1)
    mean_other = sums / jnp.maximum(cnt[None, :], 1)
    mean_other = jnp.where(lab_onehot > 0, jnp.inf, mean_other)
    mean_other = jnp.where(cnt[None, :] > 0, mean_other, jnp.inf)
    b = jnp.min(mean_other, axis=1)
    s = (b - a) / jnp.maximum(jnp.maximum(a, b), 1e-12)
    s = jnp.where(own_cnt > 1, s, 0.0)  # singleton convention
    return jnp.mean(s)


def silhouette(x: np.ndarray, labels: np.ndarray) -> float:
    k = int(labels.max()) + 1
    onehot = jax.nn.one_hot(jnp.asarray(labels), k, dtype=jnp.float32)
    return float(_silhouette_jit(jnp.asarray(x, jnp.float32), onehot))


def _agglomerate_threshold(x, thresh=0.25):
    """Tiny-n fallback: single-link merge on relative euclidean distance."""
    n = len(x)
    labels = np.arange(n)
    scale = np.mean(np.linalg.norm(x, axis=1)) + 1e-9
    for i in range(n):
        for j in range(i + 1, n):
            if np.linalg.norm(x[i] - x[j]) / scale < thresh:
                labels[labels == labels[j]] = labels[i]
    _, labels = np.unique(labels, return_inverse=True)
    return labels


def _choose_k(scores: dict[int, float], sil_floor: float, tie_tol: float):
    """Shared K-selection rule: maximize silhouette, prefer the smaller K
    on near-ties, collapse to K=1 below the floor.  Returns (chosen_k,
    best_score); chosen_k is None on the K=1 collapse."""
    best = max(scores.values())
    if best < sil_floor:
        return None, best
    return min(k for k, s in scores.items() if s >= best - tie_tol), best


def _host_preamble(x, seed, tiny_n, sil_cap):
    """Degenerate/tiny handling + the deterministic silhouette subsample,
    shared verbatim by the sequential and swept paths.  Returns either
    (labels, info) for an early exit or (None, sil_idx)."""
    n = len(x)
    if n <= 1:
        return (np.zeros(n, int),
                {"k": max(n, 0), "sil": 1.0, "mode": "trivial"}), None
    if n <= tiny_n:
        labels = _agglomerate_threshold(x)
        return (labels,
                {"k": int(labels.max()) + 1, "sil": 1.0, "mode": "tiny"}), None
    sil_idx = None
    if n > sil_cap:
        sil_idx = np.random.default_rng(seed).choice(n, sil_cap, replace=False)
    return None, sil_idx


def select_k_and_cluster(
    x: np.ndarray,
    k_max: int = 48,
    seed: int = 0,
    sil_floor: float = 0.20,
    tie_tol: float = 0.02,
    tiny_n: int = 4,
    sil_cap: int = 1200,
    iters: int = 50,
    use_pallas: bool = False,
    init: str = "host",
):
    """Paper's K-selection: maximize silhouette, prefer smaller K on ties;
    returns (labels, info).  Silhouette is scored on a deterministic
    subsample when n > sil_cap (standard O(n^2) mitigation).

    This is the sequential REFERENCE: one jitted fit + silhouette per
    candidate K.  The compiled engine (`select_k_and_cluster_swept`) returns
    identical labels/K and is the production path (repro.sampling.PlanEngine).
    `init="device"` seeds kmeans++ on-device with fold-in RNG (the engine's
    fully device-resident mode); the default `"host"` numpy seeding is
    bit-stable with the historical behavior.
    """
    x = np.asarray(x, np.float32)
    n = len(x)
    done, sil_idx = _host_preamble(x, seed, tiny_n, sil_cap)
    if done is not None:
        return done

    ks = [k for k in range(2, min(k_max, n - 1) + 1)]
    dev_init = None
    if init == "device":
        dev_init = device_init_indices(x, seed, min(k_max, n - 1))
    results = {}
    scores = {}
    for k in ks:
        lab, cent, _ = kmeans(x, k, seed=seed, iters=iters,
                              use_pallas=use_pallas, init_idx=dev_init)
        # re-label compactly (empty clusters possible)
        # lint: allow[R1] sequential reference syncs per candidate K by design
        _, lab = np.unique(lab, return_inverse=True)
        if lab.max() == 0:
            continue
        results[k] = lab
        if sil_idx is not None:
            sl = lab[sil_idx]
            if sl.max() == sl.min():
                continue
            _, sl = np.unique(sl, return_inverse=True)
            scores[k] = silhouette(x[sil_idx], sl)
        else:
            scores[k] = silhouette(x, lab)
    if not scores:
        return np.zeros(n, int), {"k": 1, "sil": 0.0, "mode": "degenerate"}
    chosen, best = _choose_k(scores, sil_floor, tie_tol)
    if chosen is None:
        return np.zeros(n, int), {"k": 1, "sil": best, "mode": "weak->K=1"}
    return results[chosen], {
        "k": int(results[chosen].max()) + 1, "sil": scores[chosen],
        "mode": "silhouette", "scores": scores,
    }


# ---------------------------------------------------------------------------
# compiled K-sweep engine: every candidate K in one executable
# ---------------------------------------------------------------------------

def bucket_points(n: int) -> int:
    """Next power-of-two points bucket >= POINT_FLOOR (the swept engine's
    padding unit; PlanEngine groups requests by this same key)."""
    b = POINT_FLOOR
    while b < n:
        b <<= 1
    return b


def bucket_batch(n: int) -> int:
    """Power-of-two batch-axis padding for a chunk of n programs (all-zero
    pmask rows are inert), so odd chunk/tail sizes share an executable."""
    b = 1
    while b < n:
        b <<= 1
    return b


def _round_sil_block(n_pad: int, sil_block: int) -> int:
    """Largest power-of-two block <= sil_block that divides the pow2 points
    bucket (a non-divisor block would silently drop distance columns).
    Shared by the sweep and the warm-pool pre-build so both resolve the
    SAME executable cache key."""
    blk = min(sil_block, n_pad)
    while n_pad % blk:
        blk &= blk - 1  # largest power of two <= blk
    return blk


def _device_kmeanspp(x, pmask, key, k_up: int):
    """On-device kmeans++ (D^2 sampling) over the masked points, fold-in
    RNG per draw.  Returns (k_up,) int32 indices; the first k entries are a
    valid kmeans++ seeding for any candidate K <= k_up (prefix property)."""
    base_logits = jnp.where(pmask > 0, 0.0, -jnp.inf)
    i0 = jax.random.categorical(jax.random.fold_in(key, 0), base_logits)
    d0 = jnp.sum((x - x[i0]) ** 2, axis=1) * pmask
    idx0 = jnp.zeros(k_up, jnp.int32).at[0].set(i0.astype(jnp.int32))

    def body(t, carry):
        idx, d = carry
        tot = jnp.sum(d)
        dlog = jnp.where(d > 0, jnp.log(jnp.maximum(d, 1e-30)), -jnp.inf)
        logits = jnp.where(tot > 1e-20, dlog, base_logits)
        nxt = jax.random.categorical(jax.random.fold_in(key, t), logits)
        d = jnp.minimum(d, jnp.sum((x - x[nxt]) ** 2, axis=1) * pmask)
        return idx.at[t].set(nxt.astype(jnp.int32)), d

    idx, _ = jax.lax.fori_loop(1, k_up, body, (idx0, d0))
    return idx


@functools.partial(jax.jit, static_argnames=("k_up",))
def _device_init_padded(xp, pmask, seed, k_up: int):
    key = jax.random.PRNGKey(seed)
    return _device_kmeanspp(xp, pmask, key, k_up)


def device_init_indices(x: np.ndarray, seed: int, k_up: int) -> np.ndarray:
    """Host entry point for the on-device kmeans++ seeding, evaluated at the
    padded bucket shape so the sequential reference and the swept engine
    draw IDENTICAL indices (categorical sampling is shape-dependent).
    Padding happens on the HOST so the executable is keyed on the bucket
    shape, not the raw n — any program of a bucket (with k_up = k_max)
    reuses one compiled init, and the warm pool can pre-build it."""
    x = np.asarray(x, np.float32)
    n = len(x)
    n_pad = bucket_points(n)
    xp = np.zeros((n_pad, x.shape[1]), np.float32)
    xp[:n] = x
    pmask = (np.arange(n_pad) < n).astype(np.float32)
    idx = _device_init_padded(jnp.asarray(xp), jnp.asarray(pmask), seed, k_up)
    return np.asarray(idx)


def _sil_sums_all(x, onehot_all, sil_block: int):
    """Blocked silhouette accumulator for EVERY candidate at once: the
    (n_pad, block) distance tile is computed once per block and contracted
    against each candidate's masked one-hot — the n x n matrix never
    materializes and the distance work is shared across candidates."""
    n_pad = x.shape[0]
    assert n_pad % sil_block == 0, (n_pad, sil_block)  # no dropped columns
    x2 = jnp.sum(x * x, axis=1)
    nb = n_pad // sil_block

    def body(acc, jb):
        xb = jax.lax.dynamic_slice_in_dim(x, jb * sil_block, sil_block)
        ohb = jax.lax.dynamic_slice_in_dim(
            onehot_all, jb * sil_block, sil_block, axis=1)
        xb2 = jnp.sum(xb * xb, axis=1)
        d2 = jnp.maximum(x2[:, None] - 2.0 * (x @ xb.T) + xb2[None, :], 0.0)
        dist = jnp.sqrt(d2)                           # (n_pad, blk)
        return acc + jnp.einsum("nb,kbc->knc", dist, ohb), None

    acc0 = jnp.zeros((onehot_all.shape[0], n_pad, onehot_all.shape[2]),
                     x.dtype)
    acc, _ = jax.lax.scan(body, acc0, jnp.arange(nb))
    return acc                                        # (num_k, n_pad, k_max)


def _sweep_core(x, pmask, init_idx, sil_mask, *, k_max: int, iters: int,
                use_pallas: bool, sil_block: int):
    """One program, every candidate K (2..k_max), one trace.

    Masking rules (DESIGN.md §8): `pmask` marks real points — padding is
    excluded from centroid sums/counts, inertia, and silhouette means;
    per-candidate `cmask` marks live centroid slots — dead slots never win
    an assignment and empty clusters keep their previous centroid.
    `init_idx` carries the kmeans++ seeding (host numpy draw or the
    on-device `device_init_indices` draw — always taken at the program's
    OWN points bucket, so results never depend on batch composition).
    """
    n_pad, d = x.shape
    ks = jnp.arange(2, k_max + 1)                     # (num_k,)
    n_real = jnp.sum(pmask)
    # same candidate set as the sequential `range(2, min(k_max, n-1) + 1)`
    k_valid = ks.astype(x.dtype) <= jnp.minimum(
        # lint: allow[R1] k_max is a static arg — trace-time constant
        jnp.asarray(float(k_max), x.dtype), n_real - 1.0)

    cent0 = x[init_idx]                               # (k_max, d) shared
    cmask_all = (jnp.arange(k_max)[None, :] < ks[:, None]).astype(x.dtype)

    if use_pallas:
        from repro.kernels.kmeans_assign.ops import (
            kmeans_assign_fused, silhouette_sums,
        )

        def lloyd_one(cmask):
            def body(cent, _):
                lab, _, sums, cnts = kmeans_assign_fused(x, cent, cmask,
                                                         pmask)
                new = jnp.where((cnts > 0)[:, None],
                                sums / jnp.maximum(cnts, 1)[:, None], cent)
                return new, None

            cent, _ = jax.lax.scan(body, cent0, None, length=iters)
            lab, _, _, _ = kmeans_assign_fused(x, cent, cmask, pmask)
            return lab

        labels_all = jax.lax.map(lloyd_one, cmask_all)  # (num_k, n_pad)
        onehot_all = (jax.nn.one_hot(labels_all, k_max, dtype=x.dtype)
                      * sil_mask[None, :, None])
        # the barrier keeps XLA from fusing the kernel into the stacking
        # update of the map: the fused copy loses the kernel's scoped-VMEM
        # limit and is refused on the TPU at 'highest' matmul precision
        sums_all = jax.lax.map(
            lambda oh: jax.lax.optimization_barrier(silhouette_sums(x, oh)),
            onehot_all)
    else:
        def lloyd_one(cmask):
            def assign(cent):
                d2 = _pairwise_sq(x, cent)
                d2 = jnp.where(cmask[None, :] > 0, d2, jnp.inf)
                return jnp.argmin(d2, axis=1)

            def body(cent, _):
                lab = assign(cent)
                onehot = (jax.nn.one_hot(lab, k_max, dtype=x.dtype)
                          * pmask[:, None])
                sums = onehot.T @ x
                cnts = onehot.sum(0)[:, None]
                new = jnp.where(cnts > 0, sums / jnp.maximum(cnts, 1), cent)
                return new, None

            cent, _ = jax.lax.scan(body, cent0, None, length=iters)
            return assign(cent)

        labels_all = jax.vmap(lloyd_one)(cmask_all)   # (num_k, n_pad)
        onehot_all = (jax.nn.one_hot(labels_all, k_max, dtype=x.dtype)
                      * sil_mask[None, :, None])
        sums_all = _sil_sums_all(x, onehot_all, sil_block)

    # vectorized masked silhouette (same math as _silhouette_jit, restricted
    # to the sil_mask subset; empty clusters are excluded via cnt > 0)
    cnt = onehot_all.sum(1)                           # (num_k, k_max)
    own_cnt = jnp.einsum("knc,kc->kn", onehot_all, cnt)
    own_sum = jnp.sum(sums_all * onehot_all, axis=2)
    a = own_sum / jnp.maximum(own_cnt - 1, 1)
    mean_other = sums_all / jnp.maximum(cnt[:, None, :], 1)
    mean_other = jnp.where(onehot_all > 0, jnp.inf, mean_other)
    mean_other = jnp.where(cnt[:, None, :] > 0, mean_other, jnp.inf)
    b = jnp.min(mean_other, axis=2)
    s = (b - a) / jnp.maximum(jnp.maximum(a, b), 1e-12)
    s = jnp.where(own_cnt > 1, s, 0.0) * sil_mask[None, :]
    sil = jnp.sum(s, axis=1) / jnp.maximum(jnp.sum(sil_mask), 1.0)
    n_live = jnp.sum(cnt > 0, axis=1)                 # clusters in subset
    ok = (k_valid > 0) & (n_live >= 2)
    return labels_all.astype(jnp.int32), sil, ok


def _sweep_fn(batch: int, n_pad: int, d: int, k_max: int, iters: int,
              use_pallas: bool, sil_block: int, shards: int = 1):
    """Process-wide executable cache: one jitted sweep per static key.
    Shapes are fixed per key, so each entry compiles exactly once —
    `ENGINE_STATS['builds']` therefore counts executable builds.

    ``shards`` is the program-axis device count the dispatch will commit
    its arguments to.  It is part of the key — jit silently re-lowers per
    input sharding, so an entry serving BOTH replicated and sharded
    arguments would hide a compile from the builds counter and break the
    warmup/zero-recompile guarantee (DESIGN.md §11)."""
    key = (batch, n_pad, d, k_max, iters, use_pallas, sil_block, shards)
    fn = _ENGINE_CACHE.get(key)
    if fn is None:
        ENGINE_STATS["builds"] += 1
        core = functools.partial(
            _sweep_core, k_max=k_max, iters=iters, use_pallas=use_pallas,
            sil_block=sil_block)
        fn = jax.jit(jax.vmap(core) if batch > 1 else core)
        _ENGINE_CACHE[key] = fn
    return fn


def _effective_shards(batch: int, data_shards: int) -> int:
    """Program-axis shard count for a dispatch: the largest power of two
    <= ``data_shards`` that divides the (pow2) batch bucket, capped by the
    devices actually present.  Shared by warm_sweep and the dispatch path
    so warmed cache keys are exactly the served keys."""
    if data_shards <= 1 or batch <= 1:
        return 1
    s = 1
    while (s << 1) <= min(batch, data_shards, jax.device_count()):
        s <<= 1
    return s


def _shard_args(args: tuple, shards: int) -> tuple:
    """Commit stacked sweep args to a 1-D data mesh over the leading
    program axis (each device holds batch/shards programs)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:shards]), ("data",))
    return tuple(
        jax.device_put(a, NamedSharding(
            mesh, PartitionSpec(*(("data",) + (None,) * (a.ndim - 1)))))
        for a in args)


def warm_sweep(batch: int, n_pad: int, d: int, k_max: int = 48,
               iters: int = 50, use_pallas: bool = False, init: str = "host",
               sil_block: int = 512, data_shards: int = 1) -> int:
    """Executable PRE-BUILD entry point for the warm pool: compile the swept
    executable for one ``(batch, points-bucket, dim)`` cache key off the
    serving path, so the first real request of a bucket never pays the
    compile.  The jitted sweep is driven once on inert inputs (all-zero
    ``pmask`` — every candidate is masked invalid and the junk outputs are
    discarded), which populates the same process-wide cache the serving
    dispatches hit.  Dispatch counters are NOT bumped — ``builds`` counts
    the compile as usual.  Returns the number of NEW executables built
    (0 when the key was already warm)."""
    B = bucket_batch(max(batch, 1))
    n_pad = bucket_points(n_pad)
    blk = _round_sil_block(n_pad, sil_block)
    shards = _effective_shards(B, data_shards)
    before = ENGINE_STATS["builds"]
    fn = _sweep_fn(B, n_pad, d, k_max, iters, use_pallas, blk, shards)
    shape = ((B, n_pad, d), (B, n_pad), (B, k_max), (B, n_pad))
    if B == 1:
        shape = tuple(s[1:] for s in shape)
    args = (jnp.zeros(shape[0], jnp.float32), jnp.zeros(shape[1], jnp.float32),
            jnp.zeros(shape[2], jnp.int32), jnp.zeros(shape[3], jnp.float32))
    if shards > 1:
        args = _shard_args(args, shards)
    jax.block_until_ready(fn(*args))
    if init == "device":
        # the dominant serving case (n > k_max) resolves k_up == k_max
        k_up = min(k_max, n_pad - 1)
        pm = np.zeros(n_pad, np.float32)
        pm[0] = 1.0  # one live point keeps the categorical logits finite
        jax.block_until_ready(
            _device_init_padded(jnp.zeros((n_pad, d), jnp.float32),
                                jnp.asarray(pm), 0, k_up))
    return ENGINE_STATS["builds"] - before


def engine_stats() -> dict:
    """Snapshot of the swept-engine counters (builds = compiles)."""
    return dict(ENGINE_STATS, cache_entries=len(_ENGINE_CACHE))


def reset_engine_stats() -> None:
    ENGINE_STATS["builds"] = 0
    ENGINE_STATS["dispatches"] = 0


def _finish_one(labels_all, sil, ok, n, ks, sil_floor, tie_tol):
    """Host-side selection over the swept scores — mirrors the sequential
    path's rule exactly (shared `_choose_k`)."""
    scores = {int(ks[i]): float(sil[i]) for i in range(len(ks)) if ok[i]}
    if not scores:
        return np.zeros(n, int), {"k": 1, "sil": 0.0, "mode": "degenerate",
                                  "engine": "sweep"}
    chosen, best = _choose_k(scores, sil_floor, tie_tol)
    if chosen is None:
        return np.zeros(n, int), {"k": 1, "sil": best, "mode": "weak->K=1",
                                  "engine": "sweep"}
    _, lab = np.unique(labels_all[chosen - 2][:n], return_inverse=True)
    return lab, {
        "k": int(lab.max()) + 1, "sil": scores[chosen], "mode": "silhouette",
        "scores": scores, "engine": "sweep",
    }


def sweep_cluster_stack(
    xs: list,
    k_max: int = 48,
    seed: int = 0,
    sil_floor: float = 0.20,
    tie_tol: float = 0.02,
    tiny_n: int = 4,
    sil_cap: int = 1200,
    iters: int = 50,
    use_pallas: bool = False,
    init: str = "host",
    sil_block: int = 512,
    data_shards: int = 1,
):
    """Plan MANY programs per dispatch: embeddings are padded to a shared
    power-of-two points bucket, stacked on a leading program axis, and every
    candidate K of every program is evaluated in ONE vmapped executable.
    Tiny/trivial programs take the host fallback (same as sequential).

    Returns a list of (labels, info) aligned with `xs`.  `seed` may be an
    int (shared) or a per-program sequence.  kmeans++ seeds (host numpy or
    `init="device"` fold-in draws) are always taken at each program's OWN
    points bucket, so a program's result is independent of which batch it
    rides in.

    ``data_shards > 1`` commits the stacked program axis to a 1-D device
    mesh (`_effective_shards` resolves the width that divides the pow2
    batch bucket), so ONE dispatch serves N_devices x the programs of a
    single-device dispatch.  Programs are row-independent — the sharded
    sweep is collective-free and its labels are bit-identical to the
    replicated dispatch.
    """
    xs = [np.asarray(x, np.float32) for x in xs]
    seeds = ([int(seed)] * len(xs) if np.isscalar(seed)
             else [int(s) for s in seed])
    out: list = [None] * len(xs)
    todo: list[int] = []
    sil_idxs: dict[int, np.ndarray] = {}
    for i, x in enumerate(xs):
        done, sil_idx = _host_preamble(x, seeds[i], tiny_n, sil_cap)
        if done is not None:
            out[i] = done
        elif x.ndim != 2 or x.shape[1] == 0:
            # featureless embeddings (d == 0): every point is identical, so
            # this is the degenerate K=1 collapse the sequential path also
            # reaches — decided on the HOST, a zero-width matrix is never
            # worth a device trace
            out[i] = (np.zeros(len(x), int),
                      {"k": 1, "sil": 0.0, "mode": "degenerate",
                       "engine": "sweep"})
        else:
            todo.append(i)
            sil_idxs[i] = sil_idx
    if not todo:
        return out

    n_pad = bucket_points(max(len(xs[i]) for i in todo))
    d = xs[todo[0]].shape[1]
    blk = _round_sil_block(n_pad, sil_block)
    # the batch axis is pow2-padded too (all-zero pmask rows are inert and
    # host-discarded), so odd chunk/tail sizes share an executable instead
    # of compiling one per distinct B
    B = bucket_batch(len(todo))
    xb = np.zeros((B, n_pad, d), np.float32)
    pmask = np.zeros((B, n_pad), np.float32)
    silm = np.zeros((B, n_pad), np.float32)
    init_idx = np.zeros((B, k_max), np.int32)
    with telemetry.span("plan.seed"):
        for row, i in enumerate(todo):
            x = xs[i]
            n = len(x)
            xb[row, :n] = x
            pmask[row, :n] = 1.0
            sil_idx = sil_idxs[i]
            if sil_idx is None:
                silm[row, :n] = 1.0
            else:
                silm[row, sil_idx] = 1.0
            k_up = min(k_max, n - 1)
            if init == "device":
                init_idx[row, :k_up] = device_init_indices(x, seeds[i], k_up)
            else:
                init_idx[row, :k_up] = _kmeanspp_init(x, k_up, seeds[i])

    shards = _effective_shards(B, data_shards)
    # padded: the points the dispatch computes, the pow2 batch and points
    # buckets included
    with telemetry.span("plan.sweep", points=sum(len(xs[i]) for i in todo),
                        padded=B * n_pad, k_max=k_max):
        fn = _sweep_fn(B, n_pad, d, k_max, iters, use_pallas, blk, shards)
        ENGINE_STATS["dispatches"] += 1
        if shards > 1:
            args = _shard_args((xb, pmask, init_idx, silm), shards)
        else:
            args = (jnp.asarray(xb), jnp.asarray(pmask),
                    jnp.asarray(init_idx), jnp.asarray(silm))
        if B > 1:
            labels_all, sil, ok = fn(*args)
        else:
            labels_all, sil, ok = (jnp.expand_dims(r, 0) for r in
                                   fn(*(a[0] for a in args)))
        labels_all = np.asarray(labels_all)
        sil = np.asarray(sil)
        ok = np.asarray(ok)
    ks = list(range(2, k_max + 1))
    with telemetry.span("plan.select"):
        for row, i in enumerate(todo):
            out[i] = _finish_one(labels_all[row], sil[row], ok[row],
                                 len(xs[i]), ks, sil_floor, tie_tol)
    return out


def select_k_and_cluster_swept(x: np.ndarray, **kw):
    """Single-program front door for the compiled K-sweep; identical
    signature/semantics to :func:`select_k_and_cluster` (plus `init` and
    `sil_block`), identical labels/K on the parity suite."""
    return sweep_cluster_stack([np.asarray(x, np.float32)], **kw)[0]
