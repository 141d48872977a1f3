"""The main path's Pallas kernels compiled by the TPU compiler, no chip.

Each test lowers a kernel (or the Pallas K-sweep) at real widths for one
chip of a described ``v5e:2x2`` topology and compiles it: the compiler
refuses here what interpret mode accepts (unaligned slices, tilings that
Mosaic and XLA disagree on, kernels over the VMEM budget).  A compile that
passes is not a chip run.

Every compile runs at the default matmul precision and at 'highest', where
each f32 matmul operand is split into bf16 parts and the kernels need more
VMEM (the main path's kernels first failed on the chip that way).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.  The persistent
compilation cache is off around these compiles (a TPU executable written
to it could not be read back without a chip).
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

N, D, K = 2048, 256, 48        # kmeans: points, embedding width, k_max


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


PRECISIONS = pytest.mark.parametrize("precision", [None, "highest"])


def _compile(fn, *shapes, precision=None):
    with jax.default_matmul_precision(precision):
        return jax.jit(fn).lower(*shapes).compile()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@PRECISIONS
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rgcn_fused_flat_compiles(one_chip, dtype, precision):
    from repro.kernels.rgcn_fused.kernel import rgcn_fused_flat_fwd

    P, Dh, O, E, nb = 4096, 128, 256, 8192, 2
    s = lambda *a: _spec(one_chip, *a)  # noqa: E731
    compiled = _compile(
        lambda h, src, dst, coef, wnorm, basis: rgcn_fused_flat_fwd(
            h, src, dst, coef, wnorm, basis, num_nodes=P, interpret=False),
        s((P, Dh), dtype), s((E,), jnp.int32), s((E,), jnp.int32),
        s((E, nb)), s((E,)), s((nb * Dh, O), dtype), precision=precision)
    assert "tpu_custom_call" in compiled.as_text()


@PRECISIONS
def test_kmeans_assign_compiles(one_chip, precision):
    from repro.kernels.kmeans_assign.kernel import kmeans_assign_fwd

    compiled = _compile(
        lambda x, c: kmeans_assign_fwd(x, c, interpret=False),
        _spec(one_chip, (N, D)), _spec(one_chip, (K, D)),
        precision=precision)
    assert "tpu_custom_call" in compiled.as_text()


@PRECISIONS
def test_kmeans_assign_fused_compiles(one_chip, precision):
    from repro.kernels.kmeans_assign.kernel import kmeans_assign_fused_fwd

    compiled = _compile(
        lambda x, c, cm, pm: kmeans_assign_fused_fwd(x, c, cm, pm,
                                                     interpret=False),
        _spec(one_chip, (N, D)), _spec(one_chip, (K, D)),
        _spec(one_chip, (K,)), _spec(one_chip, (N,)), precision=precision)
    assert "tpu_custom_call" in compiled.as_text()


@PRECISIONS
def test_silhouette_sums_compiles(one_chip, precision):
    from repro.kernels.kmeans_assign.kernel import silhouette_sums_fwd

    compiled = _compile(
        lambda x, oh: silhouette_sums_fwd(x, oh, interpret=False),
        _spec(one_chip, (N, D)), _spec(one_chip, (N, K)),
        precision=precision)
    assert "tpu_custom_call" in compiled.as_text()


@PRECISIONS
def test_pallas_sweep_compiles(one_chip, monkeypatch, precision):
    """The whole use_pallas K-sweep (k_max 48, 50 Lloyd steps) at the
    4096-point bucket of the paper's largest program (cfd, 2,425 kernels).
    The kernels pick interpret mode from the backend, which is the CPU
    here, so the test steers them to the compiled kernels."""
    import functools

    from repro.core.clustering import _sweep_core
    from repro.kernels.kmeans_assign import ops

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    n_pad = 4096
    compiled = _compile(
        functools.partial(_sweep_core, k_max=K, iters=50, use_pallas=True,
                          sil_block=512),
        _spec(one_chip, (n_pad, D)), _spec(one_chip, (n_pad,)),
        _spec(one_chip, (K,), jnp.int32), _spec(one_chip, (n_pad,)),
        precision=precision)
    assert "tpu_custom_call" in compiled.as_text()


@PRECISIONS
def test_sweep_compiles_at_the_8192_point_bucket(one_chip, precision):
    """The jnp K-sweep the cells run, at the 8,192-point bucket that
    DeepSeek-V3's decode trace (5,172 kernels) is the first program to
    reach."""
    import functools

    from repro.core.clustering import _sweep_core

    n_pad = 8192
    compiled = _compile(
        functools.partial(_sweep_core, k_max=K, iters=50, use_pallas=False,
                          sil_block=512),
        _spec(one_chip, (n_pad, D)), _spec(one_chip, (n_pad,)),
        _spec(one_chip, (K,), jnp.int32), _spec(one_chip, (n_pad,)),
        precision=precision)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
