"""The stem pool's two backward kernels compiled by the chip's own compiler
at the benchmark's shapes, for a v5e that is described, not attached
(``on-chip-measurement`` guide, section 2.3). Interpret mode passes what
Mosaic refuses: a strided load of 16-bit data, a DMA slice of a memref whose
minor dimension is 64, more VMEM than a kernel may use (all three met by
ISSUE 32's kernel on its way). A compile, never a time.

The topology is described inside a fixture of this one file and nowhere at
import time: only one process may load the TPU's library.
"""
import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from neuroimagedisttraining_tpu.ops import pool_vjp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def no_compile_cache():
    """An executable compiled for a described chip is written to the
    persistent cache and cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    with no_compile_cache():
        return jax.jit(fn).lower(*args).compile().as_text()


def test_resnet_stems_overlapping_kernel_compiles_for_the_chip(one_chip):
    """(3, 2, 1) windows over ResNet_l3's conv output at batch 16."""
    geometry = dict(window=(3,) * 3, strides=(2,) * 3, padding=(1,) * 3)
    text = compiled_text(
        lambda z, g: pool_vjp._windows_pallas(z, None, g, **geometry),
        one_chip, (16, 63, 75, 63, 64), (16, 32, 38, 32, 64))
    assert text.count("custom-call") >= 1 and "tpu_custom_call" in text


def test_alexnet_stems_disjoint_kernel_compiles_for_the_chip(one_chip):
    """(3, 3, 0) windows over AlexNet3D's conv output plus bias."""
    text = compiled_text(
        lambda c, b, m, g: pool_vjp._scatter_pallas(
            c, b, m, g, window=(3, 3, 3)),
        one_chip, (16, 59, 71, 59, 64), (64,), (16, 19, 23, 19, 64),
        (16, 19, 23, 19, 64))
    assert "tpu_custom_call" in text
