"""The stem pool's two backward kernels, the attention product's three
(ISSUE 34: under a selection; ISSUE 36: under the positions' rule) and the
held experts' seven (ISSUE 38) compiled by the chip's own compiler
at the benchmark's shapes, for a v5e that is described, not attached
(``on-chip-measurement`` guide, section 2.3). Interpret mode passes what
Mosaic refuses: a strided load of 16-bit data, a DMA slice of a memref whose
minor dimension is 64, more VMEM than a kernel may use (all three met by
ISSUE 32's kernel on its way). A compile, never a time.

The topology is described inside a fixture of this one file and nowhere at
import time: only one process may load the TPU's library.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.lib import scopes
from neuroimagedisttraining_tpu.models import decoder
from neuroimagedisttraining_tpu.ops import (
    grouped_mlp,
    masked_attention,
    pool_vjp,
)


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
    """``shapes``: bfloat16 unless a dtype comes last in one."""
    args = [jax.ShapeDtypeStruct(s[:-1], s[-1], sharding=one_chip)
            if not isinstance(s[-1], int)
            else jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
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


# keye_vl2_fed.longctx: 16,384 tokens, 8 query heads on 1 KV head of 128
ATTENTION = dict(q=(1, 16384, 1, 8, 128), kv=(1, 16384, 1, 128),
                 keep=(1, 16384, 16384, jnp.int8),
                 lse=(1, 1, 8, 1, 16384, jnp.float32))


def test_selected_attentions_forward_kernel_compiles_for_the_chip(one_chip):
    a = ATTENTION
    text = compiled_text(
        lambda q, k, v, keep: masked_attention._forward_pallas(
            q, k, v, keep, tiles=masked_attention._TILES),
        one_chip, a["q"], a["kv"], a["kv"], a["keep"])
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1


def test_selected_attentions_dq_and_dkv_kernels_compile_for_the_chip(
        one_chip):
    a = ATTENTION
    text = compiled_text(
        lambda *args: masked_attention._backward_pallas(
            *args, tiles=masked_attention._TILES),
        one_chip, a["q"], a["kv"], a["kv"], a["keep"], a["q"], a["lse"],
        a["q"])
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2


# laguna_s21_fed.train: 8,192 tokens on 1 KV head of 128; 6 query heads under
# the causal rule (full layers), 9 under a window of 512 (sliding layers)
LAGUNA = {"full": (6, 0), "window": (9, 512)}


def laguna_shapes(group):
    return dict(q=(1, 8192, 1, group, 128), kv=(1, 8192, 1, 128),
                lse=(1, 1, group, 1, 8192, jnp.float32))


@pytest.mark.parametrize("kind", sorted(LAGUNA))
def test_lagunas_forward_kernel_compiles_for_the_chip(one_chip, kind):
    """The mask from the positions: no ``keep`` operand, at the tiles the
    lowering picks for the shape (VMEM at 9 heads a grid step)."""
    group, window = LAGUNA[kind]
    a = laguna_shapes(group)
    text = compiled_text(
        lambda q, k, v: masked_attention._forward_pallas(
            q, k, v, tiles=masked_attention.tiles_of(window),
            window=window),
        one_chip, a["q"], a["kv"], a["kv"])
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1


@pytest.mark.parametrize("kind", sorted(LAGUNA))
def test_lagunas_dq_and_dkv_kernels_compile_for_the_chip(one_chip, kind):
    group, window = LAGUNA[kind]
    a = laguna_shapes(group)
    text = compiled_text(
        lambda q, k, v, *rest: masked_attention._backward_pallas(
            q, k, v, None, *rest,
            tiles=masked_attention.tiles_of(window), window=window),
        one_chip, a["q"], a["kv"], a["kv"], a["q"], a["lse"], a["q"])
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2


def test_compiled_kernels_stand_under_the_callers_scope(one_chip):
    """The benchmark joins a kernel's time to ``attention/selected`` by the
    ``op_name`` of the COMPILED program. The kernels are jitted functions
    the layers share, whose lowered body knows no caller: the compiler
    composes the names, in both passes."""
    def grads(q, k, v, keep):
        def loss(q, k, v):
            with jax.named_scope("attention"), jax.named_scope("selected"):
                out = masked_attention.masked_attention(
                    q, k, v, keep, decoder._attend, "selected")
            return out.astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = compiled_text(grads, one_chip, (1, 1024, 1, 8, 128),
                         (1, 1024, 1, 128), (1, 1024, 1, 128),
                         (1, 1024, 1024, jnp.int8))
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(scopes.direction(n) for n in names) == ["bwd", "bwd", "fwd"]
    for name in names:
        assert scopes.under(name, "attention/selected"), name
        assert name.endswith("/pallas_call"), name


# the held experts' chunk: (slots, hidden, width, held) of
# lfm2_8b_a1b_fed.longctx and keye_vl2_fed.longctx (laguna_s21_fed.train's,
# 10,240 slots on 8 experts, is not laid out in aligned groups)
EXPERTS = {"lfm2": (65536, 2048, 1792, 8), "keye": (65536, 2048, 768, 16)}


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("cell", sorted(EXPERTS))
def test_the_experts_kernels_compile_for_the_chip(one_chip, cell, backward):
    """At the row tile and the column tiles the shapes pick (VMEM: three
    weight blocks of 2048 x 896 and two row tiles of 512 x 2048, twice, in
    ``lfm2``'s backward)."""
    rows, hidden, width, held = EXPERTS[cell]
    tm = grouped_mlp.row_tile(rows, held)
    total = grouped_mlp.aligned_rows(rows, held, tm)
    kernels = grouped_mlp._pallas(tm)
    shapes = [(total, hidden), (total, jnp.float32), (held, hidden, width),
              (held, hidden, width), (held, width, hidden),
              (total // tm, jnp.int32)] + [(total, hidden)] * backward
    text = compiled_text(kernels.backward if backward else kernels.forward,
                         one_chip, *shapes)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == (
        5 if backward else 2)


def test_compiled_experts_kernels_stand_under_the_callers_scope(one_chip):
    """``experts_ms_per_round`` joins a kernel's time to ``experts`` by the
    ``op_name`` of the COMPILED program, in both passes (XLA's ``ragged-dot``
    kernels carried none)."""
    rows, held, top_k, hidden, width = 16384, 4, 2, 256, 384
    local = jnp.arange(rows) % (held + 1)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    order = jnp.argsort(local, stable=True)

    def grads(tokens, wg, wu, wd, slot_weight):
        def loss(tokens, w, slot_weight):
            with jax.named_scope("experts"):
                out = decoder.routed_part(rows, 1, tokens, w, order,
                                          slot_weight, sizes, top_k)
            return out.astype(jnp.float32).sum(), out
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(
            tokens, {"gate_proj": wg, "up_proj": wu, "down_proj": wd},
            slot_weight)

    text = compiled_text(grads, one_chip, (rows // top_k, hidden),
                         (held, hidden, width), (held, hidden, width),
                         (held, width, hidden), (rows, jnp.float32))
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # one chunk holds the worst case here: the loop over further ones is gone
    assert sorted(scopes.direction(n) for n in names) == (
        ["bwd"] * 5 + ["fwd"] * 2)
    for name in names:
        assert scopes.under(name, "experts"), name
        assert name.endswith("/pallas_call"), name


def test_the_selecting_decoders_compiler_options_are_known_to_the_chip(
        one_chip):
    """``Decoder.tpu_compiler_options`` reach ``jax.jit`` only where the
    backend is a TPU: a name the chip's compiler does not know would fail
    every program there and none here."""
    share = decoder.Share(layers=1, expert_shards=4, tensor_shards=2,
                          vocab_shards=4)
    options = decoder.decoder("keye_tiny", share).tpu_compiler_options
    assert options
    x = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16, sharding=one_chip)
    with no_compile_cache():
        jax.jit(lambda a: a @ a).lower(x).compile(compiler_options=options)
    with no_compile_cache(), pytest.raises(Exception):
        jax.jit(lambda a: a @ a).lower(x).compile(
            compiler_options={"xla_tpu_no_such_option": True})
