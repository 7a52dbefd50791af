"""The stem's max-pool backward without ``select-and-scatter`` (ISSUE 26).

``max_pool3d(z, ..., summands=(c, bias))`` tells the pool that the caller
computed ``z = c + bias``. Where the windows do not overlap (``kernel ==
strides``, no padding) its VJP is then one primitive
(``ops/pool_vjp.first_match_scatter``) that lowers to a Pallas kernel for one
TPU and to ``lax.reduce_window``'s own VJP (``select_and_scatter_add``)
everywhere else. The contract pinned here:

* values and gradients (to the conv output and to the bias) are BIT-equal to
  autodiff through ``nn.max_pool(c + bias)``, ties included (the pooled
  gradient goes to the first element of the window, in row-major (D, H, W)
  order, that equals the window's max), in bf16 and float32, with and
  without planes that floor-mode pooling drops, and under ``vmap``,
  ``jax.checkpoint`` and ``lax.map`` (the three ways the round program wraps
  the local step); the kernel itself is held to the same, interpreted;
* the window geometry and the lowering target alone select the backward;
* the forward alone compiles to what ``nn.max_pool`` compiles to.

On the chip: ``pytest -m tpu tests/test_pool_vjp.py`` (the stem's full shape).
"""
import functools
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from neuroimagedisttraining_tpu.models.alexnet3d import S2DStemStage
from neuroimagedisttraining_tpu.models.layers import max_pool3d
from neuroimagedisttraining_tpu.models.resnet3d import S2DResNetStem
from neuroimagedisttraining_tpu.ops import pool_vjp
from neuroimagedisttraining_tpu.ops.s2d import phased_sample_shape
from neuroimagedisttraining_tpu.parallel import make_mesh

DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}
# (D, H, W): with a floor-dropped remainder for window 3 / without one
EXTENTS = {"remainder": (11, 14, 11), "whole": (9, 12, 9)}


def reference(c, bias, k):
    """What the stem computed before: autodiff through the add and the pool."""
    return nn.max_pool(c + bias, window_shape=(k,) * 3, strides=(k,) * 3,
                       padding=[(0, 0)] * 3)


def ours(c, bias, k):
    return max_pool3d(c + bias, kernel=k, strides=k, summands=(c, bias))


def tied_input(shape, dtype, seed=0):
    """Whole numbers, most of them in [-2, 2]: about half the windows of 8
    and most of 27 elements hold their max more than once."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.round(rng.normal(size=shape)), dtype)


def normal(shape, dtype, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape), dtype)


def bits(a):
    a = np.asarray(a.astype(jnp.float32))
    return a.view(np.uint32)


def assert_bit_equal(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(bits(got), bits(want))


def share_of_tied_windows(x, k):
    """Share of the windows that hold their max more than once."""
    a = np.asarray(x.astype(jnp.float32))
    n = [e // k for e in a.shape[1:4]]
    a = a[:, :n[0] * k, :n[1] * k, :n[2] * k]
    a = a.reshape(a.shape[0], n[0], k, n[1], k, n[2], k, a.shape[-1])
    at_max = (a == a.max(axis=(2, 4, 6), keepdims=True)).sum(axis=(2, 4, 6))
    return float((at_max > 1).mean())


def case(dtype, extent=(11, 14, 11), lead=(2,), seed=0):
    c = tied_input(lead + extent + (8,), dtype, seed)
    bias = tied_input((8,), dtype, seed + 10)
    return c, bias


@pytest.mark.parametrize("k", [3, 2], ids=["window3", "window2"])
@pytest.mark.parametrize("extent", sorted(EXTENTS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_values_and_gradients_bit_equal_to_autodiff_with_ties(
        dtype, extent, k):
    c, bias = case(DTYPES[dtype], EXTENTS[extent])
    assert share_of_tied_windows(c + bias, k) > 0.3    # the ties are there
    want_m, want_vjp = jax.vjp(lambda a, b: reference(a, b, k), c, bias)
    got_m, got_vjp = jax.vjp(lambda a, b: ours(a, b, k), c, bias)
    assert_bit_equal(got_m, want_m)
    g = normal(want_m.shape, DTYPES[dtype])
    for got, want in zip(got_vjp(g), want_vjp(g)):
        assert_bit_equal(got, want)


@pytest.mark.parametrize("k", [3, 2], ids=["window3", "window2"])
@pytest.mark.parametrize("extent", sorted(EXTENTS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_kernel_bit_equal_to_select_and_scatter_with_ties(dtype, extent, k):
    """The Pallas kernel, interpreted: the spelling one TPU runs."""
    c, bias = case(DTYPES[dtype], EXTENTS[extent], seed=4)
    m = reference(c, bias, k)
    g = normal(m.shape, DTYPES[dtype])
    want = pool_vjp._scatter_xla(c, bias, m, g, window=(k,) * 3)
    got = pool_vjp._scatter_pallas(c, bias, m, g, window=(k,) * 3,
                                   interpret=True)
    assert_bit_equal(got, want)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_kernel_gives_dropped_planes_zero(dtype):
    c, bias = case(DTYPES[dtype], lead=(1,))
    m = reference(c, bias, 3)
    dz = pool_vjp._scatter_pallas(c, bias, m, jnp.ones_like(m),
                                  window=(3, 3, 3), interpret=True)
    dz = np.asarray(dz.astype(jnp.float32))
    assert not dz[:, 9:].any() and not dz[:, :, 12:].any()
    assert not dz[:, :, :, 9:].any()
    # one element of every whole window of every channel got the gradient
    assert dz.sum() == 3 * 4 * 3 * 8


def grad_through(wrap, pool, c, bias, g):
    def loss(a, b):
        return (wrap(pool)(a, b).astype(jnp.float32)
                * g.astype(jnp.float32)).sum()
    return jax.grad(loss, argnums=(0, 1))(c, bias)


WRAPS = {
    # the mesh round vmaps the local step over the clients of a chip
    "vmap": lambda pool: jax.vmap(pool),
    # --remat wraps the loss in jax.checkpoint
    "checkpoint": lambda pool: jax.vmap(jax.checkpoint(pool)),
    # one chip maps its clients one at a time
    "lax_map": lambda pool: lambda a, b: lax.map(lambda ab: pool(*ab), (a, b)),
}


@pytest.mark.parametrize("k", [3, 2], ids=["window3", "window2"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("wrap", sorted(WRAPS))
def test_gradients_bit_equal_under_the_rounds_transforms(wrap, dtype, k):
    # a leading client axis of 3 on the conv output AND on the bias
    c = tied_input((3, 2, 11, 14, 11, 8), DTYPES[dtype], seed=2)
    bias = tied_input((3, 8), DTYPES[dtype], seed=5)
    g = normal((3, 2) + tuple(e // k for e in (11, 14, 11)) + (8,),
               DTYPES[dtype], seed=3)
    want = grad_through(WRAPS[wrap], lambda a, b: reference(a, b, k),
                        c, bias, g)
    got = grad_through(WRAPS[wrap], lambda a, b: ours(a, b, k), c, bias, g)
    for got_leaf, want_leaf in zip(got, want):
        assert_bit_equal(got_leaf, want_leaf)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_kernel_bit_equal_under_vmap(dtype):
    c = tied_input((3, 2, 11, 14, 11, 8), DTYPES[dtype], seed=6)
    bias = tied_input((3, 8), DTYPES[dtype], seed=7)
    m = jax.vmap(lambda a, b: reference(a, b, 3))(c, bias)
    g = normal(m.shape, DTYPES[dtype])
    want = jax.vmap(functools.partial(
        pool_vjp._scatter_xla, window=(3, 3, 3)))(c, bias, m, g)
    got = jax.vmap(functools.partial(
        pool_vjp._scatter_pallas, window=(3, 3, 3), interpret=True))(
            c, bias, m, g)
    assert_bit_equal(got, want)


def test_an_unbatched_bias_under_vmap():
    # shared parameters under a vmapped cohort: only the conv output is mapped
    c = tied_input((3, 2, 11, 14, 11, 8), jnp.float32, seed=8)
    bias = tied_input((8,), jnp.float32, seed=9)
    g = normal((3, 2, 3, 4, 3, 8), jnp.float32)

    def grads(pool):
        return jax.grad(lambda a, b: (jax.vmap(
            lambda ai: pool(ai, b, 3))(a) * g).sum(), argnums=(0, 1))(c, bias)

    for got, want in zip(grads(ours), grads(reference)):
        assert_bit_equal(got, want)


# ---------------------------------------------------------------------------
# what selects the backward: the window geometry, and where it is lowered


def lowered_grad_text(fn, args, platform, shardings=None):
    loss = lambda *a: fn(*a).astype(jnp.float32).sum()    # noqa: E731
    jitted = jax.jit(jax.grad(loss), in_shardings=shardings)
    return jitted.trace(*args).lower(lowering_platforms=(platform,)).as_text()


def spelling(text):
    found = {name for name in ("select_and_scatter", "tpu_custom_call")
             if name in text}
    assert len(found) == 1, found
    return found.pop()


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("geometry,disjoint", [
    ((3, 3, 0), True), ((2, 2, 0), True),
    ((3, 2, 1), False), ((3, 3, 1), False), ((3, 2, 0), False)],
    ids=lambda v: "_".join(map(str, v)) if isinstance(v, tuple) else None)
def test_geometry_and_target_select_the_backward(geometry, disjoint, platform):
    k, s, p = geometry
    c, bias = case(jnp.bfloat16)
    text = lowered_grad_text(
        lambda a: max_pool3d(a + bias, kernel=k, strides=s, padding=p,
                             summands=(a, bias)), (c,), platform)
    kernel = disjoint and platform == "tpu"
    assert spelling(text) == ("tpu_custom_call" if kernel
                              else "select_and_scatter")


def test_without_summands_the_pool_is_reduce_windows_own():
    c, _ = case(jnp.bfloat16)
    text = lowered_grad_text(lambda a: max_pool3d(a, kernel=3, strides=3),
                             (c,), "tpu")
    assert spelling(text) == "select_and_scatter"


def test_mixed_dtypes_keep_reduce_windows_own():
    # a float32 bias on a bf16 conv output promotes the sum: not this op's
    c, _ = case(jnp.bfloat16)
    bias = jnp.ones((8,), jnp.float32)
    text = lowered_grad_text(
        lambda a: max_pool3d(a + bias, kernel=3, strides=3,
                             summands=(a, bias)), (c,), "tpu")
    assert spelling(text) == "select_and_scatter"


def test_a_mesh_keeps_select_and_scatter_on_the_tpu():
    """Mosaic kernels cannot be partitioned by GSPMD: the clients-mesh round
    must lower to the program it was."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("clients",))
    c = tied_input((2, 2, 11, 14, 11, 8), jnp.bfloat16)
    bias = tied_input((2, 8), jnp.bfloat16, seed=3)
    by_client = NamedSharding(mesh, PartitionSpec("clients"))
    text = lowered_grad_text(
        lambda a, b: jax.vmap(lambda ai, bi: ours(ai, bi, 3))(a, b),
        (c, bias), "tpu", shardings=(by_client, by_client))
    assert spelling(text) == "select_and_scatter"


@pytest.mark.parametrize("space,want", [
    (1, "tpu_custom_call"), (2, "select_and_scatter")],
    ids=["clients_manual", "space_left_automatic"])
def test_a_shard_map_over_every_sharded_axis_gives_the_kernel(space, want):
    """The same vmapped use inside ``jax.shard_map`` over ``clients``: with
    every mesh axis of more than one device manual no partitioner touches
    the op, so the chip's own sites get the kernel (the clients-mesh round,
    ``base.py:_train_clients``); a ``space`` axis of 2 that stays automatic
    is GSPMD's to partition, and keeps XLA's op."""
    mesh = make_mesh(2, space)
    c = tied_input((2, 2, 11, 14, 11, 8), jnp.bfloat16)
    bias = tied_input((2, 8), jnp.bfloat16, seed=3)
    by_client = PartitionSpec("clients")
    pooled = jax.shard_map(
        jax.vmap(lambda ai, bi: ours(ai, bi, 3)), mesh=mesh,
        in_specs=(by_client, by_client), out_specs=by_client,
        axis_names={"clients"})
    sharding = NamedSharding(mesh, by_client)
    text = lowered_grad_text(pooled, (c, bias), "tpu",
                             shardings=(sharding, sharding))
    assert spelling(text) == want


def test_blocks_too_large_for_vmem_keep_select_and_scatter():
    c = jax.ShapeDtypeStruct((64, 6, 6, 3000, 128), jnp.bfloat16)   # a shape
    bias = jnp.zeros((128,), jnp.bfloat16)
    assert pool_vjp._kernel_vmem_bytes(
        c.shape, c.dtype, (3, 3, 3)) > pool_vjp._VMEM_BUDGET
    text = lowered_grad_text(lambda a: ours(a, bias, 3), (c,), "tpu")
    assert spelling(text) == "select_and_scatter"


STEMS = {
    # module, (dense volume, stem kernel, stem pad), disjoint pool with bias?
    "alexnet3d_stem_3_3_0": (S2DStemStage, ((33, 33, 33), 5, 0), True),
    "resnet_l3_stem_3_2_1": (S2DResNetStem, ((29, 33, 29), 3, 3), False),
}


@pytest.mark.parametrize("pool_first", [True, False],
                         ids=["pool_first", "textbook_order"])
@pytest.mark.parametrize("stem", sorted(STEMS))
def test_stem_stages_follow_the_rule(stem, pool_first):
    module, volume, disjoint = STEMS[stem]
    mdl = module(features=8, pool_first=pool_first)
    x = jnp.zeros((2,) + phased_sample_shape(*volume), jnp.float32)
    params = mdl.init(jax.random.PRNGKey(0), x)
    text = lowered_grad_text(lambda p: mdl.apply(p, x), (params,), "tpu")
    # the textbook order pools the normalised tensor: no sum to tell of
    kernel = disjoint and pool_first
    assert spelling(text) == ("tpu_custom_call" if kernel
                              else "select_and_scatter")


# ---------------------------------------------------------------------------
# the forward alone


def compiled_ops(fn, *args):
    """The compiled program's instructions, names and metadata stripped."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    ops = re.findall(r"= \S+ ([\w-]+)\(", text)
    return sorted(o for o in ops if o not in ("parameter", "constant"))


@pytest.mark.parametrize("k", [3, 2], ids=["window3", "window2"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_forward_alone_compiles_to_the_one_reduce_window(dtype, k):
    c, bias = case(DTYPES[dtype])
    got = compiled_ops(lambda a, b: ours(a, b, k), c, bias)
    want = compiled_ops(lambda a, b: reference(a, b, k), c, bias)
    assert got == want
    assert got.count("reduce-window") == 1


# ---------------------------------------------------------------------------
# on the chip


@pytest.mark.tpu
def test_stem_shape_bit_equal_on_the_chip():
    """The stem's full shape through the public function, jitted: the kernel
    against ``select_and_scatter`` on the TPU itself."""
    shape = (16, 59, 71, 59, 64)
    c = (jnp.round(jax.random.normal(jax.random.PRNGKey(7), shape) * 4)
         / 4).astype(jnp.bfloat16)
    bias = (jnp.round(jax.random.normal(jax.random.PRNGKey(8), (64,)) * 4)
            / 4).astype(jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(9), (16, 19, 23, 19, 64)
                          ).astype(jnp.bfloat16)

    def grads(pool):
        return jax.jit(lambda a, b: jax.vjp(
            lambda a_, b_: pool(a_, b_, 3), a, b)[1](g))(c, bias)

    text = jax.jit(jax.grad(lambda a: ours(a, bias, 3).astype(
        jnp.float32).sum())).lower(c).as_text()
    assert spelling(text) == "tpu_custom_call"
    for got, want in zip(grads(ours), grads(reference)):
        assert bool(jnp.array_equal(
            lax.bitcast_convert_type(got, jnp.uint16),
            lax.bitcast_convert_type(want, jnp.uint16)))
