"""The stem's max-pool backward without ``select-and-scatter`` (ISSUES 26, 32).

``max_pool3d`` picks its backward by the window geometry. Where the windows
overlap or carry the ``-inf`` ring (``strides <= kernel``: ResNet_l3's
``(3, 2, 1)``), and where they are disjoint and ``summands=(c, bias)`` tells
that the caller computed ``z = c + bias``, the VJP is one primitive
(``ops/pool_vjp._scatter_p``) that lowers to a Pallas kernel for one TPU and
to ``lax.reduce_window``'s own VJP (``select_and_scatter_add``) everywhere
else. The contract pinned here:

* values, and on the CPU gradients, are BIT-equal to autodiff through
  ``nn.max_pool``, ties included (the pooled gradient goes to the first
  element of the window, in row-major (D, H, W) order, that equals the
  window's max), in bf16 and float32, on tied and on post-relu inputs (whole
  planes of zeros), with and without planes that floor-mode pooling drops,
  and under ``vmap``, ``jax.checkpoint`` and ``lax.map`` (the three ways the
  round program wraps the local step);
* the disjoint kernel, interpreted, is held to the same bit for bit; the
  overlapping one sums what up to 8 windows send one element in float32 and
  rounds once, where ``select-and-scatter`` adds in its own order: float32
  equal up to that order (1e-6 of the sum of magnitudes) and bit-equal
  wherever an element is the first match of at most one window; bf16 within
  one ulp of that sum of autodiff's, and no further than autodiff's from a
  float64 evaluation;
* the window geometry and the lowering target alone select the backward,
  and every lowering is counted (``pool_bwd_lowerings``);
* the forward alone compiles to what ``nn.max_pool`` compiles to.

On the chip: ``pytest -m tpu tests/test_pool_vjp.py`` (both stems' full shapes).
"""
import functools
import itertools
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
from neuroimagedisttraining_tpu.models.resnet3d import ResNet3DL3, S2DResNetStem
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
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
# overlapping and padded windows (ISSUE 32)

OVERLAPPING = {"3_2_1": (3, 2, 1), "3_2_0": (3, 2, 0), "3_3_1": (3, 3, 1)}


def post_relu_input(shape, dtype, seed=0):
    """What the dense ResNet_l3 pools: a relu's output, more than half of it
    zeros, and two whole planes of them."""
    x = np.maximum(np.asarray(tied_input(shape, jnp.float32, seed)), 0)
    x[:, 1] = 0
    x[:, :, 2] = 0
    return jnp.asarray(x, dtype)


INPUTS = {"tied": tied_input, "post_relu": post_relu_input}


def windows_reference(z, geometry):
    k, s, p = geometry
    return nn.max_pool(z, window_shape=(k,) * 3, strides=(s,) * 3,
                       padding=[(p, p)] * 3)


def windows_ours(z, geometry):
    k, s, p = geometry
    return max_pool3d(z, kernel=k, strides=s, padding=p)


def windows_kernel(z, g, geometry):
    k, s, p = geometry
    return pool_vjp._windows_pallas(
        z, None, g, window=(k,) * 3, strides=(s,) * 3, padding=(p,) * 3,
        interpret=True)


def first_match_in_float64(z, g, geometry):
    """The definition, window by window in numpy: ``(dz, how many windows'
    first match each element is, the sum of the magnitudes it was sent)``."""
    k, s, p = geometry
    z = np.asarray(z.astype(jnp.float32))
    g = np.asarray(g.astype(jnp.float32)).astype(np.float64)
    dz = np.zeros(z.shape, np.float64)
    count = np.zeros(z.shape, np.int64)
    sent = np.zeros(z.shape, np.float64)
    for idx in itertools.product(*(range(n) for n in g.shape[1:4])):
        box = (slice(None),) + tuple(
            slice(max(s * i - p, 0), min(s * i - p + k, e))
            for i, e in zip(idx, z.shape[1:4]))
        block = z[box]
        flat = block.reshape(block.shape[0], -1, block.shape[-1])
        first = (flat == flat.max(axis=1, keepdims=True)).argmax(axis=1)
        hit = (np.arange(flat.shape[1])[None, :, None]
               == first[:, None, :]).reshape(block.shape)
        gi = g[(slice(None),) + idx][:, None, None, None, :]
        dz[box] += hit * gi
        count[box] += hit
        sent[box] += hit * np.abs(gi)
    return dz, count, sent


def assert_first_match_contract(got, want, z, g, geometry):
    """``got``: the kernel's gradient; ``want``: autodiff's."""
    exact, count, sent = first_match_in_float64(z, g, geometry)
    assert got.shape == want.shape and got.dtype == want.dtype
    got64 = np.asarray(got.astype(jnp.float32)).astype(np.float64)
    want64 = np.asarray(want.astype(jnp.float32)).astype(np.float64)
    assert (count > 1).any() or geometry[0] == geometry[1]
    if got.dtype == jnp.float32:
        once = count <= 1
        np.testing.assert_array_equal(bits(got)[once], bits(want)[once])
        assert (np.abs(got64 - want64) <= 1e-6 * sent).all()
        assert (np.abs(got64 - exact) <= 1e-6 * sent).all()
    else:
        assert (np.abs(got64 - want64) <= 2.0 ** -7 * sent).all()
        assert (np.abs(got64 - exact)
                <= np.abs(want64 - exact) + 1e-6 * sent).all()


@pytest.mark.parametrize("kind", sorted(INPUTS))
@pytest.mark.parametrize("geometry", sorted(OVERLAPPING))
@pytest.mark.parametrize("extent", sorted(EXTENTS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_overlapping_values_and_gradients_bit_equal_to_autodiff(
        dtype, extent, geometry, kind):
    geometry = OVERLAPPING[geometry]
    z = INPUTS[kind]((2,) + EXTENTS[extent] + (8,), DTYPES[dtype])
    want_m, want_vjp = jax.vjp(lambda a: windows_reference(a, geometry), z)
    got_m, got_vjp = jax.vjp(lambda a: windows_ours(a, geometry), z)
    assert_bit_equal(got_m, want_m)
    g = normal(want_m.shape, DTYPES[dtype])
    assert_bit_equal(got_vjp(g)[0], want_vjp(g)[0])


@pytest.mark.parametrize("kind", sorted(INPUTS))
@pytest.mark.parametrize("geometry", sorted(OVERLAPPING))
@pytest.mark.parametrize("extent", sorted(EXTENTS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_overlapping_kernel_holds_the_first_match_contract(
        dtype, extent, geometry, kind):
    """The Pallas kernel, interpreted: the spelling one TPU runs. Extent
    ``whole`` leaves an odd number of windows along W, ``remainder`` an even
    one: the windows' two halves then share the lanes."""
    geometry = OVERLAPPING[geometry]
    z = INPUTS[kind]((2,) + EXTENTS[extent] + (8,), DTYPES[dtype], seed=4)
    m, vjp = jax.vjp(lambda a: windows_reference(a, geometry), z)
    g = normal(m.shape, DTYPES[dtype])
    assert_first_match_contract(
        windows_kernel(z, g, geometry), vjp(g)[0], z, g, geometry)


def test_overlapping_kernel_in_chunks_of_h(monkeypatch):
    """A VMEM budget that one chunk of H passes: the kernel cuts H, with
    ``k - 1`` rows of halo, and computes the same."""
    geometry = (3, 2, 1)
    z = tied_input((2, 6, 31, 10, 8), jnp.float32, seed=6)
    m, vjp = jax.vjp(lambda a: windows_reference(a, geometry), z)
    g = normal(m.shape, jnp.float32)
    whole = windows_kernel(z, g, geometry)
    monkeypatch.setattr(pool_vjp, "_VMEM_BUDGET", 6_000_000)
    oc, hc, _, vmem = pool_vjp._windows_plan(
        z.shape, z.dtype, (3,) * 3, (2,) * 3, (1,) * 3)
    assert (oc, hc) == (8, 12) and vmem <= 6_000_000
    assert_bit_equal(windows_kernel(z, g, geometry), whole)
    assert_first_match_contract(whole, vjp(g)[0], z, g, geometry)


@pytest.mark.parametrize("geometry", sorted(OVERLAPPING))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("wrap", sorted(WRAPS))
def test_overlapping_gradients_bit_equal_under_the_rounds_transforms(
        wrap, dtype, geometry):
    geometry = OVERLAPPING[geometry]
    z = tied_input((3, 2, 11, 14, 11, 8), DTYPES[dtype], seed=2)
    shape = jax.eval_shape(lambda a: windows_reference(a, geometry), z[0])
    g = normal((3,) + shape.shape, DTYPES[dtype], seed=3)

    def grad(pool):
        return jax.grad(lambda a: (WRAPS[wrap](lambda ai, _: pool(
            ai, geometry))(a, a).astype(jnp.float32)
            * g.astype(jnp.float32)).sum())(z)

    assert_bit_equal(grad(windows_ours), grad(windows_reference))


@pytest.mark.parametrize("clients", [1, 3])
def test_overlapping_kernel_takes_a_batch_one_at_a_time(clients):
    """The round's ``lax.map`` hands the primitive a client axis of 1, a
    mesh round a chip's sites: the kernel never sees them as its batch."""
    geometry = (3, 2, 1)
    z = tied_input((clients, 2, 9, 12, 9, 8), jnp.bfloat16, seed=6)
    m, vjp = jax.vjp(jax.vmap(lambda a: windows_reference(a, geometry)), z)
    g = normal(m.shape, jnp.bfloat16)
    got = pool_vjp._one_at_a_time(
        lambda a, b: windows_kernel(a, b, geometry), 1)(z, g)
    for i in range(clients):
        assert_first_match_contract(got[i], vjp(g)[0][i], z[i], g[i],
                                    geometry)


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


@pytest.fixture
def lowerings():
    """The process's registry, fresh: ``pool_bwd_lowerings`` by label set."""
    before = obs_metrics.set_registry(None)
    try:
        yield lambda: obs_metrics.get_registry().snapshot().get(
            "pool_bwd_lowerings", {}).get("labeled", {})
    finally:
        obs_metrics.set_registry(before)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("geometry,disjoint", [
    ((3, 3, 0), True), ((2, 2, 0), True),
    ((3, 2, 1), False), ((3, 3, 1), False), ((3, 2, 0), False)],
    ids=lambda v: "_".join(map(str, v)) if isinstance(v, tuple) else None)
def test_geometry_and_target_select_the_backward(geometry, disjoint, platform,
                                                 lowerings):
    """Every geometry here has a kernel (the disjoint ones because they are
    told of the sum); the CPU has none."""
    k, s, p = geometry
    c, bias = case(jnp.bfloat16)
    text = lowered_grad_text(
        lambda a: max_pool3d(a + bias, kernel=k, strides=s, padding=p,
                             summands=(a, bias)), (c,), platform)
    kernel = platform == "tpu"
    assert spelling(text) == ("tpu_custom_call" if kernel
                              else "select_and_scatter")
    assert lowerings() == {"geometry=%d_%d_%d,spelling=%s" % (
        k, s, p, "kernel" if kernel else "xla"): 1.0}


@pytest.mark.parametrize("geometry", [(1, 2, 0), (3, 4, 1)],
                         ids=["1_2_0", "3_4_1"])
def test_strides_past_the_window_keep_reduce_windows_own(geometry, lowerings):
    k, s, p = geometry
    c, _ = case(jnp.bfloat16)
    text = lowered_grad_text(
        lambda a: max_pool3d(a, kernel=k, strides=s, padding=p), (c,), "tpu")
    assert spelling(text) == "select_and_scatter"
    assert lowerings() == {}        # not the primitive's: nothing to choose


@pytest.mark.parametrize("kind", sorted(INPUTS))
def test_overlapping_windows_need_no_summands(kind):
    """The dense ResNet_l3 pools a relu's output, the phased one the raw
    conv output: neither is a sum the pool is told of."""
    z = INPUTS[kind]((2, 11, 14, 11, 8), jnp.bfloat16)
    text = lowered_grad_text(lambda a: windows_ours(a, (3, 2, 1)), (z,), "tpu")
    assert spelling(text) == "tpu_custom_call"


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


POOLS = {
    "disjoint_3_3_0": lambda ai, bi: ours(ai, bi, 3),
    "overlapping_3_2_1": lambda ai, bi: windows_ours(ai + bi, (3, 2, 1)),
}


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_a_mesh_keeps_select_and_scatter_on_the_tpu(pool):
    """Mosaic kernels cannot be partitioned by GSPMD: the clients-mesh round
    must lower to the program it was."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("clients",))
    c = tied_input((2, 2, 11, 14, 11, 8), jnp.bfloat16)
    bias = tied_input((2, 8), jnp.bfloat16, seed=3)
    by_client = NamedSharding(mesh, PartitionSpec("clients"))
    text = lowered_grad_text(
        lambda a, b: jax.vmap(POOLS[pool])(a, b),
        (c, bias), "tpu", shardings=(by_client, by_client))
    assert spelling(text) == "select_and_scatter"


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("space,want", [
    (1, "tpu_custom_call"), (2, "select_and_scatter")],
    ids=["clients_manual", "space_left_automatic"])
def test_a_shard_map_over_every_sharded_axis_gives_the_kernel(space, want,
                                                              pool):
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
        jax.vmap(POOLS[pool]), mesh=mesh,
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


def test_planes_too_large_for_vmem_keep_select_and_scatter(lowerings):
    """The overlapping kernel cuts H into chunks; rows of 3000 voxels of 64
    samples pass the budget however few of them a chunk holds."""
    z = jax.ShapeDtypeStruct((64, 6, 16, 3000, 128), jnp.bfloat16)
    assert pool_vjp._windows_plan(
        z.shape, z.dtype, (3,) * 3, (2,) * 3, (1,) * 3) is None
    text = lowered_grad_text(lambda a: windows_ours(a, (3, 2, 1)), (z,), "tpu")
    assert spelling(text) == "select_and_scatter"
    assert lowerings() == {"geometry=3_2_1,spelling=xla": 1.0}


def test_the_cells_stem_fits_in_two_chunks_of_h():
    """ResNet_l3's conv output at the benchmark's batch: what the kernel
    plans for it (the chip's compiler is asked by test_pallas_kernels.py)."""
    oc, hc, nq, vmem = pool_vjp._windows_plan(
        (16, 63, 75, 63, 64), jnp.bfloat16, (3,) * 3, (2,) * 3, (1,) * 3)
    assert (oc, hc, nq) == (38, 42, 20) and vmem <= pool_vjp._VMEM_BUDGET
    assert pool_vjp._columns(64, 32, 3, 2) == (2, 16, 16)
    assert pool_vjp._plane_schedule(63, 3, 2, 1) == (63, 2, 65, 3)


STEMS = {
    # module, (dense volume, stem kernel, stem pad), does the pool's geometry
    # alone give it a kernel (else: only where it is told of conv + bias)
    "alexnet3d_stem_3_3_0": (S2DStemStage, ((33, 33, 33), 5, 0), False),
    "resnet_l3_stem_3_2_1": (S2DResNetStem, ((29, 33, 29), 3, 3), True),
}


@pytest.mark.parametrize("pool_first", [True, False],
                         ids=["pool_first", "textbook_order"])
@pytest.mark.parametrize("stem", sorted(STEMS))
def test_stem_stages_follow_the_rule(stem, pool_first):
    module, volume, by_geometry = STEMS[stem]
    mdl = module(features=8, pool_first=pool_first)
    x = jnp.zeros((2,) + phased_sample_shape(*volume), jnp.float32)
    params = mdl.init(jax.random.PRNGKey(0), x)
    text = lowered_grad_text(lambda p: mdl.apply(p, x), (params,), "tpu")
    # the textbook order pools the normalised tensor: no sum to tell of,
    # and overlapping windows need none
    kernel = by_geometry or pool_first
    assert spelling(text) == ("tpu_custom_call" if kernel
                              else "select_and_scatter")


def test_the_dense_twins_pool_after_the_relu_gets_the_kernel():
    """``ResNet3DL3`` (``--layout dense``) pools with the same (3, 2, 1)
    windows AFTER the relu; its trunk has no other pool."""
    mdl = ResNet3DL3(layers=(1, 1, 1))
    x = jnp.zeros((2, 29, 33, 29, 1), jnp.float32)
    params = jax.eval_shape(mdl.init, jax.random.PRNGKey(0), x)
    params = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params)
    text = lowered_grad_text(lambda p: mdl.apply(p, x)[0], (params,), "tpu")
    assert spelling(text) == "tpu_custom_call"
    assert text.count("tpu_custom_call") == 1


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


@pytest.mark.tpu
def test_resnet_stem_shape_holds_the_contract_on_the_chip():
    """ResNet_l3's conv output at the benchmark's batch, (3, 2, 1) windows,
    through the public function, jitted: the kernel on the TPU itself
    against ``select_and_scatter`` there, which adds up to 8 terms in bf16,
    each add rounding by up to half an ulp of its partial sum (the kernel
    rounds once): within 2^-6 of the sum of the magnitudes an element is
    sent, and bit-equal where at most one window sends."""
    shape = (16, 63, 75, 63, 64)
    z = (jnp.round(jax.random.normal(jax.random.PRNGKey(7), shape) * 4)
         / 4).astype(jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(9), (16, 32, 38, 32, 64)
                          ).astype(jnp.bfloat16)
    geometry = (3, 2, 1)
    text = jax.jit(jax.grad(lambda a: windows_ours(a, geometry).astype(
        jnp.float32).sum())).lower(z).as_text()
    assert spelling(text) == "tpu_custom_call"

    def vjp(pool, g):
        return jax.jit(lambda a, b: jax.vjp(
            lambda a_: pool(a_, geometry), a)[1](b)[0])(z, g)

    @jax.jit
    def worst(got, want, sent, count):
        got, want, sent = (a.astype(jnp.float32) for a in (got, want, sent))
        diff = jnp.abs(got - want)
        return ((diff - 2.0 ** -6 * sent).max(),
                jnp.where(count.astype(jnp.float32) <= 1, diff, 0).max(),
                (count.astype(jnp.float32) > 1).sum(), jnp.isnan(got).sum())

    got, want = vjp(windows_ours, g), vjp(windows_reference, g)
    sent = vjp(windows_reference, jnp.abs(g))
    count = vjp(windows_reference, jnp.ones_like(g))
    over, once, shared, nans = (float(v) for v in worst(got, want, sent,
                                                         count))
    assert nans == 0 and shared > 0
    assert once == 0, once
    assert over <= 0, over
