"""Max-pool over NON-OVERLAPPING windows of a tensor that is a conv output
plus a per-channel bias, with a backward that leaves XLA's
``select-and-scatter`` (ISSUE 26).

``max_pool3d_of_sum(z, c, bias, window)`` is ``nn.max_pool(z)`` (window ==
strides, no padding, floor mode) for a ``z`` that the caller computed as
``c + bias``. Its VJP sends each pooled gradient to the FIRST element of its
window, in row-major (D, H, W) order, that equals the window's max --
``select_and_scatter_add`` with ``ge`` does the same, so the gradient is
bit-equal to autodiff's in every dtype (ties are common in bf16; giving every
tied element the gradient, or splitting it, would be another gradient).
Planes that floor-mode pooling drops get zero. The gradient goes to ``z``
alone: ``c`` and ``bias`` only tell the backward where ``z`` came from.

Why it wants to know (my chip runs, PR 26, TPU v5e): on the chip the stem's
conv output lives batch-on-sublanes, channels-on-lanes, D/H/W untiled. A
custom call cannot have the bias add fused into it, so given ``z`` XLA either
re-materialises ``c + bias`` for it (another pass over the largest tensor of
the round) or splits the GroupNorm statistics out of the conv fusion; either
way the kernel's gain is lost again. Given ``c`` and ``bias`` the kernel adds
in VMEM, with the rounding of XLA's own add, and the saved ``c`` is the one
tensor the conv fusion writes.

The backward is one primitive with two lowerings, chosen where the program
is lowered, by what the compiler can run there:

* a Pallas kernel where the target is a TPU, no partitioner will touch the
  op (GSPMD cannot partition a Mosaic kernel) and its blocks fit VMEM: a
  program for one device, or the inside of a ``shard_map`` that is manual
  over every mesh axis of more than one device, which is how the
  clients-mesh round trains each chip's sites (``algorithms/base.py:
  _train_clients``, ISSUE 29);
* ``lax.reduce_window``'s own VJP everywhere else: the CPU, and whatever
  GSPMD partitions (a vmapped client axis sharded over a mesh outside any
  ``shard_map``, a ``space`` axis left automatic).

Every spelling of the mask in XLA's own ops lost to ``select-and-scatter`` on
the chip (PERF.md section 6 has each one's milliseconds): XLA fuses neither
an interior ``pad`` nor a broadcast through a window-merging reshape, and
wants a concatenated dimension major. The primitive has no JVP rule: the
backward cannot be differentiated again (nothing in the package does).
"""
from __future__ import annotations

import functools
import itertools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax._src import dispatch, sharding_impls
from jax.extend.core import Primitive
from jax.interpreters import batching, mlir

# what a v5e core offers a kernel, less room for Mosaic's own scratch
_VMEM_BUDGET = 96 * 2 ** 20


def _max_pool(z, window):
    return nn.max_pool(z, window_shape=window, strides=window,
                       padding=[(0, 0)] * 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def max_pool3d_of_sum(z, c, bias, window: Tuple[int, int, int]):
    """``max_pool(z)`` on ``(N, D, H, W, C)``, windows of ``window`` at
    strides ``window``, for ``z == c + bias`` bit for bit (``bias`` per
    channel, all three of one dtype)."""
    del c, bias
    return _max_pool(z, window)


def _fwd(z, c, bias, window):
    m = _max_pool(z, window)
    return m, (c, bias, m)


def _bwd(window, res, g):
    c, bias, m = res
    return (first_match_scatter(c, bias, m, g, window),
            jnp.zeros_like(c), jnp.zeros_like(bias))


max_pool3d_of_sum.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# the two lowerings


def _scatter_xla(c, bias, m, g, *, window):
    """``lax.reduce_window``'s VJP (``select_and_scatter_add``)."""
    del m
    return jax.vjp(lambda z: _max_pool(z, window), c + bias)[1](g)[0]


def _scatter_kernel(c_ref, b_ref, m_ref, g_ref, dz_ref, *, pl, window, pooled):
    """One (window row of D) x (window row of H) block in the (D, H, W, N, C)
    view: the chip's tiles are (N, C), so a voxel is one tile and a window
    position is an index into untiled dimensions. ``pl`` is the pallas
    module, imported only where a kernel is lowered. The window's D
    positions are a loop, not unrolled: the kernel is bound by its DMAs
    either way (9.157 against 9.156 ms alone on the chip, PR 26), and
    unrolled its lowering cost every program that holds it 1.3 s of every
    start on the chip's host."""
    kd, kh, kw = window
    nd, nh, nw = pooled
    plane = list(itertools.product(range(kh), range(kw)))
    whole = jnp.logical_and(pl.program_id(0) < nd, pl.program_id(1) < nh)

    @pl.when(jnp.logical_not(whole))
    def _():    # planes past the last whole window
        dz_ref[...] = jnp.zeros(dz_ref.shape, dz_ref.dtype)

    @pl.when(whole)
    def _():
        bias = b_ref[...].astype(jnp.float32)               # (1, C)

        def one_window_along_w(w, carry):
            m = m_ref[0, 0, w].astype(jnp.float32)          # (N, C)
            g = g_ref[0, 0, w].astype(jnp.float32)

            def find(i, first):     # later positions first: the first wins
                dd = kd - 1 - i
                for j, (dh, dw) in reversed(list(enumerate(plane))):
                    cj = c_ref[dd, dh, w * kw + dw].astype(jnp.float32)
                    zj = (cj + bias).astype(c_ref.dtype).astype(jnp.float32)
                    first = jnp.where(zj == m, dd * len(plane) + j, first)
                return first

            first = lax.fori_loop(
                0, kd, find, jnp.full(m.shape, kd * len(plane), jnp.int32))

            def scatter(dd, carry):
                for j, (dh, dw) in enumerate(plane):
                    dz_ref[dd, dh, w * kw + dw] = jnp.where(
                        first == dd * len(plane) + j, g, 0.0
                    ).astype(dz_ref.dtype)
                return carry

            return lax.fori_loop(0, kd, scatter, carry)

        lax.fori_loop(0, nw, one_window_along_w, 0)
        dropped = dz_ref.shape[2] - nw * kw
        if dropped:
            dz_ref[:, :, pl.ds(nw * kw, dropped)] = jnp.zeros(
                (kd, kh, dropped) + dz_ref.shape[3:], dz_ref.dtype)


def _kernel_vmem_bytes(shape, dtype, window) -> int:
    """Double-buffered blocks of ``c`` and ``dz`` as VMEM holds them: one
    (N, C) tile a voxel, rows padded to a 32-bit sublane tile, C to lanes."""
    n, _, _, w, ch = shape
    itemsize = jnp.dtype(dtype).itemsize
    rows = 8 * max(1, 4 // itemsize)
    tile = (-(-n // rows) * rows) * (-(-ch // 128) * 128) * itemsize
    return 2 * 2 * window[0] * window[1] * w * tile


def _vma(*avals):
    """The manual mesh axes of an enclosing ``shard_map`` over which any of
    ``avals`` varies: the result varies over them as the operands do."""
    return frozenset().union(*(a.vma for a in avals))


def _scatter_pallas(c, bias, m, g, *, window, interpret=False):
    # imported here: a second and a half that only a process which lowers
    # the kernel should pay (no CPU run does)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def voxel_major(a):     # a bitcast on the chip: D, H, W are untiled
        return a.transpose(1, 2, 3, 0, 4)

    n, d, h, w, ch = c.shape
    pooled = m.shape[1:4]
    block = pl.BlockSpec(window[:2] + (w, n, ch),
                         lambda i, j: (i, j, 0, 0, 0))
    row = pl.BlockSpec(
        (1, 1, pooled[2], n, ch),
        lambda i, j: (jnp.minimum(i, pooled[0] - 1),
                      jnp.minimum(j, pooled[1] - 1), 0, 0, 0))
    dz = pl.pallas_call(
        functools.partial(_scatter_kernel, pl=pl, window=window,
                          pooled=pooled),
        grid=(pl.cdiv(d, window[0]), pl.cdiv(h, window[1])),
        in_specs=[block, pl.BlockSpec((1, ch), lambda i, j: (0, 0)),
                  row, row],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(
            (d, h, w, n, ch), c.dtype,
            vma=_vma(*map(jax.typeof, (c, bias, m, g)))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(_VMEM_BUDGET, max(
                16 * 2 ** 20,
                2 * _kernel_vmem_bytes(c.shape, c.dtype, window)))),
        interpret=interpret,
    )(voxel_major(c), bias.reshape(1, ch), voxel_major(m), voxel_major(g))
    return dz.transpose(3, 0, 1, 2, 4)


# ---------------------------------------------------------------------------
# the primitive: one meaning, the lowering picks the spelling


_scatter_p = Primitive("max_pool_first_match_scatter")
_scatter_p.def_impl(functools.partial(dispatch.apply_primitive, _scatter_p))
_scatter_p.def_abstract_eval(
    lambda c, bias, m, g, **_: c.update(weak_type=False,
                                        vma=_vma(c, bias, m, g)))


def first_match_scatter(c, bias, m, g, window):
    """``d max_pool(c + bias) / d (c + bias)`` applied to ``g``."""
    return _scatter_p.bind(c, bias, m, g, window=tuple(window),
                           batch_dims=0)


def _scatter_batch(args, dims, *, window, batch_dims):
    size = next(a.shape[d] for a, d in zip(args, dims) if d is not None)
    args = [jnp.broadcast_to(a, (size,) + a.shape) if d is None
            else jnp.moveaxis(a, d, 0) for a, d in zip(args, dims)]
    return _scatter_p.bind(*args, window=window,
                           batch_dims=batch_dims + 1), 0


batching.primitive_batchers[_scatter_p] = _scatter_batch


def _lower_as(spelling):
    def rule(ctx, *args, window, batch_dims):
        fn = functools.partial(spelling, window=window)
        for _ in range(batch_dims):
            fn = jax.vmap(fn)
        return mlir.lower_fun(fn, multiple_results=False)(ctx, *args)
    return rule


def _unpartitioned(context) -> bool:
    """Whether an op lowered under ``context`` reaches the compiler as it is:
    the program is one device's, or every mesh axis of more than one device
    is manual (the inside of a ``shard_map`` over all of them)."""
    if isinstance(context, sharding_impls.ShardingContext):
        return context.num_devices == 1
    if isinstance(context, sharding_impls.SPMDAxisContext):
        return all(size == 1 or name in context.manual_axes
                   for name, size in context.mesh.shape.items())
    return False


def _lower_tpu(ctx, *args, window, batch_dims):
    c, _, m, _ = ctx.avals_in
    fits = (min(m.shape) > 0 and 2 * _kernel_vmem_bytes(
        c.shape[batch_dims:], c.dtype, window) <= _VMEM_BUDGET)
    whole = _unpartitioned(ctx.module_context.axis_context)
    spelling = _scatter_pallas if whole and fits else _scatter_xla
    return _lower_as(spelling)(ctx, *args, window=window,
                               batch_dims=batch_dims)


mlir.register_lowering(_scatter_p, _lower_as(_scatter_xla))
mlir.register_lowering(_scatter_p, _lower_tpu, platform="tpu")
