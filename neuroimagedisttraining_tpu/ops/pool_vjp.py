"""Max-pool whose backward leaves XLA's ``select-and-scatter`` (ISSUES 26, 32),
for the two kinds of window the 3-D stems pool with: DISJOINT windows of a
tensor that is a conv output plus a per-channel bias (AlexNet3D), and windows
that OVERLAP or carry the ``-inf`` ring (ResNet_l3's (3, 2, 1)); see below.
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
# windows that overlap or carry the -inf ring (ISSUE 32): strides <= window,
# padding < window, ResNet_l3's (3, 2, 1) among them. The same primitive and
# the same choice of lowering; another kernel, because an element can be the
# first match of several windows (up to 8) and gets their sum, and because a
# window reads rows its neighbours read too. The caller tells of no sum
# (ResNet_l3's stem has no conv bias; its dense twin pools a relu's output):
# the kernel is handed the pooled tensor itself. Its sum is in float32,
# rounded once, where select-and-scatter adds in the tensor's dtype in its
# own order: equal to autodiff's up to that (tests/test_pool_vjp.py has the
# contract), bit-equal wherever at most one window sends.


def _max_pool_windows(z, window, strides, padding):
    return nn.max_pool(z, window_shape=window, strides=strides,
                       padding=[(p, p) for p in padding])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def max_pool3d_windows(z, window, strides, padding):
    """``nn.max_pool(z)`` on ``(N, D, H, W, C)`` for windows that overlap or
    are padded (``strides <= window``, ``padding < window``, floor mode)."""
    return _max_pool_windows(z, window, strides, padding)


def _windows_fwd(z, window, strides, padding):
    m = _max_pool_windows(z, window, strides, padding)
    return m, (z, m)


def _windows_bwd(window, strides, padding, res, g):
    z, m = res
    return (_scatter_p.bind(z, m, g, window=tuple(window),
                            strides=tuple(strides), padding=tuple(padding),
                            batch_dims=0),)


max_pool3d_windows.defvjp(_windows_fwd, _windows_bwd)


def _windows_xla(z, m, g, *, window, strides, padding):
    """``lax.reduce_window``'s VJP (``select_and_scatter_add``)."""
    del m
    return jax.vjp(
        lambda a: _max_pool_windows(a, window, strides, padding), z)[1](g)[0]


def _pooled(extent, k, s, p) -> int:
    return (extent + 2 * p - k) // s + 1


def _plane_schedule(d, k, s, p):
    """Along D the kernel below takes one plane a grid step. ``(covered,
    lag, steps, live)``: the planes some window covers; how many steps after
    a plane arrives its last window is complete (its last plane has arrived,
    or would have: the ``-inf`` ring takes steps too); the steps in all; the
    planes the kernel holds at a time."""
    n = _pooled(d, k, s, p)
    covered = min(d, s * (n - 1) - p + k)
    lag = max(s * min(n - 1, (e + p) // s) - p + k - 1 - e
              for e in range(covered))
    return covered, lag, max(s * (n - 1) - p + k, d + lag), max(lag + 1, k)


# windows along W in one turn of the kernel's inner loops (my chip runs,
# PR 32, ResNet_l3's stem: 4: 6.11 ms a step, 8: 4.85, 16: 4.36)
_AT_A_TIME = 16


def _columns(ch, nw, kw, sw):
    """``(stretches, windows in each, windows at a time)`` along W. With C
    of at most 64 the windows' two halves sit side by side on the 128 lanes
    and every vector operation does the work of two. The windows go several
    at a time: one address and one loop turn for as many voxels, and
    straight-line code the scheduler can pack."""
    def at_a_time(n):
        return max(c for c in range(1, min(n, _AT_A_TIME) + 1) if n % c == 0)
    if 2 * ch <= 128 and nw % 2 == 0 and (
            at_a_time(nw // 2) >= -(-(kw - sw) // sw)):
        return 2, nw // 2, at_a_time(nw // 2)
    return 1, nw, at_a_time(nw)


def _windows_plan(shape, dtype, window, strides, padding):
    """How the kernel below cuts ``H`` for a ``z`` of ``shape`` (N, D, H, W,
    C): ``(owned rows, rows read, windows, VMEM bytes)`` of one chunk for
    the fewest chunks whose buffers fit ``_VMEM_BUDGET``, or None. A chunk
    owns ``oc`` output rows and reads ``k - 1`` rows of halo on each side."""
    n, d, h, w, ch = shape
    (kd, kh, kw), (sd, sh, sw), (pd, _, pw) = window, strides, padding
    nw = _pooled(w, kw, sw, pw)
    itemsize = jnp.dtype(dtype).itemsize
    rows = 8 * max(1, 4 // itemsize)

    def tiles(lanes):
        lanes = -(-lanes // 128) * 128
        return ((-(-n // r) * r) * lanes * b
                for r, b in ((rows, itemsize), (8, 4)))

    halves, half, _ = _columns(ch, nw, kw, sw)
    (tile, _), (packed, packed32) = tiles(ch), tiles(ch * halves)
    live = _plane_schedule(d, kd, sd, pd)[3]
    for chunks in range(1, h + 1):
        oc = -(-h // chunks)
        hc = h if chunks == 1 else oc + 2 * (kh - 1)
        if hc > h or oc < 2 * (kh - 1):
            return None
        nq = min(_pooled(h, kh, sh, padding[1]), (oc + kh - 2) // sh + 1)
        ring = hc + 2 * (kh - 1)
        vmem = (tile * (2 * (hc + oc) * w + 2 * nq * nw)    # the pipeline's
                + packed * live * (hc * (sw * (half - 1) + kw)
                                   + (ring + nq) * half)
                + packed32 * (live * nq + ring) * half)
        if vmem <= _VMEM_BUDGET:
            return oc, hc, nq, vmem
    return None


def _chunk_rows(j, extent, k, s, p, n, chunk):
    """Chunk ``j`` along H: the first row it owns and the first it reads,
    its windows ``[q_lo, q_hi)`` and the window its buffers start at."""
    oc, hc, nq = chunk
    o = jnp.minimum(j * oc, extent - oc)
    i0 = jnp.clip(o - (k - 1), 0, extent - hc)
    q_lo = jnp.maximum(o + p - k + s, 0) // s
    q_hi = jnp.minimum(n - 1, (o + oc - 1 + p) // s) + 1
    return o, i0, q_lo, q_hi, jnp.minimum(q_lo, n - nq)


def _windows_kernel(z_ref, g_ref, o_ref, zring, rm, pm, gd, gh, *, pl, pltpu,
                    extents, window, strides, padding, chunk):
    """The pool is three 1-D pools, over W, then H, then D, and the first
    match in row-major (D, H, W) order is the first match of each in turn:
    the backward is their three backwards, each sending a window's gradient
    to the first row that holds the window's max and summing (float32) where
    windows share a row. The (D, H, W, N, C) view; the grid is (H chunk,
    step), a step one plane of the chunk: the plane that arrives is kept
    (``zring``) and gets its row maxima over W (``rm``) and window maxima
    over H (``pm``); the window row along D that it completes, if any, adds
    its ``g`` into the plane gradients (``gd``, the D backward); the plane
    ``lag`` steps back, which no later window touches, runs the H backward
    (``gh``) and the W backward into the output block. Rings are indexed by
    plane; rows and positions outside the tensor read ``-inf``. Everything
    the kernel keeps is packed along W (``_columns``): window ``r`` of
    the first half and window ``r + half`` share a voxel's lanes."""
    (D, H, W), (kd, kh, kw) = extents, window
    (sd, sh, sw), (pd, ph, pw) = strides, padding
    nd, nh, nw = (_pooled(e, k, s, p) for e, k, s, p in
                  zip(extents, window, strides, padding))
    OC, HC, _ = chunk
    covered, lag, _, _ = _plane_schedule(D, kd, sd, pd)
    K1, R = kh - 1, zring.shape[0]
    f32, dtype = jnp.float32, zring.dtype
    voxel = zring.shape[3:]         # (N, F * C): F stretches of windows
    C = z_ref.shape[-1]
    F, half, CW = _columns(C, nw, kw, sw)
    step = pl.program_id(1)
    o, i0, q_lo, q_hi, qb = _chunk_rows(pl.program_id(0), H, kh, sh, ph, nh,
                                        chunk)

    def loop(lo, hi, body):
        lax.fori_loop(lo, hi, lambda i, c: (body(i), c)[1], 0)

    def first_of(values, top, g):
        """``g`` where ``values[t]`` is the first to equal ``top``, else 0:
        the last one's turn comes when none before it did."""
        found, out = None, []
        for t, v in enumerate(values):
            if t == len(values) - 1:
                hit = True if found is None else jnp.logical_not(found)
            else:
                hit = v == top
                if found is not None:
                    hit = jnp.logical_and(hit, jnp.logical_not(found))
                found = hit if found is None else jnp.logical_or(found, hit)
            out.append(g if hit is True else jnp.where(hit, g, 0.0))
        return out

    def shift(cs, hist, k, s):
        """Sums for the ``s`` rows of each window that no later window
        touches, and the history the next windows inherit. ``cs[t]``: what
        row ``t`` of each of ``cw`` consecutive windows gets; row ``t`` of
        window ``r`` is row ``t + i * s`` of window ``r - i``, and ``hist``
        holds ``cs[s:]`` of the ``back`` windows before these."""
        back = -(-(k - s) // s)
        ext = [jnp.concatenate([h, c]) for h, c in zip(hist, cs[s:])]
        n = cs[0].shape[0]
        done = []
        for t in range(s):
            total = cs[t]
            for i in range(1, back + 1):
                if t + i * s < k:
                    total = total + ext[t + i * s - s][back - i:back - i + n]
            done.append(total)
        return done, tuple(e[n:] for e in ext)

    def no_history(k, s, lead=()):
        return tuple(jnp.zeros((-(-(k - s) // s),) + lead + voxel, f32)
                     for _ in range(k - s))

    def cols(r0):
        return pl.ds(r0 * CW, CW)

    @pl.when(step == 0)
    def _():    # what no pass ever writes: the -inf ring, rows no window has
        ninf = jnp.full(voxel, -jnp.inf, dtype)
        for slot in range(R):
            for row in list(range(K1)) + list(range(K1 + HC, HC + 2 * K1)):
                rm[slot, row] = jnp.broadcast_to(ninf, (half,) + voxel)

        def ring_rows(rel):     # positions the tensor lacks are never written
            for slot in range(R):
                zring[slot, rel] = jnp.broadcast_to(
                    ninf, zring.shape[2:3] + voxel)
        loop(0, HC, ring_rows)

        def clear_gh(row):
            gh[row] = jnp.zeros((half,) + voxel, f32)
        loop(0, HC + 2 * K1, clear_gh)

    def z_at(s, rel, r0):
        """Positions 0 .. kw - 1 of CW windows, each (CW, N, F * C): one
        load of the span they cover, taken apart by position (Mosaic has no
        strided load of 16-bit data; the leading dimension is untiled)."""
        span = zring[s, rel, pl.ds(sw * CW * r0, sw * (CW - 1) + kw)]
        short = (-(-kw // sw) + CW - 1) * sw - span.shape[0]
        if short:   # whole windows' worth past the last one
            span = jnp.concatenate([span, span[:short]])
        return [span[t // sw * sw:][:sw * CW].reshape(
            (CW, sw) + voxel)[:, t % sw] for t in range(kw)]

    @pl.when(step < covered)
    def _():    # the plane that arrived: its maxima over W, then over H
        s = lax.rem(step, R)

        def row(rel):
            for f in range(F):      # stretch f: positions from f * sw * half
                lo = max(pw - f * sw * half, 0)
                hi = min(zring.shape[2], W + pw - f * sw * half)
                zring[s, rel, pl.ds(lo, hi - lo), :, f * C:(f + 1) * C] = (
                    z_ref[0, rel, pl.ds(lo + f * sw * half - pw, hi - lo)])

            def col(r0):
                rm[s, K1 + rel, cols(r0)] = functools.reduce(
                    jnp.maximum, [v.astype(f32) for v in z_at(s, rel, r0)]
                ).astype(dtype)
            loop(0, half // CW, col)
        loop(0, HC, row)

        def win(q):
            base = K1 + sh * q - ph - i0

            def col(r0):
                pm[s, q - qb, cols(r0)] = functools.reduce(
                    jnp.maximum, [rm[s, base + t, cols(r0)].astype(f32)
                                  for t in range(kh)]).astype(dtype)
                gd[s, q - qb, cols(r0)] = jnp.zeros((CW,) + voxel, f32)
            loop(0, half // CW, col)
        loop(q_lo, q_hi, win)

    ends = step + pd - kd + 1       # sd * p - pd + kd - 1 == step

    @pl.when(jnp.logical_and(
        jnp.logical_and(ends >= 0, ends < sd * nd), lax.rem(ends, sd) == 0))
    def _():    # the D backward of the window row this plane completes
        planes = [step - kd + 1 + t for t in range(kd)]
        slots = [lax.rem(dd + R, R) for dd in planes]
        # a plane outside the tensor reads -inf whatever its slot holds
        outside = [t < pd or sd * (nd - 1) - pd + t >= D for t in range(kd)]
        inside = [jnp.logical_and(dd >= 0, dd < D) for dd in planes]

        def win(q):
            def col(r0):
                tops = [pm[slots[t], q - qb, cols(r0)].astype(f32)
                        for t in range(kd)]
                tops = [jnp.where(inside[t], v, -jnp.inf) if outside[t] else v
                        for t, v in enumerate(tops)]
                top = functools.reduce(jnp.maximum, tops)
                g = jnp.concatenate(
                    [g_ref[0, q - qb, pl.ds(f * half + r0 * CW, CW)]
                     for f in range(F)], axis=-1).astype(f32)
                for t, c in enumerate(first_of(tops, top, g)):
                    gd[slots[t], q - qb, cols(r0)] += c
            loop(0, half // CW, col)
        loop(q_lo, q_hi, win)

    e = step - lag

    @pl.when(jnp.logical_and(e >= 0, e < covered))
    def _():    # the plane no later window touches: H, then W backward
        s = lax.rem(e, R)

        def h_col(r0):
            def win(q, hist):
                base = K1 + sh * q - ph - i0
                rows = [rm[s, base + t, cols(r0)].astype(f32)[None]
                        for t in range(kh)]
                cs = first_of(rows, pm[s, q - qb, cols(r0)].astype(f32)[None],
                              gd[s, q - qb, cols(r0)][None])
                done, hist = shift(cs, hist, kh, sh)
                for t, v in enumerate(done):
                    gh[base + t, cols(r0)] = v[0]
                return hist
            hist = lax.fori_loop(q_lo, q_hi, win, no_history(kh, sh, (CW,)))
            # rows of the last windows that a window past the chunk shares
            zero = [jnp.zeros((1, CW) + voxel, f32)] * kh
            for i in range(-(-(kh - sh) // sh)):
                done, hist = shift(zero, hist, kh, sh)
                for t, v in enumerate(done):
                    gh[K1 + sh * (q_hi + i) - ph - i0 + t, cols(r0)] = v[0]
        loop(0, half // CW, h_col)

        past = sw * nw + kw - sw - pw       # positions no window covers

        def w_row(er):
            rel = o + er - i0

            def put(at, v, f):
                """``v``: consecutive positions of stretch ``f`` from ``at``
                on. Before the tensor: dropped, by a store to position 0
                that the true one follows; past it: not stored."""
                n = v.shape[0]
                skip = pw if f == 0 else 0
                over = max(sw * nw - pw - W, 0) if f == F - 1 else 0
                for i in range(skip):
                    o_ref[0, er, jnp.maximum(at + i, 0)] = v[i]
                for i in range(n - over, n):
                    @pl.when(at + i < W)
                    def _(i=i):
                        o_ref[0, er, at + i] = v[i]
                o_ref[0, er, pl.ds(at + skip, n - skip - over)] = (
                    v[skip:n - over])

            def first_in_row(r0, of):
                vals = [of(v.astype(f32)) for v in z_at(s, rel, r0)]
                return first_of(
                    vals, of(rm[s, K1 + rel, cols(r0)].astype(f32)),
                    of(gh[K1 + rel, cols(r0)]))

            def win(r0, hist):
                done, hist = shift(first_in_row(r0, lambda a: a), hist,
                                   kw, sw)
                side = jnp.stack(done, axis=1).reshape(
                    (sw * CW,) + voxel).astype(dtype)
                for f in range(F):
                    put(sw * (CW * r0 + f * half) - pw,
                        side[..., f * C:(f + 1) * C], f)
                return hist

            hist = no_history(kw, sw)
            if F > 1 and kw > sw:
                # the second stretch's first windows share positions with
                # the first stretch's last: those windows once more, their
                # lanes moved over, for the history alone
                _, hist = shift(first_in_row(half // CW - 1, lambda a:
                                             pltpu.roll(a, C, a.ndim - 1)),
                                hist, kw, sw)
                second = lax.broadcasted_iota(jnp.int32, voxel, 1) >= C
                hist = tuple(jnp.where(second, v, 0.0) for v in hist)
            hist = lax.fori_loop(0, half // CW, win, hist)
            zero = [jnp.zeros((1,) + voxel, f32)] * kw
            for i in range(-(-(kw - sw) // sw)):
                done, hist = shift(zero, hist, kw, sw)
                for t, v in enumerate(done):
                    if 0 <= sw * (nw + i) + t - pw < W:
                        o_ref[0, er, sw * (nw + i) + t - pw] = (
                            v[0, :, (F - 1) * C:].astype(dtype))
            if past < W:
                o_ref[0, er, pl.ds(past, W - past)] = jnp.zeros(
                    (W - past,) + o_ref.shape[3:], dtype)
        loop(0, OC, w_row)

    @pl.when(jnp.logical_and(e >= covered, e < D))
    def _():    # planes no window covers
        def clear(er):
            o_ref[0, er] = jnp.zeros(o_ref.shape[2:], dtype)
        loop(0, OC, clear)


def _windows_pallas(z, m, g, *, window, strides, padding, interpret=False):
    del m   # the maxima are the kernel's own: it needs them plane by plane
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def voxel_major(a):     # a bitcast on the chip: D, H, W are untiled
        return a.transpose(1, 2, 3, 0, 4)

    n, d, h, w, ch = z.shape
    oc, hc, nq, vmem = _windows_plan(z.shape, z.dtype, window, strides,
                                     padding)
    (kd, kh, kw), (sd, sh, sw), (pd, ph, pw) = window, strides, padding
    nd, nh, nw = g.shape[1:4]
    covered, lag, steps, live = _plane_schedule(d, kd, sd, pd)
    halves, half, _ = _columns(ch, nw, kw, sw)
    lanes = halves * ch
    ring = hc + 2 * (kh - 1)

    def rows(j):
        return _chunk_rows(j, h, kh, sh, ph, nh, (oc, hc, nq))

    def block(*sizes):      # blocks at element offsets: the halo overlaps
        return tuple(map(pl.Element, sizes + (n, ch)))

    dz = pl.pallas_call(
        functools.partial(
            _windows_kernel, pl=pl, pltpu=pltpu, extents=(d, h, w),
            window=window, strides=strides, padding=padding,
            chunk=(oc, hc, nq)),
        grid=(pl.cdiv(h, oc), steps),
        in_specs=[
            pl.BlockSpec(block(1, hc, w), lambda j, t: (
                jnp.minimum(t, covered - 1), rows(j)[1], 0, 0, 0)),
            pl.BlockSpec(block(1, nq, nw), lambda j, t: (
                jnp.clip((t + pd - kd + 1) // sd, 0, nd - 1), rows(j)[4],
                0, 0, 0))],
        out_specs=pl.BlockSpec(block(1, oc, w), lambda j, t: (
            jnp.clip(t - lag, 0, d - 1), rows(j)[0], 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (d, h, w, n, ch), z.dtype, vma=_vma(*map(jax.typeof, (z, g)))),
        scratch_shapes=[
            pltpu.VMEM((live, hc, sw * (half - 1) + kw, n, lanes),
                       z.dtype),                                    # zring
            pltpu.VMEM((live, ring, half, n, lanes), z.dtype),      # rm
            pltpu.VMEM((live, nq, half, n, lanes), z.dtype),        # pm
            pltpu.VMEM((live, nq, half, n, lanes), jnp.float32),    # gd
            pltpu.VMEM((ring, half, n, lanes), jnp.float32),        # gh
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + 8 * 2 ** 20),
        interpret=interpret,
    )(voxel_major(z), voxel_major(g))
    return dz.transpose(3, 0, 1, 2, 4)


# ---------------------------------------------------------------------------
# the primitive: one meaning, the lowering picks the spelling. Its operands
# are (c, bias, m, g) where the caller told of the sum, else (z, m, g)


_scatter_p = Primitive("max_pool_first_match_scatter")
_scatter_p.def_impl(functools.partial(dispatch.apply_primitive, _scatter_p))
_scatter_p.def_abstract_eval(
    lambda z, *rest, **_: z.update(weak_type=False, vma=_vma(z, *rest)))


def first_match_scatter(c, bias, m, g, window):
    """``d max_pool(c + bias) / d (c + bias)`` applied to ``g``."""
    return _scatter_p.bind(c, bias, m, g, window=tuple(window),
                           strides=tuple(window), padding=(0, 0, 0),
                           batch_dims=0)


def _scatter_batch(args, dims, *, batch_dims, **geometry):
    size = next(a.shape[d] for a, d in zip(args, dims) if d is not None)
    args = [jnp.broadcast_to(a, (size,) + a.shape) if d is None
            else jnp.moveaxis(a, d, 0) for a, d in zip(args, dims)]
    return _scatter_p.bind(*args, batch_dims=batch_dims + 1, **geometry), 0


batching.primitive_batchers[_scatter_p] = _scatter_batch


def _lower_as(spelling):
    def rule(ctx, *args, window, batch_dims):
        fn = functools.partial(spelling, window=window)
        for _ in range(batch_dims):
            fn = jax.vmap(fn)
        return mlir.lower_fun(fn, multiple_results=False)(ctx, *args)
    return rule


@functools.lru_cache(maxsize=None)
def _windows_kernel_of(window, strides, padding):
    """``_windows_pallas`` for one geometry, jitted: every program of a
    process that holds the kernel at the same shapes (the SNIP pass and the
    round) then traces its body once, a third of a second of every start on
    the chip's host that the second would pay again."""
    def pool_backward(z, m, g):
        return _windows_pallas(z, m, g, window=window, strides=strides,
                               padding=padding)
    return jax.jit(pool_backward)


def _one_at_a_time(spelling, batch_dims):
    """``spelling`` mapped over the leading ``batch_dims`` axes one element
    at a time: a batch axis in front of the kernel's voxel-major view costs
    a relayout copy as long as the kernel (PERF.md, PR 29)."""
    if not batch_dims:
        return spelling

    def mapped(*args):
        lead = args[0].shape[:batch_dims]
        flat = [a.reshape((-1,) + a.shape[batch_dims:]) for a in args]
        if flat[0].shape[0] == 1:
            out = spelling(*(a[0] for a in flat))[None]
        else:
            out = lax.map(lambda one: spelling(*one), tuple(flat))
        return out.reshape(lead + out.shape[1:])
    return mapped


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


def _count(spelling, window, strides, padding):
    # the choice is made once per lowering, so lowerings are what is counted
    from ..obs.metrics import get_registry
    get_registry().counter("pool_bwd_lowerings").labels(
        spelling=spelling, geometry="_".join(
            "x".join(map(str, sorted(set(v), reverse=True)))
            for v in (window, strides, padding))).inc()


def _lower(ctx, *args, window, strides, padding, batch_dims, kernels):
    told_of_sum = len(args) == 4
    z, m = ctx.avals_in[0], ctx.avals_in[-2]
    geometry = dict(window=window, strides=strides, padding=padding)
    shape = z.shape[batch_dims:]
    kernel = (
        kernels and min(m.shape) > 0
        and _unpartitioned(ctx.module_context.axis_context)
        and (2 * _kernel_vmem_bytes(shape, z.dtype, window) <= _VMEM_BUDGET
             if told_of_sum else
             _windows_plan(shape, z.dtype, **geometry) is not None))
    _count("kernel" if kernel else "xla", **geometry)
    if told_of_sum:
        return _lower_as(_scatter_pallas if kernel else _scatter_xla)(
            ctx, *args, window=window, batch_dims=batch_dims)
    if kernel:
        fn = _one_at_a_time(_windows_kernel_of(**geometry), batch_dims)
    else:
        fn = functools.partial(_windows_xla, **geometry)
        for _ in range(batch_dims):
            fn = jax.vmap(fn)
    return mlir.lower_fun(fn, multiple_results=False)(ctx, *args)


mlir.register_lowering(_scatter_p, functools.partial(_lower, kernels=False))
mlir.register_lowering(_scatter_p, functools.partial(_lower, kernels=True),
                       platform="tpu")
