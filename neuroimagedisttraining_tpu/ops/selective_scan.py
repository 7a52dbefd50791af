"""Mamba-1's selective scan, one meaning with two lowerings: per channel
``d`` and state ``n``, with the state zero before the sequence, ``h_t[n, d]
= exp(delta_t[d] a[d, n]) h_{t-1}[n, d] + delta_t[d] u_t[d] b_t[n]`` and
``y_t[d] = sum_n c_t[n] h_t[n, d]`` (D's skip is the caller's), all of it
float32. ``selective_scan(u, delta, a, b, c, body, chunk)`` is ``y``, a
``custom_vjp`` over a forward primitive (``y`` and the state entering every
chunk, the backward's residual) and a backward primitive (the gradients by
``u, delta, a, b, c``). The lowering picks the spelling (``ops/lowering.py``
has the rule):

* Pallas kernels on a TPU with no partitioner in the way, where the channels
  are a multiple of 128 lanes, the state of 8 sublanes and the chunk of 8.
  The forward's grid is (batch, channel block, chunk), the chunks in order:
  the state ``[N, channels]`` (channels on the lanes, the states on the
  sublanes) stays in VMEM scratch across them, and the state entering each
  chunk goes out as the residual. ``u`` and ``delta`` are read as ``[chunk,
  channels]`` tiles of their ``[B, S, D]`` layout and ``y`` written so; B and
  C as ``[chunk, N]`` tiles, each token's 16 values broadcast along the
  lanes once a chunk (:func:`_along_lanes`). Inside a chunk a loop over
  128-lane groups holds a group's state in registers over the chunk's tokens,
  a multiply-add a token. The backward's grid walks the chunks in reverse:
  per lane group it computes the chunk's states again from the saved one
  into VMEM, then carries the state's gradient ``g`` back over the tokens,
  ``g_{t-1} = exp(delta_t a) (g_t + c_t dy_t)``, emitting ``du`` and
  ``ddelta`` as tiles, ``da`` summed in VMEM over every token of a batch row
  and written once, and ``db``, ``dc`` as a channel block's partials ``[
  blocks, B, S, N]``, which XLA sums.
* ``body`` (``models/decoder._selective_scan_chunks``: XLA's spelling, a
  ``lax.scan`` over chunks, each a ``lax.scan`` over its tokens, computed
  again going backward) everywhere else: the CPU, widths that do not tile,
  whatever GSPMD partitions; its backward is JAX's derivative of it, run
  again from the operands.

A sequence the chunk does not divide is padded with tokens of ``delta`` 0,
which neither decay nor add. Every lowering counts itself:
``mamba_scan_lowerings{spelling, pass}`` in ``obs/metrics.get_registry()``.
The primitives have no JVP rule: the backward cannot be differentiated again
(nothing in the package does).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import lowering
from .lowering import LANES

_SUBLANES = 8
_VMEM_LIMIT = 64 * 2 ** 20
# float32 elements of a ``[chunk, channel block]`` tile, at most: the
# backward holds five such tiles twice over (1.25 MiB each at the published
# 64 x 5120), well inside its VMEM limit
_TILE = 2 ** 19


def kernels_take(channels: int, state: int, chunk: int) -> bool:
    """Whether these widths and this chunk can lower to the kernels."""
    return (channels % LANES == 0 and state % _SUBLANES == 0
            and chunk % _SUBLANES == 0)


def channel_block(channels: int, chunk: int) -> int:
    """Channels a grid step: the widest multiple of 128 that divides
    ``channels`` and keeps a ``[chunk, block]`` tile within ``_TILE``."""
    groups = channels // LANES
    fits = [g for g in range(1, groups + 1)
            if groups % g == 0 and chunk * g * LANES <= _TILE]
    return LANES * max(fits, default=1)


def _pad(t, total):
    return jnp.pad(t, [(0, 0), (0, total - t.shape[1])]
                   + [(0, 0)] * (t.ndim - 2))


def _along_lanes(rows, out_scr):
    """``rows [chunk, N]`` -> ``out_scr[t] = rows[t]`` as a column ``[N,
    128]``, the value on every lane."""
    cols = rows.T
    for t in range(rows.shape[0]):
        out_scr[t] = jnp.broadcast_to(cols[:, t:t + 1], out_scr.shape[1:])


def _params(pltpu):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _lanes(pl, j):
    return pl.ds(pl.multiple_of(j * LANES, LANES), LANES)


def _rows(pl, s):
    """The ``s``-th 8 tokens of a chunk: a grid step loads and stores whole
    ``[8, 128]`` tiles and takes a token as a static row of one (Mosaic
    loads no single row at an index it cannot prove aligned)."""
    return pl.ds(pl.multiple_of(s * _SUBLANES, _SUBLANES), _SUBLANES)


def _forward_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, saved_ref,
                    h_scr, bb_scr, cb_scr, *, pl):
    """Grid (batch, channel block, chunk), the chunk last; ``a_ref`` is
    ``a^T [N, block]``."""
    chunk, block = u_ref.shape
    at = lax.broadcasted_iota(jnp.int32, (_SUBLANES, LANES), 0)

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros(h_scr.shape, jnp.float32)

    saved_ref[...] = h_scr[...]
    _along_lanes(b_ref[...], bb_scr)
    _along_lanes(c_ref[...], cb_scr)

    def group(j, carry):
        lanes = _lanes(pl, j)
        a = a_ref[:, lanes]

        def eight(s, h):
            rows = _rows(pl, s)
            d8 = dt_ref[rows, lanes]
            x8 = d8 * u_ref[rows, lanes]
            y8 = jnp.zeros(d8.shape, jnp.float32)
            for k in range(_SUBLANES):
                t = s * _SUBLANES + k
                h = jnp.exp(d8[k:k + 1] * a) * h + x8[k:k + 1] * bb_scr[t]
                y8 = jnp.where(at == k, jnp.sum(h * cb_scr[t], axis=0,
                                                keepdims=True), y8)
            y_ref[rows, lanes] = y8
            return h

        h_scr[:, lanes] = lax.fori_loop(0, chunk // _SUBLANES, eight,
                                        h_scr[:, lanes])
        return carry

    lax.fori_loop(0, block // LANES, group, 0)


def _backward_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, saved_ref, dy_ref,
                     du_ref, ddt_ref, da_ref, db_ref, dc_ref,
                     g_scr, hist_scr, bb_scr, cb_scr, pb_scr, pc_scr, *, pl):
    """Grid (batch, channel block, chunk from the last). ``g_scr`` holds the
    gradient by the state leaving the chunk (by ``h_t`` less what ``y_t``
    adds), ``hist_scr[t]`` the state entering token ``t`` of the chunk and
    ``hist_scr[chunk]`` the one leaving it; ``pb_scr``, ``pc_scr`` the
    chunk's ``db``, ``dc`` summed over the lane groups done, still per
    lane."""
    chunk, block = u_ref.shape
    steps = chunk // _SUBLANES
    at = lax.broadcasted_iota(jnp.int32, (_SUBLANES, LANES), 0)

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_scr[...] = jnp.zeros(g_scr.shape, jnp.float32)
        da_ref[...] = jnp.zeros(da_ref.shape, jnp.float32)

    _along_lanes(b_ref[...], bb_scr)
    _along_lanes(c_ref[...], cb_scr)
    pb_scr[...] = jnp.zeros(pb_scr.shape, jnp.float32)
    pc_scr[...] = jnp.zeros(pc_scr.shape, jnp.float32)

    def group(j, carry):
        lanes = _lanes(pl, j)
        a = a_ref[:, lanes]

        def again(s, h):
            rows = _rows(pl, s)
            d8 = dt_ref[rows, lanes]
            x8 = d8 * u_ref[rows, lanes]
            for k in range(_SUBLANES):
                t = s * _SUBLANES + k
                hist_scr[t] = h
                h = jnp.exp(d8[k:k + 1] * a) * h + x8[k:k + 1] * bb_scr[t]
            return h

        hist_scr[chunk] = lax.fori_loop(0, steps, again, saved_ref[:, lanes])

        def eight(i, carry):
            g, da = carry
            s = steps - 1 - i
            rows = _rows(pl, s)
            d8, u8, dy8 = (r[rows, lanes] for r in (dt_ref, u_ref, dy_ref))
            x8 = d8 * u8
            dx8 = d_log8 = jnp.zeros(d8.shape, jnp.float32)
            for k in range(_SUBLANES - 1, -1, -1):
                t = s * _SUBLANES + k
                d = d8[k:k + 1]
                decay = jnp.exp(d * a)
                before, h = hist_scr[t], hist_scr[t + 1]
                g = g + cb_scr[t] * dy8[k:k + 1]        # by h_t
                pc_scr[t] += h * dy8[k:k + 1]
                pb_scr[t] += g * x8[k:k + 1]
                d_log = g * before * decay              # by delta_t a
                dx8 = jnp.where(at == k, jnp.sum(g * bb_scr[t], axis=0,
                                                 keepdims=True), dx8)
                d_log8 = jnp.where(at == k, jnp.sum(d_log * a, axis=0,
                                                    keepdims=True), d_log8)
                da = da + d_log * d
                g = decay * g
            ddt_ref[rows, lanes] = d_log8 + dx8 * u8
            du_ref[rows, lanes] = dx8 * d8
            return g, da

        g, da = lax.fori_loop(0, steps, eight, (
            g_scr[:, lanes], jnp.zeros(a.shape, jnp.float32)))
        g_scr[:, lanes] = g
        da_ref[:, lanes] += da
        return carry

    lax.fori_loop(0, block // LANES, group, 0)
    db_ref[...] = jnp.sum(pb_scr[...], axis=-1)
    dc_ref[...] = jnp.sum(pc_scr[...], axis=-1)


def _specs(pl, chunk, block, state, at):
    """Block specs of ``(u, delta, y and their gradients; b, c; the saved
    states; a^T)``: ``at(*grid ids) -> (batch, channel block, chunk)``."""
    def spec(shape, index):
        return pl.BlockSpec(shape, lambda *ids: index(*at(*ids)))

    return (spec((None, chunk, block), lambda b, j, c: (b, c, j)),
            spec((None, chunk, state), lambda b, j, c: (b, c, 0)),
            spec((None, None, state, block), lambda b, j, c: (b, c, 0, j)),
            spec((state, block), lambda b, j, c: (0, j)))


def _layout(u, delta, b, c, chunk):
    """Padded to whole chunks."""
    nc = -(-u.shape[1] // chunk)
    return (nc,) + tuple(_pad(t, nc * chunk) for t in (u, delta, b, c))


def _forward_pallas(u, delta, a, b, c, *, chunk, interpret=False):
    pl, pltpu = lowering.pallas()

    bsz, s_len, channels = u.shape
    state = a.shape[1]
    block = channel_block(channels, chunk)
    nc, u, delta, b, c = _layout(u, delta, b, c, chunk)
    tiles, rows, saved, shared = _specs(pl, chunk, block, state,
                                        lambda *ids: ids)
    vma = lowering.vma(*map(jax.typeof, (u, delta, a, b, c)))
    y, states = pl.pallas_call(
        functools.partial(_forward_kernel, pl=pl),
        grid=(bsz, channels // block, nc),
        in_specs=[tiles, tiles, shared, rows, rows],
        out_specs=[tiles, saved],
        out_shape=[jax.ShapeDtypeStruct(u.shape, jnp.float32, vma=vma),
                   jax.ShapeDtypeStruct((bsz, nc, state, channels),
                                        jnp.float32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((state, block), jnp.float32),
                        pltpu.VMEM((chunk, state, LANES), jnp.float32),
                        pltpu.VMEM((chunk, state, LANES), jnp.float32)],
        compiler_params=_params(pltpu),
        interpret=interpret)(u, delta, a.T, b, c)
    return y[:, :s_len], states


def _backward_pallas(u, delta, a, b, c, states, dy, *, chunk,
                     interpret=False):
    pl, pltpu = lowering.pallas()

    bsz, s_len, channels = u.shape
    state = a.shape[1]
    block = channel_block(channels, chunk)
    blocks = channels // block
    nc, u, delta, b, c = _layout(u, delta, b, c, chunk)
    dy = _pad(dy, nc * chunk)
    tiles, rows, saved, shared = _specs(
        pl, chunk, block, state, lambda b_, j, c_: (b_, j, nc - 1 - c_))
    # da summed over a batch row's chunks; b's and c's by channel block
    per_row = pl.BlockSpec((None, state, block), lambda b_, j, c_: (b_, 0, j))
    partial = pl.BlockSpec((None, None, chunk, state),
                           lambda b_, j, c_: (j, b_, nc - 1 - c_, 0))
    vma = lowering.vma(*map(jax.typeof, (u, delta, a, b, c, states, dy)))

    def out(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)

    def scratch(*shape):
        return pltpu.VMEM(shape, jnp.float32)

    du, ddelta, da, db, dc = pl.pallas_call(
        functools.partial(_backward_kernel, pl=pl),
        grid=(bsz, blocks, nc),
        in_specs=[tiles, tiles, shared, rows, rows, saved, tiles],
        out_specs=[tiles, tiles, per_row, partial, partial],
        out_shape=[out(u.shape), out(u.shape),
                   out((bsz, state, channels)),
                   out((blocks,) + b.shape), out((blocks,) + c.shape)],
        scratch_shapes=[scratch(state, block),
                        scratch(chunk + 1, state, LANES),
                        scratch(chunk, state, LANES),
                        scratch(chunk, state, LANES),
                        scratch(chunk, state, LANES),
                        scratch(chunk, state, LANES)],
        compiler_params=_params(pltpu),
        interpret=interpret)(u, delta, a.T, b, c, states, dy)
    return (du[:, :s_len], ddelta[:, :s_len], jnp.sum(da, axis=0).T,
            jnp.sum(db, axis=0)[:, :s_len], jnp.sum(dc, axis=0)[:, :s_len])


# ---------------------------------------------------------------------------
# XLA's spelling: the caller's body, differentiated by JAX


def _forward_xla(u, delta, a, b, c, *, body, chunk):
    return body(u, delta, a, b, c, chunk)


def _backward_xla(u, delta, a, b, c, states, dy, *, body, chunk):
    del states      # the body's chunks are computed again instead
    return jax.vjp(lambda *t: body(*t, chunk)[0], u, delta, a, b, c)[1](dy)


# ---------------------------------------------------------------------------
# the primitives: one meaning each, the lowering picks the spelling


def _forward_avals(u, delta, a, b, c, *, chunk, **_):
    bsz, s_len, channels = u.shape
    vma = lowering.vma(u, delta, a, b, c)
    return (u.update(weak_type=False, vma=vma),
            u.update(shape=(bsz, -(-s_len // chunk), a.shape[1], channels),
                     dtype=jnp.dtype(jnp.float32), weak_type=False, vma=vma))


def _backward_avals(u, delta, a, b, c, states, dy, **_):
    vma = lowering.vma(u, delta, a, b, c, states, dy)
    return tuple(t.update(weak_type=False, vma=vma)
                 for t in (u, delta, a, b, c))


_forward_p = lowering.primitive("selective_scan", _forward_avals)
_backward_p = lowering.primitive("selective_scan_backward", _backward_avals)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def selective_scan(u, delta, a, b, c, body, chunk: int):
    """``y [B, S, D]`` float32 for ``u, delta [B, S, D]``, ``a [D, N]``,
    ``b, c [B, S, N]``, all float32 (the module's docstring).
    ``body(u, delta, a, b, c, chunk) -> (y, states)`` is XLA's spelling of
    the same, with ``states [B, chunks, N, D]`` the state entering every
    chunk."""
    return _forward_p.bind(u, delta, a, b, c, body=body, chunk=chunk)[0]


def _fwd(u, delta, a, b, c, body, chunk):
    y, states = _forward_p.bind(u, delta, a, b, c, body=body, chunk=chunk)
    return y, (u, delta, a, b, c, states)


def _bwd(body, chunk, saved, dy):
    return tuple(_backward_p.bind(*saved, dy, body=body, chunk=chunk))


selective_scan.defvjp(_fwd, _bwd)


def _takes(avals, *, chunk, **_):
    u, _, a = avals[:3]
    return (all(t.dtype == jnp.float32 for t in avals)
            and kernels_take(u.shape[2], a.shape[1], chunk))


def _kernel_spelling(*, interpret, chunk, backward, **_):
    return lowering.jitted(
        "selective_scan_backward" if backward else "selective_scan_forward",
        _backward_pallas if backward else _forward_pallas, chunk=chunk,
        interpret=interpret)


def _xla_spelling(*, body, chunk, backward):
    return functools.partial(_backward_xla if backward else _forward_xla,
                             body=body, chunk=chunk)


lowering.register(_forward_p, _backward_p, rule=_takes,
                  kernel=_kernel_spelling, xla=_xla_spelling,
                  counter="mamba_scan_lowerings")
