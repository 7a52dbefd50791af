"""The gated delta rule's chunked scan, one meaning with two lowerings: per
value head, with the state ``S [dk, dv]`` zero before the sequence, ``S' =
exp(g_t) S_{t-1}``, ``S_t = S' + k_t outer(beta_t (v_t - S'^T k_t))``, ``o_t
= S_t^T q_t``; value head ``j`` reads key head ``j // r``. ``delta_rule(q,
k, v, g, beta, body, chunk, span)`` is ``o``, a ``custom_vjp`` over a
forward primitive (``o`` and the state entering every ``span``-th chunk,
float32, the backward's residual) and a backward primitive (the gradients by
``q, k, v, g, beta``). The lowering picks the spelling (``ops/lowering.py``
has the rule):

* Pallas kernels on a TPU with no partitioner in the way, where ``dk``
  and ``dv`` are multiples of 128 lanes and the chunk of 8 sublanes.
  The forward's grid is (batch, block of value heads, chunk), the chunks in
  order: the float32 state of each head of the block stays in VMEM scratch
  across them, and per chunk, in VMEM alone: ``G`` the chunk's cumulative
  ``g`` (from XLA, float32), every decay ``exp(G_t - G_j)`` a difference of
  it, ``L = beta k k^T`` below the diagonal and the unit lower-triangular
  inverse ``(I + L)^-1`` by block doubling (:func:`_inverse`),
  ``w_v`` and ``w_k``, the corrections ``u = w_v - w_k S``, ``o = e^G q S +
  (q k^T * decay) u`` and ``S <- e^{G_C} S + k_end^T u``. The backward's grid
  walks the same blocks of ``span`` chunks in reverse, ``2 span`` steps a
  block: the first ``span`` compute its chunks again from the saved state in
  order and keep each chunk's entering state, inverse, ``w_v``, ``w_k`` and
  ``u`` in VMEM; the last ``span`` walk them in reverse with the state's
  gradient in VMEM scratch and hand back each chunk's gradients (the public
  chunked backward of Yang, Kautz & Hatamizadeh, arXiv:2412.06464, as one
  kernel). ``q`` and ``k`` are read at their key head by the block specs,
  and a key head's gradient is summed over its value heads in the kernel.
* ``body`` (``models/decoder._delta_rule_chunks``: XLA's spelling, a
  ``lax.scan`` over steps of ``span`` chunks, each computed again going
  backward) everywhere else: the CPU, widths that do not tile, whatever GSPMD
  partitions; its backward is JAX's derivative of it, run again from the
  operands.

Products of ``q``, ``k`` and ``v`` run in their type with float32 sums, the
state enters its products in that type; the solve (every product at the
highest precision), the decays and the carried state are float32. Every
lowering counts itself: ``linear_scan_lowerings{spelling, pass}`` in
``obs/metrics.get_registry()``. The primitives have no JVP rule: the backward
cannot be differentiated again (nothing in the package does).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import lowering
from .lowering import LANES, NT, TN

_SUBLANES = 8
_VMEM_LIMIT = 64 * 2 ** 20
# value heads a grid step, at most: enough that the chunk's 64-row products
# of several heads overlap, few enough that the backward's per-chunk scratch
# of ``span`` chunks (2.75 MiB a head at 128 x 128) fits VMEM
_HEADS = 8

_NN = (((1,), (0,)), ((), ()))      # a . b


def steps_of(s_len: int, chunk: int, span: int):
    """``(span, steps)``: chunks a step, no more than the sequence has, and
    steps over the sequence padded to whole steps."""
    span = max(1, min(span, -(-s_len // chunk)))
    return span, -(-s_len // (chunk * span))


def head_block(value_heads: int, ratio: int) -> int:
    """Value heads a grid step: a divisor of ``value_heads``, whole groups of
    ``ratio`` (the value heads of a key head), a whole tile of sublanes for
    the rows of ``g`` and ``beta`` (or every head), at most ``_HEADS`` where
    such a block exists."""
    fits = [h for h in range(1, value_heads + 1)
            if value_heads % h == 0 and h % ratio == 0
            and (h % _SUBLANES == 0 or h == value_heads)]
    small = [h for h in fits if h <= _HEADS]
    return max(small) if small else min(fits)


def kernels_take(key_dim: int, value_dim: int, chunk: int) -> bool:
    """Whether these widths and this chunk can lower to the kernels."""
    return (key_dim % LANES == 0 and value_dim % LANES == 0
            and chunk % _SUBLANES == 0)


# ---------------------------------------------------------------------------
# one chunk, in VMEM: ``[C, C]`` matrices, ``[C, 1]`` columns and ``[1, C]``
# rows, float32 unless named for the compute type ``dt``. A head's work is a
# generator that yields between products which wait on each other, and a grid
# step runs its heads in lockstep (:func:`_lockstep`): the program then holds
# the heads' independent products side by side and the MXU's latency is
# spent on other heads' work. The solves of a layer-pass of Qwen3-Next's
# linear layer (8,192 systems of 64) on a TPU v5e: 16.1 ms head after head,
# 7.4 in lockstep.


def _lockstep(steps):
    """Advance each generator to its next ``yield`` in turn, until all are
    done."""
    steps = list(steps)
    while steps:
        steps = [s for s in steps if next(s, _DONE) is not _DONE]


_DONE = object()


def _mm(a, b, dims=_NN, exact=False):
    return lax.dot_general(
        a, b, dims, precision=lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


class _At:
    """Where each entry of a chunk's ``[C, C]`` matrices lies."""

    def __init__(self, chunk):
        rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.eye, self.seen, self.below = (rows == cols, rows >= cols,
                                           rows > cols)
        self.apart = rows ^ cols
        self.last = (lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
                     == chunk - 1)


def _col(row, at):
    """``[1, C]`` -> ``[C, 1]``."""
    return jnp.sum(jnp.where(at.eye, row, 0.0), axis=1, keepdims=True)


def _row(col, at):
    """``[C, 1]`` -> ``[1, C]``."""
    return jnp.sum(jnp.where(at.eye, col, 0.0), axis=0, keepdims=True)


def _last(row, at):
    """``[1, 1]``: the row's last entry."""
    return jnp.sum(jnp.where(at.last, row, 0.0), axis=1, keepdims=True)


def _inverse(lower, at):
    """``(I + lower)^-1`` for ``lower`` strictly lower triangular: the
    inverse's diagonal blocks doubled in size until one block is the whole,
    ``[[A, 0], [B, D]]^-1 = [[A', 0], [-D' B A', D']]`` with ``A'`` and ``D'``
    the halves' inverses, i.e. ``T <- T - T B T`` with ``B`` the lower left
    quarters of the doubled blocks (where row and column first differ in bit
    ``level``). Block forward substitution: no power of ``lower`` is formed,
    so nearly alike keys lose nothing; every product at the highest precision
    (as ``models/decoder.unit_lower_solve``)."""
    inverse = jnp.where(at.eye, 1.0, 0.0) - jnp.where(at.apart == 1, lower,
                                                      0.0)
    level = 1
    while (1 << level) < lower.shape[0]:
        part = _mm(jnp.where((at.apart >> level) == 1, lower, 0.0), inverse,
                   exact=True)
        yield
        inverse = inverse - _mm(inverse, part, exact=True)
        yield
        level += 1
    return inverse


def _solve(k, v, kk, g_row, b_row, at):
    """What a chunk computes before its state enters: ``G`` and ``beta`` as
    columns, the decays ``exp(G_t - G_j)`` (0 above the diagonal), the
    inverse ``(I + L)^-1``, ``w_v = T (beta v)`` and ``w_k = T (beta e^G
    k)``."""
    g_col, b_col = _col(g_row, at), _col(b_row, at)
    decay = jnp.exp(jnp.where(at.seen, g_col - g_row, -jnp.inf))
    inverse = yield from _inverse(
        jnp.where(at.below, b_col * kk * decay, 0.0), at)
    w_v = _mm(inverse, b_col * v.astype(jnp.float32), exact=True)
    w_k = _mm(inverse, (b_col * jnp.exp(g_col)) * k.astype(jnp.float32),
              exact=True)
    yield
    return g_col, b_col, decay, inverse, w_v, w_k


def _carry(state, k, g_row, g_col, w_v, w_k, dt, at):
    """The corrections ``u = w_v - w_k S`` and the state leaving the chunk,
    ``e^{G_C} S + k_end^T u`` with ``k_end = e^{G_C - G} k``."""
    g_last = _last(g_row, at)
    new = w_v - _mm(w_k.astype(dt), state.astype(dt))
    yield
    k_end = (k.astype(jnp.float32) * jnp.exp(g_last - g_col)).astype(dt)
    return new, jnp.exp(g_last) * state + _mm(k_end, new.astype(dt), TN)


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, saved_ref,
                    state_scr, *, pl, span, ratio):
    """Grid (batch, value-head block, chunk), the chunk last: each head's
    state in ``state_scr`` over the chunks; the state entering every
    ``span``-th chunk goes out as the backward's residual. ``k k^T`` and ``q
    k^T`` once a key head."""
    heads, chunk = g_ref.shape
    dk, dv = k_ref.shape[1] * ratio // heads, v_ref.shape[1] // heads
    dt = v_ref.dtype
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        state_scr[...] = jnp.zeros(state_scr.shape, jnp.float32)

    @pl.when(c % span == 0)
    def _():
        saved_ref[...] = state_scr[...]

    at = _At(chunk)
    keys = []
    for kh in range(heads // ratio):
        q = q_ref[:, kh * dk:(kh + 1) * dk]
        k = k_ref[:, kh * dk:(kh + 1) * dk]
        keys.append((q, k, _mm(k, k, NT), _mm(q, k, NT)))

    def head(h):
        q, k, kk, qk = keys[h // ratio]
        g_row = g_ref[h:h + 1, :]
        g_col, _, decay, _, w_v, w_k = yield from _solve(
            k, v_ref[:, h * dv:(h + 1) * dv], kk, g_row, b_ref[h:h + 1, :],
            at)
        state = state_scr[h]
        new, state_scr[h] = yield from _carry(state, k, g_row, g_col, w_v,
                                              w_k, dt, at)
        o = _mm((q.astype(jnp.float32) * jnp.exp(g_col)).astype(dt),
                state.astype(dt)) + _mm((qk * decay).astype(dt),
                                        new.astype(dt))
        o_ref[:, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)

    _lockstep(head(h) for h in range(heads))


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, saved_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                     run_scr, ds_scr, hist_scr, inv_scr, wv_scr, wk_scr,
                     new_scr, *, pl, span, ratio):
    """Grid (batch, value-head block, block of ``span`` chunks from the last,
    step ``i`` of ``2 span``). Steps ``i < span``: chunk ``i`` of the block
    computed again from the saved state, in order, its entering state,
    inverse, ``w_v``, ``w_k`` and ``u`` kept in scratch. Steps ``i >= span``:
    chunk ``2 span - 1 - i``, in reverse, its gradients from the kept
    quantities and ``ds_scr``, the gradient by the state leaving it, which
    becomes the gradient by the state entering it. ``dg_ref`` holds the
    gradient by ``G``, the chunk's cumulative ``g``."""
    heads, chunk = g_ref.shape
    dk, dv = k_ref.shape[1] * ratio // heads, v_ref.shape[1] // heads
    dt = v_ref.dtype
    f32 = jnp.float32
    first, i = pl.program_id(2), pl.program_id(3)
    at = _At(chunk)

    @pl.when(i < span)
    def _():
        @pl.when(i == 0)
        def _():
            run_scr[...] = saved_ref[...]

        keys = []
        for kh in range(heads // ratio):
            k = k_ref[:, kh * dk:(kh + 1) * dk]
            keys.append((k, _mm(k, k, NT)))

        def head(h):
            k, kk = keys[h // ratio]
            g_row = g_ref[h:h + 1, :]
            g_col, _, _, inverse, w_v, w_k = yield from _solve(
                k, v_ref[:, h * dv:(h + 1) * dv], kk, g_row,
                b_ref[h:h + 1, :], at)
            state = run_scr[h]
            new, run_scr[h] = yield from _carry(state, k, g_row, g_col, w_v,
                                                w_k, dt, at)
            hist_scr[i, h] = state
            inv_scr[i, h] = inverse
            wv_scr[i, h] = w_v
            wk_scr[i, h] = w_k
            new_scr[i, h] = new

        _lockstep(head(h) for h in range(heads))

    @pl.when(i >= span)
    def _():
        j = 2 * span - 1 - i

        @pl.when(jnp.logical_and(first == 0, i == span))
        def _():
            ds_scr[...] = jnp.zeros(ds_scr.shape, f32)

        keys, dq, dks = [], [], []
        for kh in range(heads // ratio):
            q = q_ref[:, kh * dk:(kh + 1) * dk]
            k = k_ref[:, kh * dk:(kh + 1) * dk]
            keys.append((q, k, _mm(k, k, NT), _mm(q, k, NT)))
            dq.append(jnp.zeros(q.shape, f32))
            dks.append(jnp.zeros(k.shape, f32))

        def head(h):
            kh = h // ratio
            q, k, kk, qk = keys[kh]
            q32, k32 = q.astype(f32), k.astype(f32)
            g_row, b_row = g_ref[h:h + 1, :], b_ref[h:h + 1, :]
            g_col, b_col = _col(g_row, at), _col(b_row, at)
            decay = jnp.exp(jnp.where(at.seen, g_col - g_row, -jnp.inf))
            v = v_ref[:, h * dv:(h + 1) * dv]
            do = do_ref[:, h * dv:(h + 1) * dv]
            state, inverse = hist_scr[j, h], inv_scr[j, h]
            w_v, w_k, new = wv_scr[j, h], wk_scr[j, h], new_scr[j, h]
            d_out = ds_scr[h]               # by the state leaving the chunk
            state_t, new_t, d_out_t = (t.astype(dt)
                                       for t in (state, new, d_out))
            g_last = _last(g_row, at)
            e_g = jnp.exp(g_col)
            to_end = jnp.exp(g_last - g_col)
            # S' = e^{G_C} S + k_end^T u
            d_state = jnp.exp(g_last) * d_out
            d_last = jnp.exp(g_last) * jnp.sum(
                jnp.sum(state * d_out, axis=1, keepdims=True), axis=0,
                keepdims=True)
            d_end = _mm(new_t, d_out_t, NT)
            d_new = _mm((k32 * to_end).astype(dt), d_out_t)
            # o = (e^G q) S + (q k^T * decay) u
            qe = q32 * e_g
            d_qe = _mm(do, state_t, NT)
            d_state += _mm(qe.astype(dt), do, TN)
            d_scores = _mm(do, new_t, NT)
            d_new += _mm((qk * decay).astype(dt), do, TN)
            yield
            # k_end = e^{G_C - G} k
            dks[kh] += to_end * d_end
            ends = to_end * jnp.sum(k32 * d_end, axis=1, keepdims=True)
            d_last += jnp.sum(ends, axis=0, keepdims=True)
            dg_col = jnp.sum(qe * d_qe, axis=1, keepdims=True) - ends
            dq[kh] += e_g * d_qe
            d_qk = (d_scores * decay).astype(dt)
            d_decay = d_scores * qk
            # u = w_v - w_k S
            d_new_t = d_new.astype(dt)
            dq[kh] += _mm(d_qk, k)
            dks[kh] += _mm(d_qk, q, TN)
            d_wk = -_mm(d_new_t, state_t, NT)
            d_state -= _mm(w_k.astype(dt), d_new_t, TN)
            ds_scr[h] = d_state
            d_rv = _mm(inverse, d_new, TN, exact=True)
            yield
            # w = T r: by r, and by L (dT = -T dL T)
            d_rk = _mm(inverse, d_wk, TN, exact=True)
            yield
            d_lower = jnp.where(
                at.below, -(_mm(d_rv, w_v, NT, exact=True)
                            + _mm(d_rk, w_k, NT, exact=True)), 0.0)
            # r_v = beta v, r_k = beta e^G k
            rk = e_g * jnp.sum(k32 * d_rk, axis=1, keepdims=True)
            db_col = jnp.sum(v.astype(f32) * d_rv, axis=1, keepdims=True) + rk
            dv_ref[:, h * dv:(h + 1) * dv] = (b_col * d_rv).astype(
                dv_ref.dtype)
            dks[kh] += (b_col * e_g) * d_rk
            dg_col += b_col * rk
            yield
            # L = beta_t (k k^T) decay, below the diagonal
            db_col += jnp.sum(d_lower * kk * decay, axis=1, keepdims=True)
            d_kk = (d_lower * b_col * decay).astype(dt)
            d_decay += d_lower * b_col * kk
            dks[kh] += _mm(d_kk, k) + _mm(d_kk, k, TN)
            # decay = exp(G_t - G_j), j <= t; G_C is G's last entry
            spread = d_decay * decay
            dg_col += jnp.sum(spread, axis=1, keepdims=True)
            dg_ref[h:h + 1, :] = (
                _row(dg_col, at) - jnp.sum(spread, axis=0, keepdims=True)
                + jnp.where(at.last, d_last, 0.0))
            db_ref[h:h + 1, :] = _row(db_col, at)

        _lockstep(head(h) for h in range(heads))
        for kh in range(heads // ratio):
            dq_ref[:, kh * dk:(kh + 1) * dk] = dq[kh].astype(dq_ref.dtype)
            dk_ref[:, kh * dk:(kh + 1) * dk] = dks[kh].astype(dk_ref.dtype)


def _layout(q, k, v, g, beta, chunk, span):
    """Padded to whole steps; ``q, k, v`` as ``[B, S, heads x width]`` (a
    chunk's heads of a block are a block of lanes), ``G`` (the cumulative
    ``g`` of each chunk) and ``beta`` as rows ``[B, chunks, H, chunk]``."""
    b, s_len, nk, dk = q.shape
    nv = v.shape[2]
    span, steps = steps_of(s_len, chunk, span)
    total = steps * span * chunk
    q, k, v, g, beta = (
        jnp.pad(t, [(0, 0), (0, total - s_len)] + [(0, 0)] * (t.ndim - 2))
        for t in (q, k, v, g, beta))
    nc = total // chunk

    def rows(t):
        return jnp.swapaxes(t.reshape(b, nc, chunk, nv), 2, 3)

    return (span, steps, q.reshape(b, total, nk * dk),
            k.reshape(b, total, nk * dk), v.reshape(b, total, -1),
            jnp.cumsum(rows(g), axis=-1), rows(beta))


def _specs(pl, shape, chunk, heads, ratio, at):
    """Block specs of ``(q and k, v and o, the rows, the saved states)``:
    ``at(*grid ids) -> (batch, chunk, head block, step)``."""
    _, _, nk, dk = shape[0]
    dv = shape[1][3]

    def spec(block, index):
        return pl.BlockSpec(block, lambda *ids: index(*at(*ids)))

    return (spec((None, chunk, heads // ratio * dk),
                 lambda b, c, hb, s: (b, c, hb)),
            spec((None, chunk, heads * dv), lambda b, c, hb, s: (b, c, hb)),
            spec((None, None, heads, chunk),
                 lambda b, c, hb, s: (b, c, hb, 0)),
            spec((None, heads, None, dk, dv),
                 lambda b, c, hb, s: (b, hb, s, 0, 0)))


def _params(pltpu, last):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel") + ("arbitrary",) * last,
        vmem_limit_bytes=_VMEM_LIMIT)


def _forward_pallas(q, k, v, g, beta, *, chunk, span, interpret=False):
    pl, pltpu = lowering.pallas()

    b, s_len, nk, dk = q.shape
    nv, dv = v.shape[2:]
    ratio = nv // nk
    heads = head_block(nv, ratio)
    span, steps, qf, kf, vf, grows, brows = _layout(q, k, v, g, beta, chunk,
                                                    span)
    nc = steps * span
    keys, values, rows, saved = _specs(
        pl, (q.shape, v.shape), chunk, heads, ratio,
        lambda b_, hb, c: (b_, c, hb, c // span))
    vma = lowering.vma(*map(jax.typeof, (q, k, v, g, beta)))
    o, states = pl.pallas_call(
        functools.partial(_forward_kernel, pl=pl, span=span, ratio=ratio),
        grid=(b, nv // heads, nc),
        in_specs=[keys, keys, values, rows, rows],
        out_specs=[values, saved],
        out_shape=[jax.ShapeDtypeStruct(vf.shape, v.dtype, vma=vma),
                   jax.ShapeDtypeStruct((b, nv, steps, dk, dv), jnp.float32,
                                        vma=vma)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32)],
        compiler_params=_params(pltpu, 1),
        interpret=interpret)(qf, kf, vf, grows, brows)
    return o.reshape(b, nc * chunk, nv, dv)[:, :s_len], states


def _backward_pallas(q, k, v, g, beta, states, do, *, chunk, span,
                     interpret=False):
    pl, pltpu = lowering.pallas()

    b, s_len, nk, dk = q.shape
    nv, dv = v.shape[2:]
    ratio = nv // nk
    heads = head_block(nv, ratio)
    span, steps, qf, kf, vf, grows, brows = _layout(q, k, v, g, beta, chunk,
                                                    span)
    nc = steps * span
    dof = jnp.pad(do, [(0, 0), (0, nc * chunk - s_len), (0, 0), (0, 0)]
                  ).reshape(vf.shape)

    def forward_at(b_, hb, p, i):
        """Steps up to ``span`` read the block's chunks in order, the rest in
        reverse."""
        block = steps - 1 - p
        return (b_, block * span + jnp.where(i < span, i, 2 * span - 1 - i),
                hb, block)

    def backward_at(b_, hb, p, i):
        """What only the reverse walk reads or writes: the block's last chunk
        until it starts, so that nothing moves before."""
        block = steps - 1 - p
        return b_, block * span + jnp.where(i < span, span - 1,
                                            2 * span - 1 - i), hb, block

    keys, values, rows, saved = _specs(pl, (q.shape, v.shape), chunk, heads,
                                       ratio, forward_at)
    keys_out, values_out, rows_out, _ = _specs(
        pl, (q.shape, v.shape), chunk, heads, ratio, backward_at)
    vma = lowering.vma(*map(jax.typeof, (q, k, v, g, beta, states, do)))

    def out(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

    def scratch(*shape):
        return pltpu.VMEM(shape, jnp.float32)

    dq, dk_, dv_, dgrows, dbrows = pl.pallas_call(
        functools.partial(_backward_kernel, pl=pl, span=span, ratio=ratio),
        grid=(b, nv // heads, steps, 2 * span),
        in_specs=[keys_out, keys, values, rows, rows, saved, values_out],
        out_specs=[keys_out, keys_out, values_out, rows_out, rows_out],
        out_shape=[out(qf.shape, q.dtype), out(kf.shape, k.dtype),
                   out(vf.shape, v.dtype), out(grows.shape, jnp.float32),
                   out(brows.shape, jnp.float32)],
        scratch_shapes=[scratch(heads, dk, dv), scratch(heads, dk, dv),
                        scratch(span, heads, dk, dv),
                        scratch(span, heads, chunk, chunk),
                        scratch(span, heads, chunk, dv),
                        scratch(span, heads, chunk, dk),
                        scratch(span, heads, chunk, dv)],
        compiler_params=_params(pltpu, 2),
        interpret=interpret)(qf, kf, vf, grows, brows, states, dof)

    def tokens(t):
        return jnp.swapaxes(t, 2, 3).reshape(b, nc * chunk, nv)[:, :s_len]

    # G is the cumulative g: g's gradient is G's summed from the chunk's end
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgrows, -1), axis=-1), -1)
    return (dq.reshape(b, -1, nk, dk)[:, :s_len],
            dk_.reshape(b, -1, nk, dk)[:, :s_len],
            dv_.reshape(b, -1, nv, dv)[:, :s_len], tokens(dg),
            tokens(dbrows))


# ---------------------------------------------------------------------------
# XLA's spelling: the caller's body, differentiated by JAX


def _forward_xla(q, k, v, g, beta, *, body, chunk, span):
    return body(q, k, v, g, beta, chunk, span)


def _backward_xla(q, k, v, g, beta, states, do, *, body, chunk, span):
    del states      # the body's steps are computed again instead
    return jax.vjp(lambda *a: body(*a, chunk, span)[0],
                   q, k, v, g, beta)[1](do)


# ---------------------------------------------------------------------------
# the primitives: one meaning each, the lowering picks the spelling


def _forward_avals(q, k, v, g, beta, *, chunk, span, **_):
    b, s_len, nv, dv = v.shape
    vma = lowering.vma(q, k, v, g, beta)
    steps = steps_of(s_len, chunk, span)[1]
    return (v.update(weak_type=False, vma=vma),
            v.update(shape=(b, nv, steps, q.shape[3], dv),
                     dtype=jnp.dtype(jnp.float32), weak_type=False, vma=vma))


def _backward_avals(q, k, v, g, beta, states, do, **_):
    vma = lowering.vma(q, k, v, g, beta, states, do)
    return tuple(a.update(weak_type=False, vma=vma)
                 for a in (q, k, v, g, beta))


_forward_p = lowering.primitive("delta_rule", _forward_avals)
_backward_p = lowering.primitive("delta_rule_backward", _backward_avals)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def delta_rule(q, k, v, g, beta, body, chunk: int, span: int):
    """``o [B, S, H, dv]`` in ``v``'s type for ``q, k [B, S, Hk, dk]`` (value
    head ``j`` reads key head ``j // (H / Hk)``), ``v [B, S, H, dv]``, ``g,
    beta [B, S, H]`` float32 (the module's docstring). ``body(q, k, v, g,
    beta, chunk, span) -> (o, states)`` is XLA's spelling of the same, with
    ``states [B, H, steps, dk, dv]`` the float32 state entering every
    ``span``-th chunk (:func:`steps_of`)."""
    return _forward_p.bind(q, k, v, g, beta, body=body, chunk=chunk,
                           span=span)[0]


def _fwd(q, k, v, g, beta, body, chunk, span):
    o, states = _forward_p.bind(q, k, v, g, beta, body=body, chunk=chunk,
                                span=span)
    return o, (q, k, v, g, beta, states)


def _bwd(body, chunk, span, saved, do):
    return tuple(_backward_p.bind(*saved, do, body=body, chunk=chunk,
                                  span=span))


delta_rule.defvjp(_fwd, _bwd)


def _takes(avals, *, chunk, **_):
    q, k, v = avals[:3]
    return (q.dtype == k.dtype == v.dtype and v.shape[2] % q.shape[2] == 0
            and kernels_take(q.shape[3], v.shape[3], chunk))


def _kernel_spelling(*, interpret, chunk, span, backward, **_):
    return lowering.jitted(
        "delta_rule_backward" if backward else "delta_rule_forward",
        _backward_pallas if backward else _forward_pallas, chunk=chunk,
        span=span, interpret=interpret)


def _xla_spelling(*, body, chunk, span, backward):
    return functools.partial(_backward_xla if backward else _forward_xla,
                             body=body, chunk=chunk, span=span)


lowering.register(_forward_p, _backward_p, rule=_takes,
                  kernel=_kernel_spelling, xla=_xla_spelling,
                  counter="linear_scan_lowerings")
