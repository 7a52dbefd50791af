"""The selecting attention's choice of keys, one meaning with two lowerings:
``index_select(q_idx, w_idx, k_idx, topk, blocks, block)`` is ``keep [B, S,
S]`` int8, for the query at position ``q`` the ``topk`` keys ``k <= q`` that
the indexer scores highest (all of them where there are no more), ties to
the lower ``k``. The scores are ``sum_j w[j] * relu(q[j] . k) / sqrt(J *
e)`` in float32 for the indexer's ``q_idx [B, S, J, e]``, ``w_idx [B, S,
J]`` (float32) and ``k_idx [B, S, e]``, the heads added in order. The op
is forward only: nothing differentiates the indexer's choice. The lowering
picks the spelling (``ops/lowering.py`` has the rule):

* a Pallas kernel on a TPU with no partitioner in the way, where the
  indexer's width is a multiple of 64 and the sequence divides
  into the mask's column blocks (:func:`kernel_takes`, by shape alone). Its
  grid is (batch, query tile, column block of the mask). At a tile's first
  column block the float32 scores against every key up to the tile's last
  query, chunk by chunk (the ``J`` products of the chunk on the MXU, the
  weighted ReLUs added head after head), go into VMEM scratch as signed
  integers in the scores' own order, a key the causal mask hides as the
  least of them; a row's ``topk``-th largest is built from the top bit
  down by 32 counts over the row in VMEM, and where some row of the tile
  has more keys equal to it than it still wants, the last equal key it
  keeps by a second bisection, on the key's index (one count a bit). Each
  column block of the tile then writes its int8 part of the mask once. A
  tile whose last query is under ``topk`` scores nothing: its rows keep
  every visible key. What the tiles do follows from positions alone; the
  crowded rows' bisection is the one step that follows the data.
* ``blocks(q_idx, w_idx, k_idx, topk, block)`` everywhere else (the CPU,
  widths and sequences that do not tile, whatever GSPMD partitions):
  ``models/decoder._selection_blocks``, the queries in blocks of ``block``,
  each scored against the keys up to its own end in XLA and chosen by
  ``decoder.select_keys``, the blocks padded and joined.

Every lowering counts itself: ``selection_lowerings{spelling}`` (``kernel``
/ ``blocks``) in ``obs/metrics.get_registry()``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import lowering
from .lowering import LANES, NT, as_col, wide

# queries a grid step: their rows of the key order live in VMEM scratch,
# ``_TQ x S`` int32 (16 MiB at 16,384 tokens)
_TQ = 256
# keys a chunk of that scratch: the scores' products and the counts step
# over the chunks up to the tile's last query
_TC = 512
# columns of the mask a grid step writes
_TO = 2048
# rows a count carries at once (8 vregs a lane tile)
_ROWS = 64
_VMEM_LIMIT = 96 * 2 ** 20
# the scratch of the key order and the keys' double-buffered block (lanes
# padded to 128) must leave this much of the limit to the rest
_SCRATCH_BUDGET = 64 * 2 ** 20
_SIGN = -2 ** 31        # int32 0x80000000: the key the causal mask hides
_WIDTH = 64             # the indexer's width must be a multiple of this


def kernel_takes(s_len: int, width: int) -> bool:
    """Whether an indexer of this width over this many tokens can lower to
    the kernel at all (shape alone; the target and the partitioner are the
    lowering's to judge)."""
    scratch = s_len * (_TQ * 4 + 2 * 2 * LANES)
    return (width % _WIDTH == 0 and s_len % _TO == 0
            and scratch <= _SCRATCH_BUDGET)


def ordered(scores):
    """float32 ``scores`` as int32 in the scores' own order, -0 as +0:
    ``decoder.ordered_keys``' unsigned integers with the top bit flipped
    (that key's 0, a hidden key, is ``_SIGN``, under every real score)."""
    bits = lax.bitcast_convert_type(jnp.where(scores == 0, 0.0, scores),
                                    jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _at(pl, start, size):
    """``size`` rows from ``start``, a multiple of ``size``."""
    return pl.ds(pl.multiple_of(start, size), size)


def _score(pl, q_ref, w_ref, k_ref, keys_scr, w_scr, *, first, chunks, scale):
    """The tile's scores against the keys of ``chunks`` chunks into
    ``keys_scr``, in :func:`ordered`'s integers, ``_SIGN`` above the
    diagonal."""
    heads, tq, _ = w_scr.shape
    e = q_ref.shape[1] // heads
    tc = keys_scr.shape[2]
    for j in range(heads):
        w_scr[j] = as_col(w_ref[j], tq)
    row = first + lax.broadcasted_iota(jnp.int32, (tq, tc), 0)

    def chunk(c, carry):
        k = k_ref[_at(pl, c * tc, tc), :]
        total = 0.0
        for j in range(heads):
            dot = lax.dot_general(q_ref[:, j * e:(j + 1) * e], k, NT,
                                  preferred_element_type=jnp.float32)
            total = total + wide(w_scr[j], tc) * jax.nn.relu(dot)
        col = c * tc + lax.broadcasted_iota(jnp.int32, (tq, tc), 1)
        keys_scr[c] = jnp.where(col <= row, ordered(total / scale), _SIGN)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


def _counts(pl, keys_scr, r0, chunks, preds):
    """Per row of the ``_ROWS`` from ``r0``, float32 ``[_ROWS, 1]``: how
    many keys of the first ``chunks`` chunks meet each ``pred(keys,
    cols)`` (both ``[_ROWS, 128]``)."""
    tc = keys_scr.shape[2]
    lane = lax.broadcasted_iota(jnp.int32, (_ROWS, LANES), 1)

    def chunk(c, accs):
        accs = list(accs)
        for lane_tile in range(tc // LANES):
            at = lane_tile * LANES
            keys = keys_scr[c, _at(pl, r0, _ROWS), at:at + LANES]
            cols = c * tc + at + lane
            for n, pred in enumerate(preds):
                accs[n] = accs[n] + jnp.where(pred(keys, cols), 1.0, 0.0)
        return tuple(accs)

    zero = jnp.zeros((_ROWS, LANES), jnp.float32)
    accs = lax.fori_loop(0, chunks, chunk, (zero,) * len(preds))
    return [jnp.sum(a, axis=1, keepdims=True) for a in accs]


def _choose(pl, keys_scr, kth_scr, last_scr, *, chunks, topk, s_len):
    """Per row of the tile, ``kth_scr`` the ``topk``-th largest key (in
    :func:`ordered`'s integers; ``_SIGN`` where the row holds fewer) and
    ``last_scr`` the last column of a key equal to it that the row keeps
    (``s_len`` where it keeps every one)."""
    tq = kth_scr.shape[0]
    nbits = max(s_len - 1, 1).bit_length()

    def rows(r, carry):
        r0 = r * _ROWS

        # the largest unsigned key (top bit flipped back) that topk reach
        def settle(_, carry):
            kth, bit = carry
            trial = (kth | bit) ^ _SIGN
            reach, = _counts(pl, keys_scr, r0, chunks,
                             [lambda keys, _: keys >= trial])
            return jnp.where(reach >= topk, trial ^ _SIGN, kth), \
                lax.shift_right_logical(bit, 1)

        kth, _ = lax.fori_loop(
            0, 32, settle,
            (jnp.zeros((_ROWS, LANES), jnp.int32), jnp.int32(_SIGN)))
        kth = kth ^ _SIGN
        above, equal = _counts(pl, keys_scr, r0, chunks,
                               [lambda keys, _: keys > kth,
                                lambda keys, _: keys == kth])
        wanted = topk - above
        # a row of fewer than topk visible keys has kth _SIGN: the hidden
        # ones equal it
        crowded = jnp.logical_and(equal > wanted, kth != _SIGN)
        kth_scr[_at(pl, r0, _ROWS)] = kth
        last_scr[_at(pl, r0, _ROWS)] = jnp.full((_ROWS, LANES), s_len,
                                              jnp.int32)

        @pl.when(jnp.max(jnp.where(crowded, 1, 0)) > 0)
        def _():
            # the largest column before which fewer than wanted keys equal
            # kth: the column of the wanted-th of them
            def narrow(_, carry):
                cut, bit = carry
                trial = cut | bit
                n, = _counts(pl, keys_scr, r0, chunks,
                             [lambda keys, cols: jnp.logical_and(
                                 keys == kth, cols < trial)])
                return jnp.where(n < wanted, trial, cut), \
                    lax.shift_right_logical(bit, 1)

            cut, _ = lax.fori_loop(
                0, nbits, narrow,
                (jnp.zeros((_ROWS, LANES), jnp.int32),
                 jnp.int32(1 << (nbits - 1))))
            last_scr[_at(pl, r0, _ROWS)] = jnp.where(crowded, cut, s_len)
        return carry

    lax.fori_loop(0, tq // _ROWS, rows, 0)


def _kernel(q_ref, w_ref, k_ref, out_ref, keys_scr, w_scr, kth_scr,
            last_scr, *, pl, topk, scale):
    """Grid (batch, query tile ``i``, column block ``c`` of the mask), ``c``
    last: the tile's keys are scored and chosen at ``c`` 0 and stay in VMEM
    scratch while the tile's column blocks are written."""
    tq = kth_scr.shape[0]
    n_chunks, _, tc = keys_scr.shape
    s_len = n_chunks * tc
    to = out_ref.shape[1]
    i, c = pl.program_id(1), pl.program_id(2)
    first = i * tq
    extent = first + tq         # the keys the tile's last query sees

    @pl.when(c == 0)
    def _():
        @pl.when(extent <= topk)
        def _():
            kth_scr[...] = jnp.full(kth_scr.shape, _SIGN, jnp.int32)
            last_scr[...] = jnp.full(last_scr.shape, s_len, jnp.int32)

        @pl.when(extent > topk)
        def _():
            chunks = (extent + tc - 1) // tc
            _score(pl, q_ref, w_ref, k_ref, keys_scr, w_scr, first=first,
                   chunks=chunks, scale=scale)
            _choose(pl, keys_scr, kth_scr, last_scr, chunks=chunks, topk=topk,
                    s_len=s_len)

    # a block of columns the tile's rows see; behind a tile under topk the
    # scratch holds an earlier tile's keys, which kth _SIGN keeps whole and
    # the causal mask cuts
    @pl.when(c * to < extent)
    def _():
        for m in range(to // tc):
            g = c * (to // tc) + m
            for r0 in range(0, tq, _ROWS):
                keys = keys_scr[g, r0:r0 + _ROWS, :]
                kth = wide(kth_scr[r0:r0 + _ROWS], tc)
                last = wide(last_scr[r0:r0 + _ROWS], tc)
                col = g * tc + lax.broadcasted_iota(jnp.int32, (_ROWS, tc), 1)
                row = first + r0 + lax.broadcasted_iota(
                    jnp.int32, (_ROWS, tc), 0)
                keep = jnp.logical_and(
                    jnp.logical_or(keys > kth, jnp.logical_and(
                        keys == kth, col <= last)), col <= row)
                out_ref[r0:r0 + _ROWS, m * tc:(m + 1) * tc] = keep.astype(
                    jnp.int8)

    @pl.when(c * to >= extent)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.int8)


def _select_pallas(q_idx, w_idx, k_idx, *, topk, interpret=False):
    pl, pltpu = lowering.pallas()

    b, s_len, heads, e = q_idx.shape
    q = q_idx.reshape(b, s_len, heads * e)          # the heads side by side
    w = w_idx.transpose(0, 2, 1)[:, :, None]        # [B, J, 1, S]
    vma = lowering.vma(*map(jax.typeof, (q_idx, w_idx, k_idx)))
    return pl.pallas_call(
        functools.partial(_kernel, pl=pl, topk=topk,
                          scale=math.sqrt(heads * e)),
        grid=(b, s_len // _TQ, s_len // _TO),
        in_specs=[
            pl.BlockSpec((None, _TQ, heads * e), lambda b_, i, c: (b_, i, 0)),
            pl.BlockSpec((None, heads, 1, _TQ),
                         lambda b_, i, c: (b_, 0, 0, i)),
            pl.BlockSpec((None, s_len, e), lambda b_, i, c: (b_, 0, 0))],
        out_specs=pl.BlockSpec((None, _TQ, _TO), lambda b_, i, c: (b_, i, c)),
        out_shape=jax.ShapeDtypeStruct((b, s_len, s_len), jnp.int8, vma=vma),
        scratch_shapes=[
            pltpu.VMEM((s_len // _TC, _TQ, _TC), jnp.int32),
            pltpu.VMEM((heads, _TQ, LANES), jnp.float32),
            pltpu.VMEM((_TQ, LANES), jnp.int32),
            pltpu.VMEM((_TQ, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(q, w, k_idx)


# ---------------------------------------------------------------------------
# the primitive: one meaning, the lowering picks the spelling


def _avals(q_idx, w_idx, k_idx, **_):
    b, s_len = q_idx.shape[:2]
    return (q_idx.update(shape=(b, s_len, s_len), dtype=jnp.dtype(jnp.int8),
                         weak_type=False,
                         vma=lowering.vma(q_idx, w_idx, k_idx)),)


_select_p = lowering.primitive("index_select", _avals)


def index_select(q_idx, w_idx, k_idx, topk: int, blocks, block: int):
    """``keep [B, S, S]`` int8 (the module's docstring); ``blocks(q_idx,
    w_idx, k_idx, topk, block)`` is XLA's spelling of the same."""
    return _select_p.bind(q_idx, w_idx, k_idx, topk=topk, blocks=blocks,
                          block=block)[0]


def _takes(avals, **_):
    q_idx = avals[0]
    return kernel_takes(q_idx.shape[1], q_idx.shape[3])


def _kernel_spelling(*, interpret, topk, **_):
    def fn(*operands):
        with jax.named_scope("select"):
            return (lowering.jitted("index_select", _select_pallas, topk=topk,
                                    interpret=interpret)(*operands),)
    return fn


def _xla_spelling(*, topk, blocks, block):
    def fn(*operands):
        return (blocks(*operands, topk, block),)
    return fn


lowering.register(_select_p, rule=_takes, kernel=_kernel_spelling,
                  xla=_xla_spelling, counter="selection_lowerings",
                  xla_spelling="blocks")
