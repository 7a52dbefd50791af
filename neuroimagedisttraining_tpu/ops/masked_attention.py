"""Softmax attention of grouped queries ``q [B, S, n, g, d]`` over ``k, v
[B, S, n, d]`` under a mask: float32 scores ``q . k / sqrt(d)``, ``-1e30``
where unseen, softmax in float32, probabilities rounded to the values' dtype
for the second product. The mask comes one of two ways:

* as data of the run (ISSUE 34; what a selecting layer computes once its keys
  are chosen): ``masked_attention(q, k, v, keep, attend, kind)`` is
  ``attend(q, k, v, keep != 0)`` for ``keep [B, S, S]`` int8 that holds
  nothing above the diagonal and something in every row (the causal mask is
  the caller's, inside ``keep``);
* as a rule of the positions (ISSUE 36; a full or a sliding layer):
  ``ruled_attention(q, k, v, window, attend, kind)`` is ``attend(q, k, v,
  window)``, attention where ``0 <= q - k < window`` (``window`` 0: ``k <=
  q`` alone). There is no mask array at all.

The forward and the backward are one primitive each with two lowerings,
chosen where the program is lowered, by what the compiler can run there (as
``ops/pool_vjp.py`` does it):

* Pallas kernels where the target is a TPU, no partitioner will touch the op
  (one device, or the inside of a ``shard_map`` over every sharded axis),
  the head width is a multiple of 128 lanes and the sequence divides into
  the kernels' tiles (:func:`tiles_of`, by the mask's shape). The scores and
  probabilities of a (query tile, key tile) pair live in VMEM and never
  reach HBM: the forward keeps a running maximum and sum per row and a
  float32 accumulator (online softmax) and hands back the rows'
  log-sum-exp; the backward computes a tile's scores again from it. The
  ``g`` query heads of a KV head share its keys, values and mask: a grid
  step loads one tile of each for all of them, so a mask that is data
  crosses HBM once a pass at one byte a pair; a rule's comes from two iotas
  and the tile's place, and a tile the rule sees whole applies none. The
  last axis of the grid steps over the tiles that the positions let a tile
  see (under a band: the band's, not the sequence's). **No tile is skipped
  for what the data holds** (device time must not follow the data); tiles
  wholly above the diagonal or behind the band are skipped by position.
* ``attend`` everywhere else (the CPU, a sequence that does not tile,
  whatever GSPMD partitions): under ``keep`` in blocks of ``BLOCK`` queries,
  each against the keys up to its own end and computed again going
  backward; under a rule the caller's whole spelling of it, differentiated
  as it stands, with no log-sum-exp (nothing there reads one).

Every lowering counts itself: ``attention_lowerings{spelling, kind, pass}``
in ``obs/metrics.get_registry()``. The primitives have no JVP rule: the
backward cannot be differentiated again (nothing in the package does).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax._src import dispatch
from jax.ad_checkpoint import checkpoint_name
from jax.extend.core import Primitive
from jax.interpreters import mlir

from .pool_vjp import _unpartitioned, _vma

# queries a block of XLA's spelling (models/decoder.FULL_ATTENTION_BLOCK)
BLOCK = 1024
# under these names a remat policy keeps what the backward reads beside its
# operands; unnamed, the forward kernel would run again going backward
SAVED_NAMES = ("attended", "attended_lse")
# ... and its operands, which a policy may keep too: the backward then does
# not project, norm and rotate the block's input again
OPERAND_NAMES = ("attention_q", "attention_k", "attention_v")

_MASKED = -1e30
# where a row's running maximum starts: over every masked score, so that a
# key tile which gives a row nothing adds exp(-1e30 - floor) = 0 to it and
# not exp(-1e30 - -1e30) = 1; under every real score
_FLOOR = -0.5e30
_LANES = 128
# (query tile, key tile) of the kernels, multiples of 128 lanes (my chip
# runs, PR 34, a layer forward + backward: 256 x 512 4.14 + 12.10 ms, 512 x
# 512 3.87 + 11.49, 512 x 1024 3.94 + 11.61, 128 x 512 5.99 + 13.32)
_TILES = (512, 512)
# ... and under a band of at most 512 keys, which computes whole tiles: three
# of 256 a query tile for two of 512 (my chip run, PR 36, a layer of 8,192
# tokens, 9 heads, window 512, forward + backward: 256 x 256 0.474 + 0.845 ms,
# 256 x 512 0.429 + 0.976, 512 x 512 0.476 + 0.978, 512 x 256 0.490 + 1.001,
# 128 x 256 0.499 + 1.009, 128 x 128 0.494 + 1.129; the causal rule at 6 heads
# keeps _TILES: 512 x 512 0.817 + 2.311, 1024 x 512 0.823 + 2.401, 512 x 1024
# 0.836 + 2.391, 256 x 512 0.836 + 2.468, 256 x 256 1.085 + 2.750)
_BAND_TILES = (256, 256)
_VMEM_LIMIT = 64 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))      # a . b^T


def _bias(keep_ref):
    """0 where kept, -1e30 where not, float32: added to the scaled scores
    (which it swallows whole), once a grid step for every head."""
    return (keep_ref[...].astype(jnp.float32) - 1.0) * -_MASKED


def _first_key_tile(i, tiles, window, maximum=jnp.maximum):
    """The first key tile that query tile ``i`` sees under ``q - window < k``
    (program ids in a kernel or an index map; ints with ``maximum=max``)."""
    return maximum(i * tiles[0] - window + 1, 0) // tiles[1]


def _last_key_tile(i, tiles):
    """... and the last one, under ``k <= q``."""
    return ((i + 1) * tiles[0] - 1) // tiles[1]


def _first_query_tile(j, tiles):
    """The first query tile that sees key tile ``j``."""
    return j * tiles[1] // tiles[0]


def _last_query_tile(j, tiles, window, s_len, minimum=jnp.minimum):
    """... and the last one."""
    return minimum(((j + 1) * tiles[1] + window - 2) // tiles[0],
                   s_len // tiles[0] - 1)


def _on_tile(pl, keep_ref, live, i, j, tiles, window, tile, transposed=False):
    """``tile(bias)`` where ``live``, for query tile ``i`` against key tile
    ``j``: the bias from ``keep_ref`` where the mask is data; where it is the
    rule ``0 <= q - k < window`` of the positions, from two iotas and the
    tile's place, and none (``tile(None)``) where the whole tile is seen."""
    tq, tk = tiles
    if keep_ref is not None:
        @pl.when(live)
        def _():
            bias = _bias(keep_ref)
            tile(bias.T if transposed else bias)
        return
    ahead = i * tq - j * tk      # q - k of the tile's first query and key
    inside = jnp.logical_and(ahead >= tk - 1, ahead + tq - 1 < window)

    @pl.when(jnp.logical_and(live, inside))
    def _():
        tile(None)

    @pl.when(jnp.logical_and(live, jnp.logical_not(inside)))
    def _():
        shape = (tk, tq) if transposed else (tq, tk)
        rows = lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = lax.broadcasted_iota(jnp.int32, shape, 1)
        dist = ahead + (cols - rows if transposed else rows - cols)
        tile(jnp.where(jnp.logical_and(dist >= 0, dist < window), 0.0,
                       _MASKED))


def _wide(col, width):
    """A per-row statistic kept on all 128 lanes, as wide as a tile."""
    return jnp.tile(col, (1, width // _LANES))


def _as_col(row, rows):
    """``row [1, rows]`` as ``[rows, 128]``, the value on every lane."""
    return jnp.broadcast_to(row, (_LANES, rows)).T


def _forward_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, lse_ref, m_scr,
                    l_scr, acc_scr, *, pl, tiles, scale, window):
    """Grid (batch, KV head, query tile ``i``, step ``j`` over the key tiles
    the query tile sees, from its first), ``j`` last: the ``g`` heads'
    running maximum, sum and output of a query tile stay in VMEM over its
    key tiles. The heads are unrolled, here and in the backward: as a
    ``fori_loop`` the kernels are half the code and lose a sixth of their
    speed (4.65 + 13.06 ms a layer for 3.84 + 11.49 on the chip, PR 34: one
    head's products no longer overlap the next one's exponentials)."""
    tk = tiles[1]
    heads, _, d = q_ref.shape
    i, step = pl.program_id(2), pl.program_id(3)
    j = _first_key_tile(i, tiles, window) + step

    @pl.when(step == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _FLOOR, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def tile(bias):
        k, v = k_ref[...], v_ref[...]
        for h in range(heads):
            s = lax.dot_general(q_ref[h], k, _NT,
                                preferred_element_type=jnp.float32) * scale
            if bias is not None:
                s = s + bias
            m_prev = m_scr[h]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _wide(m_next, tk))
            alpha = jnp.exp(m_prev - m_next)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            m_scr[h] = m_next
            acc_scr[h] = acc_scr[h] * _wide(alpha, d) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    # up to the tile that holds the diagonal
    _on_tile(pl, keep_ref, j <= _last_key_tile(i, tiles), i, j, tiles,
             window, tile)

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        for h in range(heads):
            # every row keeps a key; one that kept none reads 0, not NaN
            total = jnp.where(l_scr[h] == 0.0, 1.0, l_scr[h])
            o_ref[h] = (acc_scr[h] / _wide(total, d)).astype(o_ref.dtype)
            lse_ref[h] = (m_scr[h] + jnp.log(total)).T[:1]


def _dq_kernel(q_ref, k_ref, v_ref, keep_ref, do_ref, lse_ref, di_ref,
               dq_ref, lse_scr, di_scr, acc_scr, *, pl, tiles, scale, window):
    """The forward's grid. ``p = exp(s - lse)``, ``ds = p * (dp - di)``
    with ``di`` the rows' ``sum(do * o)``, ``dq = ds . k / sqrt(d)``."""
    tq, tk = tiles
    heads = q_ref.shape[0]
    i, step = pl.program_id(2), pl.program_id(3)
    j = _first_key_tile(i, tiles, window) + step

    @pl.when(step == 0)
    def _():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
        for h in range(heads):
            lse_scr[h] = _as_col(lse_ref[h], tq)
            di_scr[h] = _as_col(di_ref[h], tq)

    def tile(bias):
        k, v = k_ref[...], v_ref[...]
        for h in range(heads):
            s = lax.dot_general(q_ref[h], k, _NT,
                                preferred_element_type=jnp.float32) * scale
            if bias is not None:
                s = s + bias
            p = jnp.exp(s - _wide(lse_scr[h], tk))
            dp = lax.dot_general(do_ref[h], v, _NT,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - _wide(di_scr[h], tk))
            acc_scr[h] += jnp.dot(ds.astype(k.dtype), k,
                                  preferred_element_type=jnp.float32)

    _on_tile(pl, keep_ref, j <= _last_key_tile(i, tiles), i, j, tiles,
             window, tile)

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        for h in range(heads):
            dq_ref[h] = (acc_scr[h] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, keep_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, pl, tiles, scale, window,
                s_len):
    """Grid (batch, KV head, key tile ``j``, step over the query tiles that
    see it, up to its last), the step last: a key tile's two gradients stay
    in VMEM over the query tiles and the ``g`` heads that see it. The scores
    are computed transposed, ``k . q^T [tk, tq]``, so that the rows'
    statistics lie along the lanes as they are stored and both gradients
    are plain products."""
    heads = q_ref.shape[0]
    j, step = pl.program_id(2), pl.program_id(3)
    i = (_last_query_tile(j, tiles, window, s_len) - pl.num_programs(3) + 1
         + step)

    @pl.when(step == 0)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def tile(bias):
        k, v = k_ref[...], v_ref[...]
        for h in range(heads):
            q, do = q_ref[h], do_ref[h]
            s = lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32) * scale
            if bias is not None:
                s = s + bias
            p = jnp.exp(s - lse_ref[h])
            dv_scr[...] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dp = lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - di_ref[h])
            dk_scr[...] += jnp.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    # from the tile that holds the diagonal
    _on_tile(pl, keep_ref, i >= _first_query_tile(j, tiles), i, j, tiles,
             window, tile, transposed=True)

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _heads_major(a):        # [B, S, n, g, d] -> [B, n, g, S, d]
    return a.transpose(0, 2, 3, 1, 4)


def _tokens_major(a):       # and back
    return a.transpose(0, 3, 1, 2, 4)


def _kv_major(a):           # [B, S, n, d] <-> [B, n, S, d]
    return a.transpose(0, 2, 1, 3)


def _specs(pl, shape, tiles, window, keys_outer=False):
    """Block specs of ``(queries' tensors, keys' tensors, keep, rows'
    statistics)`` and the grid. The last axis of the grid steps over the
    tiles one tile of the axis before it sees under ``0 <= q - k < window``,
    as many steps as the tile that sees most; a step past them asks for the
    tile its neighbour needs, so nothing is fetched for it."""
    b, s_len, n, g, d = shape
    tq, tk = tiles
    if keys_outer:     # (b, h, j, step): query tiles up to the key tile's last
        steps = max(
            _last_query_tile(j, tiles, window, s_len, min)
            - _first_query_tile(j, tiles) + 1 for j in range(s_len // tk))

        def at(b_, h, j, step):
            i = _last_query_tile(j, tiles, window, s_len) - steps + 1 + step
            return b_, h, jnp.maximum(i, _first_query_tile(j, tiles)), j
        grid = (b, n, s_len // tk, steps)
    else:              # (b, h, i, step): key tiles from the query tile's first
        steps = max(
            _last_key_tile(i, tiles) - _first_key_tile(i, tiles, window, max)
            + 1 for i in range(s_len // tq))

        def at(b_, h, i, step):
            j = _first_key_tile(i, tiles, window) + step
            return b_, h, i, jnp.minimum(j, _last_key_tile(i, tiles))
        grid = (b, n, s_len // tq, steps)

    def spec(block, index):
        return pl.BlockSpec(block, lambda *ids: index(*at(*ids)))

    return (spec((None, None, g, tq, d), lambda b_, h, i, j: (b_, h, 0, i, 0)),
            spec((None, None, tk, d), lambda b_, h, i, j: (b_, h, j, 0)),
            spec((None, tq, tk), lambda b_, h, i, j: (b_, i, j)),
            spec((None, None, g, 1, tq),
                 lambda b_, h, i, j: (b_, h, 0, 0, i)), grid)


def _call(kernel, pl, pltpu, specs, rest_specs, out_specs, out_shape, scratch,
          operands, keep, *, interpret, **statics):
    """One kernel over ``(q, k, v, [keep,] *rest)``: with no ``keep`` the
    mask is the rule of the positions and the kernel's ``keep_ref`` None."""
    rows, keys, mask, stat, grid = specs
    in_specs = [rows, keys, keys, mask] + rest_specs
    if keep is None:
        def body(q_ref, k_ref, v_ref, *refs):
            kernel(q_ref, k_ref, v_ref, None, *refs, pl=pl, **statics)
        del in_specs[3]
    else:
        body = functools.partial(kernel, pl=pl, **statics)
        operands = operands[:3] + (keep,) + operands[3:]
    vma = _vma(*map(jax.typeof, operands))
    return pl.pallas_call(
        body, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct(s, t, vma=vma) for s, t in out_shape],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(*operands)


def _forward_pallas(q, k, v, keep=None, *, tiles, window=0, interpret=False):
    """``keep`` None: the mask is ``0 <= q - k < window`` (0: causal)."""
    # imported here: a second and a half that only a process which lowers
    # the kernel should pay (no CPU run does)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_len, n, g, d = q.shape
    window = window or s_len
    specs = _specs(pl, q.shape, tiles, window)
    rows, stat = specs[0], specs[3]
    out, lse = _call(
        _forward_kernel, pl, pltpu, specs, [], [rows, stat],
        [((b, n, g, s_len, d), q.dtype), ((b, n, g, 1, s_len), jnp.float32)],
        [(g, tiles[0], _LANES)] * 2 + [(g, tiles[0], d)],
        (_heads_major(q), _kv_major(k), _kv_major(v)), keep,
        interpret=interpret, tiles=tiles, scale=1.0 / math.sqrt(d),
        window=window)
    return _tokens_major(out), lse


def _backward_pallas(q, k, v, keep, out, lse, g_out, *, tiles, window=0,
                     interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_len, n, g, d = q.shape
    tq, tk = tiles
    window = window or s_len
    statics = dict(interpret=interpret, tiles=tiles, window=window,
                   scale=1.0 / math.sqrt(d))
    do = _heads_major(g_out)
    di = jnp.sum(_heads_major(out).astype(jnp.float32)
                 * do.astype(jnp.float32), axis=-1)[:, :, :, None]
    operands = (_heads_major(q), _kv_major(k), _kv_major(v), do, lse, di)
    specs = _specs(pl, q.shape, tiles, window)
    rows, keys, _, stat, _ = specs
    dq, = _call(
        _dq_kernel, pl, pltpu, specs, [rows, stat, stat], [rows],
        [((b, n, g, s_len, d), q.dtype)],
        [(g, tq, _LANES)] * 2 + [(g, tq, d)], operands, keep, **statics)
    specs = _specs(pl, q.shape, tiles, window, keys_outer=True)
    rows, keys, _, stat, _ = specs
    dk, dv = _call(
        _dkv_kernel, pl, pltpu, specs, [rows, stat, stat], [keys, keys],
        [((b, n, s_len, d), k.dtype), ((b, n, s_len, d), v.dtype)],
        [(tk, d)] * 2, operands, keep, s_len=s_len, **statics)
    return _tokens_major(dq), _kv_major(dk), _kv_major(dv)


@functools.lru_cache(maxsize=None)
def _kernels_of(tiles, window, interpret=False):
    """The two passes' kernels for one tiling and one mask (``window`` None:
    the ``keep`` operand; 0: causal; else the band), jitted: every layer of
    a program that holds them at the same shapes traces their bodies once."""
    opts = dict(tiles=tiles, window=window or 0, interpret=interpret)

    def attention_forward(q, k, v, *keep):
        return _forward_pallas(q, k, v, *keep, **opts)

    def attention_backward(q, k, v, *rest):     # [keep,] out, lse, g_out
        return _backward_pallas(q, k, v, *(rest[:-3] or (None,)), *rest[-3:],
                                **opts)
    return jax.jit(attention_forward), jax.jit(attention_backward)


# ---------------------------------------------------------------------------
# XLA's spelling: ``attend`` in blocks of queries


def _blocks(s_len):
    return [(i, min(i + BLOCK, s_len)) for i in range(0, s_len, BLOCK)]


def _forward_xla(q, k, v, keep, *, attend):
    outs, lses = [], []
    for i, end in _blocks(q.shape[1]):
        seen = keep[:, i:end, :end] != 0
        outs.append(attend(q[:, i:end], k[:, :end], v[:, :end], seen))
        # attend's own scores, once more for the compiler to share
        scores = jnp.einsum("...qngd,...knd->...ngqk", q[:, i:end],
                            k[:, :end], preferred_element_type=jnp.float32)
        scores = scores / math.sqrt(q.shape[-1])
        lses.append(jax.nn.logsumexp(
            jnp.where(seen[:, None, None], scores, _MASKED), axis=-1))
    return (jnp.concatenate(outs, axis=1),
            jnp.concatenate(lses, axis=-1)[:, :, :, None])


def _backward_xla(q, k, v, keep, out, lse, g_out, *, attend):
    del out, lse    # the block's product is computed again instead
    one = jax.checkpoint(attend)

    def blocked(q, k, v):
        return jnp.concatenate(
            [one(q[:, i:end], k[:, :end], v[:, :end],
                 keep[:, i:end, :end] != 0)
             for i, end in _blocks(q.shape[1])], axis=1)
    return jax.vjp(blocked, q, k, v)[1](g_out)


def _forward_ruled_xla(q, k, v, *, attend, window):
    """``attend(q, k, v, window)`` is the caller's whole spelling of the
    rule; its backward reads no log-sum-exp, so none is computed."""
    b, s_len, n, g, _ = q.shape
    return (attend(q, k, v, window),
            jnp.zeros((b, n, g, 1, s_len), jnp.float32))


def _backward_ruled_xla(q, k, v, out, lse, g_out, *, attend, window):
    del out, lse
    return jax.vjp(lambda *a: attend(*a, window), q, k, v)[1](g_out)


# ---------------------------------------------------------------------------
# the primitives: one meaning each, the lowering picks the spelling


def _forward_avals(q, k, v, *keep, **_):
    b, s_len, n, g, _ = q.shape
    vma = _vma(q, k, v, *keep)
    return (q.update(weak_type=False, vma=vma),
            q.update(shape=(b, n, g, 1, s_len), dtype=jnp.dtype(jnp.float32),
                     weak_type=False, vma=vma))


def _backward_avals(q, k, v, *rest, **_):
    vma = _vma(q, k, v, *rest)
    return tuple(a.update(weak_type=False, vma=vma) for a in (q, k, v))


def _primitive(name, avals):
    p = Primitive(name)
    p.multiple_results = True
    p.def_impl(functools.partial(dispatch.apply_primitive, p))
    p.def_abstract_eval(avals)
    return p


_forward_p = _primitive("masked_attention", _forward_avals)
_backward_p = _primitive("masked_attention_backward", _backward_avals)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attention(q, k, v, keep, attend, kind, window):
    """``keep``: ``(keep,)`` with ``window`` None, or ``()`` under the rule
    ``window``."""
    return _forward_p.bind(q, k, v, *keep, attend=attend, kind=kind,
                           window=window)[0]


def _fwd(q, k, v, keep, attend, kind, window):
    out, lse = _forward_p.bind(q, k, v, *keep, attend=attend, kind=kind,
                               window=window)
    out = checkpoint_name(out, SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    q, k, v = (checkpoint_name(a, name)
               for a, name in zip((q, k, v), OPERAND_NAMES))
    return out, (q, k, v, keep, out, lse)


def _bwd(attend, kind, window, saved, g_out):
    q, k, v, keep, out, lse = saved
    grads = _backward_p.bind(q, k, v, *keep, out, lse, g_out, attend=attend,
                             kind=kind, window=window)
    return (*grads, tuple(None for _ in keep))


_attention.defvjp(_fwd, _bwd)


def masked_attention(q, k, v, keep, attend, kind: str):
    """``attend(q, k, v, keep != 0)`` for a mask that is data (the module's
    docstring); ``kind`` labels the caller's lowerings in
    ``attention_lowerings``."""
    return _attention(q, k, v, (keep,), attend, kind, None)


def ruled_attention(q, k, v, window: int, attend, kind: str):
    """``attend(q, k, v, window)``, which must be softmax attention under
    the mask ``0 <= q - k < window`` of the positions (``window`` 0: ``k <=
    q`` alone) in ``_attend``'s precision; ``kind`` as above."""
    if window >= q.shape[1]:
        window = 0
    return _attention(q, k, v, (), attend, kind, window)


def kernels_take(head_dim: int) -> bool:
    """Whether heads of this width can lower to the kernels at all (a
    caller's remat keeps ``SAVED_NAMES`` where they can)."""
    return head_dim % _LANES == 0


def tiles_of(window):
    """(query tile, key tile) of the kernels under ``window`` (None: a
    ``keep`` operand; 0: the causal rule; else the band). A band computes
    whole tiles: at ``_TILES`` a window of 512 costs two key tiles a query
    tile, twice the pairs it sees; narrower tiles waste less and step
    more."""
    if window and window <= 2 * _BAND_TILES[1]:
        return _BAND_TILES
    return _TILES


def _lower(ctx, *args, attend, kind, window, backward, kernels,
           interpret=False):
    q, k, v = ctx.avals_in[:3]
    tiles = tiles_of(window)
    kernel = (
        kernels and _unpartitioned(ctx.module_context.axis_context)
        and q.dtype == k.dtype == v.dtype and kernels_take(q.shape[-1])
        and all(q.shape[1] % t == 0 for t in tiles))
    # the choice is made once per lowering, so lowerings are what is counted
    from ..obs.metrics import get_registry
    get_registry().counter("attention_lowerings").labels(
        spelling="kernel" if kernel else "xla", kind=kind,
        **{"pass": "backward" if backward else "forward"}).inc()
    if kernel:
        fn = _kernels_of(tiles, window, interpret)[backward]
    elif window is None:
        fn = functools.partial(_backward_xla if backward else _forward_xla,
                               attend=attend)
    else:
        fn = functools.partial(
            _backward_ruled_xla if backward else _forward_ruled_xla,
            attend=attend, window=window)
    return mlir.lower_fun(fn, multiple_results=True)(ctx, *args)


for _p, _backward in ((_forward_p, False), (_backward_p, True)):
    mlir.register_lowering(_p, functools.partial(
        _lower, backward=_backward, kernels=False))
    mlir.register_lowering(_p, functools.partial(
        _lower, backward=_backward, kernels=True), platform="tpu")
