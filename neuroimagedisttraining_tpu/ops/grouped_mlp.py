"""The held experts' SwiGLU over a chunk of slots laid out in *tile-aligned
groups* (ISSUE 38): every tile of ``tm`` rows belongs to one held expert.

``aligned_layout`` makes the layout from the sorted slots: each held expert's
rows start at a multiple of ``tm`` and take at least one tile, the gaps and the
tail hold zeros, the tail's tiles go to the last expert. So no row lies outside
every group (``jax.lax.ragged_dot`` leaves such rows undefined on a TPU), no
expert is without a tile, and the last row is always a zero row: the place of
every slot that is not in the chunk. ``tile_expert [tiles]`` is the whole
contract between the layout and the products. ``row_tile`` says by a chunk's
shape whether the layout is worth its padding: a chunk that is a small part
of the slots keeps its rows as they are sorted and comes nowhere near here.

On that layout ``forward(xs, rw, w, tile_expert, tm)`` is, row by row,
``rw[r] * (silu(xs[r] @ gate[e]) * (xs[r] @ up[e])) @ down[e]`` with ``e`` the
row's expert, and ``backward(xs, rw, w, tile_expert, gy, tm)`` the gradients
of ``<gy, forward>`` by ``xs``, ``rw`` and the three weight stacks. Each is one
primitive with two lowerings (``ops/lowering.py`` has the rule):

* Pallas kernels on a TPU with no partitioner in the way, where
  ``hidden``, ``width`` and ``tm`` are multiples of 128 lanes. Every
  kernel is a dense tiled product whose weight block a scalar-prefetched
  ``tile_expert`` chooses; consecutive tiles of one expert ask for the same
  block, which is then not fetched again. Forward: ``gate | up`` as ONE kernel
  over ``xs`` with ``silu(gate) * up`` applied to the float32 accumulators
  before the one rounding; ``down`` with the row weight in its epilogue.
  Backward: one kernel computes ``gate``, ``up`` and ``gy @ down[e]^T`` again
  for a tile and hands back the weighted activation, both pre-activation
  gradients and the row weight's gradient (``<gy @ down^T, a>``: the down
  product is not computed again); the same tiled product on the transposed
  weight blocks for the rows' gradient; ``x_tile^T @ d_tile`` accumulated
  into the expert's block, which stays resident over an expert's tiles, for
  the weights'. **Every grid is static and no tile is skipped for what the
  data holds**: device time does not follow the routing.
* ``jax.lax.ragged_dot`` on the same layout everywhere else (the CPU, widths
  that do not tile, whatever GSPMD partitions), differentiated as it stands.

Every lowering counts itself: ``expert_lowerings{spelling, product, pass}``
in ``obs/metrics.get_registry()``, one entry a product. The primitives have
no JVP rule: ``models/decoder.routed_part`` binds both from its own
derivative rule.
"""
from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax import lax

from . import lowering
from .lowering import LANES, NT, TN, wide

_VMEM_LIMIT = 96 * 2 ** 20
# what a kernel's weight blocks may take of VMEM, both buffers: decides the
# column tile (:func:`_col_tile`)
_WEIGHT_VMEM = 24 * 2 ** 20
# ... and a weight gradient's float32 accumulator
_ACC_VMEM = 8 * 2 ** 20
# the products a lowering counts, by pass
PRODUCTS = {"forward": ("gate_up", "down"),
            "backward": ("gate_up_again", "d_rows", "d_gate", "d_up",
                         "d_down")}


# the rows of a tile. One layer of ``lfm2_8b_a1b_fed.longctx``'s chunk (65,536
# slots, 8 experts of 2048 x 1792) forward + backward, my chip run, PR 38:
# 512 8.11 + 21.74 ms, 256 8.03 + 21.73 (2,048 rows fewer, as slow: steps of
# half the work); ``keye_vl2_fed.longctx``'s (16 experts of 2048 x 768) 512
# 3.81 + 10.07, 256 3.73 + 9.91; XLA's ``ragged-dot`` on the same layout
# 15.57 + 46.29 and 7.66 + 24.51.
_ROW_TILE = 512


def row_tile(rows: int, held: int):
    """The rows of a tile where aligning ``held`` groups to it adds at most
    an eighth to a chunk of ``rows`` slots, else None: such a chunk is not
    laid out in aligned groups at all (``models/decoder.routed_part`` keeps
    its rows as they are sorted). ``laguna_s21_fed.train``'s chunk of 10,240
    slots of 81,920 is one: a tile of 128 there ran the products at 67-79 %
    of the MXU's peak (``ragged-dot`` 50-55 %), but a token's sum over its 10
    slots' rows reads eight times the chunk, and the round was 5.6 % slower
    (my chip run, PR 38: PERF.md section 6)."""
    return _ROW_TILE if held * _ROW_TILE * 8 <= rows else None


def aligned_rows(rows: int, held: int, tm: int) -> int:
    """The rows of the aligned chunk: ``rows`` in whole tiles and a tile an
    expert (an expert's last tile is partly empty; one with no slot takes a
    whole empty one)."""
    return -(-rows // tm) * tm + held * tm


def aligned_layout(lo, rows: int, tm: int, order, ends, top_k: int):
    """The chunk of the sorted slots ``[lo, lo + rows)`` in tile-aligned
    groups. ``order [slots]``: the slots sorted by held expert; ``ends
    [held]``: where each expert's slots end in it. Returns

    * ``tile_expert [tiles]``: the expert of each tile of ``tm`` rows,
      non-decreasing, every expert at least once;
    * ``slot_of [aligned rows]``: the slot a row holds, ``slots`` for an
      empty row (a gap, the tail);
    * ``token_of [aligned rows]``: its token, ``slots // top_k`` for an
      empty row: the zero row appended to the tokens;
    * ``place(position)``: the row of the slot at ``position [..]`` of the
      sorted order, the last row (always empty) for one outside the chunk
      or past the held slots."""
    held, slots = ends.shape[0], order.shape[0]
    total = aligned_rows(rows, held, tm)
    starts = ends - jnp.diff(ends, prepend=0)
    first, last = (jnp.clip(a, lo, lo + rows) for a in (starts, ends))
    span = -(-jnp.maximum(last - first, 1) // tm) * tm
    row_end = jnp.cumsum(span)
    row_start = row_end - span
    tile_expert = jnp.minimum(jnp.sum(
        (tm * jnp.arange(total // tm))[:, None] >= row_end[None, :], axis=1),
        held - 1).astype(jnp.int32)
    # per tile, then a tile's rows: the tables of ``held`` entries are read
    # once a tile, not once a row
    offset = (jnp.arange(total).reshape(-1, tm)
              - row_start[tile_expert][:, None])
    filled = offset < (last - first)[tile_expert][:, None]
    position = first[tile_expert][:, None] + offset
    slot_of = jnp.where(filled, order[jnp.minimum(position, slots - 1)],
                        slots).reshape(-1)

    def place(position):
        # at most one expert's stretch of the chunk holds a position
        hit = ((position[..., None] >= first)
               & (position[..., None] < last))
        row = position + jnp.sum(jnp.where(hit, row_start - first, 0),
                                 axis=-1)
        return jnp.where(jnp.any(hit, axis=-1), row, total - 1)

    return tile_expert, slot_of, slot_of // top_k, place


def _groups(tile_expert, held, tm):
    """The groups' sizes, for ``ragged_dot``: they cover every row."""
    return tm * jnp.sum(tile_expert[None, :] == jnp.arange(held)[:, None],
                        axis=1, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# XLA's spelling


def _swiglu_xla(xs, rw, wg, wu, wd, tile_expert, *, tm):
    groups = _groups(tile_expert, wg.shape[0], tm)
    gate = jax.lax.ragged_dot(xs, wg, groups)
    up = jax.lax.ragged_dot(xs, wu, groups)
    ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, wd, groups)
    return ys * rw[:, None].astype(ys.dtype)


def _forward_xla(*operands, tm):
    return (_swiglu_xla(*operands, tm=tm),)


def _backward_xla(xs, rw, wg, wu, wd, tile_expert, gy, *, tm):
    return jax.vjp(lambda *a: _swiglu_xla(*a, tile_expert, tm=tm),
                   xs, rw, wg, wu, wd)[1](gy)


# ---------------------------------------------------------------------------
# the kernels. Grid (column tile j, row tile t), t last: a weight block
# [.., tn] is fetched once an expert a column tile, the rows' tiles stream.


def _gate_up_kernel(te_ref, x_ref, wg_ref, wu_ref, a_ref):
    x = x_ref[...]
    gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    a_ref[...] = (gate * jax.nn.sigmoid(gate) * up).astype(a_ref.dtype)


def _down_kernel(te_ref, a_ref, wd_ref, rw_ref, y_ref):
    y = jnp.dot(a_ref[...], wd_ref[...], preferred_element_type=jnp.float32)
    y_ref[...] = (y * wide(rw_ref[...], y.shape[1])).astype(y_ref.dtype)


def _gate_up_again_kernel(te_ref, x_ref, gy_ref, wg_ref, wu_ref, wd_ref,
                          rw_ref, aw_ref, dg_ref, du_ref, drw_ref):
    """A tile of ``width`` columns going backward: ``u = gy @ down[e]^T`` is
    the activation's gradient before the row weight, so ``<u, a>`` over the
    columns is the row weight's (summed here down to 128 lanes a tile)."""
    x = x_ref[...]
    gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    u = lax.dot_general(gy_ref[...], wd_ref[...], NT,
                        preferred_element_type=jnp.float32)
    sig = jax.nn.sigmoid(gate)
    silu = gate * sig
    # as the forward rounded it
    a = (silu * up).astype(aw_ref.dtype).astype(jnp.float32)
    ua = u * a
    drw_ref[...] = sum(ua[:, i:i + LANES]
                       for i in range(0, ua.shape[1], LANES))
    rw = wide(rw_ref[...], u.shape[1])
    aw_ref[...] = (a * rw).astype(aw_ref.dtype)
    da = u * rw
    dg_ref[...] = (da * up * (sig + silu * (1.0 - sig))).astype(dg_ref.dtype)
    du_ref[...] = (da * silu).astype(du_ref.dtype)


def _d_rows_kernel(te_ref, dg_ref, du_ref, wg_ref, wu_ref, dx_ref):
    dx = lax.dot_general(dg_ref[...], wg_ref[...], NT,
                         preferred_element_type=jnp.float32)
    dx += lax.dot_general(du_ref[...], wu_ref[...], NT,
                          preferred_element_type=jnp.float32)
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _d_weight_kernel(te_ref, x_ref, d_ref, dw_ref, acc_ref, *, pl):
    """Grid (column tile j, row tile t), t last: ``x_tile^T @ d_tile`` into
    the float32 block of the tile's expert, zeroed at the expert's first
    tile and written out at its last (its tiles are consecutive and the
    output block is theirs throughout)."""
    t, tiles = pl.program_id(1), pl.num_programs(1)
    expert = te_ref[t]

    @pl.when(jnp.logical_or(t == 0,
                            te_ref[jnp.maximum(t - 1, 0)] != expert))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    acc_ref[...] += lax.dot_general(x_ref[...], d_ref[...], TN,
                                    preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(t == tiles - 1,
                            te_ref[jnp.minimum(t + 1, tiles - 1)] != expert))
    def _():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _col_tile(n: int, bytes_a_column: int, budget: int) -> int:
    """The widest tile of ``n`` columns, a multiple of 128 lanes that divides
    ``n``, whose ``bytes_a_column`` a column stay inside ``budget``."""
    fits = [c for c in range(LANES, n + 1, LANES)
            if n % c == 0 and c * bytes_a_column <= budget]
    return max(fits, default=LANES)


def _pallas(tm, interpret=False):
    """The five kernels at row tile ``tm``, one function a product, and the
    two passes made of them."""
    pl, pltpu = lowering.pallas()

    def call(kernel, tile_expert, operands, in_specs, out_specs, out_shape,
             grid, scratch=(), reduces=False):
        vma = lowering.vma(*map(jax.typeof, operands))
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=list(scratch)),
            out_shape=[jax.ShapeDtypeStruct(s, d, vma=vma)
                       for s, d in out_shape],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(
                    "parallel", "arbitrary" if reduces else "parallel"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret)(tile_expert, *operands)

    def rows(cols):             # a tile of rows, all its columns
        return pl.BlockSpec((tm, cols), lambda j, t, te: (t, 0))

    def rows_at(tn):            # ... its column tile j
        return pl.BlockSpec((tm, tn), lambda j, t, te: (t, j))

    def block(k, tn):           # the expert's [k, tn] at column tile j
        return pl.BlockSpec((None, k, tn), lambda j, t, te: (te[t], 0, j))

    def block_t(tn, k):         # the expert's [tn, k] at row tile j
        return pl.BlockSpec((None, tn, k), lambda j, t, te: (te[t], j, 0))

    def lanes(rw):
        return jnp.broadcast_to(rw[:, None], (rw.shape[0], LANES))

    def gate_up(xs, wg, wu, tile_expert):
        (total, hidden), width = xs.shape, wg.shape[2]
        tn = _col_tile(width, 2 * 2 * hidden * xs.dtype.itemsize,
                       _WEIGHT_VMEM)
        return call(_gate_up_kernel, tile_expert, (xs, wg, wu),
                    [rows(hidden), block(hidden, tn), block(hidden, tn)],
                    [rows_at(tn)], [((total, width), xs.dtype)],
                    (width // tn, total // tm))[0]

    def down(a, wd, rw, tile_expert):
        (total, width), hidden = a.shape, wd.shape[2]
        tn = _col_tile(hidden, 2 * width * a.dtype.itemsize, _WEIGHT_VMEM)
        return call(_down_kernel, tile_expert, (a, wd, lanes(rw)),
                    [rows(width), block(width, tn), rows(LANES)],
                    [rows_at(tn)], [((total, hidden), a.dtype)],
                    (hidden // tn, total // tm))[0]

    def gate_up_again(xs, gy, wg, wu, wd, rw, tile_expert):
        (total, hidden), width = xs.shape, wg.shape[2]
        tn = _col_tile(width, 2 * 3 * hidden * xs.dtype.itemsize,
                       _WEIGHT_VMEM)
        aw, dg, du, drw = call(
            _gate_up_again_kernel, tile_expert,
            (xs, gy, wg, wu, wd, lanes(rw)),
            [rows(hidden), rows(hidden), block(hidden, tn), block(hidden, tn),
             block_t(tn, hidden), rows(LANES)],
            [rows_at(tn)] * 3 + [pl.BlockSpec(
                (None, tm, LANES), lambda j, t, te: (j, t, 0))],
            [((total, width), xs.dtype)] * 3
            + [((width // tn, total, LANES), jnp.float32)],
            (width // tn, total // tm))
        return aw, dg, du, jnp.sum(drw, axis=(0, 2)).astype(rw.dtype)

    def d_rows(dg, du, wg, wu, tile_expert):
        (total, width), hidden = dg.shape, wg.shape[1]
        tn = _col_tile(hidden, 2 * 2 * width * dg.dtype.itemsize,
                       _WEIGHT_VMEM)
        return call(_d_rows_kernel, tile_expert, (dg, du, wg, wu),
                    [rows(width), rows(width), block_t(tn, width),
                     block_t(tn, width)],
                    [rows_at(tn)], [((total, hidden), dg.dtype)],
                    (hidden // tn, total // tm))[0]

    def d_weight(x, d, tile_expert, held):
        """``[held, k, n]``: per expert ``x^T @ d`` over its tiles."""
        (total, k), n = x.shape, d.shape[1]
        tn = _col_tile(n, 4 * k, _ACC_VMEM)
        return call(
            functools.partial(_d_weight_kernel, pl=pl), tile_expert, (x, d),
            [rows(k), rows_at(tn)],
            [pl.BlockSpec((None, k, tn), lambda j, t, te: (te[t], 0, j))],
            [((held, k, n), x.dtype)], (n // tn, total // tm),
            scratch=[pltpu.VMEM((k, tn), jnp.float32)], reduces=True)[0]

    def forward(xs, rw, wg, wu, wd, tile_expert):
        return (down(gate_up(xs, wg, wu, tile_expert), wd, rw, tile_expert),)

    def backward(xs, rw, wg, wu, wd, tile_expert, gy):
        held = wg.shape[0]
        aw, dg, du, d_rw = gate_up_again(xs, gy, wg, wu, wd, rw, tile_expert)
        return (d_rows(dg, du, wg, wu, tile_expert), d_rw,
                d_weight(xs, dg, tile_expert, held),
                d_weight(xs, du, tile_expert, held),
                d_weight(aw, gy, tile_expert, held))

    return types.SimpleNamespace(
        gate_up=gate_up, down=down, gate_up_again=gate_up_again,
        d_rows=d_rows, d_weight=d_weight, forward=forward, backward=backward)


def _pass(*operands, tm, backward, interpret):
    kernels = _pallas(tm, interpret)
    return (kernels.backward if backward else kernels.forward)(*operands)


# ---------------------------------------------------------------------------
# the primitives: one meaning each, the lowering picks the spelling


def _forward_avals(xs, rw, wg, wu, wd, tile_expert, **_):
    return (xs.update(weak_type=False,
                      vma=lowering.vma(xs, rw, wg, wu, wd, tile_expert)),)


def _backward_avals(xs, rw, wg, wu, wd, tile_expert, gy, **_):
    vma = lowering.vma(xs, rw, wg, wu, wd, tile_expert, gy)
    return tuple(a.update(weak_type=False, vma=vma)
                 for a in (xs, rw, wg, wu, wd))


_forward_p = lowering.primitive("grouped_swiglu", _forward_avals)
_backward_p = lowering.primitive("grouped_swiglu_backward", _backward_avals)


def forward(xs, rw, w, tile_expert, tm: int):
    """``[aligned rows, hidden]``: the rows' weighted SwiGLU, each through
    its tile's expert (the module's docstring). ``w``: the three stacks
    ``gate_proj``, ``up_proj`` ``[held, hidden, width]`` and ``down_proj
    [held, width, hidden]``; ``rw [aligned rows]`` float32."""
    return _forward_p.bind(xs, rw, w["gate_proj"], w["up_proj"],
                           w["down_proj"], tile_expert, tm=tm)[0]


def backward(xs, rw, w, tile_expert, gy, tm: int):
    """``(d_xs, d_rw, d_w)``: the gradients of ``<gy, forward(xs, rw, w)>``."""
    dx, drw, dwg, dwu, dwd = _backward_p.bind(
        xs, rw, w["gate_proj"], w["up_proj"], w["down_proj"], tile_expert,
        gy, tm=tm)
    return dx, drw, {"gate_proj": dwg, "up_proj": dwu, "down_proj": dwd}


def kernels_take(hidden: int, width: int, tm: int) -> bool:
    """Whether these widths and this row tile can lower to the kernels."""
    return all(n % LANES == 0 for n in (hidden, width, tm))


def _takes(avals, *, tm, **_):
    xs, _, wg, wu, wd = avals[:5]
    return (xs.dtype == wg.dtype == wu.dtype == wd.dtype
            and kernels_take(xs.shape[1], wg.shape[2], tm))


def _kernel_spelling(*, interpret, tm, backward):
    return lowering.jitted(
        "experts_backward" if backward else "experts_forward", _pass, tm=tm,
        backward=backward, interpret=interpret)


def _xla_spelling(*, tm, backward):
    return functools.partial(_backward_xla if backward else _forward_xla,
                             tm=tm)


def _labels(*, backward, **_):
    return [{"product": product}
            for product in PRODUCTS["backward" if backward else "forward"]]


lowering.register(_forward_p, _backward_p, rule=_takes,
                  kernel=_kernel_spelling, xla=_xla_spelling,
                  counter="expert_lowerings", labels=_labels)
