"""The held experts' SwiGLU on tile-aligned groups (ops/grouped_mlp.py,
ISSUE 38): one meaning, two lowerings.

* the layout: every tile one expert's, every expert a tile, the last row
  empty, a slot's place and a row's slot each other's inverse;
* ``decoder.routed_part`` on the Pallas kernels, interpreted (the spelling one
  TPU runs), against a plain loop over the held experts: the routed sum and
  the gradients of the tokens, the three weight stacks and the slot weights,
  at the three cells' ``(hidden, width, held, top_k)`` with the row count cut,
  and over the routing's edges at a small lane-aligned shape;
* the kernels against XLA's spelling on the same layout, product by product;
* what selects the lowering: the target, the widths and the row tile, each
  product counted in ``expert_lowerings``.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.models import decoder
from neuroimagedisttraining_tpu.ops import grouped_mlp as gm
from neuroimagedisttraining_tpu.ops import lowering

TM = 128
COUNTER = "expert_lowerings"


def counted(spelling, passes=("forward", "backward")):
    return {f"pass={p},product={product},spelling={spelling}": 1.0
            for p in passes for product in gm.PRODUCTS[p]}


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """The TPU's lowering rule, interpreted, in the CPU's place, at a row
    tile the kernels take (the chunks here are too small for the rule of
    shape to give one)."""
    monkeypatch.setattr(gm, "row_tile", lambda rows, held: TM)
    with lowering.interpreted(gm._forward_p, gm._backward_p):
        yield


def close(got, want, tol=1e-4):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(1.0, np.max(np.abs(want)))


def sorted_slots(local, held):
    local = jnp.asarray(local, jnp.int32)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    return jnp.argsort(local, stable=True), sizes


# ---------------------------------------------------------------------------
# the layout


@pytest.mark.parametrize("lo,rows,tm", [(0, 64, 8), (64, 64, 8), (0, 256, 128),
                                        (128, 128, 128), (0, 96, 16)])
def test_every_tile_one_experts_every_slot_one_row(lo, rows, tm):
    held, top_k, slots = 4, 2, 256
    local = np.random.default_rng(lo + rows).integers(0, held + 3, slots)
    local = np.minimum(local, held)
    local[local == 1] = held                  # an expert with no slot
    order, sizes = sorted_slots(local, held)
    ends = jnp.cumsum(sizes)
    tile_expert, slot_of, token_of, place = gm.aligned_layout(
        lo, rows, tm, order, ends, top_k)
    total = gm.aligned_rows(rows, held, tm)
    assert tile_expert.shape == (total // tm,) and slot_of.shape == (total,)
    te = np.asarray(tile_expert)
    assert (np.diff(te) >= 0).all() and set(te) == set(range(held))
    slot_of, token_of = np.asarray(slot_of), np.asarray(token_of)
    assert slot_of[-1] == slots                 # the last row is empty
    filled = slot_of < slots
    np.testing.assert_array_equal(token_of[filled], slot_of[filled] // top_k)
    assert (token_of[~filled] == slots // top_k).all()
    # a filled row holds a slot of its tile's expert, each slot of the
    # chunk once
    np.testing.assert_array_equal(local[slot_of[filled]],
                                  np.repeat(te, tm)[filled])
    in_chunk = np.asarray(order)[lo:min(lo + rows, int(ends[-1]))]
    assert sorted(slot_of[filled]) == sorted(in_chunk)
    # ... and a slot's place is that row, the last row for every other slot
    position = np.empty(slots, np.int64)
    position[np.asarray(order)] = np.arange(slots)
    rows_of = np.asarray(place(jnp.asarray(position)))
    np.testing.assert_array_equal(slot_of[rows_of[in_chunk]], in_chunk)
    outside = np.setdiff1d(np.arange(slots), in_chunk)
    assert (rows_of[outside] == total - 1).all()


def test_the_row_tile_follows_the_chunks_shape():
    # lfm2_8b_a1b_fed.longctx and keye_vl2_fed.longctx: aligned groups
    assert gm.row_tile(65536, 8) == 512 and gm.row_tile(65536, 16) == 512
    assert gm.aligned_rows(65536, 8, 512) == 69632
    # laguna_s21_fed.train's chunk (10,240 slots of 81,920) and the tiny
    # presets': aligning would add more than an eighth; rows as sorted
    assert gm.row_tile(10240, 8) is None and gm.row_tile(128, 4) is None
    assert gm.row_tile(32768, 8) == 512 and gm.row_tile(32767, 8) is None
    assert gm.kernels_take(2048, 1792, 512)
    assert not gm.kernels_take(2048, 1800, 512)
    assert not gm.kernels_take(2048, 1792, 8)


# ---------------------------------------------------------------------------
# routed_part on the kernels against a plain loop over the held experts


def plain(tokens, w, slot_weight, local, top_k):
    """Every slot through every held expert, the slot's own kept."""
    x = jnp.repeat(tokens, top_k, axis=0)
    total = jnp.zeros_like(x)
    for e in range(w["gate_proj"].shape[0]):
        h = jax.nn.silu(x @ w["gate_proj"][e]) * (x @ w["up_proj"][e])
        total += jnp.where(local == e, slot_weight, 0.0)[:, None] * (
            h @ w["down_proj"][e])
    return total.reshape(tokens.shape[0], top_k, -1).sum(axis=1)


def operands(hidden, width, held, tokens, top_k, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    scale = 1.0 / np.sqrt(hidden)
    w = {"gate_proj": jax.random.normal(keys[0], (held, hidden, width)),
         "up_proj": jax.random.normal(keys[1], (held, hidden, width)),
         "down_proj": jax.random.normal(keys[2], (held, width, hidden))}
    return (jax.random.normal(keys[3], (tokens, hidden)),
            jax.tree_util.tree_map(lambda a: a * scale, w),
            jax.random.uniform(keys[4], (tokens * top_k,), minval=0.1))


def against_the_plain_loop(rows, chunks, local, held, top_k, tokens, w,
                           slot_weight, lowerings):
    order, sizes = sorted_slots(local, held)
    local = jnp.asarray(local)
    probe = jnp.cos(jnp.arange(tokens.size, dtype=jnp.float32)).reshape(
        tokens.shape)

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * probe), argnums=(0, 1, 2)))(
                tokens, w, slot_weight)

    out = jax.jit(lambda *a: decoder.routed_part(
        rows, chunks, *a, order, slot_weight, sizes, top_k))(tokens, w)
    # the first chunk and the loop's share one lowering
    assert lowerings() == counted("kernel", ("forward",))
    close(out, plain(tokens, w, slot_weight, local, top_k))
    got = value_and_grads(lambda t, w, sw: decoder.routed_part(
        rows, chunks, t, w, order, sw, sizes, top_k))
    want = value_and_grads(lambda t, w, sw: plain(t, w, sw, local, top_k))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all(), jax.tree_util.keystr(path)
        close(a, b)
    return out, got


# (hidden, width, held, top_k) of lfm2_8b_a1b_fed.longctx,
# keye_vl2_fed.longctx and laguna_s21_fed.train; 32 tokens
CELLS = {"lfm2": (2048, 1792, 8, 4), "keye": (2048, 768, 16, 8),
         "laguna": (3072, 1024, 8, 10)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_widths_against_a_plain_loop(cell, kernels_interpreted,
                                               lowerings):
    """The published widths (their column tiles: 896 of 1792, 768 whole,
    512 of 1024 and the rest), the row count cut to 32 tokens: one chunk of
    128 slots that the held experts' share of the slots half fills."""
    hidden, width, held, top_k = CELLS[cell]
    tokens = 32
    rng = np.random.default_rng(held)
    # each token's slots on distinct experts of 2 * held, half of them held
    local = np.stack([rng.permutation(2 * held)[:top_k]
                      for _ in range(tokens)]).reshape(-1)
    local = np.minimum(local, held)
    against_the_plain_loop(128, -(-tokens * min(top_k, held) // 128), local,
                           held, top_k, *operands(hidden, width, held, tokens,
                                                  top_k), lowerings)


def edges():
    """``name -> (rows, chunks, local)`` for 128 tokens of 2 slots on 4
    held experts (256 slots, the worst case)."""
    rng = np.random.default_rng(3)
    spread = rng.integers(0, 4, 256)

    def first(count, experts=spread):
        local = np.full(256, 4)
        at = rng.permutation(256)[:count]
        local[at] = experts[:count]
        return local
    return {
        "an_expert_with_no_slot": (128, 2, first(100, np.where(
            spread == 1, 2, spread))),
        "every_slot_on_one_expert": (128, 2, np.full(256, 2)),
        "no_held_slot": (128, 2, np.full(256, 4)),
        "ends_on_a_tile_and_a_chunk": (128, 2, first(128)),
        "ends_on_the_last_chunk": (128, 2, first(256)),
        "a_second_chunk": (128, 2, first(130)),
        "a_last_chunk_of_four": (64, 4, first(200)),
        "one_chunk_of_the_worst_case": (256, 1, first(200))}


@pytest.mark.parametrize("edge", sorted(edges()))
def test_routing_edges_against_a_plain_loop(edge, kernels_interpreted,
                                            lowerings):
    rows, chunks, local = edges()[edge]
    tokens, w, slot_weight = operands(128, 256, 4, 128, 2, seed=1)
    out, (_, (d_tokens, d_w, d_slot_weight)) = against_the_plain_loop(
        rows, chunks, local, 4, 2, tokens, w, slot_weight, lowerings)
    # a slot that is not held moves nothing, an expert with no slot learns
    # nothing
    np.testing.assert_array_equal(
        np.asarray(d_slot_weight)[local == 4], 0.0)
    for e in range(4):
        if not (local == e).any():
            for leaf in jax.tree_util.tree_leaves(d_w):
                np.testing.assert_array_equal(np.asarray(leaf[e]), 0.0)
    if edge == "no_held_slot":
        assert not np.asarray(out).any() and not np.asarray(d_tokens).any()


# ---------------------------------------------------------------------------
# the kernels against XLA's spelling on the same layout


def test_each_pass_on_the_kernels_is_xlas_on_the_same_layout():
    hidden, width, held = 256, 384, 4
    tile_expert = jnp.asarray([0, 0, 1, 2, 2, 3, 3], jnp.int32)
    total = TM * tile_expert.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    xs, gy = (jax.random.normal(k, (total, hidden)) for k in keys[:2])
    rw = jax.random.uniform(keys[2], (total,))
    wg, wu = (jax.random.normal(k, (held, hidden, width)) * 0.1
              for k in keys[3:5])
    wd = jax.random.normal(keys[5], (held, width, hidden)) * 0.1
    forward, backward = (
        gm._kernel_spelling(interpret=True, tm=TM, backward=b)
        for b in (False, True))
    args = (xs, rw, wg, wu, wd, tile_expert)
    close(forward(*args)[0], gm._forward_xla(*args, tm=TM)[0])
    for got, want in zip(backward(*args, gy),
                         gm._backward_xla(*args, gy, tm=TM)):
        close(got, want)


# ---------------------------------------------------------------------------
# what selects the lowering


def lowered_grad_text(hidden, width, rows, platform, dtype=jnp.bfloat16):
    held, top_k, tokens = 4, 2, rows // 2
    w = {"gate_proj": jnp.zeros((held, hidden, width), dtype),
         "up_proj": jnp.zeros((held, hidden, width), dtype),
         "down_proj": jnp.zeros((held, width, hidden), dtype)}
    order, sizes = sorted_slots(np.arange(tokens * top_k) % (held + 1), held)

    def loss(tokens, w, slot_weight):
        return decoder.routed_part(rows, 1, tokens, w, order, slot_weight,
                                   sizes, top_k).astype(jnp.float32).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        jnp.zeros((tokens, hidden), dtype), w,
        jnp.zeros((tokens * top_k,), jnp.float32)).lower(
            lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize("hidden,width,rows,platform,spelling", [
    (256, 384, 16384, "tpu", "kernel"), (256, 384, 16384, "cpu", "xla"),
    # widths that are no multiple of 128 lanes
    (256, 192, 16384, "tpu", "xla"), (64, 384, 16384, "tpu", "xla"),
    (32, 16, 16384, "cpu", "xla"),
    # a chunk too small to align its groups: its rows as they are sorted,
    # the primitives not bound at all
    (256, 384, 8192, "tpu", None), (256, 384, 8192, "cpu", None)],
    ids=lambda v: str(v))
def test_target_and_shapes_select_the_lowering(hidden, width, rows, platform,
                                               spelling, lowerings):
    text = lowered_grad_text(hidden, width, rows, platform)
    # the forward's two kernels are dead in a gradient; the backward's five
    # are one jitted function (one chunk holds the worst case: no loop)
    assert text.count("tpu_custom_call") == (
        5 if spelling == "kernel" else 0)
    assert text.count("call @experts_backward") == (spelling == "kernel")
    assert lowerings() == (counted(spelling, ("backward",)) if spelling
                           else {})
    # rows of ``hidden`` numbers are scattered on the sorted path alone
    updates = [types.split(", ")[-1] for types in re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \(([^)]*)\) ->', text, flags=re.S)]
    assert (f"tensor<{rows}x{hidden}xbf16>" in updates) == (spelling is None)
