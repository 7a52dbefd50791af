"""The attention product under a mask (ops/masked_attention.py), the mask
data of the run (a selecting layer's) or a rule of the positions (a full or
a sliding layer's): one meaning, two lowerings.

* the Pallas kernels, interpreted (the spelling one TPU runs), against
  ``decoder._attend`` under the same mask: the output and the gradients of
  ``q``, ``k`` and ``v``, for one query head a KV head and for eight, under
  a selection whose rows leave their first key tiles empty and under the
  plain causal mask;
* the same kernels under a rule (no mask operand) against XLA's spelling of
  the rule, ``decoder.full_attention`` and ``decoder.window_attention`` as
  the CPU lowers them: 1, 6 and 9 heads a KV head, the causal rule and two
  bands, so that tiles wholly inside, on the edge of and wholly outside the
  seen region all occur;
* what selects the lowering: the target, the head width and whether the
  sequence divides into the kernels' tiles, each choice counted in
  ``attention_lowerings``;
* the whole selecting decoder with the kernels forced (interpreted) against
  the same decoder on XLA's spelling: logits and gradients.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from neuroimagedisttraining_tpu.models import decoder, make_apply_fn
from neuroimagedisttraining_tpu.ops import lowering
from neuroimagedisttraining_tpu.ops import masked_attention as ma

SEQ, WIDTH, TILES = 512, 128, (128, 128)
COUNTER = "attention_lowerings"


def nearest_keys(topk):
    """``select_keys`` under scores that fall with the distance: a query
    keeps the ``topk`` keys before it. Past ``topk + 128`` a row's first key
    tile is empty, and the tile under the diagonal's neighbour is empty for
    every row of its query tile."""
    pos = jnp.arange(SEQ, dtype=jnp.float32)
    scores = -jnp.abs(pos[:, None] - pos[None, :])[None]
    return decoder.select_keys(scores, 0, topk)


def causal():
    pos = jnp.arange(SEQ)
    return (pos[None, :] <= pos[:, None])[None]


MASKS = {"selection": functools.partial(nearest_keys, 64), "causal": causal}


def case(group, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (1, SEQ, 2, group, WIDTH))
    k, v = (jax.random.normal(key, (1, SEQ, 2, WIDTH)) for key in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], q.shape)


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert np.max(np.abs(got - want)) <= tol * max(1.0, np.max(np.abs(want)))


def test_the_selection_leaves_tiles_empty():
    """What the cases below are for: a row whose first key tiles are empty,
    and a tile under the diagonal that no row of its query tile selects."""
    keep = np.asarray(nearest_keys(64))[0]
    assert keep.sum(axis=1).min() >= 1
    assert not keep[300, :128].any() and keep[300, 128:].any()
    assert not keep[384:, :128].any()


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("group", [1, 8])
def test_kernels_against_attend_under_the_same_mask(group, mask):
    q, k, v, g_out = case(group)
    seen = MASKS[mask]()
    keep = seen.astype(jnp.int8)
    want, vjp = jax.vjp(lambda *a: decoder._attend(*a, seen), q, k, v)
    out, lse = ma._forward_pallas(q, k, v, keep, tiles=TILES, interpret=True)
    close(out, want)
    close(lse, ma._forward_xla(q, k, v, keep, attend=decoder._attend)[1])
    got = ma._backward_pallas(q, k, v, keep, out, lse, g_out, tiles=TILES,
                              interpret=True)
    for a, b in zip(got, vjp(g_out)):
        close(a, b)


def test_a_row_that_keeps_nothing_reads_zero_not_nan():
    """No caller sends one (every query sees itself); the kernels must not
    spread a NaN over the tile if one does."""
    q, k, v, g_out = case(1)
    keep = causal().astype(jnp.int8).at[:, 200].set(0)
    out, lse = ma._forward_pallas(q, k, v, keep, tiles=TILES, interpret=True)
    grads = ma._backward_pallas(q, k, v, keep, out, lse, g_out, tiles=TILES,
                                interpret=True)
    assert all(bool(jnp.isfinite(a).all()) for a in (out, lse) + grads)
    assert not np.asarray(out[:, 200]).any()


# 0: causal; a band as wide as a key tile (a query tile sees two key tiles,
# none wholly) and twice that (three, the middle one wholly)
RULES = {"causal": 0, "band_of_one_tile": TILES[1],
         "band_of_two_tiles": 2 * TILES[1]}


def test_the_rules_leave_tiles_of_every_kind():
    """What the cases below are for: under each rule the 4 x 4 tiles hold
    one wholly unseen and one cut by an edge; the causal rule and the wider
    band also one wholly seen, and the bands one unseen UNDER the diagonal
    (the grid does not step over it)."""
    pos = np.arange(SEQ)
    dist = pos[:, None] - pos[None, :]
    for name, window in RULES.items():
        seen = (dist >= 0) & (dist < (window or SEQ))
        tiles = seen.reshape(4, TILES[0], 4, TILES[1]).transpose(0, 2, 1, 3)
        full = tiles.all(axis=(2, 3))
        none = ~tiles.any(axis=(2, 3))
        assert none.any() and (~full & ~none).any(), name
        assert full.any() == (name != "band_of_one_tile"), name
        assert np.tril(none).any() == (window != 0), name
        # the grid steps over the key tiles a query tile can see (the query
        # tiles that see a key tile), not over all four
        for keys_outer in (False, True):
            grid = ma._specs(pl, (1, SEQ, 2, 1, WIDTH), TILES, window or SEQ,
                             keys_outer)[-1]
            assert grid == (1, 2, 4, window // TILES[1] + 1 if window else 4)


@pytest.mark.parametrize("rule", sorted(RULES))
@pytest.mark.parametrize("group", [1, 6, 9])
def test_kernels_under_a_rule_against_xlas_spelling_of_it(group, rule):
    q, k, v, g_out = case(group)
    window = RULES[rule]

    def spelled(q, k, v):
        if window:
            return decoder.window_attention(q, k, v, window)
        return decoder.full_attention(q, k, v)
    want, vjp = jax.vjp(spelled, q, k, v)
    out, lse = ma._forward_pallas(q, k, v, tiles=TILES, window=window,
                                  interpret=True)
    close(out, want)
    got = ma._backward_pallas(q, k, v, None, out, lse, g_out, tiles=TILES,
                              window=window, interpret=True)
    for a, b in zip(got, vjp(g_out)):
        close(a, b)


# ---------------------------------------------------------------------------
# what selects the lowering


def lowered_grad_text(seq, width, platform, kind="selected",
                      dtype=jnp.bfloat16):
    q = jnp.zeros((1, seq, 1, 8, width), dtype)
    k = jnp.zeros((1, seq, 1, width), dtype)
    keep = jnp.ones((1, seq, seq), jnp.int8)

    def loss(q, k, v):
        out = {"selected": lambda: ma.masked_attention(
                   q, k, v, keep, decoder._attend, "selected"),
               "full": lambda: decoder.full_attention(q, k, v),
               "window": lambda: decoder.window_attention(q, k, v, 512)
               }[kind]()
        return out.astype(jnp.float32).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, k, k).lower(
        lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize("seq,width,platform,spelling,kind", [
    (1024, 128, "tpu", "kernel", "selected"),
    (1024, 256, "tpu", "kernel", "selected"),
    (1024, 128, "cpu", "xla", "selected"),
    # does not divide into the tiles
    (1024 + 128, 128, "tpu", "xla", "selected"),
    (192, 128, "tpu", "xla", "selected"), (1024, 64, "tpu", "xla", "selected"),
    (1024, 128, "tpu", "kernel", "full"),
    (1024, 128, "tpu", "kernel", "window"),
    (1024, 128, "cpu", "xla", "full"), (1024, 128, "cpu", "xla", "window"),
    # lfm2_8b_a1b's heads: two would share a lane tile
    (1024, 64, "tpu", "xla", "full"), (1024, 64, "tpu", "xla", "window"),
    # a band's narrower tiles divide what the causal rule's do not
    (1024 + 256, 128, "tpu", "xla", "full"),
    (1024 + 256, 128, "tpu", "kernel", "window")],
    ids=lambda v: str(v))
def test_target_and_shapes_select_the_lowering(seq, width, platform, spelling,
                                               kind, lowerings):
    text = lowered_grad_text(seq, width, platform, kind)
    # the forward and the two backward kernels, or none
    assert text.count("tpu_custom_call") == (3 if spelling == "kernel" else 0)
    assert lowerings() == {
        f"kind={kind},pass={p},spelling={spelling}": 1.0
        for p in ("forward", "backward")}


# ---------------------------------------------------------------------------
# the whole decoder on the kernels


def selecting_decoder():
    """``keye_tiny`` with heads of 128, the width the kernels take."""
    share = decoder.Share(layers=2, expert_shards=4, tensor_shards=2,
                          vocab_shards=4)
    cfg = decoder.held_config("keye_tiny", share)
    cfg = dict(cfg, head_dim=WIDTH, rope_scaling=dict(
        cfg["rope_scaling"], mrope_section=[16, 24, 24]))
    model = decoder.Decoder(decoder._freeze(cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0,
                                cfg["vocab_size"])
    params = jax.tree_util.tree_map(
        lambda a: a * 8.0 if a.ndim > 1 else a,
        model.init(jax.random.PRNGKey(0), tokens[:, :16])["params"])
    apply_fn = make_apply_fn(model)

    def loss_and_logits(p):
        logits = apply_fn(p, tokens, train=True, rng=jax.random.PRNGKey(0))
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1)
                        - logits[..., 0]), logits
    return params, jax.jit(jax.value_and_grad(loss_and_logits, has_aux=True))


def test_the_decoder_on_the_kernels_is_the_decoder_on_attend(
        monkeypatch, lowerings):
    """The TPU's lowering rule, interpreted, in the CPU's place, against the
    CPU's own."""
    monkeypatch.setattr(ma, "_TILES", TILES)
    monkeypatch.setattr(ma, "_BAND_TILES", TILES)
    with lowering.interpreted(ma._forward_p, ma._backward_p):
        params, step = selecting_decoder()
        (loss, logits), grads = step(params)
    # a program's layers share one lowering; init's 16 tokens do not tile
    assert lowerings() == {
        "kind=selected,pass=forward,spelling=kernel": 1.0,
        "kind=selected,pass=backward,spelling=kernel": 1.0,
        "kind=selected,pass=forward,spelling=xla": 1.0}
    (want_loss, want_logits), want = selecting_decoder()[1](params)
    close(loss, want_loss)
    close(logits, want_logits)
    flat, want_flat = (dict(jax.tree_util.tree_leaves_with_path(t))
                       for t in (grads, want))
    assert flat.keys() == want_flat.keys()
    for path, leaf in flat.items():
        close(leaf, want_flat[path], tol=1e-4)
    moved = [path for path, leaf in flat.items() if np.asarray(leaf).any()]
    assert any("q_proj" in str(p) and "indexer" not in str(p) for p in moved)
