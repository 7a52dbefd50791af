"""The selecting attention's choice of keys (ops/index_select.py): one
meaning, two lowerings.

* the Pallas kernel, interpreted (the spelling one TPU runs, at small
  tiles), against XLA's spelling (``decoder._selection_blocks``: the
  blocks of ``decoder.select_keys(decoder.index_scores(...))``, padded and
  joined): the int8 masks must be EQUAL, bit for bit, over distinct scores,
  exact ties at the cut (the ReLUs' zeros; small integers; rows with every
  score equal), rows with fewer visible keys than ``topk``, tiles wholly
  under ``topk``, a batch of two and sequences of several tiles;
* what selects the lowering: the target, the partitioner and the shapes,
  each lowering counted in ``selection_lowerings``;
* the selecting decoder on the interpreted kernel against the decoder on
  the blocks.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.models import decoder
from neuroimagedisttraining_tpu.ops import index_select as isel
from neuroimagedisttraining_tpu.ops import lowering

# (query tile, key chunk, column block, rows a count) for the interpreter
TILES = dict(_TQ=64, _TC=128, _TO=256, _ROWS=32)
COUNTER = "selection_lowerings"


@pytest.fixture
def kernel_interpreted(monkeypatch):
    """The TPU's lowering rule, interpreted at small tiles, in the CPU's
    place."""
    for name, value in TILES.items():
        monkeypatch.setattr(isel, name, value)
    with lowering.interpreted(isel._select_p):
        yield


def select(q_idx, w_idx, k_idx, topk, block=128):
    """The op as the decoder binds it, in a program of its own."""
    return jax.jit(lambda q, w, k: isel.index_select(
        q, w, k, topk, decoder._selection_blocks, block))(q_idx, w_idx, k_idx)


def operands(batch, seq, heads=4, width=64, *, seed=0, kind="normal"):
    """``q_idx`` and ``k_idx`` bfloat16, ``w_idx`` float32, drawn so that
    the scores are ``kind``:

    * ``normal``: distinct;
    * ``relu_zeros``: half the keys score an exact zero in every head (the
      ReLUs'), so that the cut falls among them in every row that sees
      fewer than ``2 topk`` keys;
    * ``integers``: queries and keys of -1, 0 and 1, weights 1: a few
      dozen values, each shared by many keys at every cut;
    * ``zero_weights``: the rows of the second quarter weigh every head 0:
      every key of such a row scores 0 (signed zeros both ways)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (batch, seq, heads, width))
    k = jax.random.normal(keys[1], (batch, seq, width))
    w = jax.random.normal(keys[2], (batch, seq, heads))
    if kind == "relu_zeros":
        q = jnp.abs(q)
        k = jnp.where((jnp.arange(seq) % 2 == 0)[None, :, None],
                      -jnp.abs(k), jnp.abs(k))
        w = jnp.abs(w)
    elif kind == "integers":
        q, k = jnp.round(q), jnp.round(k)
        q, k = jnp.clip(q, -1, 1), jnp.clip(k, -1, 1)
        w = jnp.ones_like(w)
    elif kind == "zero_weights":
        w = w.at[:, seq // 4:seq // 2].set(0.0)
    return q.astype(jnp.bfloat16), w, k.astype(jnp.bfloat16)


CASES = {
    # (batch, sequence, topk, how the scores are drawn)
    "distinct_scores": (1, 512, 128, "normal"),
    "relu_zeros_at_the_cut": (1, 512, 128, "relu_zeros"),
    "integer_scores_tied_at_every_cut": (1, 512, 128, "integers"),
    "rows_with_every_score_equal": (1, 512, 128, "zero_weights"),
    # rows 256-299 of a scored tile see fewer keys than topk
    "rows_with_fewer_keys_than_topk": (1, 512, 300, "normal"),
    # the first four tiles end within topk: none is scored
    "tiles_wholly_under_topk": (1, 512, 256, "relu_zeros"),
    "every_tile_under_topk": (1, 512, 512, "normal"),
    "batch_of_two": (2, 512, 128, "relu_zeros"),
    "several_tiles_and_column_blocks": (1, 1024, 192, "integers"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_mask_is_the_blocks_mask(case, lowerings):
    batch, seq, topk, kind = CASES[case]
    ops = operands(batch, seq, kind=kind)
    want = np.asarray(select(*ops, topk))
    assert lowerings() == {"spelling=blocks": 1.0}
    with pytest.MonkeyPatch.context() as mp:
        for name, value in TILES.items():
            mp.setattr(isel, name, value)
        got = np.asarray(jax.jit(functools.partial(
            isel._select_pallas, topk=topk, interpret=True))(*ops))
    assert got.dtype == np.int8 and got.shape == (batch, seq, seq)
    np.testing.assert_array_equal(got, want)
    # every row keeps min(topk, visible) keys and nothing above the diagonal
    np.testing.assert_array_equal(
        got.sum(-1), np.broadcast_to(np.minimum(np.arange(seq) + 1, topk),
                                     (batch, seq)))
    assert not np.triu(got, 1).any()


def test_crowded_rows_keep_their_lowest_equal_keys(kernel_interpreted):
    """A row whose every score is 0 keeps its first ``topk`` keys, and a
    row under the ReLUs' zeros keeps every key that scores above 0 and the
    lowest zeros it still wants."""
    seq, topk = 512, 128
    ops = operands(1, seq, kind="zero_weights")
    got = np.asarray(select(*ops, topk))
    for row in (seq // 4, seq // 2 - 1):
        np.testing.assert_array_equal(got[0, row], np.arange(seq) < topk)
    q, w, k = operands(1, seq, kind="relu_zeros")
    got = np.asarray(select(q, w, k, topk))
    scores = np.asarray(decoder.index_scores(q, w, k))[0]
    row = 200       # 201 keys visible, 100 of them above 0
    positive = (scores[row] > 0) & (np.arange(seq) <= row)
    zeros = np.flatnonzero((scores[row] == 0) & (np.arange(seq) <= row))
    want = positive.copy()
    assert positive.sum() == 100
    want[zeros[:topk - positive.sum()]] = True
    np.testing.assert_array_equal(got[0, row], want)


def test_the_op_on_the_kernel_is_the_op_on_the_blocks(kernel_interpreted,
                                                      lowerings):
    """Bound as the decoder binds it, through the TPU's lowering rule: the
    kernel's mask, counted once."""
    ops = operands(2, 512, kind="integers")
    got = np.asarray(select(*ops, 128))
    assert lowerings() == {"spelling=kernel": 1.0}
    want = np.asarray(decoder._selection_blocks(*ops, 128, 128))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# what selects the lowering


def lowered_text(seq, width, platform, heads=16):
    q = jnp.zeros((1, seq, heads, width), jnp.bfloat16)
    w = jnp.zeros((1, seq, heads), jnp.float32)
    k = jnp.zeros((1, seq, width), jnp.bfloat16)
    return jax.jit(lambda q, w, k: isel.index_select(
        q, w, k, 2048, decoder._selection_blocks, 1024)).trace(q, w, k).lower(
            lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize("seq,width,platform,spelling", [
    (16384, 64, "tpu", "kernel"),
    (4096, 128, "tpu", "kernel"),
    (16384, 64, "cpu", "blocks"),
    # the sequence does not divide into the mask's column blocks
    (4096 + 1024, 64, "tpu", "blocks"),
    # the tiny presets' indexer: 8 wide
    (4096, 8, "tpu", "blocks"),
    # a row of the key order past the VMEM scratch's budget
    (65536, 64, "tpu", "blocks")],
    ids=lambda v: str(v))
def test_target_and_shapes_select_the_lowering(seq, width, platform, spelling,
                                               lowerings):
    text = lowered_text(seq, width, platform)
    assert text.count("tpu_custom_call") == (spelling == "kernel")
    assert lowerings() == {f"spelling={spelling}": 1.0}


@pytest.mark.parametrize("seq,width,takes", [
    (16384, 64, True), (2048, 128, True), (16384, 8, False),
    (16384 + 128, 64, False), (65536, 64, False)])
def test_kernel_takes_by_shape_alone(seq, width, takes):
    assert isel.kernel_takes(seq, width) is takes


# ---------------------------------------------------------------------------
# the whole selecting decoder on the kernel


def selecting_decoder(seq=256):
    """``keye_tiny`` (an indexer of 4 heads 8 wide, ``topk`` 8) at 256
    tokens, weights scaled so that the scores spread."""
    share = decoder.Share(layers=2, expert_shards=4, tensor_shards=2,
                          vocab_shards=4)
    cfg = decoder.held_config("keye_tiny", share)
    model = decoder.Decoder(decoder._freeze(cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, seq), 0,
                                cfg["vocab_size"])
    params = jax.tree_util.tree_map(
        lambda a: a * 8.0 if a.ndim > 1 else a,
        model.init(jax.random.PRNGKey(0), tokens[:, :16])["params"])

    def loss_and_kept(p):
        logits, sown = model.apply({"params": p}, tokens,
                                   mutable=[decoder.EXPERT_STATS])
        kept = jax.tree_util.tree_leaves(
            sown[decoder.EXPERT_STATS], is_leaf=lambda x: isinstance(
                x, tuple))
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1)
                        - logits[..., 0]), kept
    return params, jax.jit(jax.value_and_grad(loss_and_kept, has_aux=True))


def test_the_decoder_on_the_kernel_is_the_decoder_on_the_blocks(
        lowerings, monkeypatch):
    """The tiny preset's indexer is 8 wide, which the kernel's rule leaves
    to the blocks: with the rule's width patched to 8 the decoder's loss,
    gradients and selections on the kernel (interpreted at small tiles, in
    the CPU's place) equal those on the blocks."""
    for name, value in dict(TILES, _WIDTH=8).items():
        monkeypatch.setattr(isel, name, value)
    with lowering.interpreted(isel._select_p):
        params, step = selecting_decoder()
        (loss, kept), grads = step(params)
    # the program's two layers share one lowering; init's 16 tokens do not
    # tile
    assert lowerings() == {"spelling=kernel": 1.0, "spelling=blocks": 1.0}
    (want_loss, want_kept), want = selecting_decoder()[1](params)
    assert float(loss) == float(want_loss)
    for a, b in zip(jax.tree_util.tree_leaves(kept),
                    jax.tree_util.tree_leaves(want_kept)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
