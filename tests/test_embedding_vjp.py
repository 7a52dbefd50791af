"""The token lookup with a backward of its own (ops/embedding.py, ISSUE 40):
its value and its gradient are ``jnp.take``'s own, bit for bit, at every
width; a width XLA's scatter takes whole is ``jnp.take`` itself (the same
jaxpr); a wider one scatters column slabs behind a ``custom_vjp``."""
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.ops import embedding

ROWS, TOKENS = 48, 40
# the four language cells' widths (2048 twice), and one that no slab divides
WIDTHS = (2048, 3072, 5120, 5000)


def _ids(kind, shape=(1, TOKENS)):
    """Ids into a table of ``ROWS`` rows; row ``ROWS - 1`` never occurs."""
    if kind == "duplicates":       # 40 draws of 12 ids: every id repeats
        return jax.random.randint(jax.random.PRNGKey(5), shape, 0, 12)
    if kind == "distinct":
        return jax.random.permutation(
            jax.random.PRNGKey(6), ROWS - 1)[:TOKENS].reshape(shape)
    assert kind == "all_the_same"
    return jnp.full(shape, 7, jnp.int32)


def _take(table, ids):
    return jnp.take(table, ids, axis=0)


class _Embed(nn.Module):
    """A module whose one leaf is looked up, as ``Decoder``'s is."""
    look: callable
    width: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, ids):
        table = self.param("embed", nn.initializers.normal(1.0),
                           (ROWS, self.width), self.dtype)
        return self.look(table, ids)


def _value_and_gradient(look, wrap, table, ids, g):
    """``look(table, ids)`` and the gradient of ``<g, look(table, ids)>`` by
    the table; ``wrap``: the lookup as it stands, under ``jax.vmap`` over
    three clients (each its own table, ids and cotangent), or inside a
    module under ``nn.remat`` with the decoder's kind of policy."""
    def dot(t, i, c):
        if wrap == "remat":
            module = nn.remat(
                _Embed, policy=jax.checkpoint_policies.save_only_these_names(
                    "attended"))(look, t.shape[1], t.dtype)
            out = module.apply({"params": {"embed": t}}, i)
        else:
            out = look(t, i)
        return jnp.sum(out.astype(jnp.float32) * c.astype(jnp.float32)), out

    grad = jax.grad(dot, has_aux=True)
    if wrap == "vmap":
        grad = jax.vmap(grad)
        table = jnp.stack([table, table * 2, -table])
        ids = jnp.stack([ids, (ids + 3) % (ROWS - 1), ids[:, ::-1]])
        g = jnp.stack([g, g[:, ::-1], g * 0.5])
    return jax.jit(grad)(table, ids, g)


CASES = [(w, d, k, "plain") for w in WIDTHS
         for d in ("bfloat16", "float32")
         for k in ("duplicates", "distinct", "all_the_same")]
CASES += [(w, "bfloat16", "duplicates", wrap) for w in WIDTHS
          for wrap in ("vmap", "remat")]


@pytest.mark.parametrize("width,dtype,kind,wrap", CASES, ids=[
    "-".join(map(str, case)) for case in CASES])
def test_value_and_gradient_are_jnp_takes_bit_for_bit(width, dtype, kind,
                                                      wrap):
    dtype = jnp.dtype(dtype)
    table = jax.random.normal(jax.random.PRNGKey(1), (ROWS, width), dtype)
    g = jax.random.normal(jax.random.PRNGKey(2), (1, TOKENS, width), dtype)
    ids = _ids(kind)
    got_grad, got = _value_and_gradient(embedding.lookup, wrap, table, ids, g)
    want_grad, want = _value_and_gradient(_take, wrap, table, ids, g)
    assert got.dtype == want.dtype == dtype
    assert got_grad.dtype == dtype and got_grad.shape == want_grad.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(got_grad, np.float32),
                                  np.asarray(want_grad, np.float32))
    # a row no token names gets no gradient, a row every token names all of
    # it (in float32: bfloat16 rounds after every addition, as the
    # scatter-add does)
    rows = np.asarray(got_grad, np.float32).reshape(-1, ROWS, width)[0]
    assert not rows[ROWS - 1].any()
    if kind == "all_the_same" and wrap == "plain":
        assert not np.delete(rows, 7, axis=0).any()
        if dtype == jnp.float32:
            np.testing.assert_allclose(
                rows[7], np.asarray(g, np.float32)[0].sum(0), rtol=2e-5,
                atol=2e-5)


@pytest.mark.parametrize("width", [2048, 3072, 5120])
def test_the_tied_heads_leaf_gets_both_uses(width):
    """``tie_word_embeddings``: the table is the head too, and its gradient
    is the sum of the lookup's and the product's."""
    table = jax.random.normal(jax.random.PRNGKey(1), (ROWS, width),
                              jnp.bfloat16) * 0.05
    ids = _ids("duplicates")

    def loss(look, t):
        x = look(t, ids)
        logits = jnp.einsum("bsh,vh->bsv", x, t,
                            preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.log_softmax(logits)[..., 3])

    got = jax.jit(jax.grad(lambda t: loss(embedding.lookup, t)))(table)
    want = jax.jit(jax.grad(lambda t: loss(_take, t)))(table)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # and neither use alone is the whole of it
    head_only = jax.jit(jax.grad(lambda t: loss(
        lambda table, i: jax.lax.stop_gradient(_take(table, i)), t)))(table)
    assert np.abs(np.asarray(got - head_only, np.float32)).max() > 0


@pytest.mark.parametrize("width,slabs", [
    (32, 1), (128, 1), (2048, 1), (3072, 1), (4096, 1), (2560, 2),
    (5120, 2), (5000, 4), (6144, 2)])
def test_one_slab_is_jnp_take_itself_and_more_are_a_custom_vjp(width, slabs,
                                                               lowerings):
    """The rule hangs on the table's width alone. One slab: the jaxpr of the
    lookup and of its gradient are ``jnp.take``'s, character for character.
    More: a ``custom_vjp`` whose backward holds one scatter-add a slab, each
    as wide as its slab, and counts itself."""
    cut = embedding.slabs_of(width)
    assert len(cut) == slabs
    assert [a for a, _ in cut] == [sum(n for _, n in cut[:i])
                                   for i in range(slabs)]
    assert sum(n for _, n in cut) == width
    # every slab is 2^k or 3 * 2^k columns, at most the widest
    for _, n in cut:
        assert n <= embedding._WIDEST
        odd = n // (n & -n)
        assert odd in (1, 3), cut
    table = jax.ShapeDtypeStruct((ROWS, width), jnp.bfloat16)
    ids = _ids("duplicates")

    def programs(look):
        value = jax.make_jaxpr(lambda t: look(t, ids))(table)
        grad = jax.make_jaxpr(jax.grad(lambda t: jnp.sum(
            look(t, ids).astype(jnp.float32))))(table)
        return str(value), str(grad)

    mine = programs(embedding.lookup)
    counted = lowerings("embed_lowerings")
    if slabs == 1:
        assert mine == programs(_take)
        assert "custom_vjp" not in mine[0] + mine[1]
        # ``jnp.take``'s own gradient passes through no code of the module
        assert counted == {"pass=forward,spelling=take": 2.0}
    else:
        assert "custom_vjp" in mine[0]
        widths = [int(w) for w in re.findall(
            rf"bf16\[{ROWS},(\d+)\] = scatter-add", mine[1])]
        assert widths == [n for _, n in cut]
        assert counted == {"pass=forward,spelling=slabs": 2.0,
                           "pass=backward,spelling=slabs": 1.0}
