"""Token shards: the federated data of a language-model job.

``x_train [C, n, S]`` int32 ids of the vocabulary rows a chip holds and
``y_train [C, n, S]`` the next ids; the last position of a sequence has no
next id and carries ``NO_TARGET`` (the loss gives it weight 0; the sequence
is not shortened). One document a sequence, no padding.

``make_token_shards`` is the synthetic stand-in (``--dataset token_shards``):
ids with Zipf marginals and a seeded first-order structure (every id has a
few likely successors), so that training lowers the loss. Real corpora enter
as pre-tokenised arrays through :func:`token_shards`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .types import FederatedData

NO_TARGET = -1
FANOUT = 4          # likely successors of an id
RESTART = 0.1       # share of positions drawn from the marginals afresh


def next_ids(tokens: jax.Array) -> jax.Array:
    """The targets of ``tokens [..., S]``: the ids shifted left by one, the
    last position ``NO_TARGET``."""
    return jnp.concatenate(
        [tokens[..., 1:], jnp.full_like(tokens[..., :1], NO_TARGET)], axis=-1)


def token_shards(x_train, x_test, vocab: int) -> FederatedData:
    """``FederatedData`` over ``[C, n, S]`` id arrays (every row valid)."""
    x_train, x_test = (jnp.asarray(a, jnp.int32) for a in (x_train, x_test))
    count = lambda a: jnp.full((a.shape[0],), a.shape[1], jnp.int32)  # noqa: E731
    return FederatedData(
        x_train=x_train, y_train=next_ids(x_train), n_train=count(x_train),
        x_test=x_test, y_test=next_ids(x_test), n_test=count(x_test),
        class_num=vocab)


def markov_ids(key, rows: int, length: int, vocab: int) -> jax.Array:
    """``[rows, length]`` int32 ids: Zipf marginals (id r with weight
    1 / (r + 1)), and each id followed by one of its ``FANOUT`` seeded
    successors except at a ``RESTART`` share of positions."""
    k_table, k_start, k_steps = jax.random.split(key, 3)
    cdf = jnp.cumsum(1.0 / jnp.arange(1, vocab + 1, dtype=jnp.float32))
    cdf = cdf / cdf[-1]

    def marginal(k, shape):
        return jnp.minimum(jnp.searchsorted(cdf, jax.random.uniform(k, shape)),
                           vocab - 1).astype(jnp.int32)

    table = marginal(k_table, (vocab, FANOUT))
    likely = jnp.log(jnp.asarray([0.6, 0.2, 0.1, 0.1][:FANOUT]))

    def step(cur, k):
        k_next, k_fresh, k_pick = jax.random.split(k, 3)
        follow = table[cur, jax.random.categorical(k_next, likely,
                                                   shape=(rows,))]
        fresh = jax.random.uniform(k_pick, (rows,)) < RESTART
        nxt = jnp.where(fresh, marginal(k_fresh, (rows,)), follow)
        return nxt, nxt

    start = marginal(k_start, (rows,))
    _, rest = jax.lax.scan(step, start, jax.random.split(k_steps, length - 1))
    return jnp.concatenate([start[None], rest], axis=0).T


def make_token_shards(seed: int, n_clients: int, vocab: int,
                      sequence_length: int, train_per_client: int,
                      test_per_client: int = 1, sharding=None
                      ) -> FederatedData:
    """The synthetic token cohort, made by one jitted program on the
    device(s) ``sharding`` names (default: JAX's default device)."""
    rows = train_per_client + test_per_client

    def build(key):
        ids = markov_ids(key, n_clients * rows, sequence_length, vocab)
        ids = ids.reshape(n_clients, rows, sequence_length)
        parts = ids[:, :train_per_client], ids[:, train_per_client:]
        return tuple((x, next_ids(x),
                      jnp.full((n_clients,), x.shape[1], jnp.int32))
                     for x in parts)

    (x, y, n), (xt, yt, nt) = jax.jit(build, out_shardings=sharding)(
        jax.random.PRNGKey(seed))
    return FederatedData(x_train=x, y_train=y, n_train=n, x_test=xt,
                         y_test=yt, n_test=nt, class_num=vocab)
