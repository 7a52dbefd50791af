"""Ring gossip over the clients mesh axis via ``lax.ppermute``.

SURVEY §2.6: the reference's decentralized algorithms exchange models by
explicit peer sends (simulated); on TPU a ring-topology gossip step is two
``ppermute`` rotations over ICI plus a weighted sum — no host, no
materialized N×N adjacency. The general-graph path remains the adjacency
contraction used by DisPFL/DPSGD (``mix_over_clients``); this primitive is
the fast path for the reference's ``cs=ring`` neighborhood
(``dispfl_api.py:207-212``: each client averages itself with its two ring
neighbors) when per-client state is sharded one-client-per-device.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def ring_mix(
    tree: Any,
    mesh: Mesh,
    weights: Tuple[float, float, float] = (1 / 3, 1 / 3, 1 / 3),
    axis_name: str = "clients",
):
    """One gossip round on a ring: ``out_i = w_self*x_i + w_left*x_{i-1}
    + w_right*x_{i+1}`` (indices mod N) for every leaf's leading client
    axis, computed with two ``ppermute`` rotations under ``shard_map``.

    ``weights`` = (self, left-neighbor, right-neighbor); the reference's
    ring average is the default uniform (1/3, 1/3, 1/3)
    (``_benefit_choose`` ring + uniform ``_aggregate_func``,
    ``dpsgd_api.py:169-178``).
    """
    n = mesh.shape[axis_name]
    if n < 3:
        raise ValueError(
            f"ring_mix needs a clients axis of >= 3 (got {n}): with 2 "
            "devices both rotations hit the same neighbor, which doubles "
            "its weight relative to the normalized ring adjacency — use "
            "the adjacency-contraction path for tiny rings")
    w_self, w_left, w_right = weights
    fwd = [(i, (i + 1) % n) for i in range(n)]   # receive from left
    bwd = [(i, (i - 1) % n) for i in range(n)]   # receive from right

    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if getattr(x, "ndim", 0) == 0 or x.shape[0] != n:
            raise ValueError(
                f"leaf {jax.tree_util.keystr(path)} leading axis "
                f"{getattr(x, 'shape', ())} != clients extent {n}")

    # ONE shard_map over the whole pytree (prefix spec): a single traced
    # program with all rotations, instead of a separately-dispatched pair
    # of ppermutes per leaf (dispatch costs ~5-6 ms each on the bench env)
    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name))
    def mix_tree(t):
        def mix_leaf(x):
            from_left = lax.ppermute(x, axis_name, fwd)
            from_right = lax.ppermute(x, axis_name, bwd)
            return w_self * x + w_left * from_left + w_right * from_right

        return jax.tree_util.tree_map(mix_leaf, t)

    return mix_tree(tree)
