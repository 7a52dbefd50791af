"""How a sparse model's tokens fall on the experts this chip holds.

The decoder's sparse MLP (``models/decoder.py:SparseMLP``) sows, into the
collection ``expert_stats``, the number of routed slots that fell on each
held expert, every token's chosen experts and, where the router chooses by
its scores plus a selection bias, the experts the scores alone would choose;
all free unless a caller opens the collection. :func:`record_expert_load`
runs one forward of a batch with it open and sets three gauges in a metrics
registry (and a fourth where a layer selects its keys: :mod:`.selection`;
two more where a layer has a state-space mixer: :mod:`.ssm_carry`):

``expert_load_max_over_mean``
    the fullest held expert's slots over the mean of the held experts',
    in the layer where that ratio is worst (1.0 = perfectly even);
``held_slot_share``
    slots that fell on held experts over all routed slots (held /
    published in expectation, e.g. 8 / 256);
``expert_bias_swap_share``
    the share of (token, sparse layer) pairs whose chosen experts are not
    the top-k of the scores without the bias: a witness that the bias path
    is live on this cohort, not a lever of speed (the bias is drawn from the
    seed, and the expert layer computes a fixed chunk whatever the routing).
    Set only where the router has a bias; no gauge for any other model.
"""
from __future__ import annotations

import numpy as np

COLLECTION = "expert_stats"


def sown_by_depth(sown, name: str) -> list:
    """What the decoder's layers sowed under ``name``, in depth order."""
    import jax

    found = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            sown.get(COLLECTION, {})):
        keys = [getattr(k, "key", None) for k in path]
        if name in keys:
            found.append((next((int(k.rpartition("_")[2]) for k in keys
                                if isinstance(k, str)
                                and k.startswith("layers_")), 0), leaf))
    return [leaf for _, leaf in sorted(found, key=lambda t: t[0])]


def stacked_stats(sown) -> dict:
    """``{"held_counts": [layers, held], "top_experts": [layers, tokens,
    k]}`` from the collections a forward returned, the sparse layers in
    depth order, with ``"unbiased_experts"`` (as ``top_experts``) where the
    router has a selection bias; ``{}`` where the model sowed no such
    statistics."""
    import jax.numpy as jnp

    found = {name: sown_by_depth(sown, name)
             for name in ("held_counts", "top_experts", "unbiased_experts")}
    if not found["held_counts"]:
        return {}
    return {name: jnp.stack(leaves) for name, leaves in found.items()
            if leaves}


def record_expert_load(algo, params, registry) -> dict:
    """Gauges of the first training batch of client 0 (``hp.batch_size``
    rows) through ``algo``'s model, from ONE forward with the collection
    open: the three above and, where a layer selects its keys,
    ``selected_key_share`` (:mod:`.selection`); where one has a state-space
    mixer, ``ssm_chunk_carry`` and ``ssm_dt_mean`` (:mod:`.ssm_carry`).
    ``{}`` and no gauge for a model that sows nothing. Returns what it
    set."""
    import jax

    from .selection import (key_share, set_selected_key_share,
                            stacked_selection)
    from .ssm_carry import carry_stats, set_ssm_carry

    def gauges(p, x):
        _, sown = algo.apply_fn(p, x, train=False, rng=None,
                                mutable=[COLLECTION])
        kept = stacked_selection(sown)
        return (stacked_stats(sown),
                None if kept is None else key_share(kept), carry_stats(sown))

    x = algo.data.x_train[0, :algo.hp.batch_size]
    stats, share, carry = jax.jit(gauges)(params, x)
    return {**set_expert_load(stats, registry),
            **set_selected_key_share(share, registry),
            **set_ssm_carry(carry, registry)}


def set_expert_load(stats: dict, registry) -> dict:
    """The gauges of :func:`stacked_stats`' ``stats`` set in ``registry``
    (``expert_bias_swap_share`` only where the model sowed
    ``unbiased_experts``); ``{}`` and no gauge where ``stats`` is empty.
    Returns what it set."""
    if not stats:
        return {}
    counts = np.asarray(stats["held_counts"], np.float64)     # [L, held]
    chosen = np.asarray(stats["top_experts"])
    slots = chosen.shape
    out = {
        "expert_load_max_over_mean": float(np.max(
            counts.max(axis=1) / np.maximum(counts.mean(axis=1), 1e-9))),
        "held_slot_share": float(counts.sum() / (slots[0] * slots[1]
                                                 * slots[2])),
    }
    if "unbiased_experts" in stats:
        out["expert_bias_swap_share"] = float(np.mean(np.any(
            np.sort(chosen, -1) != np.sort(stats["unbiased_experts"], -1),
            axis=-1)))
    for name, value in out.items():
        registry.gauge(name).set(value)
    return out
