"""How a sparse model's tokens fall on the experts this chip holds.

The decoder's sparse MLP (``models/decoder.py:SparseMLP``) sows, into the
collection ``expert_stats``, the number of routed slots that fell on each
held expert and every token's chosen experts; both are free unless a caller
opens the collection. :func:`record_expert_load` runs one forward of a batch
with it open and sets two gauges in a metrics registry:

``expert_load_max_over_mean``
    the fullest held expert's slots over the mean of the held experts',
    in the layer where that ratio is worst (1.0 = perfectly even);
``held_slot_share``
    slots that fell on held experts over all routed slots (held /
    published in expectation, e.g. 8 / 256).
"""
from __future__ import annotations

import numpy as np

COLLECTION = "expert_stats"


def stacked_stats(sown) -> dict:
    """``{"held_counts": [layers, held], "top_experts": [layers, tokens,
    k]}`` from the collections a forward returned, the sparse layers in
    depth order; ``{}`` where the model sowed no such statistics."""
    import jax
    import jax.numpy as jnp

    found = {"held_counts": [], "top_experts": []}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            sown.get(COLLECTION, {})):
        keys = [getattr(k, "key", None) for k in path]
        depth = next((int(k.rpartition("_")[2]) for k in keys
                      if isinstance(k, str) and k.startswith("layers_")), 0)
        for name in found:
            if name in keys:
                found[name].append((depth, leaf))
    if not found["held_counts"]:
        return {}
    return {name: jnp.stack([leaf for _, leaf in
                             sorted(leaves, key=lambda t: t[0])])
            for name, leaves in found.items()}


def expert_stats(apply_fn, params, x) -> dict:
    """:func:`stacked_stats` of one forward of ``x`` through ``apply_fn``
    (eval mode)."""
    _, sown = apply_fn(params, x, train=False, rng=None,
                       mutable=[COLLECTION])
    return stacked_stats(sown)


def record_expert_load(algo, params, registry) -> dict:
    """Gauges of the first training batch of client 0 (``hp.batch_size``
    rows) through ``algo``'s model; ``{}`` and no gauge for a model without
    experts. Returns what it set."""
    import jax

    x = algo.data.x_train[0, :algo.hp.batch_size]
    stats = jax.jit(lambda p, x: expert_stats(algo.apply_fn, p, x))(params, x)
    return set_expert_load(stats, registry)


def set_expert_load(stats: dict, registry) -> dict:
    """The two gauges of :func:`stacked_stats`' ``stats`` set in
    ``registry``; ``{}`` and no gauge where ``stats`` is empty. Returns what
    it set."""
    if not stats:
        return {}
    counts = np.asarray(stats["held_counts"], np.float64)     # [L, held]
    slots = np.asarray(stats["top_experts"]).shape
    out = {
        "expert_load_max_over_mean": float(np.max(
            counts.max(axis=1) / np.maximum(counts.mean(axis=1), 1e-9))),
        "held_slot_share": float(counts.sum() / (slots[0] * slots[1]
                                                 * slots[2])),
    }
    for name, value in out.items():
        registry.gauge(name).set(value)
    return out
