"""How much of the causal square a selecting attention keeps.

A decoder layer whose queries attend the keys a learned indexer selects
(``models/decoder.py:selected_attention``) sows its selection ``[B, S, S]``
into the collection ``expert_stats``, beside the sparse MLP's statistics;
free unless a caller opens the collection. The one forward of
:func:`.expert_load.record_expert_load` reads it too and sets one gauge more:

``selected_key_share``
    all chosen (query, key) pairs over all visible ones, over the layers
    that select: with ``topk`` keys a query and sequences of ``S`` tokens
    ``(k (k + 1) / 2 + (S - k) k) / (S (S + 1) / 2)`` for ``k = min(topk,
    S)``; 1.0 is full attention.
"""
from __future__ import annotations

from .expert_load import sown_by_depth

GAUGE = "selected_key_share"


def stacked_selection(sown):
    """``[layers, B, S, S]`` bool from the collections a forward returned,
    the selecting layers in depth order; ``None`` where no layer selects."""
    import jax.numpy as jnp

    kept = sown_by_depth(sown, "selected_keys")
    return jnp.stack(kept) if kept else None


def key_share(kept):
    """The share of the visible pairs that ``kept [layers, B, S, S]``
    keeps."""
    layers, rows, s_len = kept.shape[:3]
    return kept.sum(dtype="float32") / (layers * rows * s_len * (s_len + 1)
                                        / 2)


def set_selected_key_share(share, registry) -> dict:
    """The gauge set in ``registry`` (no gauge for ``None``). Returns what
    it set."""
    if share is None:
        return {}
    registry.gauge(GAUGE).set(float(share))
    return {GAUGE: float(share)}
