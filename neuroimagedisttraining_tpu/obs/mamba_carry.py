"""How much of a Mamba-1 mixer's state crosses its scan's chunks.

A decoder layer with a Mamba-1 mixer (``models/decoder.py:Mamba``) sows, into
the collection ``expert_stats`` beside the sparse MLP's statistics, the mean
over its channels and states of ``exp(A sum over the chunk of delta)`` for
each chunk of its scan, ``[B, chunks]``; free unless a caller opens the
collection. The one forward of :func:`.expert_load.record_expert_load` (and
a benchmark family's reference check) reads it and sets one gauge more:

``mamba_chunk_carry``
    the mean over channels, states, layers and chunks of the share of a
    chunk's entering state that survives the chunk; 0 says the scan is
    local on this cohort and what it carries between chunks does nothing,
    1 that nothing decays.
"""
from __future__ import annotations

from .expert_load import sown_by_depth

GAUGE = "mamba_chunk_carry"


def carry_share(sown):
    """The mean kept share as an array from the collections a forward
    returned; ``None`` where no layer has a Mamba-1 mixer."""
    import jax.numpy as jnp

    kept = sown_by_depth(sown, "mamba_chunk_keep")
    if not kept:
        return None
    return jnp.mean(jnp.stack(kept))


def set_mamba_carry(share, registry) -> dict:
    """The gauge set in ``registry`` (none for ``None``). Returns what it
    set."""
    if share is None:
        return {}
    registry.gauge(GAUGE).set(float(share))
    return {GAUGE: float(share)}
