"""How much of a state-space mixer's state crosses its scan's chunks.

A decoder layer with a state-space mixer (``models/decoder.py:StateSpace``)
sows, into the collection ``expert_stats`` beside the sparse MLP's
statistics, what each chunk of its scan keeps of the state that enters it,
``exp(sum over the chunk of dt A)`` as ``[B, chunks, heads]``, and its
``dt [B, S, heads]``; free unless a caller opens the collection. The one
forward of :func:`.expert_load.record_expert_load` reads them too and sets
two gauges more:

``ssm_chunk_carry``
    the mean over heads, layers and chunks of the share of a chunk's
    entering state that survives the chunk; 0 says the scan is local on this
    cohort and what it carries between chunks does nothing, 1 that nothing
    decays;
``ssm_dt_mean``
    the mean step ``dt`` (after the softplus) over tokens, heads and layers.
"""
from __future__ import annotations

from .expert_load import sown_by_depth

GAUGES = ("ssm_chunk_carry", "ssm_dt_mean")


def carry_stats(sown):
    """``(mean kept share, mean dt)`` as arrays from the collections a
    forward returned; ``None`` where no layer has a state-space mixer."""
    import jax.numpy as jnp

    kept = sown_by_depth(sown, "ssm_chunk_keep")
    if not kept:
        return None
    return (jnp.mean(jnp.stack(kept)),
            jnp.mean(jnp.stack(sown_by_depth(sown, "ssm_dt"))))


def set_ssm_carry(stats, registry) -> dict:
    """The gauges set in ``registry`` (none for ``None``). Returns what it
    set."""
    if stats is None:
        return {}
    out = {name: float(value) for name, value in zip(GAUGES, stats)}
    for name, value in out.items():
        registry.gauge(name).set(value)
    return out
