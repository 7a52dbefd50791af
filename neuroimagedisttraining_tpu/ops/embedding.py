"""The token lookup ``table[ids]`` with a backward of its own (ISSUE 40).

The lookup's value is ``jnp.take(table, ids, axis=0)``. Its gradient by the
table is a scatter-add of the cotangent's rows, and the time XLA's TPU
scatter takes is a sawtooth in the table's WIDTH (8192 bf16 rows into
``[32640, W]``, my chip runs, PR 40; PERF.md section 6 has the sweep by 128
columns): over each of the stretches up to 15, 16-20, 21-30 and 31-40 tiles
of 128 columns it grows like ``c / (L - tiles)`` toward the stretch's end
(``L`` 16, 21, 31, 41) and falls back where the next begins. 2048 columns
take 2.7 ms, 2560 13.8; 3968 2.9, 4096 3.1, 4992 29.7, **5120 58.4**. What
is paid there is paid by the table's rows and not by the rows scattered
(57.4-60.4 ms at 4096-16,384 tokens), in bfloat16 and float32 alike, sorted
or not, whatever ``mode`` says.

So the backward here scatters **column slabs**: the cotangent ``[..., H]`` cut
into slabs of ``2^k`` or ``3 * 2^k`` columns, at most 4096
(:func:`slab_columns`: every such width lies early in its stretch and reads
its size's worth, 0.8-3.7 ms), each through the transpose of ``jnp.take``
itself into ``[V, n]``, the slabs joined along the columns. Every slab's
scatter is the one ``jnp.take``'s own gradient would run at that width, on
the same rows in the same order, so the joined gradient is bit-equal to it.
**One rule, by shape**: a table whose width is one slab is ``jnp.take`` as it
stands: the same jaxpr, no ``custom_vjp``, no join.

Every trace counts itself: ``embed_lowerings{spelling, pass}`` in
``obs/metrics.get_registry()``; ``spelling`` is ``take`` where the lookup
keeps ``jnp.take``'s own gradient (which passes through no code of this
module: only its forward is counted) and ``slabs`` where the backward is
the one below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# the widest slab. 8192 bf16 rows into ``[32640, 5120]``, forward + backward
# (my chip runs, PR 40): 4096 + 1024 columns 5.6 ms, five slabs of 1024 5.1,
# ten of 512 4.5, one scatter of 5120 59.7, the one-hot product 16.4
_WIDEST = 4096


def slab_columns(width: int) -> int:
    """The slab a table's next ``width`` columns start with: the largest
    ``2^k`` or ``3 * 2^k`` that ``width`` and ``_WIDEST`` hold (the
    module's docstring has why those)."""
    most = min(width, _WIDEST)
    power = 1 << (most.bit_length() - 1)
    wider = power + power // 2
    return wider if wider <= most else power


def slabs_of(width: int):
    """``(first column, columns)`` of each slab of a table ``width`` wide."""
    out, first = [], 0
    while first < width:
        out.append((first, slab_columns(width - first)))
        first += out[-1][1]
    return out


def _count(spelling: str, direction: str) -> None:
    from ..obs.metrics import get_registry
    get_registry().counter("embed_lowerings").labels(
        spelling=spelling, **{"pass": direction}).inc()


def _take(table, ids):
    return jnp.take(table, ids, axis=0)


def lookup(table, ids):
    """``table[ids]``: ``[*ids.shape, H]`` of a table ``[V, H]``, the value
    and the gradient of ``jnp.take(table, ids, axis=0)`` (the module's
    docstring)."""
    rows, width = table.shape
    slabs = slabs_of(width)
    if len(slabs) == 1:
        _count("take", "forward")
        return _take(table, ids)
    _count("slabs", "forward")

    def backward(ids, g):
        _count("slabs", "backward")
        # each slab through the transpose of the lookup itself: the scatter
        # ``jnp.take``'s gradient runs at that width
        parts = [jax.linear_transpose(
            functools.partial(_take, ids=ids),
            jax.ShapeDtypeStruct((rows, n), g.dtype))(g[..., a:a + n])[0]
            for a, n in slabs]
        return jnp.concatenate(parts, axis=1), None

    sliced = jax.custom_vjp(_take)
    sliced.defvjp(lambda table, ids: (_take(table, ids), ids), backward)
    return sliced(table, ids)
