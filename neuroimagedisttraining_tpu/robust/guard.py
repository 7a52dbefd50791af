"""In-jit non-finite quarantine: detect, degrade, recover — inside the round.

The reference's only defense (``robust_aggregation.py``) clips norms; a
single NaN/Inf update (a diverged client, a bit flip on the wire, an
injected fault from ``robust.faults``) still poisons the aggregate and
every subsequent round. This module screens the ``[C, ...]``-stacked
local updates with ONE per-client bool reduce before ``_aggregate``,
zero-weights the quarantined clients, renormalizes the aggregation
weights over the survivors, and — when nobody survives — carries the
previous global model unchanged.

Design invariants (tests/test_guard.py pins all three):

* **bit-identity when clean** — every transform is a ``jnp.where``
  *select*, never arithmetic, so a round with zero quarantined clients
  produces bit-for-bit the unguarded aggregate (weights untouched, rows
  untouched, aggregate selected as-is);
* **wire-agnostic** — sanitized rows are exact zeros with zero weight,
  so every ``agg_impl`` (dense / bucketed / bf16 / int8 / sparse)
  aggregates the survivor subset exactly as if the quarantined clients
  had never reported (adding zero-weighted zero rows is exact in fp);
* **no NaN propagation** — quarantined rows are select-replaced with
  zeros BEFORE any contraction (``0 * NaN`` is NaN, so zero-weighting
  alone would not be enough).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

#: renormalization floor — only reachable when every client is
#: quarantined, in which case the aggregate is discarded anyway
#: (``carry_if_empty``)
_EPS = 1e-12


def _row_select(ok: jax.Array, ndim: int) -> jax.Array:
    """Broadcast the per-client bool vector against an [C, ...] leaf."""
    return ok.reshape(ok.shape + (1,) * (ndim - 1))


def finite_screen(stacked: Any) -> jax.Array:
    """Per-client all-finite flag over every leaf of a [C, ...]-stacked
    pytree: ONE [C] bool reduce (the in-graph screen the round program
    runs before aggregation)."""
    flags = None
    for x in jax.tree_util.tree_leaves(stacked):
        f = jnp.all(jnp.isfinite(x), axis=tuple(range(1, x.ndim))) \
            if x.ndim > 1 else jnp.isfinite(x)
        flags = f if flags is None else jnp.logical_and(flags, f)
    if flags is None:
        raise ValueError("finite_screen: empty pytree")
    return flags


def quarantine(stacked: Any, weights: jax.Array,
               ok: jax.Array) -> Tuple[Any, jax.Array, jax.Array]:
    """Quarantine the ``~ok`` clients: select-replace their rows with
    exact zeros, zero their weights, and renormalize the weights over the
    survivors. Returns ``(sanitized, new_weights, survivors)`` with
    ``survivors`` the int32 survivor count.

    When every client is ok this is a bitwise no-op: the row select
    returns the input rows and the weight renormalization is bypassed by
    a scalar select (dividing by the re-summed weights would perturb the
    last bit of an already-normalized vector). The sanitize is an
    unconditional O(C x params) write — the round program never pays it
    on clean rounds because :func:`guarded_aggregate` (which calls this
    inside its bad branch) gates the whole thing behind one
    ``lax.cond``."""
    w_masked = jnp.where(ok, weights, jnp.zeros_like(weights))
    total = jnp.sum(w_masked)
    any_bad = jnp.logical_not(jnp.all(ok))
    new_weights = jnp.where(
        any_bad, w_masked / jnp.maximum(total, _EPS), weights)
    sanitized = jax.tree_util.tree_map(
        lambda x: jnp.where(
            _row_select(ok, x.ndim), x, jnp.zeros_like(x)),
        stacked)
    survivors = jnp.sum(ok.astype(jnp.int32))
    return sanitized, new_weights, survivors


def guarded_aggregate(stacked: Any, weights: jax.Array, ok: jax.Array,
                      aggregate_fn, fallback: Any) -> Any:
    """The round's fused quarantine+aggregate spelling: ONE ``lax.cond``
    over the whole aggregation. The clean branch runs ``aggregate_fn``
    on the untouched inputs — bitwise the unguarded aggregate, and the
    only full-tree work a clean round pays beyond it is the read-only
    finite screen that produced ``ok`` (not measured on a chip: no cell
    runs the guard). The bad branch select-zeroes the quarantined rows,
    renormalizes the weights over the survivors, aggregates, and carries
    ``fallback`` (the previous global model) when nobody survived.

    ``aggregate_fn(stacked, weights)`` must be traceable under
    ``lax.cond`` — every ``agg_impl`` wire qualifies (the collectives
    see a replicated predicate)."""
    any_bad = jnp.logical_not(jnp.all(ok))

    def bad(args):
        st, wv = args
        sanitized, w_new, survivors = quarantine(st, wv, ok)
        return carry_if_empty(
            aggregate_fn(sanitized, w_new), fallback, survivors)

    def clean(args):
        st, wv = args
        return aggregate_fn(st, wv)

    return jax.lax.cond(any_bad, bad, clean, (stacked, weights))


def carry_if_empty(aggregate: Any, fallback: Any,
                   survivors: jax.Array) -> Any:
    """Survivor count 0 ⇒ the round degrades to a no-op: select the
    previous global model instead of the (all-zero-weight) aggregate."""
    keep = survivors > 0
    return jax.tree_util.tree_map(
        lambda a, f: jnp.where(keep, a, f.astype(a.dtype)),
        aggregate, fallback)


def merge_residual(ok: jax.Array, new_rows: Any, prev_rows: Any) -> Any:
    """Error-feedback residual × quarantine (``agg_impl='topk'``): a
    quarantined client never shipped anything this round, and its
    compensated delta may carry the very poison the screen caught — so
    its residual row KEEPS the previous value. A pure row select (never
    arithmetic): NaN in ``new_rows`` cannot propagate through it, which
    is the 'a quarantined client's residual must not leak into later
    rounds' invariant (tests/test_agg_topk_hier.py pins it). Clean
    rounds (all ok) select every new row bitwise."""
    return jax.tree_util.tree_map(
        lambda n, p: jnp.where(_row_select(ok, n.ndim), n, p),
        new_rows, prev_rows)


def merge_updates(ok: jax.Array, updates: Any, personal: Any,
                  sel_idx: jax.Array) -> Any:
    """The personal-stack protection: the rows to scatter back into the
    [C, ...] personal stack — each selected client's update where it
    survived, its PREVIOUS personal row where it was quarantined or
    dropped (those clients never delivered anything). The fallback gather
    (``personal[sel_idx]``) runs inside the rare branch, so a clean round
    pays nothing beyond the ``all(ok)`` scalar."""
    def _fix(args):
        upd, pers, sel = args
        from ..core.state import tree_index

        return jax.tree_util.tree_map(
            lambda u, p: jnp.where(_row_select(ok, u.ndim), u, p),
            upd, tree_index(pers, sel))

    return jax.lax.cond(
        jnp.all(ok), lambda args: args[0], _fix,
        (updates, personal, sel_idx))
