"""Communication observability: the analytical wire-cost model.

This module prices the cross-chip aggregation wire *analytically*, per ``agg_impl`` and per top-level leaf group, so every
round's JSONL line carries the modeled bytes-on-the-wire, the analyzer
(schema v3 ``comm`` section) can report measured-vs-modeled efficiency,
and the what-if table projects every alternative wire at the live mask
density — the measure-before-optimize substrate for ROADMAP Open item 3
(hierarchical/overlapped aggregation, error-feedback top-k).

What is modeled: the per-device transmitted collective payload of ONE
central aggregation (the exact quantity the low-precision and sparse
wires of ``parallel/collectives.py`` shrink):

* **dense / bucketed** — the f32 psum payload: 4 bytes/param (the
  bucketed impl moves the same bytes, pipelined one leaf-group bucket
  per collective);
* **bf16** — 2 bytes/param (``all_gather`` of the bf16-cast partials,
  f32 accumulation on every receiver);
* **int8** — 1 byte/param on the padded bucket-row layout plus one f32
  scale per (leaf, bucket-row) — ``collectives._quantize_int8``'s
  per-row max-abs scales ride the wire with the payload;
* **sparse** — 4 bytes per LIVE coordinate: kernel leaves shrink to the
  :class:`~..parallel.collectives.SparsePlan`'s gathered index size,
  non-kernel leaves stay dense — so sparse bytes scale with the live
  mask density, not the parameter count;
* **topk** — 8 bytes per SELECTED coordinate (f32 value + int32 index;
  ``collectives.topk_count`` of each leaf's live set at the configured
  density): the per-client shipped payload of the error-feedback top-k
  wire. The residual never ships — it is algorithm state — so the
  modeled bytes are residual-free by construction, and
  :func:`topk_payload` builds exactly this serialization for the
  ``Message`` pin tests;
* **hier** — the CROSS-SLICE hop only, at the configured
  ``agg_hier_wire`` precision (bf16 2 B/param default; int8 adds the
  per-bucket-row scales; 'sparse' prices the compressed-plan f32
  payload): the intra-slice full-precision psum rides the fast domain
  and is deliberately excluded — pricing the slow-domain wire is the
  model's point.

The model is static per run (masks are static on every path that
supports ``agg_impl='sparse'``), so the per-round "computation" is free:
``ObsSession`` joins the same values onto every JSONL line — the
in-jit-cheapest possible round metric. Validation against REAL
serialized bytes goes through ``comm/message.py``:
:func:`message_payload_nbytes` predicts ``Message.to_bytes()`` sizes
exactly (tests/test_comm_model_properties.py pins dense / bf16 /
masked-sparse payloads within the documented header budget), and the
comm backends' :class:`~..comm.base.CommCounters` count what actually
crossed a transport.

:func:`probe_agg_ms` adds the measured side: one timed aggregation of a
shape-matched synthetic cohort through the algorithm's OWN ``_aggregate``
path — a pure readout (local PRNG, no run state touched) whose wall time
becomes the per-round ``comm_agg_ms`` / ``comm_agg_share`` stamps.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

__all__ = [
    "COMM_PREFIX", "MESSAGE_BASE_OVERHEAD", "MESSAGE_PER_LEAF_OVERHEAD",
    "WireCostModel", "message_overhead_budget", "message_payload_nbytes",
    "probe_agg_cost", "probe_agg_ms", "probe_aggregate", "topk_payload",
]

#: every wire-model metric key starts with this (the analyzer's and the
#: schema stamp's key-space contract — a record carrying any ``comm_*``
#: key is obs-schema v3)
COMM_PREFIX = "comm_"

#: documented ``Message.to_bytes`` framing budget: MAGIC(4) + u32 header
#: length(4) + the JSON header. The header holds the params dict plus,
#: per tensor entry, a treedef string and one index dict per leaf
#: (dtype/shape/offset/nbytes[, sparse kind + bitmap_nbytes]) — bounded
#: by a base cost plus a per-leaf cost. The property test pins
#: ``payload <= serialized <= payload + message_overhead_budget(leaves)``.
MESSAGE_BASE_OVERHEAD = 256
MESSAGE_PER_LEAF_OVERHEAD = 256


def message_overhead_budget(n_leaves: int) -> int:
    """Upper bound on the non-payload (framing + JSON header) bytes of a
    ``Message`` carrying ``n_leaves`` tensor leaves."""
    return MESSAGE_BASE_OVERHEAD + MESSAGE_PER_LEAF_OVERHEAD * max(
        int(n_leaves), 0)


def message_payload_nbytes(tree: Any, mask: Any = None) -> int:
    """EXACT raw-blob byte count ``Message.to_bytes`` appends for one
    ``add_tensor(tree)`` entry (``mask=None``) or one
    ``add_masked_tensor(tree, mask)`` entry: dense leaf ->
    ``size * itemsize``; mask-sparse leaf -> ``nnz * itemsize`` values
    plus the ``ceil(size / 8)``-byte packed bitmap. The full serialized
    message is this plus the JSON header framing, which is bounded by
    :func:`message_overhead_budget`."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    if mask is None:
        total = 0
        for leaf in leaves:
            arr = np.asarray(leaf)
            total += arr.size * arr.dtype.itemsize
        return total
    mask_leaves = jax.tree_util.tree_leaves(mask)
    if len(mask_leaves) != len(leaves):
        raise ValueError(
            f"mask has {len(mask_leaves)} leaves, tree has {len(leaves)}")
    total = 0
    for leaf, m in zip(leaves, mask_leaves):
        arr = np.asarray(leaf)
        nnz = int(np.count_nonzero(np.asarray(m)))
        total += nnz * arr.dtype.itemsize + (arr.size + 7) // 8
    return total


def topk_payload(tree: Any, k_frac: float, mask: Any = None) -> Any:
    """The SERIALIZED form of one client's error-feedback top-k update:
    per leaf, the ``collectives.topk_count`` largest-|value| coordinates
    of the (optionally mask-restricted) flat leaf as an int32 ``idx``
    array plus a values array in the leaf's dtype — the residual-free
    wire (the residual is algorithm state and never ships).

    ``message_payload_nbytes`` of this payload equals
    ``sum_i topk_count(live_i, k_frac) * (4 + itemsize)`` exactly —
    i.e. :meth:`WireCostModel.leaf_bytes(..., 'topk')` for f32 leaves —
    which is what the property pins in
    tests/test_comm_model_properties.py verify against real
    ``Message.to_bytes`` output. Host-side only (numpy argpartition);
    ties at the k-th magnitude resolve by flat index — deterministic."""
    import jax

    from ..parallel.collectives import topk_count

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    mask_leaves = (jax.tree_util.tree_leaves(mask) if mask is not None
                   else [None] * len(leaves))
    if len(mask_leaves) != len(leaves):
        raise ValueError(
            f"mask has {len(mask_leaves)} leaves, tree has {len(leaves)}")
    out = []
    for leaf, m in zip(leaves, mask_leaves):
        flat = np.asarray(leaf).reshape(-1)
        live = np.arange(flat.size)
        if m is not None:
            live = np.flatnonzero(np.asarray(m).reshape(-1))
        k = topk_count(max(int(live.size), 1), k_frac)
        vals = flat[live] if live.size else np.zeros(1, flat.dtype)
        cand = live if live.size else np.zeros(1, np.int64)
        order = np.argpartition(-np.abs(vals), min(k, vals.size) - 1)
        sel = np.sort(cand[order[:k]]).astype(np.int32)
        out.append({"idx": sel, "val": flat[sel].astype(flat.dtype)
                    if live.size else vals[:k]})
    return jax.tree_util.tree_unflatten(treedef, out)


#: per-param wire bytes of the non-bucket-dependent impls (int8 and
#: sparse are computed per leaf — see :meth:`WireCostModel.leaf_bytes`)
WIRE_BYTES_PER_PARAM = {"dense": 4.0, "bucketed": 4.0, "bf16": 2.0}

#: one f32 max-abs scale per (leaf, bucket-row) on the int8 wire
INT8_SCALE_BYTES = 4.0


class WireCostModel:
    """Static bytes-on-the-wire model for every ``agg_impl``.

    Built host-side once per run from the ``jax.eval_shape`` params
    template (no device compute); emits the ``comm_*`` round-metric
    dict :meth:`round_metrics` that ``ObsSession`` joins onto every
    JSONL line and the analyzer's what-if table reads back.
    """

    def __init__(self, leaf_sizes: Tuple[int, ...],
                 leaf_live: Tuple[Optional[int], ...],
                 group_names: Tuple[str, ...],
                 leaf_group_index: Tuple[int, ...], *,
                 agg_impl: str = "dense", bucket_size: int = 0,
                 n_devices: int = 1,
                 density: Optional[float] = None,
                 topk_density: float = 0.1,
                 hier_wire: str = "bf16"):
        from ..parallel.collectives import (
            AGG_IMPLS,
            DEFAULT_BUCKET_SIZE,
            HIER_WIRES,
        )

        if agg_impl not in AGG_IMPLS:
            raise ValueError(f"agg_impl {agg_impl!r} not in {AGG_IMPLS}")
        if hier_wire not in HIER_WIRES:
            raise ValueError(
                f"hier_wire {hier_wire!r} not in {HIER_WIRES}")
        if not 0.0 < topk_density <= 1.0:
            raise ValueError(
                f"topk_density {topk_density} not in (0, 1]")
        if not (len(leaf_sizes) == len(leaf_live)
                == len(leaf_group_index)):
            raise ValueError(
                "leaf_sizes / leaf_live / leaf_group_index lengths differ "
                f"({len(leaf_sizes)}/{len(leaf_live)}/"
                f"{len(leaf_group_index)})")
        self.leaf_sizes = tuple(int(s) for s in leaf_sizes)
        self.leaf_live = tuple(leaf_live)
        self.group_names = tuple(group_names)
        self.leaf_group_index = tuple(leaf_group_index)
        self.agg_impl = agg_impl
        self.bucket_size = int(bucket_size) or DEFAULT_BUCKET_SIZE
        self.n_devices = max(1, int(n_devices))
        self.n_params = sum(self.leaf_sizes)
        #: None = no mask/plan known — the sparse what-if is omitted
        self.density = density
        #: topk's configured shipped fraction (defaulted so the what-if
        #: table can project topk even on runs using another impl)
        self.topk_density = float(topk_density)
        #: hier's cross-slice wire precision (the priced hop)
        self.hier_wire = hier_wire
        self._impls = AGG_IMPLS

    # -- construction ----------------------------------------------------
    @classmethod
    def from_params(cls, params_template: Any, *, agg_impl: str = "dense",
                    bucket_size: int = 0, n_devices: int = 1,
                    plan=None, topk_density: float = 0.1,
                    hier_wire: str = "bf16") -> "WireCostModel":
        """Model from a params pytree (concrete or ``jax.eval_shape``
        template). ``plan`` is the live-coordinate
        :class:`~..parallel.collectives.SparsePlan` (None = no mask:
        sparse bytes are not projected)."""
        import jax

        from .numerics import layer_groups

        names, index = layer_groups(params_template)
        leaves = jax.tree_util.tree_leaves(params_template)
        sizes = tuple(
            int(np.prod(l.shape)) if l.shape else 1 for l in leaves)
        live: Tuple[Optional[int], ...] = (None,) * len(leaves)
        density = None
        if plan is not None:
            if len(plan.idx) != len(leaves):
                raise ValueError(
                    f"sparse plan has {len(plan.idx)} leaves, params "
                    f"template has {len(leaves)} — built for a "
                    "different tree")
            live = tuple(None if ix is None else int(ix.size)
                         for ix in plan.idx)
            density = float(plan.density)
        return cls(sizes, live, names, index, agg_impl=agg_impl,
                   bucket_size=bucket_size, n_devices=n_devices,
                   density=density, topk_density=topk_density,
                   hier_wire=hier_wire)

    @classmethod
    def from_algorithm(cls, algo, state: Any = None
                       ) -> "WireCostModel":
        """Model for one built algorithm: params template via
        ``jax.eval_shape``, the live mask density from the algorithm's
        sparse plan (or, when ``state`` carries a concrete ``mask``
        tree, a plan built from it — the LIVE density, not an assumed
        one), device count from the ``clients`` mesh the data lives
        on."""
        import jax

        from ..parallel.collectives import build_sparse_plan

        template = algo.params_template()
        _ensure_agg_plan(algo, state)
        plan = getattr(algo, "_agg_sparse_plan", None)
        if plan is None and state is not None:
            mask = getattr(state, "mask", None)
            if mask is not None:
                plan = build_sparse_plan(jax.tree_util.tree_map(
                    np.asarray, mask))
        mesh = algo._agg_mesh()
        n_devices = 1
        if mesh is not None and "clients" in getattr(
                mesh, "axis_names", ()):
            n_devices = int(mesh.shape["clients"])
        return cls.from_params(
            template, agg_impl=algo.agg_impl,
            bucket_size=algo.agg_bucket_size, n_devices=n_devices,
            plan=plan,
            topk_density=getattr(algo, "agg_topk_density", 0.1),
            hier_wire=getattr(algo, "agg_hier_wire", "bf16"))

    # -- the model -------------------------------------------------------
    def _int8_bytes(self, n: int) -> float:
        # collectives._wire_reduce_groups int8 layout: the leaf is
        # padded to nb rows of b elements, one f32 scale per row
        b = min(self.bucket_size, max(n, 1))
        nb = -(-n // b) if n else 0
        return float(nb * b) + INT8_SCALE_BYTES * nb

    def leaf_bytes(self, i: int, impl: str) -> float:
        """Modeled wire bytes of leaf ``i`` under ``impl``."""
        n = self.leaf_sizes[i]
        live = self.leaf_live[i]
        if impl == "sparse":
            return 4.0 * (n if live is None else live)
        if impl == "topk":
            # the shipped payload: topk_count of the LIVE set, 4 B f32
            # value + 4 B int32 index each (residual-free — the
            # remainder stays in state, never on the wire). The same
            # topk_count rule builds topk_payload, so this prediction
            # is EXACT against Message serialization.
            from ..parallel.collectives import topk_count

            return 8.0 * topk_count(n if live is None else live,
                                    self.topk_density)
        if impl == "hier":
            # cross-slice hop only (intra-slice psum is the fast
            # domain), at the configured wire precision
            wire = self.hier_wire
            if wire == "sparse":
                return 4.0 * (n if live is None else live)
            if wire == "int8":
                return self._int8_bytes(n)
            return {"f32": 4.0, "bf16": 2.0}[wire] * n
        if impl == "int8":
            return self._int8_bytes(n)
        return WIRE_BYTES_PER_PARAM[impl] * n

    def bytes_for(self, impl: str) -> float:
        """Total modeled per-device wire bytes of one aggregation."""
        if impl not in self._impls:
            raise ValueError(f"impl {impl!r} not in {self._impls}")
        return sum(self.leaf_bytes(i, impl)
                   for i in range(len(self.leaf_sizes)))

    def group_bytes(self, impl: Optional[str] = None) -> Dict[str, float]:
        """Modeled wire bytes per TOP-LEVEL leaf group (the params
        tree's top-level modules — the same grouping obs/numerics.py
        gauges use, so byte and norm attribution line up)."""
        impl = impl or self.agg_impl
        out = {g: 0.0 for g in self.group_names}
        for i, gi in enumerate(self.leaf_group_index):
            out[self.group_names[gi]] += self.leaf_bytes(i, impl)
        return out

    def what_if(self) -> Dict[str, float]:
        """Every ``agg_impl``'s modeled bytes at the current density —
        the mask-dependent wires (sparse; hier's sparse cross-slice
        wire) only when a mask/plan is known. topk projects always (its
        density is a config knob, defaulted when unconfigured)."""
        def known(impl):
            if impl == "sparse" or (impl == "hier"
                                    and self.hier_wire == "sparse"):
                return self.density is not None
            return True

        return {impl: self.bytes_for(impl) for impl in self._impls
                if known(impl)}

    def round_metrics(self) -> Dict[str, float]:
        """The per-round ``comm_*`` metric dict (all floats — static
        per run, joined onto every JSONL line by ``ObsSession``)."""
        m: Dict[str, float] = {
            "comm_bytes_wire": self.bytes_for(self.agg_impl),
            "comm_density": (1.0 if self.density is None
                             else self.density),
            "comm_n_params": float(self.n_params),
            "comm_n_devices": float(self.n_devices),
        }
        for impl, b in self.what_if().items():
            m[f"comm_bytes_{impl}"] = b
        for g, b in self.group_bytes().items():
            m[f"comm_bytes_group/{g}"] = b
        return m


def _ensure_agg_plan(algo, state: Any) -> None:
    """SalientGrads builds its sparse gather plan lazily at the first
    round; the wire model and probe run BEFORE any round, so trigger
    the same host-side build here (idempotent, a no-op off the sparse
    path or without a state)."""
    ensure = getattr(algo, "_ensure_agg_plan", None)
    if ensure is not None and state is not None:
        ensure(state)


def _synthetic_cohort(algo):
    """(template, stacked, weights): a shape-matched synthetic cohort
    for the probes — generated from a LOCAL PRNG key, so no run state
    or run RNG is touched (the bit-inert obs contract)."""
    import jax
    import jax.numpy as jnp

    template = algo.params_template()
    leaves, treedef = jax.tree_util.tree_flatten(template)
    s = algo.clients_per_round
    key = jax.random.PRNGKey(0)
    stacked = jax.tree_util.tree_unflatten(treedef, [
        jax.random.normal(jax.random.fold_in(key, i),
                          (s,) + tuple(l.shape), jnp.float32) * 0.01
        for i, l in enumerate(leaves)])
    weights = jnp.full((s,), 1.0 / s, jnp.float32)
    return template, stacked, weights


def probe_aggregate(algo, state: Any = None, iters: int = 4,
                    timing: bool = True, cost: bool = True,
                    registry=None) -> Dict[str, Any]:
    """Probe ONE central aggregation through the algorithm's own
    ``_aggregate`` path (impl, bucket size, sparse plan, mesh —
    everything the round program uses), on a shape-matched synthetic
    cohort built ONCE and shared by both measurements (at flagship
    scale the stacked cohort is hundreds of MB — it must not be
    materialized twice):

    * ``agg_ms`` (``timing``) — wall ms per aggregation via
      ``collectives.time_weighted_agg``;
    * ``flops`` / ``bytes_accessed`` / ``compile_s`` (``cost``) — AOT
      ``jit_cost_analysis`` of a single-agg program: the no-trace side
      of the devtrace fallback (``share_from_cost_analysis`` consumes
      them against a round program's cost); None where the backend
      reports nothing.

    Pure readout: a LOCAL PRNG key generates the cohort, no run state
    or run RNG is touched, so the training trajectory stays
    bit-identical (the obs contract).
    """
    import jax

    _ensure_agg_plan(algo, state)
    template, stacked, weights = _synthetic_cohort(algo)
    rng = jax.random.PRNGKey(1)
    out: Dict[str, Any] = {}
    if timing:
        from ..parallel.collectives import time_weighted_agg

        def agg_fn(st, wv, i):
            # rng passed unconditionally: only int8 consumes it
            return algo._aggregate(st, wv, jax.random.fold_in(rng, i))

        out["agg_ms"] = time_weighted_agg(
            agg_fn, stacked, weights, template, iters) * 1e3
    if cost:
        from .compile import jit_cost_analysis

        @jax.jit
        def one_agg(st, wv):
            return algo._aggregate(st, wv, rng)

        out.update(jit_cost_analysis(one_agg, stacked, weights,
                                     registry=registry,
                                     entry="aggregate"))
    return out


def probe_agg_ms(algo, iters: int = 4, state: Any = None) -> float:
    """Wall ms of one aggregation — :func:`probe_aggregate`'s timing
    half alone."""
    return probe_aggregate(algo, state=state, iters=iters,
                           cost=False)["agg_ms"]


def probe_agg_cost(algo, state: Any = None,
                   registry=None) -> Dict[str, Any]:
    """AOT cost analysis of one aggregation —
    :func:`probe_aggregate`'s cost half alone."""
    return probe_aggregate(algo, state=state, timing=False,
                           registry=registry)
