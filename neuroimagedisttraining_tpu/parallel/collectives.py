"""Communication-efficient cross-chip aggregation collectives.

The dense federated aggregate (``core.state.weighted_tree_sum``) moves every
f32 parameter across ICI every round as ONE monolithic contraction. On the
chip that is 0.09-0.23 ms of a 668-1,573 ms round at 2.57 M parameters and
33 ms of 1,801 at 568 M (``aggregate_ms_per_round``, PERF.md section 5), so
nothing here is on a measured critical path yet (ROADMAP D6). This module is
the ``agg`` subsystem that shrinks and overlaps that transfer, composable
levers behind one ``weighted_mean`` surface:

* **bucketed** — per-leaf local partials inside ``shard_map``, reduced by
  ONE multi-operand ``psum`` per fixed-size bucket, so XLA can pipeline
  bucket k's collective against bucket k+1's local compute (and against
  the tail of local training) instead of one serialized all-reduce
  barrier. Bucket boundaries snap to leaf boundaries of the
  ``vectorize_weights`` flattening order: flattening into buckets costs
  a full extra copy of the cohort matrix while whole-leaf groups cost
  nothing. Off-mesh the bucketed contraction is element-for-element
  the dense one: bit-equal op by op, and within a few ulp inside a
  jitted round, where XLA associates a [C, N_total] bucket and a
  [C, n_leaf] dot in different orders (tests/test_collectives.py).
* **low-precision wire** — per-device f32 local partials are cast to bf16
  (or stochastic-rounded int8 with a per-bucket scale) for the cross-chip
  hop and accumulated in f32 on every receiver (``all_gather`` of the
  wire payload + f32 tree-sum), halving (or quartering) the bytes moved
  while master weights stay f32.
* **mask-aware sparse** — for static-mask algorithms (SalientGrads: the
  SNIP mask is fixed after init) a host-built :class:`SparsePlan` gathers
  only the live coordinates of each kernel leaf (the union over clients
  when masks are stacked — a static shared index set). On-mesh each
  device gathers its LOCAL clients' live columns before the contraction,
  so the local reduce AND the per-bucket collectives run on the
  compressed representation (~density x the work and bytes); the dense
  layout is rebuilt once at the end by a static inverse-permutation
  gather (scatter is pathologically slow on XLA:CPU — measured 65 ms vs
  1.6 ms for the gather spelling at flagship scale). The mask-weighted
  denominator (``sum(masks)``) is computed on the same compressed
  representation when per-client masks are supplied. With honored masks
  the result is bit-equal to the dense mask-weighted aggregate.
* **error-feedback top-k** (``agg_impl='topk'``) — per-leaf-group top-k
  magnitude selection on the clients' COMPENSATED deltas (delta plus the
  error-feedback residual the algorithm carries in state — Deep Gradient
  Compression, Lin et al. 2018). The wire cost scales with information
  (k selected coordinates: value + index), not parameter count; the
  unsent remainder accumulates in the residual so nothing is ever
  dropped, only deferred. :func:`topk_sparsify` is the selection kernel,
  :func:`topk_weighted_mean` the aggregate; the residual bookkeeping
  lives in ``algorithms/base.py`` (it is state, not a wire concern).
  With a :class:`SparsePlan` the selection runs on the compressed live
  coordinates, so k is a fraction of the LIVE set (SalientGrads
  composition).
* **hierarchical two-stage reduce** (``agg_impl='hier'``) — BlueConnect
  (Cho et al. 2019) style: a full-precision ``psum`` over
  ``axis_index_groups`` of ``hier_inner`` adjacent devices (the fast
  intra-slice domain), then ONE cross-slice collective per leaf-group
  bucket in a configurable low-precision wire (bf16 / int8 — f32
  accumulation) across the ``outer = devices/inner`` slices. Off-mesh
  (or with one slice) it degrades to the exact f32 bucketed reduce.
* **compute/comm overlap** (``overlap=True``, the default) — the
  shard_map reduce issues each leaf-group bucket's collective
  immediately after computing THAT group's local partials instead of
  materializing every leaf's partial first: group k's collective and
  group k+1's local contraction have no data dependency, so XLA's
  scheduler can pipeline wire against compute (and, in the fused scan
  path, against the tail of local training that produces later groups'
  leaves). Scheduling-only: per-bucket math is bit-identical either
  way, so the knob never enters run identity. What overlap it buys on
  a chip is not measured (``obs/devtrace.py`` reduces a trace to the
  collective-vs-compute overlap; the one four-chip cell runs ``dense``).

Everything is jit-traceable and composes with the Byzantine-robust defenses
(``robust.aggregation`` transforms the stacked locals BEFORE aggregation, so
any ``agg_impl`` consumes defended trees unchanged).
"""
from __future__ import annotations

import time
from functools import partial
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

#: 256k f32 = 1 MiB per bucket on the wire — large enough that per-collective
#: latency amortizes, small enough that several buckets cover the 2.57M-param
#: flagship tree and leave XLA real pipelining freedom.
DEFAULT_BUCKET_SIZE = 1 << 18

WIRE_FORMATS = ("f32", "bf16", "int8")

#: the ``agg_impl`` hyperparameter surface (algorithms/base.py)
AGG_IMPLS = ("dense", "bucketed", "bf16", "int8", "sparse", "topk",
             "hier")

#: cross-slice wire choices of the hierarchical reduce ("sparse" =
#: compressed-plan f32 across slices — SalientGrads only)
HIER_WIRES = ("f32", "bf16", "int8", "sparse")


class FlatSpec(NamedTuple):
    """Shape/dtype record to rebuild a pytree from its flat vector."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    dtypes: Tuple[Any, ...]
    total: int


def flat_spec(tree: Any, stacked: bool = False) -> FlatSpec:
    """Describe ``tree``'s leaves; ``stacked=True`` strips the leading
    client axis so the spec describes ONE client's (or the aggregate's)
    tree."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(
        tuple(x.shape[1:] if stacked else x.shape) for x in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    dtypes = tuple(x.dtype for x in leaves)
    return FlatSpec(treedef, shapes, sizes, dtypes, int(sum(sizes)))


def tree_to_vec(tree: Any) -> jax.Array:
    """Flatten a pytree into one vector (the ``vectorize_weights``
    flattening of ``robust.aggregation``, hoisted here so the defense and
    the aggregation buckets share one definition)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate([x.reshape(-1) for x in leaves])


def vec_to_tree(vec: jax.Array, spec: FlatSpec) -> Any:
    """Rebuild the pytree described by ``spec`` from its flat vector."""
    out = []
    off = 0
    for shape, size, dtype in zip(spec.shapes, spec.sizes, spec.dtypes):
        out.append(vec[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(spec.treedef, out)


def stacked_to_mat(stacked: Any) -> jax.Array:
    """[C, ...]-stacked pytree -> one [C, N] f32 matrix (f32 is the master
    weight / accumulation dtype; a no-op cast for the f32 param trees this
    framework aggregates)."""
    leaves = jax.tree_util.tree_leaves(stacked)
    c = leaves[0].shape[0]
    return jnp.concatenate(
        [x.reshape(c, -1).astype(jnp.float32) for x in leaves], axis=1)


# ---------------------------------------------------------------------------
# wire formats
# ---------------------------------------------------------------------------

def _check_wire(wire: str, rng) -> None:
    if wire not in WIRE_FORMATS:
        raise ValueError(f"wire {wire!r} not in {WIRE_FORMATS}")
    if wire == "int8" and rng is None:
        raise ValueError("wire='int8' needs an rng for stochastic rounding")


def _stochastic_round(x: jax.Array, rng: jax.Array) -> jax.Array:
    f = jnp.floor(x)
    return f + (jax.random.uniform(rng, x.shape) < (x - f)).astype(x.dtype)


def _int8_scale(x: jax.Array) -> jax.Array:
    """Per-bucket (last-axis) max-abs/127 scale, keepdims. ONE spelling
    shared by the XLA chain and the fused-kernel routing: XLA's
    algebraic simplifier rewrites the constant divide differently under
    jit than eagerly (measured one-ulp scale drift), so backend
    bit-identity requires both backends to trace the IDENTICAL scale
    subgraph, not merely equivalent math."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    return jnp.where(amax > 0, amax / 127.0, 1.0)


def _quantize_int8(x: jax.Array, rng: jax.Array):
    """Per-bucket (last-axis) max-abs scaling + stochastic rounding.
    Returns (int8 payload, f32 scale broadcastable against it)."""
    scale = _int8_scale(x)
    q = jnp.clip(_stochastic_round(x / scale, rng), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def wire_roundtrip_mat(mat: jax.Array, wire: str, *,
                       bucket_size: int = DEFAULT_BUCKET_SIZE,
                       rng: Optional[jax.Array] = None) -> jax.Array:
    """Encode+decode each row of an ``[S, N]`` client-delta matrix
    through the ``wire`` format — WHAT THE SERVER WOULD SEE after the
    cross-chip hop, without reducing.

    The low-precision wires commute with the weighted SUM (cast, then
    accumulate in f32 — the ``_reduce_mat`` contract) but NOT with order
    statistics: a robust aggregator must rank the values the receiver
    decodes, not the f32 values the sender held, or the robust statistic
    silently runs on data the wire never carried. ``robust_agg`` on a
    compressed ``agg_impl`` therefore pushes every row through this
    roundtrip before the statistic.

    bf16 is the plain double cast; int8 pads each row to whole
    ``bucket_size`` buckets and applies the per-(row, bucket)
    stochastic-rounded quantization — the IDENTICAL ``_quantize_int8``
    spelling the reducing wire uses, so one client's decoded row here
    matches its contribution there bit-for-bit when given the same
    rng. f32 is the identity."""
    _check_wire(wire, rng)
    if wire == "f32":
        return mat
    if wire == "bf16":
        return mat.astype(jnp.bfloat16).astype(jnp.float32)
    s, n = mat.shape
    b = min(bucket_size, max(n, 1))
    nb = -(-n // b)
    pad = nb * b - n
    if pad:
        mat = jnp.pad(mat, ((0, 0), (0, pad)))
    buckets = mat.reshape(s, nb, b)
    q, scale = _quantize_int8(buckets, rng)
    deq = q.astype(jnp.float32) * scale
    return deq.reshape(s, -1)[:, :n]


def _check_vma(wire: str) -> bool:
    """The all_gather wires ARE replicated (every device gathers and sums
    the same partials) but shard_map's static varying-axes check can't see
    through the gather+sum, so it is disabled for those; the f32 psum path
    keeps it."""
    return wire == "f32"


def _mesh_axis_rows(mesh, axis_name: str, c: int) -> int:
    """Usable device count along ``axis_name`` for a C-row stacked axis;
    0 disables the shard_map path (no mesh / axis missing / C not
    divisible — e.g. a partial-participation round on an 8-wide mesh)."""
    if mesh is None or axis_name not in getattr(mesh, "axis_names", ()):
        return 0
    d = int(mesh.shape[axis_name])
    if d <= 1 or c % d:
        return 0
    return d


# ---------------------------------------------------------------------------
# leaf-group buckets (the shard_map reduce core)
# ---------------------------------------------------------------------------

def _leaf_groups(sizes, bucket_size: int) -> List[List[int]]:
    """Greedy partition of the leaf list (``tree_leaves`` order — the
    ``vectorize_weights`` flattening order) into contiguous groups of
    >= ``bucket_size`` elements. Each group is ONE multi-operand
    collective; snapping bucket boundaries to leaf boundaries keeps the
    bucketing copy-free (see module docstring)."""
    groups: List[List[int]] = []
    cur: List[int] = []
    acc = 0
    for i, s in enumerate(sizes):
        cur.append(i)
        acc += int(s)
        if acc >= bucket_size:
            groups.append(cur)
            cur, acc = [], 0
    if cur:
        groups.append(cur)
    return groups


def _group_vals(payload, g):
    """One group's payload vectors; thunk entries (the overlap spelling:
    each leaf's local contraction deferred until ITS group reduces, so
    group k's collective and group k+1's contraction are independent
    and XLA can pipeline them) are forced here, at issue time."""
    return tuple(payload[i]() if callable(payload[i]) else payload[i]
                 for i in g)


def _int8_leaf_reduce(v, i, kd, axis_name, bucket_size, groups=None):
    """One leaf's int8-wire reduce: pad to bucket rows, quantize with a
    per-(device,leaf) stochastic-rounding key, all_gather payload +
    scales (optionally over ``axis_index_groups``), f32 accumulate."""
    n = v.shape[0]
    b = min(bucket_size, max(n, 1))
    nb = -(-n // b)
    pad = nb * b - n
    vb = jnp.pad(v, (0, pad)).reshape(nb, b)
    q, s = _quantize_int8(vb, jax.random.fold_in(kd, i))
    gq = jax.lax.all_gather(q, axis_name, axis_index_groups=groups)
    gs = jax.lax.all_gather(s, axis_name, axis_index_groups=groups)
    return jnp.sum(gq.astype(jnp.float32) * gs, axis=0).reshape(-1)[:n]


def _wire_reduce_groups(payload, groups, *, axis_name: str, wire: str,
                        key, bucket_size: int):
    """INSIDE shard_map: reduce a list of per-device flat f32 local-
    partial vectors across ``axis_name``, one collective per leaf-group
    bucket — multi-operand ``psum`` for f32; ``all_gather`` of the
    wire-cast payload + f32 tree-sum for bf16/int8 (low-precision wire,
    f32 accumulation). Independent per-bucket collectives are what XLA
    can pipeline against each other and the producing compute; payload
    entries may be thunks (see :func:`_group_vals`) so each group's
    contraction is emitted right before its own collective."""
    out = [None] * len(payload)
    for g in groups:
        vals = _group_vals(payload, g)
        if wire == "f32":
            red = jax.lax.psum(vals, axis_name)
        elif wire == "bf16":
            gath = jax.lax.all_gather(
                tuple(v.astype(jnp.bfloat16) for v in vals), axis_name)
            red = tuple(jnp.sum(x.astype(jnp.float32), axis=0)
                        for x in gath)
        else:  # int8: per-bucket scales within each leaf payload
            kd = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
            red = tuple(
                _int8_leaf_reduce(v, i, kd, axis_name, bucket_size)
                for i, v in zip(g, vals))
        for i, r in zip(g, red):
            out[i] = r
    return out


def resolve_hier_inner(n_devices: int, requested: int = 0) -> int:
    """Devices per intra-slice group of the hierarchical reduce.

    ``requested > 0`` must divide the axis size (a static config error
    otherwise — raised at trace/build time, never silently adjusted);
    ``requested`` of 1 or >= the axis size means one stage, returned as
    0 (disabled). ``requested == 0`` auto-picks the largest divisor d
    with ``d*d <= n_devices`` (the balanced two-stage split: 8 devices
    -> 2x4, 16 -> 4x4); axes of <= 2 devices have no second stage."""
    if requested and (requested < 0
                      or (requested > 1 and n_devices % requested)):
        # validated BEFORE the small-axis early return: a typo'd inner
        # must fail on the 2-device dev mesh, not only when promoted
        raise ValueError(
            f"hier_inner {requested} must divide the {n_devices}-"
            "device clients axis (intra-slice groups are equal-size "
            "device blocks)")
    if n_devices <= 2:
        return 0
    if requested:
        return requested if 1 < requested < n_devices else 0
    inner = 1
    for d in range(2, n_devices):
        if n_devices % d == 0 and d * d <= n_devices:
            inner = d
    return inner if inner > 1 else 0


def _hier_index_groups(n_devices: int, inner: int):
    """(intra, inter) ``axis_index_groups``: contiguous ``inner``-device
    blocks are one slice; position-matched devices across the
    ``n_devices // inner`` slices form the cross-slice groups."""
    outer = n_devices // inner
    intra = [[s * inner + i for i in range(inner)] for s in range(outer)]
    inter = [[s * inner + i for s in range(outer)] for i in range(inner)]
    return intra, inter


def _hier_reduce_groups(payload, groups, *, axis_name: str, wire: str,
                        key, bucket_size: int, n_devices: int,
                        inner: int):
    """INSIDE shard_map: the two-stage hierarchical reduce. Stage 1 is a
    FULL-PRECISION multi-operand ``psum`` within each ``inner``-device
    slice (the fast domain — ICI inside a slice); stage 2 moves each
    slice's partial across the slow domain once per leaf-group bucket in
    the configured ``wire`` (f32 psum, or bf16/int8 all_gather + f32
    accumulation). Every device ends with the full reduction (the two
    group partitions compose to the whole axis)."""
    intra, inter = _hier_index_groups(n_devices, inner)
    out = [None] * len(payload)
    for g in groups:
        vals = _group_vals(payload, g)
        part = jax.lax.psum(vals, axis_name, axis_index_groups=intra)
        if wire == "f32":
            red = jax.lax.psum(part, axis_name, axis_index_groups=inter)
        elif wire == "bf16":
            gath = jax.lax.all_gather(
                tuple(v.astype(jnp.bfloat16) for v in part), axis_name,
                axis_index_groups=inter)
            red = tuple(jnp.sum(x.astype(jnp.float32), axis=0)
                        for x in gath)
        else:  # int8 cross-slice wire: key per slice, not per device —
            # every device in a slice holds the identical partial and
            # must quantize it identically
            kd = jax.random.fold_in(
                key, jax.lax.axis_index(axis_name) // inner)
            red = tuple(
                _int8_leaf_reduce(v, i, kd, axis_name, bucket_size,
                                  groups=inter)
                for i, v in zip(g, part))
        for i, r in zip(g, red):
            out[i] = r
    return out


# ---------------------------------------------------------------------------
# mask-aware sparse plan
# ---------------------------------------------------------------------------

class SparsePlan(NamedTuple):
    """Host-built gather plan: per leaf the flat live-coordinate indices
    (None = dense leaf — non-kernel leaves, or kernels with no dead
    coordinate). Static per round-block: valid exactly while the mask it
    was built from is the live one (SalientGrads' SNIP mask is fixed for
    the whole run, ``masks_evolve=False``)."""

    idx: Tuple[Optional[np.ndarray], ...]
    dense_size: int
    compressed_size: int

    @property
    def density(self) -> float:
        return self.compressed_size / max(self.dense_size, 1)


def build_sparse_plan(mask: Any, stacked: bool = False) -> SparsePlan:
    """Gather plan from a CONCRETE mask pytree (host-side numpy walk — do
    not call under trace). ``stacked=True`` unions live coordinates over
    the leading client axis, producing the static shared index superset
    the compressed reduce needs."""
    from ..ops.sparsity import host_live_indices

    idx = tuple(host_live_indices(mask, stacked=stacked))
    leaves = jax.tree_util.tree_leaves(mask)
    dense = 0
    comp = 0
    for m, ix in zip(leaves, idx):
        size = int(np.prod(m.shape[1:] if stacked else m.shape))
        dense += size
        comp += size if ix is None else int(ix.size)
    return SparsePlan(idx=idx, dense_size=dense, compressed_size=comp)


def _plan_check(stacked: Any, plan: SparsePlan):
    leaves = jax.tree_util.tree_leaves(stacked)
    if len(leaves) != len(plan.idx):
        raise ValueError(
            f"sparse plan has {len(plan.idx)} leaves, tree has "
            f"{len(leaves)} — the plan was built for a different tree")
    return leaves


def _inverse_idx(ix: np.ndarray, size: int) -> np.ndarray:
    """dense coordinate -> compressed position, out-of-range (= the
    take-fill zero) for dead coordinates."""
    inv = np.full(size, ix.size, np.int32)
    inv[ix] = np.arange(ix.size, dtype=np.int32)
    return inv


def _expand_leaf(red: jax.Array, ix: Optional[np.ndarray],
                 shape, dtype) -> jax.Array:
    """Compressed reduced leaf -> dense layout via the static inverse-
    permutation GATHER (take with fill; scatter is ~40x slower on
    XLA:CPU). Dead coordinates of an honored-mask aggregate are exactly
    0 — the fill value."""
    size = int(np.prod(shape)) if shape else 1
    if ix is None:
        return red.reshape(shape).astype(dtype)
    out = jnp.take(red, jnp.asarray(_inverse_idx(ix, size)),
                   mode="fill", fill_value=0)
    return out.reshape(shape).astype(dtype)


def _compress(stacked: Any, plan: SparsePlan) -> jax.Array:
    """[C, ...]-stacked pytree -> [C, M_compressed] f32 matrix holding
    each dense leaf in full and each sparse leaf's live coordinates
    (the off-mesh spelling; on-mesh the same gather runs per device on
    its local clients inside shard_map)."""
    leaves = _plan_check(stacked, plan)
    c = leaves[0].shape[0]
    cols = []
    for x, ix in zip(leaves, plan.idx):
        flat = x.reshape(c, -1).astype(jnp.float32)
        cols.append(flat if ix is None
                    else jnp.take(flat, jnp.asarray(ix), axis=1))
    return jnp.concatenate(cols, axis=1)


def _expand_vec(vec: jax.Array, stacked: Any, plan: SparsePlan) -> Any:
    """Inverse of :func:`_compress` for the reduced [M_compressed]
    vector."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    out = []
    off = 0
    for x, ix in zip(leaves, plan.idx):
        shape = x.shape[1:]
        n = (int(np.prod(shape)) if shape else 1) if ix is None \
            else int(ix.size)
        out.append(_expand_leaf(vec[off:off + n], ix, shape, x.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# error-feedback top-k selection
# ---------------------------------------------------------------------------

def plan_dead_select(stacked: Any, plan: SparsePlan) -> Any:
    """Select-zero the DEAD coordinates of a [C, ...]-stacked pytree
    (a ``jnp.where`` against the plan's static live mask — never
    arithmetic, so NaN rows cannot smear). The topk round body applies
    it to the compensated deltas when a plan exists: dead coordinates
    must neither enter the residual (they would sit there forever —
    selection never ships them) nor the selection itself."""
    _plan_check(stacked, plan)
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    out = []
    for x, ix in zip(leaves, plan.idx):
        if ix is None:
            out.append(x)
            continue
        shape = x.shape[1:]
        size = int(np.prod(shape)) if shape else 1
        live = np.zeros(size, bool)
        live[ix] = True
        mask = jnp.asarray(live.reshape(shape))
        out.append(jnp.where(mask, x, jnp.zeros_like(x)))
    return jax.tree_util.tree_unflatten(treedef, out)


def topk_count(n: int, k_frac: float) -> int:
    """Selected-coordinate count for a segment of ``n`` coordinates at
    fraction ``k_frac`` — ``min(n, max(1, ceil(k_frac * n)))``. ONE
    rounding rule shared by the in-jit selection, the wire-cost model
    (obs/comm.py) and the serialization payload builder — but applied
    to different partitions: the model and ``topk_payload`` price/ship
    per LEAF (byte-exact against each other, pinned), while
    :func:`topk_sparsify` selects per leaf-GROUP bucket (many small
    leaves can share one threshold). The counts coincide when a group
    holds one leaf; when a bucket packs several small leaves the
    per-leaf ceil/``max(1,..)`` floors (and exact-threshold ties,
    which selection keeps) bound the difference — drift the
    error-feedback residual absorbs by construction."""
    if not 0.0 < k_frac <= 1.0:
        raise ValueError(f"topk density {k_frac} not in (0, 1]")
    return min(max(int(n), 1), max(1, int(np.ceil(k_frac * n))))


def topk_sparsify(stacked: Any, k_frac: float, *,
                  plan: Optional[SparsePlan] = None,
                  bucket_size: int = DEFAULT_BUCKET_SIZE,
                  sample: int = 0, kernels: str = "xla") -> Any:
    """Per-leaf-group top-k magnitude selection over a [C, ...]-stacked
    pytree: within each leaf-group bucket (the same
    :func:`_leaf_groups` partition every collective uses), each client
    keeps its ``topk_count(group_size, k_frac)`` largest-|value|
    coordinates and zeroes the rest. With a ``plan`` the selection runs
    on the COMPRESSED live coordinates (SalientGrads: k is a fraction
    of the live set, and dead coordinates — exact zeros on every
    honored-mask input — can never be selected ahead of live ones).

    Deterministic and trace-safe: the threshold is the k-th largest
    magnitude per (client, group); coordinates tying it exactly are all
    kept (a measure-zero event on continuous deltas, and the
    all-zero-row edge keeps the row unchanged — sparsifying an exact
    zero contributes exactly zero to wire and residual alike).

    The per-group threshold comes from ``ops.topk_select``'s
    threshold-refinement search (``kernels='xla'`` default / the pallas
    VMEM-resident kernel / the legacy ``'sort'`` ``lax.top_k``
    spelling) — every backend yields the SAME float, so they select
    bit-identical coordinate sets under the module's tie-break contract.
    The sort spelling was the wire's scaling wall (26.7 s/agg exact at
    scale-32, RESULTS Round-12; XLA:CPU ``top_k`` is sort-bound in n at
    any k); the bit-space search replaced it at ~O(31 n) compares with
    no trajectory change.

    ``sample > 0`` estimates each group's threshold from a strided
    ~``sample``-element subsample instead of the full row
    (``topk_select.sampled_threshold`` — the Deep Gradient Compression
    hierarchical-sampling trick): deterministic (fixed stride, no RNG),
    and the shipped count is only approximately k — which error
    feedback absorbs by construction (over- or under-selection just
    shifts coordinates between wire and residual). 0 (the default)
    keeps the exact selection, which the threshold backends price at a
    flat ~31 passes — sampling is an optimization now, not a
    necessity."""
    from ..ops.topk_select import select_threshold

    if plan is not None:
        _plan_check(stacked, plan)
    leaves = jax.tree_util.tree_leaves(stacked)
    idxs = plan.idx if plan is not None else (None,) * len(leaves)
    psizes = [
        (int(np.prod(x.shape[1:])) if x.ndim > 1 else 1)
        if ix is None else int(ix.size)
        for x, ix in zip(leaves, idxs)]
    groups = _leaf_groups(psizes, bucket_size)
    offs = np.concatenate([[0], np.cumsum(psizes)]).astype(int)
    mat = _compress(stacked, plan) if plan is not None \
        else stacked_to_mat(stacked)
    cols = []
    for g in groups:
        start, end = offs[g[0]], offs[g[-1] + 1]
        seg = mat[:, start:end]
        n = int(end - start)
        k = topk_count(n, k_frac)
        av = jnp.abs(seg)
        thr = select_threshold(av, k, kernels=kernels, sample=sample)
        cols.append(jnp.where(av >= thr, seg, jnp.zeros_like(seg)))
    sp_mat = jnp.concatenate(cols, axis=1)
    # rebuild the stacked tree layout (dense leaves reshape; compressed
    # leaves expand by the static inverse-permutation gather per client)
    treedef = jax.tree_util.tree_flatten(stacked)[1]
    out = []
    for i, (x, ix) in enumerate(zip(leaves, idxs)):
        block = sp_mat[:, offs[i]:offs[i + 1]]
        if ix is None:
            out.append(block.reshape(x.shape).astype(x.dtype))
        else:
            size = int(np.prod(x.shape[1:])) if x.ndim > 1 else 1
            dense = jnp.take(block, jnp.asarray(_inverse_idx(ix, size)),
                             axis=1, mode="fill", fill_value=0)
            out.append(dense.reshape(x.shape).astype(x.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def topk_weighted_mean(stacked: Any, weights: jax.Array, k_frac: float,
                       *, plan: Optional[SparsePlan] = None, mesh=None,
                       axis_name: str = "clients",
                       bucket_size: int = DEFAULT_BUCKET_SIZE,
                       overlap: bool = True,
                       sample: int = 0,
                       kernels: str = "xla") -> Tuple[Any, Any]:
    """The ``agg_impl='topk'`` aggregate: sparsify each client's row
    (:func:`topk_sparsify`), then the weighted mean of the sparsified
    rows through the bucketed (plan-compressed when given) reduce.
    Returns ``(aggregate, sparsified)`` — the caller owns the
    error-feedback bookkeeping (``residual' = compensated -
    sparsified``); callers without residual state use index [0].

    The selection is per-client-local (element-wise after the
    per-group threshold), so on a ``clients`` mesh it runs where each
    client's row lives and only the sparsified contraction crosses
    chips; the simulated reduce moves the dense-layout zeros, while the
    INFORMATION cost (k values + k indices per group) is what
    ``obs.comm.WireCostModel`` prices and a cross-silo transport ships
    (``obs.comm.topk_payload``)."""
    sp = topk_sparsify(stacked, k_frac, plan=plan,
                       bucket_size=bucket_size, sample=sample,
                       kernels=kernels)
    kw = dict(mesh=mesh, axis_name=axis_name, bucket_size=bucket_size,
              overlap=overlap, kernels=kernels)
    if plan is not None:
        agg = sparse_weighted_mean(sp, weights, plan, **kw)
    else:
        agg = weighted_mean(sp, weights, **kw)
    return agg, sp


# ---------------------------------------------------------------------------
# the public weighted means
# ---------------------------------------------------------------------------

def _reduce_mat(mat: jax.Array, weights: jax.Array, *,
                bucket_size: int = DEFAULT_BUCKET_SIZE,
                wire: str = "f32", rng: Optional[jax.Array] = None,
                kernels: str = "xla") -> jax.Array:
    """Off-mesh reduce: out[j] = sum_c weights[c] * mat[c, j] in bucket
    layout — element-for-element the dense reduction (for
    ``wire='f32'`` bit-equal as an op of its own, a few ulp apart where
    XLA fuses the two into different programs; the wire casts apply per client since there is no
    per-device partial to cast).

    ``kernels='pallas'`` routes the int8 wire through the fused
    quantize+reduce pallas kernel (ops/pallas_kernels.py): the
    stochastic-rounding uniforms and per-bucket scale are computed here
    with the exact rng call and spelling of the XLA chain, so the
    backends are bit-identical (pinned by tests/test_pallas_kernels.py)
    at every bucket size. The f32/bf16 wires have no quantize chain to
    fuse and always use the tensordot spelling."""
    _check_wire(wire, rng)
    c, n = mat.shape
    w = weights.astype(jnp.float32)
    bucket_size = min(bucket_size, max(n, 1))
    nb = -(-n // bucket_size)
    pad = nb * bucket_size - n
    if pad:
        mat = jnp.pad(mat, ((0, 0), (0, pad)))
    buckets = mat.reshape(c, nb, bucket_size)
    if wire == "bf16":
        buckets = buckets.astype(jnp.bfloat16).astype(jnp.float32)
    elif wire == "int8":
        from ..ops import pallas_kernels as _pk

        if kernels == "pallas":
            u = jax.random.uniform(rng, buckets.shape)
            scale = _int8_scale(buckets)
            out = _pk.fused_quantize_reduce(buckets, w, u,
                                            scale[..., 0])
            return out.reshape(-1)[:n]
        q, scale = _quantize_int8(buckets, rng)
        buckets = q.astype(jnp.float32) * scale
    out = jnp.tensordot(w, buckets, axes=1)
    return out.reshape(-1)[:n]


def _mesh_reduce_leaves(stacked: Any, weights: jax.Array, *, mesh,
                        axis_name: str, bucket_size: int, wire: str, rng,
                        plan: Optional[SparsePlan] = None,
                        masks: Any = None, hier_inner: int = 0,
                        overlap: bool = True) -> List[jax.Array]:
    """shard_map weighted reduce over the mesh-sharded client axis,
    returning the flat reduced payload per leaf (compressed to the plan's
    live coordinates when given; with ``masks`` the payload list is
    num-leaves followed by den-leaves). Each device contracts only its
    LOCAL clients — compressed BEFORE the contraction on the sparse path,
    so local compute and wire both scale with density — and each
    leaf-group bucket is one collective.

    ``hier_inner > 1`` routes each bucket through the two-stage
    hierarchical reduce (:func:`_hier_reduce_groups`: full-precision
    intra-slice psum, ``wire`` across slices). ``overlap`` (default)
    defers each leaf's local contraction into its group's reduce step so
    group k's collective and group k+1's contraction interleave in
    emission order — scheduling freedom only, bit-identical results."""
    key = rng if rng is not None else jax.random.PRNGKey(0)
    leaves = jax.tree_util.tree_leaves(stacked)
    idxs = plan.idx if plan is not None else (None,) * len(leaves)
    psizes = [
        (int(np.prod(x.shape[1:])) if x.ndim > 1 else 1)
        if ix is None else int(ix.size)
        for x, ix in zip(leaves, idxs)]
    if masks is not None:
        psizes = psizes * 2
    groups = _leaf_groups(psizes, bucket_size)
    jidx = [None if ix is None else jnp.asarray(ix) for ix in idxs]
    # hier_inner: 0 = single-stage (the default reduce); -1 = hier with
    # the auto slice split; > 1 = hier with that many devices per slice
    n_devices = int(mesh.shape[axis_name])
    inner = resolve_hier_inner(n_devices, max(hier_inner, 0)) \
        if hier_inner else 0
    if hier_inner and not inner:
        # one slice (hier_inner >= axis, or a <= 2-device axis): the
        # whole reduce lives inside the full-precision fast domain and
        # the configured CROSS-slice wire never fires — degrade to the
        # exact f32 bucketed reduce, the same degeneration as the
        # off-mesh fallback (weighted_mean's "wire never fires"
        # contract), instead of silently quantizing the intra-slice hop
        wire = "f32"
    if inner:
        def reduce_groups(payload, k):
            return _hier_reduce_groups(
                payload, groups, axis_name=axis_name, wire=wire, key=k,
                bucket_size=bucket_size, n_devices=n_devices,
                inner=inner)
    else:
        def reduce_groups(payload, k):
            return _wire_reduce_groups(
                payload, groups, axis_name=axis_name, wire=wire, key=k,
                bucket_size=bucket_size)

    def local_payload(st_leaves, wv):
        """Per-leaf local-contraction thunks: with ``overlap`` they are
        forced inside the group loop (contraction emitted right before
        its own collective); without, all up front (the serialized
        contract-everything-then-reduce order)."""
        def make(x, ix):
            def thunk():
                xf = x.reshape(x.shape[0], -1).astype(jnp.float32)
                if ix is not None:
                    xf = jnp.take(xf, ix, axis=1)
                return jnp.tensordot(wv, xf, axes=1)
            return thunk

        thunks = [make(x, ix) for x, ix in zip(st_leaves, jidx)]
        return thunks if overlap else [t() for t in thunks]

    # hier's axis_index_groups psums produce slice-varying intermediates
    # the static varying-axes check cannot see through, so it is disabled
    # there like on the all_gather wires
    check_vma = False if inner else _check_vma(wire)
    in_specs = (P(axis_name), P(axis_name), P())
    if masks is None:
        @partial(shard_map, mesh=mesh, in_specs=in_specs, out_specs=P(),
                 check_vma=check_vma)
        def agg(st, wv, k):
            payload = local_payload(jax.tree_util.tree_leaves(st), wv)
            return tuple(reduce_groups(payload, k))

        return list(agg(stacked, weights.astype(jnp.float32), key))

    @partial(shard_map, mesh=mesh,
             in_specs=(P(axis_name),) + in_specs, out_specs=P(),
             check_vma=check_vma)
    def agg_masked(st, mk, wv, k):
        xm = jax.tree_util.tree_map(
            lambda x, m: x.astype(jnp.float32) * m.astype(jnp.float32),
            st, mk)
        payload = local_payload(jax.tree_util.tree_leaves(xm), wv) + \
            local_payload(jax.tree_util.tree_leaves(mk), wv)
        return tuple(reduce_groups(payload, k))

    return list(agg_masked(stacked, masks, weights.astype(jnp.float32),
                           key))


def weighted_mean(stacked: Any, weights: jax.Array, *, mesh=None,
                  axis_name: str = "clients",
                  bucket_size: int = DEFAULT_BUCKET_SIZE,
                  wire: str = "f32", rng: Optional[jax.Array] = None,
                  hier_inner: int = 0, overlap: bool = True,
                  kernels: str = "xla") -> Any:
    """Weighted mean over the leading client axis, via the bucketed
    (optionally low-precision-wire) reduce. Drop-in for
    ``core.state.weighted_tree_sum`` (callers pass already-normalized
    weights); ``wire='f32'`` off-mesh is the same sum (see
    :func:`_reduce_mat` for how far "same" goes). With a usable
    ``clients`` mesh the whole reduce runs inside ``shard_map`` on
    per-leaf local partials with one collective per leaf-group bucket —
    the [C, N] client matrix is never materialized.

    ``hier_inner`` enables the two-stage hierarchical reduce on-mesh
    (full-precision psum inside each ``hier_inner``-device slice, then
    ``wire`` across slices; 0 = auto-split via
    :func:`resolve_hier_inner`). Off-mesh there are no slices and the
    fallback is the EXACT f32 bucketed contraction — the one-slice
    degeneration, in which the cross-slice wire never fires.

    ``kernels='pallas'`` fuses the off-mesh int8 wire's quantize+reduce
    into one pallas pass (see :func:`_reduce_mat`; bit-identical by
    contract). The on-mesh shard_map path keeps its per-device op chain
    unchanged — its wire quantize runs per DEVICE inside the collective,
    a different (and already collective-fused) dataflow."""
    _check_wire(wire, rng)
    leaves = jax.tree_util.tree_leaves(stacked)
    c = leaves[0].shape[0]
    if _mesh_axis_rows(mesh, axis_name, c):
        red = _mesh_reduce_leaves(
            stacked, weights, mesh=mesh, axis_name=axis_name,
            bucket_size=bucket_size, wire=wire, rng=rng,
            hier_inner=hier_inner, overlap=overlap)
        _, treedef = jax.tree_util.tree_flatten(stacked)
        return jax.tree_util.tree_unflatten(treedef, [
            r.reshape(x.shape[1:]).astype(x.dtype)
            for r, x in zip(red, leaves)])
    spec = flat_spec(stacked, stacked=True)
    vec = _reduce_mat(stacked_to_mat(stacked), weights,
                      bucket_size=bucket_size,
                      wire="f32" if hier_inner else wire, rng=rng,
                      kernels=kernels)
    return vec_to_tree(vec, spec)


def sparse_weighted_mean(stacked: Any, weights: jax.Array, plan: SparsePlan,
                         *, masks: Any = None, mesh=None,
                         axis_name: str = "clients",
                         bucket_size: int = DEFAULT_BUCKET_SIZE,
                         wire: str = "f32",
                         rng: Optional[jax.Array] = None,
                         hier_inner: int = 0,
                         overlap: bool = True,
                         kernels: str = "xla") -> Any:
    """Mask-aware sparse weighted mean: reduce only the plan's live
    coordinates — local compute and the cross-chip transfer scale with
    ~density — then rebuild the dense layout with one static inverse-
    permutation gather per leaf.

    ``masks=None`` (SalientGrads: one global mask, weights already
    normalized) is the plain weighted mean of honored-mask locals —
    bit-equal to the dense aggregate, whose dead coordinates are exactly
    0. With ``masks`` ([C, ...]-stacked per-client masks) the result is
    the mask-weighted mean ``sum(w*m*x) / sum(w*m)`` with BOTH numerator
    and denominator reduced on the compressed representation (coordinates
    no client holds live divide to 0) — bit-equal to the dense
    mask-weighted aggregate.
    """
    _check_wire(wire, rng)
    leaves = _plan_check(stacked, plan)
    treedef = jax.tree_util.tree_flatten(stacked)[1]
    c = leaves[0].shape[0]
    if _mesh_axis_rows(mesh, axis_name, c):
        red = _mesh_reduce_leaves(
            stacked, weights, mesh=mesh, axis_name=axis_name,
            bucket_size=bucket_size, wire=wire, rng=rng, plan=plan,
            masks=masks, hier_inner=hier_inner, overlap=overlap)
        if masks is not None:
            num, den = red[:len(leaves)], red[len(leaves):]
            red = [jnp.where(d > 0, n / jnp.where(d > 0, d, 1.0), 0.0)
                   for n, d in zip(num, den)]
        return jax.tree_util.tree_unflatten(treedef, [
            _expand_leaf(r, ix, x.shape[1:], x.dtype)
            for r, ix, x in zip(red, plan.idx, leaves)])
    kw = dict(bucket_size=bucket_size,
              wire="f32" if hier_inner else wire, rng=rng,
              kernels=kernels)
    if masks is None:
        vec = _reduce_mat(_compress(stacked, plan), weights, **kw)
        return _expand_vec(vec, stacked, plan)
    mmat = _compress(masks, plan)
    num = _reduce_mat(_compress(stacked, plan) * mmat, weights, **kw)
    if rng is not None:
        kw["rng"] = jax.random.fold_in(rng, 1)
    den = _reduce_mat(mmat, weights, **kw)
    vec = jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)
    return _expand_vec(vec, stacked, plan)


def masked_weighted_mean(stacked: Any, weights: jax.Array,
                         masks: Any) -> Any:
    """Dense reference for the mask-weighted aggregate:
    ``sum_c w_c m_c x_c / sum_c w_c m_c`` per coordinate, 0 where no
    client holds the coordinate live (the ``sum(masks)`` denominator of
    the reference's sparse-personalized aggregation). The sparse path
    (:func:`sparse_weighted_mean` with ``masks``) is bit-equal to this."""
    w = weights.astype(jnp.float32)

    def leaf(x, m):
        xf = x.astype(jnp.float32)
        mf = m.astype(jnp.float32)
        num = jnp.tensordot(w, xf * mf, axes=1)
        den = jnp.tensordot(w, mf, axes=1)
        out = jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)
        return out.astype(x.dtype)

    return jax.tree_util.tree_map(leaf, stacked, masks)


# ---------------------------------------------------------------------------
# timing harness
# ---------------------------------------------------------------------------

def time_weighted_agg(agg_fn, stacked: Any, weights: jax.Array,
                      out_template: Any, iters: int = 8) -> float:
    """Wall-clock seconds per aggregation of ``agg_fn(stacked,
    weights, i)`` — the timing harness of obs/comm.py's
    ``probe_agg_ms`` (``--obs_comm``): an
    in-graph ``fori_loop`` over ``iters`` calls with ``jnp.roll``-ed
    weights so XLA cannot hoist the contraction, accumulated into an
    ``out_template``-shaped f32 tree, timed after one compile+warmup
    run (a scalar fetch forces completion)."""

    @jax.jit
    def run(st, wv):
        def body(i, acc):
            out = agg_fn(st, jnp.roll(wv, i), i)
            return jax.tree_util.tree_map(
                lambda a, o: a + o.astype(a.dtype), acc, out)

        acc0 = jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, jnp.float32), out_template)
        return jax.lax.fori_loop(0, iters, body, acc0)

    out = run(stacked, weights)  # compile + warmup
    float(jax.tree_util.tree_leaves(out)[0].sum())
    t0 = time.perf_counter()
    out = run(stacked, weights)
    float(jax.tree_util.tree_leaves(out)[0].sum())
    return (time.perf_counter() - t0) / iters
