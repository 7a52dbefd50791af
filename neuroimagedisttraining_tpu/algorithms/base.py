"""Algorithm base class + shared federated-round machinery.

The reference gives every algorithm an API class with a Python round loop
(``fedml_api/standalone/<algo>/<algo>_api.py``) that iterates clients
sequentially. Here the round is one jitted SPMD program; the host loop only
(a) samples the round's client subset (tiny, and kept on host to preserve the
reference's cross-algorithm reproducibility contract — ``np.random.seed(
round_idx)`` before sampling, ``fedavg_api.py:92-100``) and (b) logs metrics.
"""
from __future__ import annotations

import abc
import logging
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.state import HyperParams
from ..core.trainer import make_eval_fn
from ..data.types import FederatedData
from ..models import make_apply_fn
from ..obs import trace as obs_trace

logger = logging.getLogger(__name__)


def _personal_metrics(correct, loss_sum, total):
    """Per-client eval terms -> the personal-eval protocol metrics
    (mean of per-client accuracies AND mean of per-client MEAN losses —
    sailentgrads_api.py:276-283 appends each client's ``test_loss`` and
    reports ``sum/len``, so uneven test shards do NOT reweight the
    protocol loss; the earlier sample-weighted ``sum(loss_sum)/
    sum(total)`` here was an unrecorded deviation, fixed per ADVICE r5 —
    see PARITY.md). The ONE definition all three personal eval paths
    share (full, incremental merge, cache-only re-reduce): the
    incremental cache's bitwise-identity contract rests on these
    reductions being literally the same code."""
    totals = jnp.maximum(total, 1)
    acc = correct.astype(jnp.float32) / totals
    return {
        "acc_per_client": acc,
        "acc": jnp.mean(acc),
        "loss": jnp.mean(loss_sum / totals),
        # raw per-client terms seed/refresh the incremental-eval cache
        "correct": correct, "loss_sum": loss_sum, "total": total,
    }


def _device_memory_limit() -> int:
    """Bytes this process's first device may hold, 0 where the backend keeps
    no such statistic (the CPU)."""
    return (jax.local_devices()[0].memory_stats() or {}).get(
        "bytes_limit", 0)


def sample_client_indexes(
    round_idx: int, client_num_in_total: int, client_num_per_round: int,
    retry: int = 0,
) -> np.ndarray:
    """Seeded per-round client sampling (fedavg_api.py:92-100 semantics:
    reseed numpy with the round index so every algorithm draws the same
    subsets — the reference's intentional comparability contract).

    ``retry`` re-samples the cohort for a watchdog rollback-retry
    (robust/recovery.py): the draw stays a pure function of
    (round_idx, retry) — no host RNG state — so a killed-and-resumed run
    replays the identical retry cohorts. ``retry=0`` is bit-compatible
    with the reference contract. Full participation is arange regardless
    (there is no alternative cohort to draw)."""
    if client_num_in_total == client_num_per_round:
        return np.arange(client_num_in_total, dtype=np.int32)
    if retry:
        # golden-ratio stride keeps retry seeds disjoint from every round
        # index a realistic run can reach
        np.random.seed((round_idx + 0x9E3779B1 * retry) % (2 ** 32))
    else:
        np.random.seed(round_idx)
    return np.random.choice(
        range(client_num_in_total), client_num_per_round, replace=False
    ).astype(np.int32)


class FusedMetrics:
    """A fused block's per-round metric series, fetched lazily in ONE
    host transfer (the in-graph ``packed`` stack; see ``_get_fused_fn``).
    Until materialized, holding it costs nothing — the driver dispatches
    the next block first, then materializes the previous one."""

    def __init__(self, ys_device, packed):
        self._ys = ys_device
        self._packed = packed
        self._host = None

    def materialize(self) -> Dict[str, Any]:
        if self._host is None:
            flat, treedef = jax.tree_util.tree_flatten(self._ys)
            vals = np.asarray(self._packed)  # one transfer for the block
            self._host = jax.tree_util.tree_unflatten(
                treedef, [vals[i] for i in range(len(flat))])
            self._ys = self._packed = None  # free the device buffers
        return self._host

    def __getitem__(self, key):
        return self.materialize()[key]

    def __contains__(self, key):
        return key in self.materialize()


class FedAlgorithm(abc.ABC):
    """Base class: owns model apply fn, data, hyperparams, and jitted kernels."""

    name: str = "base"

    def __init__(
        self,
        model,
        data: FederatedData,
        hp: HyperParams,
        loss_type: str = "bce",
        frac: float = 1.0,
        eval_batch: int = 32,
        seed: int = 0,
        client_chunk: Optional[int] = None,
        compute_dtype: Optional[str] = None,
        channel_inject: bool = False,
        remat_local: bool = False,
        eval_clients: int = 0,
        augment="auto",
        agg_impl: str = "dense",
        agg_bucket_size: int = 0,
        agg_topk_density: float = 0.1,
        agg_topk_sample: int = 0,
        agg_hier_wire: str = "bf16",
        agg_hier_inner: int = 0,
        agg_overlap: bool = True,
        agg_kernels: str = "xla",
        fault_spec: str = "",
        guard: Optional[bool] = None,
        robust_agg: str = "none",
        robust_trim: float = 0.2,
        robust_krum_f: int = 0,
        robust_norm_bound: float = 5.0,
        obs_numerics: bool = False,
        donate_state: bool = False,
        client_store: str = "device",
        store_hot_clients: int = 64,
        store_dir: Optional[str] = None,
    ):
        from ..parallel.collectives import AGG_IMPLS, DEFAULT_BUCKET_SIZE

        self.model = model
        self.data = data
        self.hp = hp
        self.loss_type = loss_type
        self.seed = seed
        self.num_clients = data.num_clients
        self.clients_per_round = max(1, int(round(self.num_clients * frac)))
        self.client_chunk = client_chunk
        # mixed precision: f32 master weights + (e.g.) bf16 conv/matmul
        # compute — see make_apply_fn. "bfloat16" is the TPU-native choice.
        self.compute_dtype = (
            jnp.dtype(compute_dtype) if compute_dtype is not None else None
        )
        # channel_inject: volumes stored channel-less, channel appended at
        # apply time (see make_apply_fn docstring for the HBM-tiling why)
        self.channel_inject = channel_inject
        # remat_local: rematerialized local steps (core/trainer.py) — more
        # concurrent clients per chip at the cost of a second forward pass
        self.remat_local = remat_local
        # agg_impl: the cross-chip aggregation path of the central
        # weighted mean (parallel/collectives.py). "dense" (default) is
        # the exact monolithic contraction of weighted_tree_sum;
        # "bucketed" pipelines fixed-size per-bucket reduces; "bf16"/
        # "int8" add a low-precision wire with f32 accumulation; "sparse"
        # (static-mask algorithms only — SalientGrads) reduces on the
        # mask's live coordinates. Consumed by _aggregate; algorithms
        # without a central aggregate ignore it.
        if agg_impl not in AGG_IMPLS:
            raise ValueError(f"agg_impl {agg_impl!r} not in {AGG_IMPLS}")
        self.agg_impl = agg_impl
        self.agg_bucket_size = agg_bucket_size or DEFAULT_BUCKET_SIZE
        # topk: error-feedback top-k sparsification — the residual is
        # ALGORITHM STATE (State.agg_residual, checkpointed), so only
        # algorithms that declare topk_supported (and thread the
        # residual through their round bodies) may select it
        from ..parallel.collectives import topk_count

        # validated on EVERY impl, not just topk: the --obs_comm what-if
        # table prices the topk wire at this density on every run, so an
        # out-of-range value must fail here, not mid-run in WireCostModel
        topk_count(1, agg_topk_density)
        if agg_impl == "topk":
            if not self.topk_supported:
                raise ValueError(
                    f"{self.name}: agg_impl='topk' carries an error-"
                    "feedback residual in algorithm state; only the "
                    "central-aggregate algorithms that thread it "
                    "(fedavg/salientgrads) support it")
        self.agg_topk_density = agg_topk_density
        # 0 = exact per-group top-k; N = deterministic strided-subsample
        # threshold estimate (~N candidates/group — the DGC sampling
        # trick; EF absorbs the approximate shipped count)
        self.agg_topk_sample = int(agg_topk_sample)
        # hier: two-stage reduce — full-precision psum inside each
        # agg_hier_inner-device slice, agg_hier_wire across slices
        # (0 = auto slice split; 'sparse' wire = compressed-plan f32,
        # static-mask algorithms only)
        from ..parallel.collectives import HIER_WIRES

        if agg_hier_wire not in HIER_WIRES:
            raise ValueError(
                f"agg_hier_wire {agg_hier_wire!r} not in {HIER_WIRES}")
        self.agg_hier_wire = agg_hier_wire
        if int(agg_hier_inner) < 0:
            # the collectives layer uses -1 internally as the auto-split
            # sentinel; from config, 0 IS auto — a negative here is a
            # typo that would otherwise silently run the auto split
            # while run_identity records the never-applied request
            raise ValueError(
                f"agg_hier_inner {agg_hier_inner} must be >= 0 "
                "(0 = balanced auto split)")
        self.agg_hier_inner = int(agg_hier_inner)
        # overlap: group-ordered dispatch — each leaf-group bucket's
        # collective is emitted right after its own local contraction
        # (bit-identical math; scheduling freedom only, so it never
        # enters run identity)
        self.agg_overlap = bool(agg_overlap)
        # agg_kernels: XLA-vs-pallas backend for the wire's selection /
        # quantize kernels (ops/topk_select.py, ops/pallas_kernels.py).
        # Bit-identical by the tie-break contract, so it never enters
        # run identity (census class: inert, like agg_overlap /
        # donate_state); interpret mode keeps CPU runs on the same
        # kernel code a TPU session compiles for real.
        from ..ops.topk_select import check_kernels

        self.agg_kernels = check_kernels(agg_kernels)
        self._agg_sparse_plan = None   # set by static-mask subclasses
        self._agg_mesh_known = False   # lazily discovered from the data
        self._agg_mesh_val = None
        # fault_spec: deterministic PRNG-keyed fault injection on the
        # stacked local updates (robust/faults.py) — per-round dropout,
        # stragglers, NaN poison, Byzantine scaling, all derived from the
        # run seed so a resumed run replays the identical trace. guard:
        # the in-jit non-finite quarantine before _aggregate
        # (robust/guard.py); None = auto (on exactly when faults are
        # injected). Both live in the shared central-aggregate round body
        # (_train_selected_weighted) — algorithms without one ignore them
        # (and the CLI runner refuses the flags for those).
        from ..robust.faults import (make_fault_fn, make_labelflip_fn,
                                     parse_fault_spec)

        self.fault_spec = parse_fault_spec(fault_spec)
        self.fault_fn = (make_fault_fn(self.fault_spec, seed)
                         if self.fault_spec is not None
                         and self.fault_spec.any_active else None)
        # labelflip rides the DATA path (poisoned labels corrupt what the
        # client learns from, before training) — a separate hook from the
        # post-training delta injector, same key derivation
        self.labelflip_fn = make_labelflip_fn(
            self.fault_spec, seed,
            num_classes=int(getattr(model, "num_classes", 2) or 2))
        self.guard_enabled = (bool(guard) if guard is not None
                              else self.fault_fn is not None)
        if self.fault_fn is not None and not self.guard_enabled \
                and self.fault_spec.drop > 0:
            # nan/scale/straggle without the guard is a legitimate
            # undefended-chaos ablation (the poison really propagates);
            # drop WITHOUT the guard is silently inert — the 'dropped'
            # client's untouched update still aggregates at full weight
            raise ValueError(
                "fault_spec drop=... requires the guard (it is what "
                "excludes dropped clients from the aggregate); don't "
                "pass guard=False, or remove drop from the spec")
        if self.guard_enabled and self.guard_metrics_supported:
            # instance override: the guarded round also reports its
            # per-round quarantine counters (floats — the fused packed-
            # metric contract)
            self._round_metric_names = tuple(self._round_metric_names) + (
                "clients_dropped", "clients_quarantined")
        # robust_agg: Byzantine-robust replacement for the central
        # weighted mean (robust/aggregation.py — median / trimmed_mean /
        # krum / multikrum / norm_krum over the stacked client deltas).
        # Composes with every agg_impl: on a compressed wire the
        # statistic runs on the wire-DECODED rows
        # (collectives.wire_roundtrip_mat — ranking what the server
        # receives, not what the sender held), and under agg_impl='topk'
        # on the sparsified error-feedback rows. Orthogonal to the
        # transform defenses (defense clips/noises the stacked locals
        # first; the robust statistic then consumes the defended rows)
        # and to the guard (the estimators read the quarantine's
        # renormalized weights as their survivor mask).
        from ..robust.aggregation import ROBUST_AGGS

        if robust_agg not in ROBUST_AGGS:
            raise ValueError(
                f"robust_agg {robust_agg!r} not in {ROBUST_AGGS}")
        self.robust_agg = robust_agg
        if not 0.0 <= float(robust_trim) < 0.5:
            raise ValueError(
                f"robust_trim {robust_trim} must be in [0, 0.5) — "
                "trimming half or more per side leaves no survivors")
        self.robust_trim = float(robust_trim)
        if int(robust_krum_f) < 0:
            raise ValueError(
                f"robust_krum_f {robust_krum_f} must be >= 0 "
                "(0 = auto ceil(0.2 * cohort))")
        self.robust_krum_f = int(robust_krum_f)
        if float(robust_norm_bound) <= 0:
            raise ValueError(
                f"robust_norm_bound {robust_norm_bound} must be > 0")
        self.robust_norm_bound = float(robust_norm_bound)
        self._retry_nonce = 0  # watchdog rollback-retry cohort re-draw
        # eval_clients: sampled-eval mode (SURVEY §7's O(N^2)-eval
        # hard-part): evaluate a fixed seeded subset of clients instead of
        # the whole cohort; 0 = all. Reported means are over the subset.
        self._eval_idx = None
        if eval_clients and eval_clients < self.num_clients:
            self._eval_idx = jnp.asarray(np.sort(
                np.random.RandomState(seed).choice(
                    self.num_clients, eval_clients, replace=False)
            ).astype(np.int32))
        # shape used for parameter init: stored sample shape plus the
        # injected channel axis
        self.init_sample_shape = tuple(data.sample_shape) + (
            (1,) if channel_inject else ())
        # ... and its dtype: integer inputs (token ids) as stored, anything
        # floating as float32 (make_apply_fn casts it to the compute type)
        self.init_sample_dtype = (
            data.x_train.dtype
            if jnp.issubdtype(data.x_train.dtype, jnp.integer)
            else jnp.dtype(jnp.float32))
        # obs_numerics: in-jit training-dynamics telemetry
        # (obs/numerics.py) — per-layer-group update/grad norms,
        # non-finite precursor gauges, per-client drift/cosine, mask
        # dynamics — appended to _round_metric_names as ordinary f32
        # scalars so both the unfused record path and the fused
        # packed-metric transfer carry them sync-free. The plan's layer
        # groups come from the eval_shape params template (no compute);
        # off (the default) is bit-inert. Like every obs knob it never
        # enters run/checkpoint identity.
        self._numerics_plan = None
        if obs_numerics and self.numerics_supported:
            from ..obs.numerics import NumericsPlan

            self._numerics_plan = NumericsPlan.from_params(
                self.params_template(), slots=self.clients_per_round,
                with_mask=self.numerics_with_mask)
            self._round_metric_names = tuple(self._round_metric_names) \
                + self._numerics_plan.metric_names
        if hp.batching == "epoch":
            from ..parallel.multihost import host_client_counts

            n_biggest = int(np.max(host_client_counts(data.n_train)))
            budget = hp.steps_per_epoch * hp.batch_size
            if budget < n_biggest:
                logger.warning(
                    "epoch batching with steps_per_epoch*batch_size=%d < "
                    "largest client shard (%d): epochs are truncated — each "
                    "epoch trains on a fresh random %d-subset per client "
                    "instead of the full shard (the runner sizes "
                    "steps_per_epoch to ceil(max(n_i)/batch) and never "
                    "hits this)", budget, n_biggest, budget)
        # Training-time augmentation (reference parity: every CIFAR/tiny
        # batch goes through RandomCrop(H,4)+flip, cifar10/data_loader.py:
        # 46-50 — there is no off switch in the reference). "auto" turns it
        # on exactly when the loader declared the dataset augmentable
        # (data.aug_pad_value set); False disables; a callable is used as
        # the (rng, xb) -> xb augmentation directly.
        if callable(augment):
            self.augment_fn = augment
        elif augment in ("auto", True, 1) and \
                getattr(data, "aug_pad_value", None) is not None:
            import functools

            from ..data.cifar import random_crop_flip

            self.augment_fn = functools.partial(
                random_crop_flip, padding=4,
                pad_value=np.asarray(data.aug_pad_value, np.float32))
        else:
            self.augment_fn = None
        self.apply_fn = make_apply_fn(
            model, compute_dtype=self.compute_dtype,
            channel_inject=channel_inject)
        self.eval_client = make_eval_fn(self.apply_fn, loss_type, eval_batch)
        # donate_state: the state-ownership protocol (README "State
        # ownership & donation"). When on (and the algorithm declares
        # donate_supported), the round/finetune/fused/mask entry points
        # take OWNERSHIP of their input state via donate_argnums — the
        # [C, model] personal stack (and topk residual / eval cache)
        # aliases in place instead of being rewritten into a fresh
        # (1+C)-model allocation every call. The caller's input state is
        # INVALID after the call; any caller that deliberately re-runs
        # from a saved state must borrow a copy via clone_state first.
        # Bit-identical to the borrow path (aliasing only) — inert for
        # run identity; pinned by tests/test_donation.py.
        self._donate = bool(donate_state) and self.donate_supported
        # eval_cache: the in-state incremental personal-eval cache
        # (subclasses that support it set self.eval_cache before
        # super().__init__; everyone else is False). Validated here so
        # an unsupported combination dies at construction.
        self.eval_cache = bool(getattr(self, "eval_cache", False))
        if self.eval_cache:
            if not getattr(self, "track_personal", True):
                raise ValueError(
                    f"{self.name}: eval_cache caches the per-client "
                    "personal-eval terms — it needs the personal stack "
                    "(track_personal=True)")
            if self._eval_idx is not None:
                raise ValueError(
                    f"{self.name}: eval_cache indexes the full [C] "
                    "cohort; the sampled-eval subset (eval_clients) "
                    "composes poorly with it — use one or the other")
            # the O(S) in-graph row eval of the round body; an attr so
            # the forward-count test can wrap it and pin the width
            self._eval_cache_rows = self._vmap_clients(
                self.eval_client, in_axes=(0, 0, 0, 0))
        # client_store: the population-residency mode (core/client_store
        # .py — ROADMAP Open item 2). "device" (default) is today's
        # fully-resident layout; "host"/"disk" move the per-client rows
        # (personal_params, topk agg_residual) OFF device: state holds
        # None between rounds, each round attaches a transient [S]
        # cohort slab gathered from the store and stages the trained
        # slab back. The round program is the SAME round_fn traced at
        # slab width — sel_idx becomes stack positions arange(S) and the
        # population ids ride in through _trace_pop_idx for the two
        # reads that need them (fault keying, eval-cache scatter) — so
        # streamed runs are bit-identical to resident runs
        # (tests/test_client_store.py pins it) with HBM flat in C.
        # Residency never enters run identity (inert, like donate_state).
        self._trace_pop_idx = None  # set ONLY while tracing a store round
        self._store = None
        self._round_jit_store = None
        self._store_round_raw = None
        self._store_eval_cache = None   # host (correct, loss_sum, total)
        self._store_eval_dirty: List[np.ndarray] = []
        self._host_data = None          # cached numpy views of the shards
        self._host_test = None
        self.client_store = client_store
        self.store_hot_clients = int(store_hot_clients)
        if client_store != "device":
            from ..core.client_store import STORE_MODES, ClientStore

            if client_store not in ("device",) + STORE_MODES:
                raise ValueError(
                    f"client_store {client_store!r} not in "
                    f"{('device',) + STORE_MODES}")
            if not self.store_supported:
                raise ValueError(
                    f"{self.name}: client_store={client_store!r} needs "
                    "the store-backed round entry (fedavg/salientgrads/"
                    "ditto — the central-aggregate algorithms whose "
                    "per-client rows stream by cohort)")
            if self.clients_per_round >= self.num_clients:
                raise ValueError(
                    f"{self.name}: client_store streams the SAMPLED "
                    "cohort; full participation keeps every row on "
                    "device each round, so there is nothing to stream "
                    "— use client_store='device' (or frac < 1)")
            if self._eval_idx is not None:
                raise ValueError(
                    f"{self.name}: eval_clients indexes the resident "
                    "[C] personal stack; with client_store the stack "
                    "is not resident — use one or the other")
            if not getattr(self, "track_personal", True) \
                    and self.agg_impl != "topk":
                raise ValueError(
                    f"{self.name}: client_store={client_store!r} with "
                    "track_personal=False and no topk residual has no "
                    "per-client rows to stream — drop --client_store "
                    "(the run is already O(S) in device memory)")
            self._store = ClientStore(
                self.num_clients, mode=client_store,
                hot_clients=store_hot_clients, root=store_dir)
            # The residency contract covers the DATA shards too: loaders
            # hand back device-backed [C] stacks (pad_stack ends in
            # jnp.asarray), and a full-[C] x_train alone defeats
            # HBM-flat-in-C before the first round runs. Pull the shards
            # to host once so the device copies free; every store-mode
            # read goes through the numpy views in _store_host_rows.
            self.data = jax.tree_util.tree_map(
                lambda a: np.array(jax.device_get(a), copy=True),
                self.data)
        self._fused_cache: Dict[Any, Any] = {}  # (block, eval_every) -> jit
        self._personal_cache_reset()
        self._check_stack_fits()
        if self._folds():
            # the folding round keeps the global model until the last
            # client has started from it, so a donated state aliases
            # nothing in place and costs one more copy of the model
            # (2.1 GiB of temporaries at 568 M parameters, compiled for a
            # described v5e, PR 28): it borrows its state
            self._donate = False
        self._build()

    def init_model_params(self, rng: jax.Array):
        """The model's parameters for this cohort's sample shape and dtype."""
        from ..models import init_params

        with obs_trace.span("init_params"):
            return init_params(self.model, rng, self.init_sample_shape,
                               self.init_sample_dtype)

    def params_template(self):
        """The parameters' shapes and dtypes, nothing computed."""
        return jax.eval_shape(
            lambda: self.init_model_params(jax.random.PRNGKey(0)))

    # -- per-algorithm pieces -------------------------------------------------
    @abc.abstractmethod
    def _build(self) -> None:
        """Construct jitted round/eval functions."""

    @abc.abstractmethod
    def init_state(self, rng: jax.Array) -> Any:
        """Build the initial server state (params replicated / stacked)."""

    @abc.abstractmethod
    def run_round(self, state: Any, round_idx: int) -> Any:
        """Execute one federated round; returns (state, train_metrics dict)."""

    def eval_metrics(self, state: Any, x_test, y_test,
                     n_test) -> Dict[str, Any]:
        """Traceable eval hook (the fused round loop calls it in-graph).
        Subclasses implement this, or implement ``_eval_impl(state, x, y,
        n, personal_fn)`` (the algorithms with a partial-participation
        personal stack — the shared wrappers below route it), or override
        ``evaluate``; this guard restores the fail-fast contract that
        de-abstracting ``evaluate`` removed."""
        impl = getattr(self, "_eval_impl", None)
        if impl is not None:
            # traceable: the in-state eval cache when it is live (the
            # O(C)-forwards-free re-reduce), else the full personal
            # eval. Store mode without the cache routes to the host-side
            # store eval (NOT traceable — but the only in-graph caller,
            # the fused eval cadence, is refused with the store)
            pf = self._cache_personal_fn(state) or (
                self._personal_eval_store if self._store is not None
                else self._eval_personal)
            return impl(state, x_test, y_test, n_test, pf)
        raise NotImplementedError(
            f"{type(self).__name__} must implement eval_metrics (traceable"
            " eval over explicit test arrays), _eval_impl, or override"
            " evaluate")

    def evaluate(self, state: Any) -> Dict[str, Any]:
        """Evaluate per the reference protocol (global and/or personal
        per-client accuracy, mean over clients — sailentgrads_api.py:231-285).

        Default: algorithms providing ``_eval_impl`` get the host path
        with the INCREMENTAL personal eval (``_personal_eval_cached``);
        everyone else delegates to the traceable ``eval_metrics`` hook.
        Algorithms with host-side eval composition (DisPFL's per-round
        local tests, FedFomo) override ``evaluate`` directly."""
        d = self.data
        impl = getattr(self, "_eval_impl", None)
        if impl is not None:
            # in-state eval cache first (jitted [C] re-reduce, zero
            # forwards), then the store-backed incremental eval (the
            # personal stack is not resident), then the host-side
            # incremental cache
            pf = self._cache_personal_fn(state, jit=True) or (
                self._personal_eval_store if self._store is not None
                else self._personal_eval_cached)
            return impl(state, d.x_test, d.y_test, d.n_test, pf)
        return self.eval_metrics(state, d.x_test, d.y_test, d.n_test)

    def finalize(self, state: Any):
        """Optional end-of-training pass after the last round. Returns
        ``(state, record_or_None)``; the record (if any) is appended to the
        run history with ``round = -1`` (the reference's convention for the
        FedAvg final fine-tune pass, ``fedavg_api.py:79-88``)."""
        return state, None

    # whether per-client masks change between rounds (DisPFL fire/regrow,
    # SubAvg pruning) — if False the per-round cost record is constant and
    # the runner reuses it instead of pulling params to host every round
    masks_evolve: bool = False

    #: whether this algorithm's _round_jit threads the guard's per-round
    #: quarantine counters into its metric outputs (FedAvg/SalientGrads).
    #: Algorithms sharing _train_selected_weighted without threading the
    #: counters (Ditto's global leg) still get the guard itself.
    guard_metrics_supported: bool = False

    #: whether this algorithm's round body threads the in-jit numerics
    #: telemetry (obs/numerics.py) through its outputs — same support
    #: surface as guard_metrics_supported (the central-aggregate round).
    numerics_supported: bool = False

    #: whether the numerics plan also emits mask dynamics (churn /
    #: cross-client agreement) — static-mask algorithms (SalientGrads)
    numerics_with_mask: bool = False

    #: whether this algorithm's State carries the error-feedback
    #: residual (``agg_residual``) and its round body threads it through
    #: ``_train_selected_weighted`` — the ``agg_impl='topk'`` support
    #: surface (FedAvg/SalientGrads). The residual is real state: it is
    #: checkpointed, and a topk lineage is NOT interchangeable with
    #: other impls' checkpoints (run_identity splits it).
    topk_supported: bool = False

    #: whether this algorithm's jit entry points honor ``donate_state``
    #: (FedAvg/SalientGrads/Ditto — the central-aggregate rounds whose
    #: round bodies return every state field, so donation aliases the
    #: whole state in place). Requires the base ``_fused_data_args``
    #: layout: the donating fused program returns the threaded data
    #: arrays and ``run_rounds_fused`` rebinds ``self.data`` from them.
    donate_supported: bool = False

    #: whether this algorithm's round entry composes with the population
    #: client store (``--client_store host|disk``): its round_fn takes
    #: (state, sel_idx, round_idx, x, y, n[, test...]) with the
    #: per-client rows living on State.personal_params/agg_residual, and
    #: its body is width-polymorphic — the same trace runs at cohort-slab
    #: width [S] with sel_idx = arange(S) (FedAvg/SalientGrads/Ditto).
    store_supported: bool = False

    def clone_state(self, state: Any) -> Any:
        """Borrow API of the state-ownership protocol: a deep on-device
        copy of ``state``. Under ``donate_state`` every round/fused/
        finetune call CONSUMES its input state, so a caller that still
        needs the original afterwards — the watchdog's last-good, a
        harness re-running from a saved state, an equivalence
        gate replaying both spellings from one s0 — clones first and
        donates the clone (or donates the original and keeps the
        clone). A same-size copy when donation is off too, so caller
        code stays mode-independent."""
        return jax.tree_util.tree_map(jnp.copy, state)

    def _jit_entry(self, fn, donate=0):
        """jit an entry point under the ownership protocol:
        ``donate_argnums=donate`` when this instance donates, plain jit
        otherwise. Entry points donated here must return (or pass
        through) every input-state leaf so XLA can alias each donated
        buffer to an output — an unmatched donated leaf degrades to a
        copy-with-warning, never to corruption. A model may bring what
        its programs ask of the TPU's compiler (``tpu_compiler_options``:
        the selecting decoder's, models/decoder.py); no other backend
        knows the names."""
        kwargs = {} if not self._donate else {"donate_argnums": donate}
        options = getattr(self.model, "tpu_compiler_options", None)
        if options and jax.default_backend() == "tpu":
            kwargs["compiler_options"] = dict(options)
        return jax.jit(fn, **kwargs)

    def cost_trained_clients_per_round(self) -> int:
        """Client training passes one round actually runs (cost accounting).
        Default: the sampled subset. Decentralized/personalized algorithms
        that train the whole cohort (DisPFL/DPSGD/FedFomo) or several legs
        per client (Ditto) override this."""
        return self.clients_per_round

    def cost_snapshot(self, state: Any):
        """(params, mask) of one representative client for the per-round
        FLOPs/comm accounting (``stat_info``'s ``sum_training_flops`` /
        ``sum_comm_params``, ``sailentgrads_api.py:137-138``). For stacked
        personalized states the representative is the client whose overall
        mask density is closest to the cohort mean — client 0 would bias
        the counters when densities differ systematically across clients
        (DisPFL ``--diff_spa`` assigns client 0 the sparsest mask)."""
        params = getattr(state, "global_params", None)
        mask = getattr(state, "mask", None)
        rep = 0
        if mask is None:
            masks = getattr(state, "masks", None)
            if masks is not None:
                nz = sum(
                    jnp.count_nonzero(
                        m, axis=tuple(range(1, m.ndim))).astype(jnp.float32)
                    for m in jax.tree_util.tree_leaves(masks))
                dens = nz / jnp.maximum(jnp.sum(nz), 1.0)  # relative is enough
                rep = int(jnp.argmin(jnp.abs(dens - jnp.mean(dens))))
                mask = jax.tree_util.tree_map(lambda m: m[rep], masks)
        if params is None:
            stacked = getattr(state, "personal_params", None)
            if stacked is not None:
                params = jax.tree_util.tree_map(lambda p: p[rep], stacked)
        return params, mask

    # -- shared helpers -------------------------------------------------------
    def _selected_client_indexes(self, round_idx: int) -> np.ndarray:
        """``sample_client_indexes`` plus the full-participation contract
        check: ``_train_selected_weighted`` statically SKIPS the sel_idx
        gathers when ``clients_per_round == num_clients`` (the gathers
        would materialize a second full cohort copy on TPU), so the draw
        must be exactly ``arange(C)`` — a future permuted/sorted draw
        would silently misalign shards, sample weights, and the
        locals_-to-personal_params scatter. Cheap host-side guard
        (ADVICE r5); runs before dispatch, never under trace."""
        # retry passed only when set: the 3-arg call stays the reference
        # contract's exact signature (and test monkeypatch surface)
        with obs_trace.span("sample"):
            sel = sample_client_indexes(
                round_idx, self.num_clients, self.clients_per_round,
                retry=self._retry_nonce) if self._retry_nonce else \
                sample_client_indexes(
                    round_idx, self.num_clients, self.clients_per_round)
        if self.clients_per_round == self.num_clients and \
                not np.array_equal(sel, np.arange(self.num_clients)):
            raise ValueError(
                f"{self.name}: full participation requires sel_idx == "
                f"arange({self.num_clients}) — the round program "
                "statically skips the client gathers on that invariant; "
                f"got {sel!r}")
        return sel

    def set_retry_nonce(self, nonce: int) -> None:
        """Watchdog rollback-retry hook (robust/recovery.py): subsequent
        ``_selected_client_indexes`` draws re-sample the cohort with this
        nonce (0 = the reference draw). The fused path never retries —
        ``_fused_host_inputs`` precomputes draws with whatever nonce is
        set, which the runner pins to 0."""
        self._retry_nonce = int(nonce)

    def _agg_mesh(self):
        """The ``clients`` mesh the data lives on (None off-mesh), for the
        shard_map aggregation paths. Resolved once, lazily: the data is
        placed before the algorithm is built (the runner, the benchmark)."""
        if not self._agg_mesh_known:
            from ..parallel.mesh import mesh_of

            self._agg_mesh_val = mesh_of(self.data.x_train)
            self._agg_mesh_known = True
        return self._agg_mesh_val

    def place_state(self, state):
        """``state`` with the leaves that sit uncommitted on one device (a
        fresh PRNG key, a model initialised before the data was placed)
        replicated on the data's mesh, which is how a round returns them.
        Left where they are, the first round is lowered and compiled (or
        loaded) for their shardings and the second for the round's own:
        two programs for one, in every process (PERF.md section 6, PR 29:
        4.7 s of ``mesh4``'s set-up). Nothing moves off a mesh, or in a
        multi-process run, whose placement is ``parallel/multihost.py``'s."""
        mesh = self._agg_mesh()
        if mesh is None or jax.process_count() > 1:
            return state
        from jax.sharding import NamedSharding, PartitionSpec

        everywhere = NamedSharding(mesh, PartitionSpec())
        with obs_trace.span("place_state"):
            return jax.tree_util.tree_map(
                lambda a: jax.device_put(a, everywhere)
                if isinstance(a, jax.Array) and not a.committed else a,
                state)

    def _require_plan(self, what: str):
        if self._agg_sparse_plan is None:
            raise ValueError(
                f"{self.name}: {what} needs a static-mask gather plan "
                "(_agg_sparse_plan) built from the concrete mask before "
                "the round traces — only fixed-mask algorithms "
                "(SalientGrads) support it")
        return self._agg_sparse_plan

    def _aggregate(self, stacked, weights, rng=None):
        """The central weighted mean over the stacked client axis, routed
        by ``agg_impl`` (parallel/collectives.py). ``dense`` is bit-for-
        bit today's ``weighted_tree_sum``; every other impl trades exact
        association (and, for bf16/int8, wire precision — f32 master
        weights and accumulation always) for smaller / pipelined
        cross-chip transfers. Robust defenses already transformed
        ``stacked`` before this point, so they compose with every impl.

        ``topk`` here is the WIRE KERNEL only — top-k selection + reduce
        of whatever ``stacked`` holds (probes and benches time this
        path); the round body's :meth:`_topk_aggregate` owns the
        delta/residual bookkeeping around it."""
        with jax.named_scope("aggregate"):
            if self.agg_impl == "dense":
                from ..core.state import weighted_tree_sum

                return weighted_tree_sum(stacked, weights)
            from ..parallel import collectives

            kw = dict(mesh=self._agg_mesh(),
                      bucket_size=self.agg_bucket_size,
                      overlap=self.agg_overlap,
                      kernels=self.agg_kernels)
            if self.agg_impl == "topk":
                return collectives.topk_weighted_mean(
                    stacked, weights, self.agg_topk_density,
                    plan=self._agg_sparse_plan,
                    sample=self.agg_topk_sample, **kw)[0]
            if self.agg_impl == "hier":
                if self.agg_hier_wire == "sparse":
                    return collectives.sparse_weighted_mean(
                        stacked, weights,
                        self._require_plan("agg_hier_wire='sparse'"),
                        wire="f32", hier_inner=self.agg_hier_inner or -1,
                        **kw)
                return collectives.weighted_mean(
                    stacked, weights, wire=self.agg_hier_wire,
                    hier_inner=self.agg_hier_inner or -1, rng=rng, **kw)
            kw["rng"] = rng
            if self.agg_impl == "sparse":
                return collectives.sparse_weighted_mean(
                    stacked, weights,
                    self._require_plan("agg_impl='sparse'"), **kw)
            wire = {"bucketed": "f32", "bf16": "bf16", "int8": "int8"}[
                self.agg_impl]
            return collectives.weighted_mean(
                stacked, weights, wire=wire, **kw)

    def _robust_wire(self) -> str:
        """The wire format whose decode the robust statistic must rank:
        the agg_impl's cross-chip payload format. f32 for the exact
        impls (dense/bucketed/sparse are the same f32 contraction; topk
        has its own sparsified-row path in :meth:`_topk_aggregate`)."""
        if self.agg_impl in ("bf16", "int8"):
            return self.agg_impl
        if self.agg_impl == "hier" and \
                self.agg_hier_wire in ("bf16", "int8"):
            return self.agg_hier_wire
        return "f32"

    def _robust_aggregate(self, stacked, weights, global_params,
                          rng=None):
        """The ``--robust_agg`` central aggregate: replace the weighted
        mean with a Byzantine-robust statistic over the stacked client
        DELTAS (local − global; the estimators are shift-equivariant, so
        working in delta space changes nothing for median/trimmed-mean/
        Krum selection — but it is what norm_krum's clip stage and the
        wire roundtrip are defined on).

        On a compressed wire (bf16/int8, or hier's cross-slice wire)
        each delta row is first pushed through the wire's encode/decode
        (``collectives.wire_roundtrip_mat``): order statistics do not
        commute with quantization, so the statistic must rank the values
        the server would decode — int8 uses the round's ``agg_rng``
        stochastic-rounding draw, keeping the round bit-deterministic.

        ``lax.cond``-traceable with the same (stacked, weights)
        signature as :meth:`_aggregate`, so ``guard.guarded_aggregate``
        threads it unchanged: quarantine renormalizes the weights
        (quarantined rows exactly 0 — the estimators' survivor mask) and
        ``carry_if_empty`` covers the zero-survivor round."""
        from ..parallel import collectives
        from ..robust.aggregation import robust_combine_mat

        with jax.named_scope("robust_aggregate"):
            spec = collectives.flat_spec(stacked, stacked=True)
            mat = collectives.stacked_to_mat(stacked)
            gvec = collectives.tree_to_vec(global_params).astype(
                jnp.float32)
            deltas = mat - gvec[None]
            deltas = collectives.wire_roundtrip_mat(
                deltas, self._robust_wire(),
                bucket_size=self.agg_bucket_size, rng=rng)
            combined = robust_combine_mat(
                deltas, weights, self.robust_agg,
                trim_frac=self.robust_trim, krum_f=self.robust_krum_f,
                norm_bound=self.robust_norm_bound)
            return collectives.vec_to_tree(gvec + combined, spec)

    def _full_batches(self, hp: Optional[HyperParams] = None) -> bool:
        """Static guarantee for core.trainer's epoch fast path: every
        client's shard covers steps_per_epoch*batch_size samples, so all
        batches are full and all steps active (checked host-side on the
        concrete counts at build time; bit-identical semantics)."""
        hp = hp or self.hp
        if hp.batching != "epoch":
            return False
        from ..parallel.multihost import host_client_counts

        n = host_client_counts(self.data.n_train)
        return bool((n >= hp.steps_per_epoch * hp.batch_size).all())

    def _vmap_clients(self, fn, in_axes, max_chunk=None):
        """vmap ``fn`` over the leading client axis, optionally chunked
        (``max_chunk`` clients at once; not given: ``client_chunk``).

        On a pod, the full vmap is the right thing: each client's work lands
        on its own device. On fewer devices than clients, the vmapped
        activations of every client are live at once and can exceed HBM
        (AlexNet3D at full ABCD resolution); ``client_chunk`` trades that
        concurrency for a ``lax.map`` over chunks of clients — still one
        jitted program with zero host round-trips.
        """
        vfn = jax.vmap(fn, in_axes=in_axes)
        max_chunk = max_chunk or self.client_chunk
        if not max_chunk:
            return vfn

        def chunked(*args):
            # snap the chunk to the largest divisor of this call's client
            # count (the round uses clients_per_round, the SNIP pass all
            # clients — both shapes are static at trace time)
            first_mapped = next(
                a for ax, a in zip(in_axes, args) if ax is not None
            )
            n = jax.tree_util.tree_leaves(first_mapped)[0].shape[0]
            chunk = min(max_chunk, n)
            while n % chunk:
                chunk -= 1

            if chunk == 1:
                # no reshape: lax.map over the raw client axis. The
                # (C, n, ...) -> (C, 1, n, ...) reshape of the general
                # path materializes a full tiled COPY of the cohort on
                # TPU (measured 10.9 GB for the 32-client ABCD cohort —
                # the copy, not the model, is what OOMed the C=32 cell);
                # per-slice expand_dims inside the scan body is free
                def body1(chunk_args):
                    rebuilt = []
                    si = 0
                    for ax, a in zip(in_axes, args):
                        if ax is None:
                            rebuilt.append(a)  # closed-over, unbatched
                        else:
                            rebuilt.append(jax.tree_util.tree_map(
                                lambda x: x[None], chunk_args[si]))
                            si += 1
                    return jax.tree_util.tree_map(
                        lambda x: x[0], vfn(*rebuilt))

                mapped_in = tuple(
                    a for ax, a in zip(in_axes, args) if ax is not None
                )
                return jax.lax.map(body1, mapped_in)

            def reshape_in(ax, a):
                if ax is None:
                    return a
                return jax.tree_util.tree_map(
                    lambda x: x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:]),
                    a,
                )

            stacked = [reshape_in(ax, a) for ax, a in zip(in_axes, args)]

            def body(chunk_args):
                rebuilt = []
                si = 0
                for ax, a in zip(in_axes, args):
                    if ax is None:
                        rebuilt.append(a)  # closed-over, unbatched
                    else:
                        rebuilt.append(chunk_args[si])
                        si += 1
                return vfn(*rebuilt)

            mapped_in = tuple(
                s for ax, s in zip(in_axes, stacked) if ax is not None
            )
            out = jax.lax.map(body, mapped_in)
            return jax.tree_util.tree_map(
                lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]),
                out,
            )

        return chunked

    def _train_clients(self, client_update, n_clients: int):
        """``client_update`` mapped over the leading client axis of all its
        arguments but the round index: the local training of a round.

        Where the cohort lies on a ``clients`` mesh whose devices divide
        ``n_clients``, the mapping runs inside ``jax.shard_map`` over that
        axis: each chip lowers the training of its own sites as one device's
        program, which is what ``_vmap_clients`` was written for and what a
        Mosaic kernel needs (GSPMD cannot partition one:
        ``ops/pool_vjp.py``). Nothing in the local training talks across
        clients, so the work is the parent's; what surrounds it (gathers,
        faults, defenses, the aggregate's all-reduce) stays GSPMD's. A mesh
        with another axis of more than one device (``space``: GSPMD
        partitions each volume through the conv) and a client count the
        devices do not divide keep the one vmapped program.

        A chip that holds several of the round's sites is the one
        memory-limited device holding several clients of
        ``runner._auto_client_chunk``, and the same rule answers: it maps
        them one at a time unless ``client_chunk`` says otherwise. On the
        v5e the vmapped pair is also the slower program, by 22 % of the
        round (PERF.md section 6, PR 29)."""
        from jax.sharding import PartitionSpec as P

        in_axes = (0, 0, 0, 0, 0, 0, 0, None, 0)
        mesh = self._agg_mesh()
        n_dev = mesh.shape.get("clients", 1) if mesh is not None else 1
        if n_dev == 1 or mesh.size != n_dev or n_clients % n_dev:
            return self._vmap_clients(client_update, in_axes)
        auto = 1 if n_clients > n_dev and _device_memory_limit() else None
        return jax.shard_map(
            self._vmap_clients(client_update, in_axes,
                               self.client_chunk or auto),
            mesh=mesh, out_specs=P("clients"),
            in_specs=tuple(P() if ax is None else P("clients")
                           for ax in in_axes))

    def _train_selected_weighted(
        self, client_update, global_params, mask, sel_idx, round_idx,
        round_key, x_train, y_train, n_train, defense=None,
        residual=None,
    ):
        """Shared round body for global-model algorithms (FedAvg,
        SalientGrads): gather the selected clients' shards, broadcast the
        global model (and mask) along the client axis, run vmapped local
        SGD, optionally apply a robust-aggregation defense to the local
        models, and return the sample-weighted average, the (pre-defense)
        local models, the mean loss, the fault/guard stats, and the
        updated error-feedback residual
        (fedavg_api.py:40-117 / sailentgrads_api.py:112-147,212-227).

        Fault tolerance (robust/faults.py + robust/guard.py): when a
        ``fault_spec`` is set, the deterministic injector corrupts the
        stacked local models AFTER training (they model wire/client
        faults); when the guard is on, a single [S] finite-screen plus
        the injector's dropout flags quarantine the unusable clients —
        their rows are select-zeroed, the weights renormalize over the
        survivors, and a survivor count of 0 carries the previous global
        model. Both are pure selects when no client faults, so a guarded
        clean round is bit-identical to the unguarded one — and the
        sanitized tree feeds ``_aggregate`` unchanged, so quarantine
        composes with every ``agg_impl`` wire and the clip/DP defenses.

        The 4th return value is ``None`` when the guard is off, else a
        dict with ``ok`` ([S] survivor flags — callers use it to keep
        quarantined clients' previous personal models) and the f32
        ``clients_dropped`` / ``clients_quarantined`` counters.

        ``residual`` is the [C, ...] error-feedback residual stack
        (``agg_impl='topk'`` only — required there, ignored-and-returned
        otherwise): the 5th return value is the updated stack. The topk
        aggregate runs on compensated deltas and composes with the guard
        by construction — see :meth:`_topk_aggregate`."""
        from ..core.state import broadcast_tree, zeros_like_tree

        if self._folds():
            return self._train_selected_folded(
                client_update, global_params, mask, sel_idx, round_idx,
                round_key, x_train, y_train, n_train) + (residual,)
        if self.clients_per_round == self.num_clients:
            # full participation: sample_client_indexes always returns
            # arange (base.py early return), so the gathers are identity
            # — and jnp.take on the cohort materializes a second full
            # copy on TPU (measured 9.1 GB at C=32 full volume, the OOM
            # line of the clients32 cell). Statically skip them.
            n_sel, x_sel, y_sel = n_train, x_train, y_train
        else:
            with jax.named_scope("cohort_gather"):
                n_sel = jnp.take(n_train, sel_idx)
                x_sel = jnp.take(x_train, sel_idx, axis=0)
                y_sel = jnp.take(y_train, sel_idx, axis=0)
        if self.labelflip_fn is not None:
            # label-flip poisons the DATA PATH (before training — the
            # other fault kinds corrupt what leaves the client, this one
            # corrupts what the client learns from). Keys off the
            # population client id like the injector.
            lf_idx = sel_idx if self._trace_pop_idx is None \
                else self._trace_pop_idx
            y_sel = self.labelflip_fn(y_sel, lf_idx, round_idx)
        s = sel_idx.shape[0]
        params0 = broadcast_tree(global_params, s)
        mask_b = broadcast_tree(mask, s)
        mom0 = zeros_like_tree(params0)
        keys = jax.random.split(round_key, s + 1)
        # named_scope: trace-time HLO metadata only (zero runtime cost,
        # numerics untouched). The round's scopes, all string literals at
        # their call sites: cohort_gather, local_train, guard, aggregate,
        # robust_aggregate, personal_update, numerics, eval_cache (this
        # file; the first three also in _train_selected_folded);
        # batch_gather, optimizer (core/trainer.py, inside
        # local_train); stem with conv, norm, pool inside it
        # (models/alexnet3d.py:phased_stem_stage, inside the model's
        # module); embed, attention with full or window or cross (a
        # layer that reads another's K and V) or (a selecting layer's)
        # indexer, select and selected inside it, short_conv (a conv
        # layer's whole operator), ssm (a state-space mixer) and
        # linear_attention (a Gated DeltaNet layer) each with conv, scan
        # and norm inside it, mamba (a Mamba-1 mixer) with scan inside it,
        # gmu (a gated memory unit), router, experts, shared_expert,
        # dense_mlp, lm_head (models/decoder.py; lm_head also around the
        # per-token CE in core/losses.py).
        # The forward/backward pass needs none: JAX prints it as
        # the jvp()/transpose(jvp()) wrapper of the op_name.
        # benchmarks/metrics/*.json read these scopes BY NAME from the
        # device trace: a rename is an edit to both (and to PERF.md
        # section 3), and shows only after the persistent compile cache
        # is emptied (its key leaves HLO metadata out).
        with jax.named_scope("local_train"):
            params_out, _, losses = self._train_clients(client_update, s)(
                params0, mom0, mask_b, keys[:s], x_sel, y_sel, n_sel,
                round_idx, params0)
        dropped = None
        if self.fault_fn is not None:
            # inject AFTER training: faults model what leaves the client
            # (dropout, partial work, NaN poison, Byzantine scaling), so
            # the faulted tree is also what the personal stack would see.
            # The injector keys each fault off the POPULATION client id;
            # in store mode sel_idx is slab positions arange(S), so the
            # ids ride in via _trace_pop_idx — same values as resident.
            fault_idx = sel_idx if self._trace_pop_idx is None \
                else self._trace_pop_idx
            params_out, dropped = self.fault_fn(
                params_out, global_params, fault_idx, round_idx)
        # the defense guards the *aggregate*; each client's own (personal)
        # model stays its locally-trained weights, as in the reference where
        # w_per_mdls is set before any server-side processing
        defended = params_out
        if defense is not None:
            defended = defense.apply(params_out, global_params, keys[s])
        weights = n_sel.astype(jnp.float32)
        weights = weights / jnp.maximum(jnp.sum(weights), 1.0)
        agg_rng = None
        if self.agg_impl == "int8" or (
                self.agg_impl == "hier"
                and self.agg_hier_wire == "int8"):
            # stochastic-rounding draw; folded off round_key so the
            # client/defense key consumption (and hence the default
            # path's numerics) is untouched
            agg_rng = jax.random.fold_in(round_key, 0x616767)  # "agg"
        fstats = None
        ok = None
        if self.guard_enabled:
            from ..robust import guard as _guard

            with jax.named_scope("guard"):
                finite = _guard.finite_screen(defended)
                if dropped is not None:
                    ok = jnp.logical_and(finite, jnp.logical_not(dropped))
                    n_dropped = jnp.sum(dropped.astype(jnp.float32))
                    # quarantined = screened by the finite guard among the
                    # clients that did report (dropouts counted separately)
                    n_quar = jnp.sum(jnp.logical_and(
                        jnp.logical_not(finite), jnp.logical_not(dropped)
                    ).astype(jnp.float32))
                else:
                    ok = finite
                    n_dropped = jnp.asarray(0.0, jnp.float32)
                    n_quar = jnp.sum(
                        jnp.logical_not(finite).astype(jnp.float32))
            fstats = {"ok": ok, "clients_dropped": n_dropped,
                      "clients_quarantined": n_quar}
        if self.robust_agg != "none" and self.agg_impl != "topk":
            # the robust statistic REPLACES the weighted mean; same
            # (stacked, weights) signature, so the guard threads it
            # through guarded_aggregate unchanged
            def agg_fn(st, wv):
                return self._robust_aggregate(
                    st, wv, global_params, agg_rng)
        else:
            def agg_fn(st, wv):
                return self._aggregate(st, wv, agg_rng)
        if self.agg_impl == "topk":
            new_global, new_residual = self._topk_aggregate(
                defended, global_params, residual, sel_idx, weights, ok)
        elif self.guard_enabled:
            from ..robust import guard as _guard

            new_global = _guard.guarded_aggregate(
                defended, weights, ok, agg_fn, global_params)
            new_residual = residual
        else:
            new_global = agg_fn(defended, weights)
            new_residual = residual
        return (new_global, params_out, jnp.mean(losses), fstats,
                new_residual)

    def _stack_readers(self) -> List[str]:
        """The options of this run that read the selected clients' stacked
        local models ``[S, model]`` after training, by the flag that sets
        each; empty when the round needs the weighted mean and the loss
        alone. Algorithms that do not say so (``track_personal``) are taken
        to read the stack."""
        asked = {
            "--track_personal 1 (the personal models are the trained "
            "clients' local ones)": getattr(self, "track_personal", True),
            "--defense_type": getattr(self, "defense", None) is not None,
            "--fault_spec": (self.fault_fn is not None
                             or self.labelflip_fn is not None),
            "--guard": self.guard_enabled,
            f"--robust_agg {self.robust_agg}": self.robust_agg != "none",
            f"--agg_impl {self.agg_impl}": self.agg_impl != "dense",
            "--obs_numerics": self._numerics_plan is not None,
            f"--client_store {self.client_store}": self._store is not None,
        }
        return [flag for flag, on in asked.items() if on]

    def _folds(self) -> bool:
        """Whether the round folds each client into the weighted sum as it
        finishes (:meth:`_train_selected_folded`): the clients train one at
        a time and nothing reads their stacked local models."""
        return self.client_chunk == 1 and not self._stack_readers()

    def _check_stack_fits(self) -> None:
        """Refuse at build a round that must stack the local models on a
        device that cannot hold the stack: with the clients trained one at
        a time on one memory-limited device (``client_chunk`` 1) the stacked
        body keeps the global model, the broadcast start and the trained
        stack, ``(2 S + 1)`` models; the folding body keeps two."""
        readers = self._stack_readers()
        limit = _device_memory_limit()
        if not readers or not limit or self.client_chunk != 1 \
                or not hasattr(self, "track_personal"):
            return
        model_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in
                          jax.tree_util.tree_leaves(self.params_template()))
        need = (2 * self.clients_per_round + 1) * model_bytes
        if need > limit:
            raise ValueError(
                f"{self.name}: {', '.join(readers)} read(s) the stacked "
                f"local models of the {self.clients_per_round} clients of "
                f"a round: {need / 2**30:.1f} GiB with the global model, "
                f"and the device holds {limit / 2**30:.1f} GiB. Without "
                "them the round folds each client into the weighted sum "
                "as it finishes and keeps two models")

    def _train_selected_folded(self, client_update, global_params, mask,
                               sel_idx, round_idx, round_key, x_train,
                               y_train, n_train):
        """The round body of :meth:`_train_selected_weighted` where nothing
        reads the stacked locals and the clients train one at a time: a
        ``lax.scan`` over the selected clients that carries the running
        weighted sum, under the same scopes. Same selection, keys and
        weights; the new global differs from the stacked body's by float32
        summation order, the loss not at all. A client carries no momentum
        buffer when ``hp.momentum == 0``. Returns ``(new_global, None,
        mean_loss, None)``: no locals, no guard statistics."""
        from ..core.state import zeros_like_tree

        s = sel_idx.shape[0]
        keys = jax.random.split(round_key, s + 1)[:s]
        weights = jnp.take(n_train, sel_idx).astype(jnp.float32)
        weights = weights / jnp.maximum(jnp.sum(weights), 1.0)
        mom0 = zeros_like_tree(global_params) if self.hp.momentum else None

        def one_client(total, client):
            idx, key, weight = client
            with jax.named_scope("cohort_gather"):
                x, y, n = (jax.lax.dynamic_index_in_dim(a, idx, 0, False)
                           for a in (x_train, y_train, n_train))
            with jax.named_scope("local_train"):
                params, _, loss = client_update(
                    global_params, mom0, mask, key, x, y, n, round_idx,
                    global_params)
            with jax.named_scope("aggregate"):
                total = jax.tree_util.tree_map(
                    lambda t, p: t + weight.astype(p.dtype) * p, total,
                    params)
            return total, loss

        new_global, losses = jax.lax.scan(
            one_client, zeros_like_tree(global_params),
            (sel_idx, keys, weights))
        return new_global, None, jnp.mean(losses), None

    def _topk_aggregate(self, locals_, global_params, residual, sel_idx,
                        weights, ok):
        """The ``agg_impl='topk'`` round aggregate with error feedback
        (Deep Gradient Compression semantics on the federated round):

        1. each selected client's delta = local − global, COMPENSATED by
           its carried residual row;
        2. per-leaf-group top-k selection + weighted mean of the
           sparsified rows (``collectives.topk_weighted_mean`` — the
           wire);
        3. the unsent remainder (compensated − sparsified) becomes the
           client's new residual row — nothing is dropped, only
           deferred;
        4. ``new_global = global + aggregate(sparsified)``.

        Guard composition (``ok`` = the finite screen's survivor flags,
        None when the guard is off): quarantined rows are select-zeroed
        BEFORE selection and the weights renormalize over survivors —
        the same ``lax.cond``-gated spelling as
        ``guard.guarded_aggregate``, so a clean round runs topk on the
        untouched inputs (bit-identical to guard-off) and never pays
        the O(C x params) sanitize/merge; zero survivors carries the
        previous global; and a quarantined client's residual row keeps
        its PREVIOUS value (``guard.merge_residual`` — the poisoned
        compensated delta must not leak into later rounds through the
        residual)."""
        from ..core.state import tree_index, tree_scatter_update
        from ..parallel import collectives
        from ..robust import guard as _guard

        if residual is None:
            raise ValueError(
                f"{self.name}: agg_impl='topk' round body called without "
                "the residual stack — init_state must seed "
                "State.agg_residual (zeros_like the personal stack "
                "layout) when agg_impl='topk'")
        full = self.clients_per_round == self.num_clients
        # full participation skips the identity gather (the same
        # second-cohort-copy hazard as the data gathers above)
        res_sel = residual if full else tree_index(residual, sel_idx)
        comp = jax.tree_util.tree_map(
            lambda loc, g, r: (loc - g[None]) + r,
            locals_, global_params, res_sel)
        if self._agg_sparse_plan is not None:
            # static-mask composition: dead coordinates never ship (the
            # compressed selection can't see them), so they must not
            # enter the residual either — a select against the plan's
            # live mask (round 0's dense init would otherwise sit in
            # the residual forever)
            comp = collectives.plan_dead_select(
                comp, self._agg_sparse_plan)
        def run_topk(comp_in, w):
            if self.robust_agg != "none":
                # robust statistic under error feedback: sparsify each
                # client's compensated delta as usual (the wire), then
                # combine the SPARSIFIED rows robustly instead of
                # weighted-mean — a rejected client's shipped
                # coordinates still leave its residual (EF subtracts
                # what was SENT, not what the server accepted; the
                # rejected mass is simply gone, which is the point)
                from ..robust.aggregation import robust_combine_mat

                sp = collectives.topk_sparsify(
                    comp_in, self.agg_topk_density,
                    plan=self._agg_sparse_plan,
                    bucket_size=self.agg_bucket_size,
                    sample=self.agg_topk_sample)
                agg_update = collectives.vec_to_tree(
                    robust_combine_mat(
                        collectives.stacked_to_mat(sp), w,
                        self.robust_agg, trim_frac=self.robust_trim,
                        krum_f=self.robust_krum_f,
                        norm_bound=self.robust_norm_bound),
                    collectives.flat_spec(sp, stacked=True))
            else:
                agg_update, sp = collectives.topk_weighted_mean(
                    comp_in, w, self.agg_topk_density,
                    plan=self._agg_sparse_plan, mesh=self._agg_mesh(),
                    bucket_size=self.agg_bucket_size,
                    overlap=self.agg_overlap,
                    sample=self.agg_topk_sample)
            new_global = jax.tree_util.tree_map(
                lambda g, u: (g + u).astype(g.dtype), global_params,
                agg_update)
            new_rows = jax.tree_util.tree_map(
                lambda c, s: c - s, comp_in, sp)
            return new_global, new_rows

        if ok is None:
            new_global, new_rows = run_topk(comp, weights)
        else:
            # the guarded dense path's lax.cond spelling
            # (guard.guarded_aggregate): the clean branch runs topk on
            # the untouched inputs, so a clean round never pays the
            # O(C x params) quarantine sanitize / residual merge — only
            # the read-only finite screen that produced ``ok``
            def bad(args):
                c, wv = args
                comp_in, w, survivors = _guard.quarantine(c, wv, ok)
                ng, nr = run_topk(comp_in, w)
                ng = _guard.carry_if_empty(ng, global_params, survivors)
                nr = _guard.merge_residual(ok, nr, res_sel)
                return ng, nr

            new_global, new_rows = jax.lax.cond(
                jnp.logical_not(jnp.all(ok)), bad,
                lambda args: run_topk(*args), (comp, weights))
        new_residual = new_rows if full else tree_scatter_update(
            residual, sel_idx, new_rows)
        return new_global, new_residual

    def _guarded_personal_update(self, personal, locals_, sel_idx, fstats):
        """Scatter the selected clients' trained models into the [C, ...]
        personal stack (w_per_mdls semantics), guard-aware: quarantined /
        dropped clients never delivered an update, so their previous
        personal rows are kept (and NaN poison stays out of the stack).
        Shared by every round_fn that carries a personal stack."""
        if personal is None:
            return None
        from ..core.state import tree_scatter_update

        with jax.named_scope("personal_update"):
            upd = locals_
            if fstats is not None:
                from ..robust import guard as _guard

                upd = _guard.merge_updates(
                    fstats["ok"], locals_, personal, sel_idx)
            return tree_scatter_update(personal, sel_idx, upd)

    def _numerics_outputs(self, old_global, new_global, locals_,
                          mask=None):
        """The in-jit numerics telemetry scalars (obs/numerics.py) for
        this round, in ``_round_metric_names`` order — ``()`` when
        ``--obs_numerics`` is off (bit-inert). Computed on the round's
        already-live arrays under its own ``named_scope`` so the XLA
        device trace labels the readout alongside local_train / guard /
        aggregate."""
        if self._numerics_plan is None:
            return ()
        with jax.named_scope("numerics"):
            return self._numerics_plan.compute(
                old_global, new_global, locals_, mask=mask)

    def _round_outputs(self, state, mean_loss, fstats, numerics=()):
        """A round_fn's return tuple, matching ``_round_metric_names``:
        ``(state, train_loss)`` plus the guard's per-round counters when
        this algorithm threads them (guard_metrics_supported), plus the
        in-jit numerics scalars when ``--obs_numerics`` is on."""
        if fstats is None or not self.guard_metrics_supported:
            return (state, mean_loss) + tuple(numerics)
        return (state, mean_loss, fstats["clients_dropped"],
                fstats["clients_quarantined"]) + tuple(numerics)

    def _train_stacked(self, client_update, params_stack, mask_stack,
                       round_idx, round_key, x, y, n, prox_target=None):
        """Every client trains its own stacked state on its own shard —
        the whole-cohort local-training pass used by the decentralized /
        personalized algorithms (DisPFL, DPSGD, FedFomo, Local, Ditto's
        personal leg). Returns (params_stack, momentum_stack, losses[C])."""
        from ..core.state import zeros_like_tree

        c = jax.tree_util.tree_leaves(params_stack)[0].shape[0]
        keys = jax.random.split(round_key, c)
        mom0 = zeros_like_tree(params_stack)
        if prox_target is None:
            prox_target = params_stack
        return self._train_clients(client_update, c)(
            params_stack, mom0, mask_stack, keys, x, y, n, round_idx,
            prox_target)

    def _make_global_eval(self):
        eval_client = self.eval_client
        eval_idx = self._eval_idx

        @jax.jit
        def eval_all(params, x_test, y_test, n_test):
            if eval_idx is not None:  # sampled-eval subset
                x_test = jnp.take(x_test, eval_idx, axis=0)
                y_test = jnp.take(y_test, eval_idx, axis=0)
                n_test = jnp.take(n_test, eval_idx)
            correct, loss_sum, total = jax.vmap(
                lambda x, y, n: eval_client(params, x, y, n)
            )(x_test, y_test, n_test)
            totals = jnp.maximum(total, 1)
            acc = correct.astype(jnp.float32) / totals
            return {
                "acc_per_client": acc,
                "acc": jnp.mean(acc),
                "loss": jnp.sum(loss_sum) / jnp.maximum(jnp.sum(total), 1),
            }

        return eval_all

    # -- incremental personal eval --------------------------------------------
    # At frac<1 only the TRAINED clients' personal models change per round
    # (w_per_mdls semantics), so the per-round personal eval can reuse the
    # previous per-client (correct, loss_sum, total) for unsampled clients
    # and re-evaluate only the clients trained since the last eval —
    # O(rounds_since_eval x clients_per_round) forwards instead of O(C).
    # The cache lives OUTSIDE the algorithm State (not checkpointed, not in
    # the fused scan carry): validity is guarded by object identity — the
    # cache applies only to the exact personal_params object produced by
    # this algorithm's own run_round chain, so evaluating any other state
    # (a restored checkpoint, a saved earlier state, a finalize output)
    # falls back to the full eval and reseeds. Accuracies are bitwise
    # identical to the full eval (integer counts / totals over identical
    # params); losses agree to f32 round-off — the subset-width eval
    # program may reassociate a client's loss-sum reduction vs the
    # full-width program (measured 1 ulp; the same tolerance the
    # fused-vs-unfused eval gate carries). tests/test_cost_personal.py
    # pins both.

    def _personal_cache_reset(self) -> None:
        self._pers_cache = None       # (correct[C], loss_sum[C], total[C])
        self._pers_expected = None    # the personal_params object cached
        self._pers_dirty: List[np.ndarray] = []  # sel draws since last eval

    def _note_personal_update(self, old_pers, new_pers, sel_idx) -> None:
        """Called by run_round after the round program is dispatched:
        ``new_pers`` differs from ``old_pers`` only at ``sel_idx``."""
        if old_pers is None or new_pers is None:
            return
        if self._eval_idx is not None:
            # sampled-eval mode never uses the cache — don't accumulate
            # an unbounded dirty list for a statically-disabled path
            return
        if self._pers_expected is not old_pers:
            # unknown lineage (fresh state, resume, fused block):
            # the next eval reseeds from a full pass
            self._pers_cache = None
            self._pers_dirty = []
        self._pers_dirty.append(np.asarray(sel_idx))
        self._pers_expected = new_pers

    def _personal_eval_cached(self, pers, x_test, y_test, n_test):
        """Personal-eval protocol result, incrementally when valid."""
        if (self._pers_cache is None or pers is not self._pers_expected
                or self._eval_idx is not None):
            # full pass (also the sampled-eval mode — its subset indexing
            # composes poorly with the per-client cache)
            ev = self._eval_personal(pers, x_test, y_test, n_test)
            if self._eval_idx is None:
                self._pers_cache = (ev["correct"], ev["loss_sum"],
                                    ev["total"])
                self._pers_expected = pers
                self._pers_dirty = []
            return ev
        dirty = np.concatenate(self._pers_dirty) if self._pers_dirty \
            else np.zeros((0,), np.int32)
        if dirty.size >= self.num_clients:
            ev = self._eval_personal(pers, x_test, y_test, n_test)
        elif dirty.size == 0:
            # nothing changed since the last eval (e.g. the finalize
            # re-eval): recompute the protocol means from the cached
            # per-client terms — same [C]-shaped reductions, no forwards
            if not hasattr(self, "_pers_metrics_fn"):
                self._pers_metrics_fn = jax.jit(_personal_metrics)
            ev = self._pers_metrics_fn(*self._pers_cache)
        else:
            if not hasattr(self, "_eval_personal_merge_fn"):
                self._eval_personal_merge_fn = \
                    self._make_personal_eval_merge()
            ev = self._eval_personal_merge_fn(
                pers, jnp.asarray(dirty.astype(np.int32)),
                *self._pers_cache, x_test, y_test, n_test)
        self._pers_cache = (ev["correct"], ev["loss_sum"], ev["total"])
        self._pers_expected = pers
        self._pers_dirty = []
        return ev

    def _make_personal_eval_merge(self):
        """jit: evaluate ONLY the ``sel`` clients' personal models, merge
        into the cached per-client arrays, return the protocol metrics
        (identical reductions to ``_make_personal_eval``). Duplicate
        entries in ``sel`` recompute identical values — harmless."""
        eval_client = self.eval_client
        vmapped = self._vmap_clients(eval_client, in_axes=(0, 0, 0, 0))

        @jax.jit
        def eval_merge(params_stack, sel, correct, loss_sum, total,
                       x_test, y_test, n_test):
            from ..core.state import tree_index

            sub = tree_index(params_stack, sel)
            c_s, l_s, t_s = vmapped(
                sub, jnp.take(x_test, sel, axis=0),
                jnp.take(y_test, sel, axis=0), jnp.take(n_test, sel))
            correct = correct.at[sel].set(c_s)
            loss_sum = loss_sum.at[sel].set(l_s)
            total = total.at[sel].set(t_s)
            return _personal_metrics(correct, loss_sum, total)

        return eval_merge

    def _make_personal_eval(self):
        """Eval stacked per-client params, each on its own client's test
        set. Runs through ``_vmap_clients`` so ``client_chunk`` bounds the
        concurrent per-client activations — personal eval carries
        per-client WEIGHTS, so XLA cannot fold the client axis into the
        conv batch the way the shared-params global eval does, and the
        full vmap at ABCD volume would hold every client's eval
        activations at once."""
        eval_client = self.eval_client
        eval_idx = self._eval_idx
        vmapped = self._vmap_clients(eval_client, in_axes=(0, 0, 0, 0))

        @jax.jit
        def eval_personal(params_stack, x_test, y_test, n_test):
            if eval_idx is not None:  # sampled-eval subset
                from ..core.state import tree_index

                params_stack = tree_index(params_stack, eval_idx)
                x_test = jnp.take(x_test, eval_idx, axis=0)
                y_test = jnp.take(y_test, eval_idx, axis=0)
                n_test = jnp.take(n_test, eval_idx)
            correct, loss_sum, total = vmapped(
                params_stack, x_test, y_test, n_test
            )
            return _personal_metrics(correct, loss_sum, total)

        return eval_personal

    # -- in-state incremental personal eval (--eval_cache) --------------------
    # The host-side cache above cannot ride the fused scan (its validity
    # is object identity) and dies with the process. eval_cache moves
    # the per-client (correct, loss_sum, total) terms INTO algorithm
    # state: the round body evaluates ONLY the trained clients' post-
    # guard personal rows and scatters them into the cache — O(S)
    # forwards per round instead of O(C) per eval — and the eval (host
    # or in the fused cond branch) is a [C] re-reduce with ZERO
    # forwards. Because the cache is state, it checkpoints, resumes,
    # rides the fused carry, and rolls back with the watchdog (a
    # rolled-back round's cache rows are discarded with the state —
    # a poisoned attempt can never leave a row behind). Quarantined
    # clients keep their previous personal rows (merge_updates), so
    # their re-evaluated cache rows reproduce the previous values:
    # poison-free by construction. State-schema change: eval_cache
    # lineages split both identities ('evcache' — the r5 track_personal
    # / PR-7 agg_residual migration pattern).

    def _seed_eval_cache(self, personal):
        """Initial cache: one full personal eval of the fresh stack
        (a one-time O(C) pass at init; every later round pays O(S))."""
        if not self.eval_cache or personal is None:
            return None
        d = self.data
        ev = self._eval_personal(personal, d.x_test, d.y_test, d.n_test)
        return {"correct": ev["correct"], "loss_sum": ev["loss_sum"],
                "total": ev["total"]}

    def _update_eval_cache(self, cache, new_personal, sel_idx,
                           x_test, y_test, n_test):
        """In-graph cache refresh (round body): evaluate the selected
        clients' (post-guard) personal rows, scatter into the cache.
        Full participation updates every row in place (the sel gathers
        would materialize a second stack copy — same hazard as the
        training-data gathers)."""
        if cache is None:
            return None
        from ..core.state import tree_index

        with jax.named_scope("eval_cache"):
            if self.clients_per_round == self.num_clients:
                c, ls, t = self._eval_cache_rows(
                    new_personal, x_test, y_test, n_test)
                return {"correct": c, "loss_sum": ls, "total": t}
            # store mode: sel_idx addresses the [S] slab (stack
            # positions; the gathers below are identity over the slab
            # and the test rows arrive pre-gathered at the same width),
            # while the [C] cache scatter needs the population ids the
            # store wrapper parked in _trace_pop_idx. Same indices, same
            # values, same width-S eval program as resident.
            scatter_idx = sel_idx if self._trace_pop_idx is None \
                else self._trace_pop_idx
            sub = tree_index(new_personal, sel_idx)
            c, ls, t = self._eval_cache_rows(
                sub, jnp.take(x_test, sel_idx, axis=0),
                jnp.take(y_test, sel_idx, axis=0),
                jnp.take(n_test, sel_idx))
            return {"correct": cache["correct"].at[scatter_idx].set(c),
                    "loss_sum": cache["loss_sum"].at[scatter_idx].set(ls),
                    "total": cache["total"].at[scatter_idx].set(t)}

    def _cache_personal_fn(self, state, jit: bool = False):
        """The personal-eval fn backed by ``state.eval_cache`` (the
        zero-forwards [C] re-reduce), or None when the cache is off or
        not live on this state (e.g. post-finetune, where the stack was
        retrained wholesale and finalize dropped the stale cache) — the
        caller then falls back to the full/host-cached eval."""
        cache = getattr(state, "eval_cache", None)
        if not self.eval_cache or cache is None:
            return None
        if jit and not hasattr(self, "_pers_metrics_fn"):
            self._pers_metrics_fn = jax.jit(_personal_metrics)
        fn = self._pers_metrics_fn if jit else _personal_metrics

        def from_cache(_pers, _x, _y, _n):
            return fn(cache["correct"], cache["loss_sum"],
                      cache["total"])

        return from_cache

    # -- fused multi-round execution ------------------------------------------
    #: True for algorithms whose only host-side per-round work is the
    #: seeded client draw; their whole round block can run as ONE jitted
    #: program (an outer ``lax.scan`` over rounds — the TPU-idiomatic
    #: extension of "no Python between clients" to "no Python between
    #: rounds"). The draws stay host-precomputed with the exact
    #: ``np.random.seed(round_idx)`` calls of the unfused path, so the
    #: reference's cross-algorithm sampling contract (fedavg_api.py:92-100)
    #: is preserved bit-for-bit.
    supports_fused: bool = False

    #: names for the scalars ``_round_jit`` returns after the state
    _round_metric_names = ("train_loss",)

    def _fused_host_inputs(self, round_idx: int):
        """The per-round host-side inputs of ``run_round``, to be stacked
        along a leading round axis for the fused scan. Standard centralized
        algorithms: the seeded (contract-checked) client draw."""
        return (self._selected_client_indexes(round_idx),)

    def _fused_data_args(self):
        """Round-invariant device args of ``_round_jit`` after round_idx."""
        d = self.data
        return (d.x_train, d.y_train, d.n_train)

    def _get_fused_fn(self, block: int, eval_every: int,
                      store: bool = False):
        """Build (and cache per (block, eval_every)) the jitted K-round
        program: ``lax.scan`` over the round body with the eval cadence
        folded in-graph via ``lax.cond`` (zero host round-trips inside a
        block; the reference's ``frequency_of_the_test`` cadence,
        main_sailentgrads.py:90).

        Memory structure (the C=32 OOM fix): the cohort data (and, when
        the eval cadence or the eval cache consumes them, the test
        arrays) ride the scan CARRY as explicit pass-through loop state
        instead of closed-over body constants. A closure constant of a
        scan body lowers to a while-loop invariant that XLA must COPY
        into the loop's buffer space when the jit parameter cannot be
        aliased — the "second cohort copy" that OOMed the C=32 cell
        (RESULTS.md section 3). As loop state returned unchanged, the
        buffers alias in-place through the loop; with ``donate_state``
        the whole chain aliases — jit parameter -> loop state -> output
        (the program returns the threaded arrays, and
        ``run_rounds_fused`` rebinds ``self.data`` to the aliased
        outputs so the caller's view stays valid)."""
        cache = self._fused_cache
        key = (block, eval_every, store)
        if key in cache:
            return cache[key]
        # store=True: same program shape over the block-union [U] slab —
        # the two host inputs per round are (slab positions, population
        # ids) instead of the single resident draw, the data args are
        # the union's [U] rows instead of the full cohort, and the round
        # call is the store wrapper (parks the population ids in
        # _trace_pop_idx around the unchanged round_fn). Within-block
        # row chaining rides the carried slab exactly as it rides the
        # carried [C] stack resident — bit-identical by construction.
        n_host = 2 if store else len(self._fused_host_inputs(0))
        n_data = len(self._fused_data_args())
        # test arrays enter the loop only when consumed (eval cadence
        # in-graph, or the per-round eval-cache update); an eval-free
        # block without the cache drops them entirely so they are not
        # made loop-resident for nothing
        use_test = bool(eval_every) or self.eval_cache
        # calling the RAW round fn (not its jitted wrapper) inside the
        # scan body: same primitives inlined, and it keeps a donated
        # _round_jit's donate_argnums from being re-interpreted inside
        # an outer trace
        if store:
            self._get_store_round_jit()  # builds _store_round_raw
            round_call = self._store_round_raw
        else:
            round_call = getattr(self, "_round_fn", None) or \
                self._round_jit

        def fused(state, host_stack, round_ids, *args):
            def body(carry, xs):
                s, data_args, test_args = carry
                hins, r = xs[:n_host], xs[n_host]
                extra = test_args if self.eval_cache else ()
                out = round_call(s, *hins, r, *data_args, *extra)
                s, metrics = out[0], out[1:]
                # fail fast if a subclass's _round_jit outputs drifted from
                # its _round_metric_names — dict(zip(...)) would silently
                # drop or mislabel metrics (ADVICE r4). An explicit raise,
                # not assert: python -O must not strip the trace-time
                # contract (ADVICE r5)
                if len(metrics) != len(self._round_metric_names):
                    raise ValueError(
                        f"{type(self).__name__}._round_jit returned "
                        f"{len(metrics)} metrics but _round_metric_names "
                        f"has {len(self._round_metric_names)}")
                ys = dict(zip(self._round_metric_names, metrics))
                if eval_every:
                    # branches defined HERE so the test arrays they read
                    # are the carry's loop-state views, not hoisted
                    # closure constants (the second-copy hazard again)
                    def eval_branch(sb):
                        return {k: v for k, v in
                                self.eval_metrics(sb, *test_args).items()
                                if not k.startswith("acc_per")}

                    def zero_branch(sb):
                        shapes = jax.eval_shape(eval_branch, sb)
                        return jax.tree_util.tree_map(
                            lambda t: jnp.zeros(t.shape, t.dtype), shapes)

                    do = (r.astype(jnp.int32) + 1) % eval_every == 0
                    ys["eval"] = jax.lax.cond(
                        do, eval_branch, zero_branch, s)
                return (s, data_args, test_args), ys

            carry0 = (state, args[:n_data],
                      args[n_data:] if use_test else ())
            (state, data_out, test_out), ys = jax.lax.scan(
                body, carry0, host_stack + (round_ids,))
            # pack every per-round scalar series into ONE f32 array: the
            # host materializes a block's metrics in a single transfer
            # instead of one blocking fetch per leaf. CONTRACT: every _round_metric_names /
            # eval_metrics leaf must be an inexact (floating) scalar — the
            # f32 cast is the canonical record dtype, and an int/bool
            # metric would be silently coerced (raised here, ADVICE r4;
            # explicit raise so python -O cannot strip it, ADVICE r5)
            for x in jax.tree_util.tree_leaves(ys):
                if not jnp.issubdtype(x.dtype, jnp.inexact):
                    raise TypeError(
                        f"per-round metrics must be floating (got "
                        f"{x.dtype}); the packed single-transfer stack "
                        "records f32")
            packed = jnp.stack([
                x.astype(jnp.float32)
                for x in jax.tree_util.tree_leaves(ys)])
            if self._donate:
                # return the threaded arrays so every donated input has
                # an aliasable output (run_rounds_fused rebinds
                # self.data to these — the caller's data stays valid)
                return state, ys, packed, data_out + test_out
            return state, ys, packed

        if self._donate:
            donated = (0,) + tuple(range(
                3, 3 + n_data + (3 if use_test else 0)))
            # _jit_entry: donation + the persistent-cache guard +
            # forwarded .lower for the donation audit
            fn = cache[key] = self._jit_entry(fused, donate=donated)
        else:
            fn = cache[key] = jax.jit(fused)
        return fn

    def run_rounds_fused(self, state: Any, start_round: int,
                         n_rounds: int, eval_every: int = 0):
        """Run ``n_rounds`` federated rounds as one jitted program.

        Returns ``(state, ys)`` where ``ys`` is a :class:`FusedMetrics`:
        indexing it (or calling ``.materialize()``) fetches the whole
        block's metric series in ONE host transfer as a pytree of numpy
        arrays with a leading round axis of length ``n_rounds``. When
        ``eval_every`` is set, ``ys["eval"]`` holds the eval metrics
        (zeros on non-eval rounds — ``lax.cond`` skips their compute).
        Semantically identical to ``n_rounds`` ``run_round`` calls
        (tests/test_fused_rounds.py pins it); the win is dispatch/fetch
        amortization: one program launch and one metric materialization
        per block instead of per round.

        Ownership: under ``donate_state`` this call CONSUMES ``state``
        (and the current ``self.data`` arrays — they are donated into
        the scan carry and ``self.data`` is rebound to the aliased
        outputs). Callers re-running from a saved state must
        ``clone_state`` first; callers holding the pre-call data arrays
        must re-read them from ``self.data``.
        """
        if self._store is not None:
            return self._run_rounds_fused_store(
                state, start_round, n_rounds, eval_every)
        if not self.supports_fused:
            raise ValueError(
                f"{self.name}: fused rounds need every per-round host "
                "input to be a pure function of round_idx; this "
                "algorithm's host work is data-DEPENDENT (FedFomo biases "
                "its neighbor draw by accumulated weights read back from "
                "device, fedfomo_api.py:130-144; TurboAggregate's "
                "share/reconstruct protocol is host-interactive) — run "
                "it with fuse_rounds=1")
        host = [self._fused_host_inputs(r)
                for r in range(start_round, start_round + n_rounds)]
        host_stack = tuple(
            jnp.asarray(np.stack([h[i] for h in host]))
            for i in range(len(host[0])))
        round_ids = jnp.arange(
            start_round, start_round + n_rounds, dtype=jnp.float32)
        fn = self._get_fused_fn(n_rounds, eval_every)
        out = fn(
            state, host_stack, round_ids,
            *self._fused_data_args(), self.data.x_test,
            self.data.y_test, self.data.n_test)
        if self._donate:
            state, ys, packed, rets = out
            self._adopt_fused_args(rets)
        else:
            state, ys, packed = out
        return state, FusedMetrics(ys, packed)

    def _adopt_fused_args(self, rets) -> None:
        """Rebind ``self.data`` to the donated fused program's aliased
        pass-through outputs (same buffers, fresh valid handles). The
        base ``_fused_data_args`` layout (x/y/n train) is the
        donate_supported contract; the test triplet is present exactly
        when the program consumed it."""
        n_data = len(self._fused_data_args())
        d, t = rets[:n_data], rets[n_data:]
        kw = dict(x_train=d[0], y_train=d[1], n_train=d[2])
        if t:
            kw.update(x_test=t[0], y_test=t[1], n_test=t[2])
        self.data = self.data.replace(**kw)

    # -- population client store (--client_store host|disk) -------------------
    # The round program in store mode IS the resident round program with
    # the [C] axis replaced by the cohort slab: sel_idx = arange(S)
    # (unfused) or the block-union stack positions (fused), so every
    # slab gather in the round body is an identity/slab-local take of
    # rows whose VALUES match what the resident gather would have
    # produced — jnp.take of equal rows + the same vmapped per-row math
    # at the same width + the same reductions is bit-identical output.
    # The two places the body needs POPULATION ids (fault keying, the
    # [C] eval-cache scatter) read them from _trace_pop_idx, parked by
    # the wrapper below for the duration of the trace. Quarantined slab
    # rows keep their previous values in the round body (merge_updates /
    # merge_residual) and are staged back unchanged, so the store ends
    # up holding the pre-poison value: the no-poison-leak pin extends to
    # host RAM and disk by construction.

    def _get_store_round_jit(self):
        """The jitted store-mode round entry: the UNCHANGED round_fn
        traced at slab width behind the population-id wrapper. Donates
        its state arg exactly like ``_round_jit`` — under donate_state
        the cohort slab MOVES through the round rather than copying."""
        if self._round_jit_store is None:
            raw = getattr(self, "_round_fn", None)
            if raw is None:
                raise ValueError(
                    f"{self.name}: client_store needs the raw round fn "
                    "(self._round_fn) to wrap")

            def store_round(state, stack_idx, pop_idx, round_idx,
                            *row_args):
                self._trace_pop_idx = pop_idx
                try:
                    return raw(state, stack_idx, round_idx, *row_args)
                finally:
                    self._trace_pop_idx = None

            self._store_round_raw = store_round
            self._round_jit_store = self._jit_entry(store_round)
        return self._round_jit_store

    def _store_host_rows(self, test: bool = False):
        """Cached host (numpy) views of the training/test shards: store
        mode never materializes the full [C] data on device — each
        round's [S] rows are host-side ``np.take`` copies, device_put as
        part of the gather. On numpy-backed data (the population-scale
        path) the cache is a zero-copy view."""
        d = self.data
        if test:
            if self._host_test is None:
                self._host_test = (np.asarray(d.x_test),
                                   np.asarray(d.y_test),
                                   np.asarray(d.n_test))
            return self._host_test
        if self._host_data is None:
            self._host_data = (np.asarray(d.x_train),
                               np.asarray(d.y_train),
                               np.asarray(d.n_train))
        return self._host_data

    def _store_gather_rows(self, state, ids):
        """Host->device staging for one round/block: gather the
        cohort's store rows (timed inside the store — the cumulative
        ``store_gather_ms`` gauge) plus the ids' data/test rows from the
        cached host views. Returns (state.replace kwargs, row args).
        The gather commits any still-staged previous-round slabs first,
        so chained rounds read the newest adopted rows."""
        store = self._store
        kw = {}
        with obs_trace.span("store_gather"):
            if store.has_field("personal_params"):
                kw["personal_params"] = jax.device_put(
                    store.gather("personal_params", ids))
            if store.has_field("agg_residual"):
                kw["agg_residual"] = jax.device_put(
                    store.gather("agg_residual", ids))
            xh, yh, nh = self._store_host_rows()
            row_args = [jnp.asarray(np.take(xh, ids, axis=0)),
                        jnp.asarray(np.take(yh, ids, axis=0)),
                        jnp.asarray(np.take(nh, ids))]
            if self.eval_cache:
                xt, yt, nt = self._store_host_rows(test=True)
                row_args += [jnp.asarray(np.take(xt, ids, axis=0)),
                             jnp.asarray(np.take(yt, ids, axis=0)),
                             jnp.asarray(np.take(nt, ids))]
        return kw, tuple(row_args)

    def _store_adopt_round(self, new_state, ids):
        """Post-round adoption: park the trained row slabs in the
        store's staging area (still device arrays — the host transfer is
        deferred to commit, so the async dispatch pipelining survives)
        and drop them from state. They reach storage at the next
        gather/flush; a watchdog rollback (``store_discard``) drops them
        first, so a rolled-back attempt's rows never touch storage."""
        store = self._store
        kw = {}
        if store.has_field("personal_params"):
            store.stage("personal_params", ids, new_state.personal_params)
            kw["personal_params"] = None
            self._store_eval_dirty.append(np.asarray(ids))
        if store.has_field("agg_residual"):
            store.stage("agg_residual", ids, new_state.agg_residual)
            kw["agg_residual"] = None
        return new_state.replace(**kw) if kw else new_state

    def _store_prefetch_next(self, next_ids, cur_ids) -> None:
        """The double-buffering hook: warm the predicted next cohort's
        host rows while the current (async-dispatched) program runs.
        Rows the current cohort dirtied are excluded — their newest
        values are the staged slabs the next gather commits."""
        cur = set(int(i) for i in np.asarray(cur_ids))
        ids = [int(i) for i in np.asarray(next_ids) if int(i) not in cur]
        if not ids:
            return
        for name in self._store.field_names():
            self._store.prefetch(name, ids)

    def _run_round_store(self, state: Any, round_idx: int):
        """One streamed round (the store-mode ``run_round`` body):
        gather the sampled cohort's rows host->device, run the
        slab-width round program, stage the trained slab back, prefetch
        the next round's cohort."""
        sel = self._selected_client_indexes(round_idx)
        kw, row_args = self._store_gather_rows(state, sel)
        slab_state = state.replace(**kw) if kw else state
        s = int(sel.shape[0])
        with obs_trace.span("dispatch_round"):
            out = self._get_store_round_jit()(
                slab_state, jnp.arange(s, dtype=jnp.int32),
                jnp.asarray(sel), jnp.asarray(round_idx, jnp.float32),
                *row_args)
        new_state, metrics = out[0], out[1:]
        if len(metrics) != len(self._round_metric_names):
            raise ValueError(
                f"{type(self).__name__} store round returned "
                f"{len(metrics)} metrics but _round_metric_names has "
                f"{len(self._round_metric_names)}")
        new_state = self._store_adopt_round(new_state, sel)
        self._store_prefetch_next(
            sample_client_indexes(round_idx + 1, self.num_clients,
                                  self.clients_per_round), sel)
        return new_state, dict(zip(self._round_metric_names, metrics))

    def _run_rounds_fused_store(self, state: Any, start_round: int,
                                n_rounds: int, eval_every: int = 0):
        """Fused blocks over the store: one gather of the block-UNION's
        [U] rows, one jitted scan in which round i addresses the slab at
        ``searchsorted(union, sels[i])`` (so within-block row chaining
        rides the carried slab exactly as it rides the resident [C]
        stack), one writeback of the whole union on the flush path. The
        in-graph eval cadence needs the full cohort resident and is
        refused — the runner evaluates between blocks instead."""
        if eval_every:
            raise ValueError(
                f"{self.name}: the fused in-graph eval cadence "
                "(frequency_of_the_test with fuse_rounds>1) evaluates "
                "the full [C] cohort inside the block; with "
                "--client_store the cohort is not resident — evaluate "
                "between blocks (eval_every=0) or run fuse_rounds=1")
        sels = np.stack([
            self._selected_client_indexes(r)
            for r in range(start_round, start_round + n_rounds)])
        union = np.unique(sels).astype(np.int32)
        views = np.searchsorted(union, sels).astype(np.int32)
        kw, row_args = self._store_gather_rows(state, union)
        slab_state = state.replace(**kw) if kw else state
        host_stack = (jnp.asarray(views),
                      jnp.asarray(sels.astype(np.int32)))
        round_ids = jnp.arange(
            start_round, start_round + n_rounds, dtype=jnp.float32)
        fn = self._get_fused_fn(n_rounds, eval_every, store=True)
        out = fn(slab_state, host_stack, round_ids, *row_args)
        if self._donate:
            new_state, ys, packed, _rets = out
            # _rets: the donated [U] row slabs threaded through the
            # carry so every donated input has an aliasable output —
            # dropped here (self.data still holds the full cohort on
            # host; there is nothing to rebind in store mode)
        else:
            new_state, ys, packed = out
        new_state = self._store_adopt_round(new_state, union)
        nxt = np.unique(np.concatenate([
            sample_client_indexes(r, self.num_clients,
                                  self.clients_per_round)
            for r in range(start_round + n_rounds,
                           start_round + 2 * n_rounds)]))
        self._store_prefetch_next(nxt, union)
        return new_state, FusedMetrics(ys, packed)

    def _store_register_fields(self, params) -> None:
        """init_state hook (store mode): register the streamed fields
        with their lazy per-row defaults — personal rows default to the
        init params (what the resident broadcast would hold), topk
        residual rows to zeros. An untrained row costs NOTHING until
        first written: at --track_personal 0 under topk the residual no
        longer allocates full-population zeros, only trained rows.
        Re-registration resets the store (a fresh init_state)."""
        store = self._store
        if getattr(self, "track_personal", True):
            store.register("personal_params", params)
        if self.agg_impl == "topk":
            store.register(
                "agg_residual",
                jax.tree_util.tree_map(jnp.zeros_like, params))
        self._store_eval_cache = None
        self._store_eval_dirty = []

    def _store_has_personal(self) -> bool:
        """True when the personal stack lives in the client store (state
        holds None between rounds) — ``_eval_impl``'s personal-branch
        test alongside ``state.personal_params is not None``."""
        return self._store is not None and \
            self._store.has_field("personal_params")

    def store_discard(self) -> None:
        """Watchdog RETRY/SKIP hook (the runner calls it on rollback):
        drop the rolled-back attempt's staged rows before anything
        commits them — the no-poison-leak pin extended to host RAM and
        disk — and invalidate the store eval cache (a full reseed at the
        next eval is always correct)."""
        if self._store is None:
            return
        self._store.discard()
        self._store_eval_cache = None
        self._store_eval_dirty = []

    def store_flush(self) -> None:
        """Commit staged rows to storage — the runner's pre-checkpoint
        barrier (the store snapshot must carry the adopted rows)."""
        if self._store is not None:
            self._store.commit()

    def _personal_eval_store(self, _pers, x_test, y_test, n_test):
        """Personal-eval protocol result over the STORE-resident stack —
        the host-side incremental twin of ``_personal_eval_cached``,
        with the dirty-row gather going to the store instead of the (not
        resident) [C] device stack. Same three tiers at the same widths
        and with the same jitted reductions, so results match the
        resident incremental path bitwise (accuracy) / to its documented
        1-ulp loss tolerance. ``_pers`` is ignored (None in store
        mode)."""
        store = self._store
        dirty = np.concatenate(self._store_eval_dirty) \
            if self._store_eval_dirty else np.zeros((0,), np.int64)
        if self._store_eval_cache is None or \
                dirty.size >= self.num_clients:
            # full pass: the one O(C) transfer (seed / post-resume /
            # post-rollback); population-scale runs eval rarely or not
            # at all (the runner's eval cadence flag)
            stack = jax.device_put(store.gather_all("personal_params"))
            ev = self._eval_personal(stack, x_test, y_test, n_test)
        elif dirty.size == 0:
            if not hasattr(self, "_pers_metrics_fn"):
                self._pers_metrics_fn = jax.jit(_personal_metrics)
            ev = self._pers_metrics_fn(*self._store_eval_cache)
        else:
            if not hasattr(self, "_store_eval_merge_fn"):
                self._store_eval_merge_fn = self._make_store_eval_merge()
            sel = dirty.astype(np.int32)
            sub = jax.device_put(store.gather("personal_params", sel))
            ev = self._store_eval_merge_fn(
                sub, jnp.asarray(sel), *self._store_eval_cache,
                x_test, y_test, n_test)
        self._store_eval_cache = (ev["correct"], ev["loss_sum"],
                                  ev["total"])
        self._store_eval_dirty = []
        return ev

    def _make_store_eval_merge(self):
        """jit twin of ``_make_personal_eval_merge`` taking the dirty
        rows PRE-GATHERED (host rows from the store) instead of indexing
        the resident stack: the same vmapped row eval at the same
        |dirty| width, the same scatter, the same reductions."""
        vmapped = self._vmap_clients(self.eval_client,
                                     in_axes=(0, 0, 0, 0))

        @jax.jit
        def eval_merge_rows(sub, sel, correct, loss_sum, total,
                            x_test, y_test, n_test):
            c_s, l_s, t_s = vmapped(
                sub, jnp.take(x_test, sel, axis=0),
                jnp.take(y_test, sel, axis=0), jnp.take(n_test, sel))
            correct = correct.at[sel].set(c_s)
            loss_sum = loss_sum.at[sel].set(l_s)
            total = total.at[sel].set(t_s)
            return _personal_metrics(correct, loss_sum, total)

        return eval_merge_rows

    def _fused_block_loop(self, state, start_round: int, total: int,
                          block: int, eval_every: int, on_record,
                          timed: bool = False, on_block=None):
        """The shared fused-block driver (library ``run(fuse_rounds=K)``
        and the CLI runner's ``--fuse_rounds`` both use it): dispatch
        block b+1, then materialize and emit block b's per-round records
        — the device queue never drains. ``on_record(round_idx, rec,
        state_out)`` receives each round's record in order plus the
        emitting block's (already computed) output state;
        ``on_block(end_round, state_out)`` fires once per flushed block
        (the runner's block-granular checkpoint hook).

        ``timed=True`` stamps ``round_time_s`` as the block's
        flush-to-flush wall time split evenly: flushes happen after the
        blocking materialize, so the per-run SUM equals wall time and
        per-round attribution is ±1 block (the fused analogue of
        DeferredRecords' timed semantics — the dispatch itself is async
        and takes microseconds, so timing it would be meaningless).

        A success-path flush error propagates; only when an exception is
        already unwinding is the final flush best-effort (the pending
        block's device state may be gone)."""
        mark = time.perf_counter()
        pending = None  # previous block, dispatched but not yet fetched

        def flush(p):
            nonlocal mark
            r0, k, ys, state_out = p
            # obs span at the ONE place the fused path already syncs
            # (per-round spans would force device syncs inside the
            # block); whole-block timing is the documented degradation
            with obs_trace.span("fused_block_flush") as sp:
                sp.add("start_round", r0)
                sp.add("rounds", k)
                host = dict(ys.materialize())  # blocks until complete
            now = time.perf_counter()
            wall, mark = now - mark, now
            ev = host.pop("eval", None)
            for i in range(k):
                rec: Dict[str, Any] = {"round": r0 + i}
                for name in self._round_metric_names:
                    rec[name] = float(host[name][i])
                if ev is not None and (r0 + i + 1) % eval_every == 0:
                    rec.update({k2: float(v[i]) for k2, v in ev.items()})
                if timed:
                    rec["round_time_s"] = wall / k
                on_record(r0 + i, rec, state_out)
            if on_block is not None:
                # block boundary: state_out is computed (materialize
                # above waited on it) — checkpoint-granularity hook
                on_block(r0 + k, state_out)

        try:
            for r0 in range(start_round, total, block):
                k = min(block, total - r0)
                if pending is not None and self._donate:
                    # ownership: the next dispatch CONSUMES the pending
                    # block's output state, which flush still reads
                    # (cost snapshot, block-boundary checkpoint) — so a
                    # donating loop flushes BEFORE dispatching. The
                    # dispatch-ahead pipelining below is the borrow
                    # path's; what donation loses is only the overlap of
                    # host record emission with the next block's compute
                    p, pending = pending, None
                    flush(p)
                with obs_trace.span("fused_block_dispatch") as sp:
                    sp.add("start_round", r0)
                    state, ys = self.run_rounds_fused(
                        state, r0, k, eval_every=eval_every)
                if pending is not None:
                    # clear BEFORE flushing: if flush raises mid-way
                    # (e.g. on_block checkpoint save), the finally must
                    # not re-emit the block's already-appended records
                    p, pending = pending, None
                    flush(p)
                pending = (r0, k, ys, state)
            if pending is not None:
                p, pending = pending, None
                flush(p)  # success path: a flush error propagates
        finally:
            if pending is not None:  # an exception is unwinding and this
                try:                 # block's flush never started
                    flush(pending)
                except Exception:  # crashed mid-block: device state gone
                    logger.exception("fused block metrics lost")
        return state

    def _run_fused(self, comm_rounds: int, eval_every: int, state: Any,
                   finalize: bool, block: int):
        """``run`` with the round loop executed in fused blocks
        (``_fused_block_loop``)."""
        if state is None:
            state = self.init_state(jax.random.PRNGKey(self.seed))
        history: List[Dict[str, Any]] = []

        def on_record(r, rec, _state_out):
            history.append(rec)
            logger.info("%s round %d: %s", self.name, r, rec)

        state = self._fused_block_loop(
            state, 0, comm_rounds, block, eval_every, on_record,
            timed=True)
        return self._finalize_into_history(
            state, history, finalize)

    def _finalize_into_history(self, state, history, finalize: bool):
        """Shared tail of both drivers: run the algorithm's end-of-training
        pass and append its record (round = -1) to the history."""
        from ..utils.records import to_float

        final_record = None
        if finalize:
            state, final_record = self.finalize(state)
        if final_record is not None:
            record = {k: to_float(v) for k, v in final_record.items()}
            history.append(record)
            logger.info("%s final: %s", self.name, record)
        return state, history

    # -- driver ---------------------------------------------------------------
    def run(
        self,
        comm_rounds: int,
        eval_every: int = 1,
        state: Any = None,
        callback=None,
        finalize: bool = True,
        fuse_rounds: int = 1,
    ):
        """The federated training driver (the reference's ``API.train()``).

        ``finalize=False`` skips the algorithm's end-of-training pass (e.g.
        FedAvg's final fine-tune) for callers that only need the round loop.

        ``fuse_rounds=K`` (supported algorithms) executes the loop in
        K-round fused programs — see ``run_rounds_fused``. Incompatible
        with ``callback``: per-round host control (checkpointing) is
        exactly what fusion removes.

        ``round_time_s`` is stamped at flush boundaries (see
        utils.records.DeferredRecords): the per-run SUM equals wall time
        exactly, per-round attribution is ±1 round under the deferred
        fetch.
        """
        # the whole call is one span: at depth 0 (a caller's block of
        # rounds) its exit samples the allocator, after the last flush
        with obs_trace.span("run", {"rounds": comm_rounds,
                                    "fuse_rounds": fuse_rounds}):
            return self._run(comm_rounds, eval_every, state, callback,
                             finalize, fuse_rounds)

    def _run(self, comm_rounds: int, eval_every: int, state: Any, callback,
             finalize: bool, fuse_rounds: int):
        """``run`` under its span. A round is a step span ``round`` with
        the children ``sample``, ``dispatch_round``, ``evaluate`` and
        ``flush`` (the round before's record, fetched once this round is
        dispatched; the last one's directly under ``run``)."""
        from ..utils.records import DeferredRecords, to_float

        if fuse_rounds > 1:
            if callback is not None:
                raise ValueError(
                    "fuse_rounds > 1 removes per-round host control; "
                    "per-round callbacks (checkpointing) need "
                    "fuse_rounds=1")
            return self._run_fused(
                comm_rounds, eval_every, state, finalize, fuse_rounds)
        if state is None:
            state = self.init_state(jax.random.PRNGKey(self.seed))
        state = self.place_state(state)
        history: List[Dict[str, Any]] = []
        # metric host-fetches run one round late (utils/records.py): a
        # callback opts into immediate conversion since it observes
        # records as they land
        deferred = DeferredRecords(
            log=lambda rec: logger.info(
                "%s round %s: %s", self.name, rec["round"], rec),
            timed=True)
        try:
            for r in range(comm_rounds):
                t0 = time.perf_counter()
                with obs_trace.step_span("round", r):
                    state, train_metrics = self.run_round(state, r)
                    record = {"round": r, **dict(train_metrics)}
                    if eval_every and (r + 1) % eval_every == 0:
                        with obs_trace.span("evaluate"):
                            ev = self.evaluate(state)
                        record.update({k: v for k, v in ev.items()
                                       if not k.startswith("acc_per")})
                    history.append(record)
                    if callback is not None:
                        for k, v in record.items():
                            record[k] = to_float(v)
                        record["round_time_s"] = time.perf_counter() - t0
                        logger.info("%s round %d: %s", self.name, r,
                                    record)
                        callback(r, state, record)
                    else:
                        deferred.push(record)
        except BaseException:
            deferred.flush_safely()  # emit the last completed round
            raise
        deferred.flush()
        return self._finalize_into_history(
            state, history, finalize)
