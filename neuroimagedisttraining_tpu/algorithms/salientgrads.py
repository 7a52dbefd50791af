"""SalientGrads — the flagship algorithm: SNIP-masked sparse federated
training on site-partitioned neuroimaging data.

Re-design of ``fedml_api/standalone/sailentgrads/sailentgrads_api.py``:
  1. Before round 0, every client computes SNIP saliency scores on its own
     shard (itersnip iterations, ``client.py:29-50``), the server averages
     them (``snip.py:120-140``) and thresholds a single *global* mask at
     ``dense_ratio`` (``snip.py:80-116``, via ``sailentgrads_api.py:47-66``).
  2. Then FedAvg rounds where every local SGD step re-masks the weights
     (``my_model_trainer.py:213-216``) and aggregation is the
     sample-weighted mean (``sailentgrads_api.py:212-227``).

Here the scoring pass is a vmapped ``jax.grad`` w.r.t. an all-ones mask
multiplier (mean over clients = the "saliency psum"), and the training round
is the same single jitted SPMD program as FedAvg with the mask broadcast
along the client axis.

Like the reference, each trained client's locally-trained weights are kept
as its *personal* model (``w_per_mdls[cur_clnt] = w_per``,
``sailentgrads_api.py:107-110,133``) and the per-round eval protocol tests
BOTH the global model and every client's personal model on its local test
set (``_test_on_all_clients(w_global, w_per_mdls, round_idx)``,
``:238,262-283``), plus one final eval at round -1 after the loop
(``:147``). ``track_personal=False`` drops the on-device stack for
large-C simulations (same opt-out as FedAvg's).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import struct

from ..core.state import broadcast_tree
from ..core.trainer import make_client_update
from ..obs import trace as obs_trace
from ..ops.sparsity import make_snip_score_fn, mask_density, mask_from_scores
from .base import FedAlgorithm


@struct.dataclass
class SalientGradsState:
    global_params: Any
    mask: Any
    # [C, ...] — w_per_mdls (sailentgrads_api.py:107-110), or None when
    # personal tracking is off. Initialized to dense copies of the initial
    # global model (the reference's mask multiply at init is commented
    # out, :110) and updated with each trained client's masked local
    # weights. Same HBM caveat as FedAvgState.personal_params.
    personal_params: Any
    rng: jax.Array
    # [C, ...] error-feedback residual of agg_impl='topk', or None for
    # every other impl (see FedAvgState.agg_residual). Locals honor the
    # static SNIP mask, so deltas — and inductively the residual — are
    # exact zeros on dead coordinates: the top-k selection (compressed
    # to the plan's live set) can never ship a dead coordinate.
    agg_residual: Any = None
    # per-client personal-eval cache (--eval_cache), or None — see
    # FedAvgState.eval_cache (same semantics, same lineage split)
    eval_cache: Any = None


class SalientGrads(FedAlgorithm):
    name = "salientgrads"
    supports_fused = True
    guard_metrics_supported = True
    numerics_supported = True
    numerics_with_mask = True
    topk_supported = True
    donate_supported = True
    store_supported = True

    def __init__(self, *args, dense_ratio: float = 0.5,
                 itersnip_iterations: int = 1, defense=None,
                 snip_mask: bool = True,
                 stratified_sampling: bool = False,
                 stratified_mode: str = "exact",
                 track_personal: bool = True,
                 eval_cache: bool = False, **kwargs):
        self.dense_ratio = dense_ratio
        self.itersnip_iterations = itersnip_iterations
        # optional robust.RobustAggregator (fedml_core/robustness wiring)
        self.defense = defense
        # --snip_mask 0: all-ones mask, the reference's dense-control mode
        # (sailentgrads_api.py:91-103)
        self.snip_mask = snip_mask
        # --stratified_sampling: per-class-balanced SNIP scoring.
        # stratified_mode="exact" (default) replays the reference's
        # StratifiedKFold(25, shuffle, seed 42) schedule, scoring each
        # split's TRAIN side (client.py:32-42) via a host-computed
        # pad+mask index schedule; "balanced" is the fast path — 25
        # class-balanced random batch draws (documented approximation,
        # see ops/sparsity.make_snip_score_fn).
        self.stratified_sampling = stratified_sampling
        if stratified_mode not in ("exact", "balanced"):
            raise ValueError(
                f"stratified_mode {stratified_mode!r} not in "
                "('exact', 'balanced')")
        self.stratified_mode = stratified_mode
        # track_personal=False drops the on-device w_per_mdls stack and the
        # personal half of the per-round eval — O(C x model) HBM
        self.track_personal = track_personal
        # eval_cache: the in-state incremental personal-eval cache
        # (base.py "--eval_cache" section); validated in the base ctor
        self.eval_cache = bool(eval_cache)
        super().__init__(*args, **kwargs)

    def _build(self) -> None:
        self.client_update = make_client_update(
            self.apply_fn, self.loss_type, self.hp,
            mask_grads=False, mask_params_post_step=True,
            remat=self.remat_local,
            full_batches=self._full_batches(),
            augment_fn=self.augment_fn,
        )
        self._fold_sched = None
        if self.snip_mask and self.stratified_sampling and \
                self.stratified_mode == "exact":
            # the reference's exact StratifiedKFold(25, shuffle, seed 42)
            # schedule, computed host-side per client (labels are tiny;
            # multihost cohorts should use stratified_mode="balanced" —
            # the schedule needs every client's labels on every host)
            import numpy as np

            from ..ops.sparsity import (
                make_snip_fold_score_fn,
                stacked_fold_schedules,
            )

            idx, w = stacked_fold_schedules(
                np.asarray(self.data.y_train),
                np.asarray(self.data.n_train))
            self._fold_sched = (jnp.asarray(idx), jnp.asarray(w))
            self.snip_fold_scores = make_snip_fold_score_fn(
                self.apply_fn, self.loss_type, augment_fn=self.augment_fn)
        else:
            self.snip_scores = make_snip_score_fn(
                self.apply_fn, self.loss_type, self.hp.batch_size,
                stratified=self.stratified_sampling,
                num_classes=self.data.class_num,
                augment_fn=self.augment_fn,
            )

        def global_mask_fn(params, x_train, y_train, n_train, rng):
            """All clients score their own shards; mean; global top-k."""
            c = x_train.shape[0]
            keys = jax.random.split(rng, c)
            params_b = broadcast_tree(params, c)
            if self._fold_sched is not None:
                idx, w = self._fold_sched
                scores = self._vmap_clients(
                    self.snip_fold_scores, in_axes=(0, 0, 0, 0, 0, 0),
                )(params_b, x_train, y_train, idx, w, keys)
            else:
                # balanced mode scores over 25 balanced batches (the
                # reference's n_splits=25, client.py:36)
                n_iters = 25 if self.stratified_sampling \
                    else self.itersnip_iterations
                scores = self._vmap_clients(
                    lambda p, x, y, n, k: self.snip_scores(
                        p, x, y, n, k, n_iters
                    ),
                    in_axes=(0, 0, 0, 0, 0),
                )(params_b, x_train, y_train, n_train, keys)
            # server-side mean over clients (snip.py:120-140)
            mean_scores = jax.tree_util.tree_map(
                lambda s: jnp.mean(s, axis=0), scores
            )
            # params returned unchanged: under donate_state the donated
            # params buffers alias to this pass-through output, so the
            # caller (init_state) keeps a valid handle while XLA reuses
            # the buffers for the scoring pass's scratch
            return mask_from_scores(mean_scores, self.dense_ratio,
                                    kernels=self.agg_kernels), params

        self._global_mask_jit = self._jit_entry(global_mask_fn)

        def round_fn(state: SalientGradsState, sel_idx, round_idx,
                     x_train, y_train, n_train, *test_args):
            rng, round_key = jax.random.split(state.rng)
            new_global, locals_, mean_loss, fstats, new_residual = \
                self._train_selected_weighted(
                    self.client_update, state.global_params, state.mask,
                    sel_idx, round_idx, round_key, x_train, y_train,
                    n_train, defense=self.defense,
                    residual=state.agg_residual,
                )
            if self.defense is not None or self.agg_impl == "topk":
                # weak-DP noise lands on every leaf — and the topk
                # delta update leaves round 0's dense init on dead
                # coordinates (g + update touches only live coords);
                # re-mask so the global model keeps the SNIP sparsity
                # invariant either way (one fused pass per leaf under
                # the pallas backend; p*m is elementwise, so the
                # backends are trivially bit-identical)
                if self.agg_kernels == "pallas":
                    from ..ops.pallas_kernels import fused_mask_apply

                    new_global = fused_mask_apply(new_global, state.mask)
                else:
                    new_global = jax.tree_util.tree_map(
                        lambda p, m: p * m, new_global, state.mask)
            # w_per_mdls[cur_clnt] = the client's (pre-defense) locally
            # trained weights (sailentgrads_api.py:133), guard-aware
            new_personal = self._guarded_personal_update(
                state.personal_params, locals_, sel_idx, fstats)
            # --eval_cache: refresh ONLY the trained clients' cache rows
            # (see FedAvg.round_fn — identical semantics)
            new_cache = state.eval_cache
            if self.eval_cache:
                new_cache = self._update_eval_cache(
                    state.eval_cache, new_personal, sel_idx, *test_args)
            # in-jit numerics telemetry (--obs_numerics) incl. mask
            # churn / cross-client agreement; AFTER the defense re-mask
            # so the update norms see the adopted global. () when off
            nums = self._numerics_outputs(
                state.global_params, new_global, locals_,
                mask=state.mask)
            return self._round_outputs(
                SalientGradsState(global_params=new_global,
                                  mask=state.mask,
                                  personal_params=new_personal, rng=rng,
                                  agg_residual=new_residual,
                                  eval_cache=new_cache),
                mean_loss, fstats, nums)

        self._round_fn = round_fn
        self._round_jit = self._jit_entry(round_fn)
        self._eval_global = self._make_global_eval()
        self._eval_personal = self._make_personal_eval()

    def init_state(self, rng: jax.Array) -> SalientGradsState:
        p_rng, m_rng, s_rng = jax.random.split(rng, 3)
        params = self.init_model_params(p_rng)
        if not self.snip_mask:
            # --snip_mask 0: dense-control mode, all-ones mask
            # (sailentgrads/client.py:95-103)
            mask = jax.tree_util.tree_map(jnp.ones_like, params)
        else:
            with obs_trace.span("snip_mask"):
                # params rebound to the pass-through output: under
                # donate_state the input buffers were donated and THIS
                # is the valid (aliased) handle
                mask, params = self._global_mask_jit(
                    params, self.data.x_train, self.data.y_train,
                    self.data.n_train, m_rng,
                )
        from ..core.state import zeros_like_tree

        if self._store is not None:
            # store mode: per-client rows live in the client store with
            # lazy defaults (dense init-params rows — the reference's
            # commented-out init mask multiply — / zero residual); state
            # holds None between rounds. See FedAvg.init_state.
            self._store_register_fields(params)
            ev_cache = None
            if self.eval_cache:
                ev_cache = self._seed_eval_cache(
                    broadcast_tree(params, self.num_clients))
            return SalientGradsState(
                global_params=params, mask=mask, personal_params=None,
                rng=s_rng, agg_residual=None, eval_cache=ev_cache)
        personal = (broadcast_tree(params, self.num_clients)
                    if self.track_personal else None)
        return SalientGradsState(
            global_params=params, mask=mask,
            # w_per_mdls init: dense copies of the initial global model —
            # the reference's init-time mask multiply is commented out
            # (sailentgrads_api.py:107-110)
            personal_params=personal,
            rng=s_rng,
            # topk: zero residual per client (masked by construction —
            # deltas of mask-honoring locals are zero on dead coords)
            agg_residual=(zeros_like_tree(
                broadcast_tree(params, self.num_clients))
                if self.agg_impl == "topk" else None),
            # --eval_cache: seeded by one full personal eval (one-time
            # O(C); later rounds refresh O(S) rows in-graph)
            eval_cache=self._seed_eval_cache(personal))

    def _ensure_agg_plan(self, state: SalientGradsState) -> None:
        """Host-side, before the round program traces: build the
        mask-aware sparse gather plan from the CONCRETE mask. Valid for
        the whole run — the SNIP mask is fixed after init
        (``masks_evolve=False``), which is exactly why SalientGrads can
        run ``agg_impl='sparse'`` (and compressed-selection
        ``'topk'`` / the ``'hier'`` sparse cross-slice wire): the
        live-coordinate set is static per round-block. With a weak-DP
        defense the compressed reduce also drops the noise landing on
        dead kernel coordinates — the same invariant the explicit
        post-aggregation re-mask enforces."""
        needs_plan = self.agg_impl in ("sparse", "topk") or (
            self.agg_impl == "hier" and self.agg_hier_wire == "sparse")
        if needs_plan and self._agg_sparse_plan is None:
            from ..parallel.collectives import build_sparse_plan

            self._agg_sparse_plan = build_sparse_plan(state.mask)

    def run_round(self, state: SalientGradsState, round_idx: int):
        self._ensure_agg_plan(state)  # host-side, before any trace
        if self._store is not None:
            # streamed cohort residency: same round body at slab width
            return self._run_round_store(state, round_idx)
        sel = self._selected_client_indexes(round_idx)
        d = self.data
        # read BEFORE dispatch: under donate_state the call consumes
        # `state` (the ownership lint holds driver paths to this order)
        old_pers = state.personal_params
        extra = ((d.x_test, d.y_test, d.n_test)
                 if self.eval_cache else ())
        # dispatch-time span (async): the round's device phases are
        # labeled by named_scope inside the jitted body instead
        with obs_trace.span("dispatch_round"):
            out = self._round_jit(
                state, jnp.asarray(sel),
                jnp.asarray(round_idx, jnp.float32),
                d.x_train, d.y_train, d.n_train, *extra,
            )
        new_state = out[0]
        # only the trained clients' personal models changed — feed the
        # incremental personal-eval cache (base._personal_eval_cached)
        self._note_personal_update(
            old_pers, new_state.personal_params, sel)
        return new_state, dict(zip(self._round_metric_names, out[1:]))

    def run_rounds_fused(self, state, start_round, n_rounds, eval_every=0):
        self._ensure_agg_plan(state)  # before the fused program traces
        return super().run_rounds_fused(state, start_round, n_rounds,
                                        eval_every=eval_every)

    def finalize(self, state: SalientGradsState):
        """One final global+personal eval after the last round — the
        reference's ``_test_on_all_clients(w_global, w_per_mdls, -1)``
        (``sailentgrads_api.py:147``; no fine-tune, unlike FedAvg)."""
        ev = self.evaluate(state)
        record = {"round": -1,
                  **{k: v for k, v in ev.items()
                     if not k.startswith("acc_per")}}
        return state, record

    def _eval_impl(self, state, x_test, y_test, n_test,
                   personal_fn) -> Dict[str, Any]:
        # routed by the base wrappers (eval_metrics = traceable full
        # personal eval; evaluate = incremental cached one). The
        # reference protocol tests the global model AND every client's
        # personal model on its local test set (sailentgrads_api.py:238,
        # 262-283); global params are already masked (the aggregate of
        # masked locals; assert via density)
        ev = self._eval_global(state.global_params, x_test, y_test, n_test)
        out = {
            "global_acc": ev["acc"],
            "global_loss": ev["loss"],
            "mask_density": mask_density(state.mask),
            "acc_per_client": ev["acc_per_client"],
        }
        if state.personal_params is not None or \
                self._store_has_personal():
            evp = personal_fn(
                state.personal_params, x_test, y_test, n_test)
            out.update(personal_acc=evp["acc"], personal_loss=evp["loss"])
        return out
