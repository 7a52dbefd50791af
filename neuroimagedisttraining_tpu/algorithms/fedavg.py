"""FedAvg — canonical centralized federated averaging.

Re-design of ``fedml_api/standalone/fedavg/fedavg_api.py:40-117``: sample
frac*N clients, local SGD on each, sample-count-weighted average. The
reference runs clients sequentially and averages CPU state_dicts
(``fedavg_api.py:102-117``); here the entire round — broadcast, vmapped local
training, weighted aggregation — is a single jitted program, and with the
client axis sharded over a mesh the weighted sum lowers to an ICI all-reduce.

Like the reference, each client's last locally-trained weights are kept as
its *personal* model (``w_per_mdls``, ``fedavg_api.py:42-45,66-67``) and both
global and personal models are evaluated per round
(``_test_on_all_clients(w_global, w_per_mdls, round_idx)``, ``:119-173``).
After the last round every client fine-tunes once from the final global
model with ``round_idx = -1`` and the pair is evaluated one final time
(``fedavg_api.py:79-88``).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..core.state import (
    broadcast_tree,
    zeros_like_tree,
)
from ..core.trainer import make_client_update
from ..obs import trace as obs_trace
from .base import FedAlgorithm


@struct.dataclass
class FedAvgState:
    global_params: Any
    # [C, ...] — w_per_mdls (fedavg_api.py:42-45), or None when personal
    # tracking is off. NOTE the HBM scaling: the stack is one full model per
    # client ON DEVICE (the reference keeps w_per_mdls in host RAM), so very
    # large --client_num_in_total simulations should pass --track_personal 0
    # unless they need per-client personal models/eval.
    personal_params: Any
    rng: jax.Array
    # [C, ...] error-feedback residual of agg_impl='topk' (the unsent
    # remainder of each client's compensated delta — Deep Gradient
    # Compression semantics), or None for every other impl. Real state:
    # checkpointed with the same lineage rules as personal_params (a
    # topk lineage is identity-split from the other impls, whose states
    # have no residual — the r5 track_personal migration pattern).
    agg_residual: Any = None
    # per-client personal-eval cache {correct[C], loss_sum[C], total[C]}
    # (--eval_cache), or None when off. Real state: the round body
    # refreshes only the trained clients' rows (O(S) forwards), evals
    # re-reduce it with zero forwards, it rides the fused scan carry,
    # and it checkpoints — an evcache lineage splits identity (the same
    # r5/topk state-structure rule).
    eval_cache: Any = None


class FedAvg(FedAlgorithm):
    name = "fedavg"
    supports_fused = True
    guard_metrics_supported = True
    numerics_supported = True
    topk_supported = True
    donate_supported = True
    store_supported = True

    def __init__(self, *args, defense=None, track_personal: bool = True,
                 eval_cache: bool = False, **kwargs):
        # optional robust.RobustAggregator (fedml_core/robustness wiring)
        self.defense = defense
        # track_personal=False drops the on-device w_per_mdls stack (and the
        # final fine-tune that exists to produce it) — O(C x model) HBM
        self.track_personal = track_personal
        # eval_cache: the in-state incremental personal-eval cache
        # (base.py "--eval_cache" section); validated in the base ctor
        self.eval_cache = bool(eval_cache)
        super().__init__(*args, **kwargs)

    def _build(self) -> None:
        self.client_update = make_client_update(
            self.apply_fn, self.loss_type, self.hp,
            mask_grads=False, mask_params_post_step=False,
            remat=self.remat_local, full_batches=self._full_batches(),
            augment_fn=self.augment_fn,
        )

        def round_fn(state: FedAvgState, sel_idx, round_idx,
                     x_train, y_train, n_train, *test_args):
            rng, round_key = jax.random.split(state.rng)
            new_global, locals_, mean_loss, fstats, new_residual = \
                self._train_selected_weighted(
                    self.client_update, state.global_params,
                    state.global_params,  # dense path: mask unused, DCE'd
                    sel_idx, round_idx, round_key, x_train, y_train,
                    n_train, defense=self.defense,
                    residual=state.agg_residual,
                )
            new_personal = self._guarded_personal_update(
                state.personal_params, locals_, sel_idx, fstats)
            # --eval_cache: refresh ONLY the trained clients' cache rows
            # from their post-guard personal rows (quarantined rows
            # re-evaluate their kept previous models — poison-free)
            new_cache = state.eval_cache
            if self.eval_cache:
                new_cache = self._update_eval_cache(
                    state.eval_cache, new_personal, sel_idx, *test_args)
            # in-jit numerics telemetry (--obs_numerics): pure readout
            # on the round's live arrays, () when off
            nums = self._numerics_outputs(
                state.global_params, new_global, locals_)
            return self._round_outputs(
                FedAvgState(global_params=new_global,
                            personal_params=new_personal, rng=rng,
                            agg_residual=new_residual,
                            eval_cache=new_cache),
                mean_loss, fstats, nums)

        self._round_fn = round_fn
        self._round_jit = self._jit_entry(round_fn)

        def finetune_fn(state: FedAvgState, x_train, y_train, n_train):
            """Final fine-tune: every client trains once from the final
            global model at round_idx=-1 (fedavg_api.py:79-88)."""
            rng, key = jax.random.split(state.rng)
            c = self.num_clients
            params0 = broadcast_tree(state.global_params, c)
            mom0 = zeros_like_tree(params0)
            keys = jax.random.split(key, c)
            params_out, _, _ = self._vmap_clients(
                self.client_update, in_axes=(0, 0, 0, 0, 0, 0, 0, None, 0)
            )(params0, mom0, params0, keys, x_train, y_train, n_train,
              jnp.asarray(-1.0, jnp.float32), params0)
            # eval_cache passes through for donation aliasing; finalize
            # drops it on the host (the fine-tune retrained EVERY row,
            # so the cache is stale wholesale)
            return FedAvgState(global_params=state.global_params,
                               personal_params=params_out, rng=rng,
                               agg_residual=state.agg_residual,
                               eval_cache=state.eval_cache)

        self._finetune_jit = self._jit_entry(finetune_fn)
        self._eval_global = self._make_global_eval()
        self._eval_personal = self._make_personal_eval()

    def init_state(self, rng: jax.Array) -> FedAvgState:
        p_rng, s_rng = jax.random.split(rng)
        params = self.init_model_params(p_rng)
        if self._store is not None:
            # store mode: the per-client rows live in the client store
            # (lazy defaults — init params / zero residual; nothing
            # materializes until a row trains) and state holds None
            # between rounds. The eval cache is seeded from a TRANSIENT
            # resident broadcast — identical values to the resident
            # seed — and freed right after.
            self._store_register_fields(params)
            ev_cache = None
            if self.eval_cache:
                ev_cache = self._seed_eval_cache(
                    broadcast_tree(params, self.num_clients))
            return FedAvgState(
                global_params=params, personal_params=None, rng=s_rng,
                agg_residual=None, eval_cache=ev_cache)
        personal = (broadcast_tree(params, self.num_clients)
                    if self.track_personal else None)
        return FedAvgState(
            global_params=params,
            personal_params=personal,
            rng=s_rng,
            # topk: zero residual per client (same [C, model] HBM
            # footprint caveat as personal_params)
            agg_residual=(zeros_like_tree(
                broadcast_tree(params, self.num_clients))
                if self.agg_impl == "topk" else None),
            # --eval_cache: seed with one full personal eval (one-time
            # O(C); every later round refreshes O(S) rows in-graph)
            eval_cache=self._seed_eval_cache(personal),
        )

    def run_round(self, state: FedAvgState, round_idx: int):
        if self._store is not None:
            # streamed cohort residency: gather [S] rows host->device,
            # run the same round body at slab width, stage rows back
            return self._run_round_store(state, round_idx)
        sel = self._selected_client_indexes(round_idx)
        d = self.data
        # read BEFORE dispatch: under donate_state the call consumes
        # `state` (the host cache only compares object identity, but
        # the ownership lint holds driver paths to read-before-donate)
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

    def finalize(self, state: FedAvgState):
        if not self.track_personal:
            # the fine-tune pass exists to produce the personal models
            # (fedavg_api.py:79-88); nothing to produce when untracked
            return state, None
        with obs_trace.span("finetune"):
            state = self._finetune_jit(
                state, self.data.x_train, self.data.y_train,
                self.data.n_train)
        if self._store is not None:
            # the fine-tune retrained EVERY client from the final
            # global — a transient O(C) device stack (population-scale
            # runs skip finalize; this serves the reference protocol at
            # moderate C). Adopt it into the store wholesale, drop it
            # from state; the final eval below re-seeds from the store.
            self._store.stage("personal_params",
                              np.arange(self.num_clients),
                              state.personal_params)
            self._store.commit()
            self._store_eval_cache = None
            self._store_eval_dirty = []
            state = state.replace(personal_params=None)
        if self.eval_cache:
            # the fine-tune retrained EVERY personal row: the cache is
            # stale wholesale — drop it so evaluate falls back to the
            # full personal eval (None marks "not live on this state")
            state = state.replace(eval_cache=None)
        ev = self.evaluate(state)
        record = {"round": -1, "finetune": True,
                  **{k: v for k, v in ev.items()
                     if not k.startswith("acc_per")}}
        return state, record

    def _eval_impl(self, state, x_test, y_test, n_test,
                   personal_fn) -> Dict[str, Any]:
        # routed by the base wrappers: eval_metrics passes the traceable
        # full personal eval, evaluate the incremental cached one
        ev = self._eval_global(state.global_params, x_test, y_test, n_test)
        out = {"global_acc": ev["acc"], "global_loss": ev["loss"],
               "acc_per_client": ev["acc_per_client"]}
        if state.personal_params is not None or \
                self._store_has_personal():
            evp = personal_fn(
                state.personal_params, x_test, y_test, n_test)
            out.update(personal_acc=evp["acc"], personal_loss=evp["loss"])
        return out
