"""Ditto — personalized FL with a proximal pull toward the global model.

Re-design of ``fedml_api/standalone/ditto/ditto_api.py:40-78``: each sampled
client (a) trains a copy of the global model normally (contributing to the
sample-weighted FedAvg aggregate) and (b) trains its *personal* model with
the manual post-step proximal update ``w -= lr*lambda*(w - w_global)``
(``ditto/my_model_trainer.py:63-64``), pulling it toward the pre-round
global. The reference uses ``--epochs`` for the global leg and
``--local_epochs`` for the personal leg; both default to the shared
HyperParams here (override via ``personal_hp``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from flax import struct

from ..core.state import (
    broadcast_tree,
    tree_index,
    tree_scatter_update,
)
from ..core.trainer import make_client_update
from ..core.state import HyperParams
from .base import FedAlgorithm


@struct.dataclass
class DittoState:
    global_params: Any
    personal_params: Any  # [C, ...]
    rng: jax.Array


class Ditto(FedAlgorithm):
    name = "ditto"
    supports_fused = True
    donate_supported = True
    store_supported = True
    _round_metric_names = ("train_loss", "personal_train_loss")

    def cost_trained_clients_per_round(self) -> int:
        # each selected client trains a global AND a personal leg
        return 2 * self.clients_per_round

    def __init__(self, *args, lamda: float = 0.5,
                 personal_hp: Optional[HyperParams] = None, **kwargs):
        self.lamda = lamda
        self._personal_hp = personal_hp
        super().__init__(*args, **kwargs)

    def _build(self) -> None:
        self.client_update = make_client_update(
            self.apply_fn, self.loss_type, self.hp,
            mask_grads=False, mask_params_post_step=False,
            remat=self.remat_local, full_batches=self._full_batches(),
            augment_fn=self.augment_fn,
        )
        self.personal_update = make_client_update(
            self.apply_fn, self.loss_type, self._personal_hp or self.hp,
            mask_grads=False, mask_params_post_step=False,
            prox_lambda=self.lamda,
            remat=self.remat_local,
            full_batches=self._full_batches(self._personal_hp or self.hp),
            augment_fn=self.augment_fn,
        )

        def round_fn(state: DittoState, sel_idx, round_idx,
                     x_train, y_train, n_train):
            rng, k_global, k_personal = jax.random.split(state.rng, 3)
            # (a) global leg: standard FedAvg round (the guard, when on,
            # protects this aggregate too; Ditto does not thread the
            # quarantine counters into its metrics — guard_metrics_supported)
            new_global, _, mean_loss, _fstats, _res = \
                self._train_selected_weighted(
                    self.client_update, state.global_params,
                    state.global_params, sel_idx, round_idx, k_global,
                    x_train, y_train, n_train,
                )
            # (b) personal leg: prox-pulled toward the PRE-round global
            s = sel_idx.shape[0]
            p_sel = tree_index(state.personal_params, sel_idx)
            prox_target = broadcast_tree(state.global_params, s)
            trained_p, _, p_losses = self._train_stacked(
                self.personal_update, p_sel, p_sel, round_idx, k_personal,
                jnp.take(x_train, sel_idx, axis=0),
                jnp.take(y_train, sel_idx, axis=0),
                jnp.take(n_train, sel_idx),
                prox_target=prox_target,
            )
            new_personal = tree_scatter_update(
                state.personal_params, sel_idx, trained_p
            )
            return (
                DittoState(global_params=new_global,
                           personal_params=new_personal, rng=rng),
                mean_loss,
                jnp.mean(p_losses),
            )

        self._round_fn = round_fn
        self._round_jit = self._jit_entry(round_fn)
        self._eval_global = self._make_global_eval()
        self._eval_personal = self._make_personal_eval()

    def init_state(self, rng: jax.Array) -> DittoState:
        p_rng, s_rng = jax.random.split(rng)
        params = self.init_model_params(p_rng)
        if self._store is not None:
            # store mode: the personal stack lives in the client store
            # (lazy init-params default rows); state holds None between
            # rounds. See FedAvg.init_state.
            self._store_register_fields(params)
            return DittoState(global_params=params,
                              personal_params=None, rng=s_rng)
        return DittoState(
            global_params=params,
            personal_params=broadcast_tree(params, self.num_clients),
            rng=s_rng,
        )

    def run_round(self, state: DittoState, round_idx: int):
        if self._store is not None:
            # streamed cohort residency: same round body at slab width
            return self._run_round_store(state, round_idx)
        sel = self._selected_client_indexes(round_idx)
        # read BEFORE dispatch: under donate_state the call consumes
        # `state` (the ownership lint holds driver paths to this order)
        old_pers = state.personal_params
        new_state, g_loss, p_loss = self._round_jit(
            state, jnp.asarray(sel), jnp.asarray(round_idx, jnp.float32),
            self.data.x_train, self.data.y_train, self.data.n_train,
        )
        # only the selected clients' personal legs trained — feed the
        # incremental personal-eval cache (base._personal_eval_cached)
        self._note_personal_update(
            old_pers, new_state.personal_params, sel)
        return new_state, {"train_loss": g_loss,
                           "personal_train_loss": p_loss}

    def _eval_impl(self, state, x_test, y_test, n_test,
                   personal_fn) -> Dict[str, Any]:
        # routed by the base wrappers: eval_metrics passes the traceable
        # full personal eval, evaluate the incremental cached one
        ev_g = self._eval_global(state.global_params, x_test, y_test, n_test)
        ev_p = personal_fn(
            state.personal_params, x_test, y_test, n_test)
        return {
            "global_acc": ev_g["acc"], "global_loss": ev_g["loss"],
            "personal_acc": ev_p["acc"], "personal_loss": ev_p["loss"],
            "acc_per_client": ev_p["acc_per_client"],
        }
