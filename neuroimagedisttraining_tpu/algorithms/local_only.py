"""Local-only baseline: no communication, each client trains its own model.

Re-design of ``fedml_api/standalone/local/local_api.py:51-84``: the sampled
clients continue training their personal models; there is no aggregation.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import struct

from ..core.state import broadcast_tree, tree_index, tree_scatter_update
from ..core.trainer import make_client_update
from .base import FedAlgorithm, sample_client_indexes


@struct.dataclass
class LocalOnlyState:
    personal_params: Any  # [C, ...]
    rng: jax.Array


class LocalOnly(FedAlgorithm):
    name = "local"
    supports_fused = True

    def _build(self) -> None:
        self.client_update = make_client_update(
            self.apply_fn, self.loss_type, self.hp,
            mask_grads=False, mask_params_post_step=False,
            remat=self.remat_local, full_batches=self._full_batches(),
            augment_fn=self.augment_fn,
        )

        def round_fn(state: LocalOnlyState, sel_idx, round_idx,
                     x_train, y_train, n_train):
            rng, round_key = jax.random.split(state.rng)
            p_sel = tree_index(state.personal_params, sel_idx)
            trained, _, losses = self._train_stacked(
                self.client_update, p_sel, p_sel, round_idx, round_key,
                jnp.take(x_train, sel_idx, axis=0),
                jnp.take(y_train, sel_idx, axis=0),
                jnp.take(n_train, sel_idx),
            )
            new_personal = tree_scatter_update(
                state.personal_params, sel_idx, trained
            )
            return (LocalOnlyState(personal_params=new_personal, rng=rng),
                    jnp.mean(losses))

        self._round_jit = jax.jit(round_fn)
        self._eval_personal = self._make_personal_eval()

    def init_state(self, rng: jax.Array) -> LocalOnlyState:
        p_rng, s_rng = jax.random.split(rng)
        params = self.init_model_params(p_rng)
        return LocalOnlyState(
            personal_params=broadcast_tree(params, self.num_clients),
            rng=s_rng,
        )

    def run_round(self, state: LocalOnlyState, round_idx: int):
        sel = sample_client_indexes(
            round_idx, self.num_clients, self.clients_per_round
        )
        state, loss = self._round_jit(
            state, jnp.asarray(sel), jnp.asarray(round_idx, jnp.float32),
            self.data.x_train, self.data.y_train, self.data.n_train,
        )
        return state, {"train_loss": loss}

    def eval_metrics(self, state: LocalOnlyState, x_test, y_test,
                     n_test) -> Dict[str, Any]:
        ev = self._eval_personal(
            state.personal_params, x_test, y_test, n_test)
        return {"personal_acc": ev["acc"], "personal_loss": ev["loss"],
                "acc_per_client": ev["acc_per_client"]}
