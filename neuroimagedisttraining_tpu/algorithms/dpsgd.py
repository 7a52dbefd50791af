"""DPSGD — Decentralized Parallel SGD (gossip averaging, not diff. privacy).

Re-design of ``fedml_api/standalone/dpsgd/dpsgd_api.py:41-103``: every round
each client uniformly averages its neighborhood's personal models
(``_aggregate_func`` :169-178, neighborhood from ``_benefit_choose``
:116-139 random/ring/full), then trains locally. The reference additionally
reports a global average and runs a fine-tune pass every 100 rounds
(:88-101); here the global average is computed in ``evaluate``.

TPU-native: all personal models live stacked [C, ...]; the gossip step is
one row-normalized adjacency contraction (``mix_over_clients``) — an
all-gather + GEMM over ICI instead of per-edge sends.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..core.state import broadcast_tree, mix_over_clients
from ..core.trainer import make_client_update
from ..parallel.topology import neighbor_adjacency
from .base import FedAlgorithm


@struct.dataclass
class DPSGDState:
    personal_params: Any  # [C, ...]
    rng: jax.Array


class DPSGD(FedAlgorithm):
    name = "dpsgd"
    # the only per-round host input is the neighbor adjacency, a pure
    # function of round_idx (np.random.RandomState(round_idx) inside
    # neighbor_adjacency — _benefit_choose's seeded draw, dpsgd_api.py:
    # 116-139), so a K-round block precomputes the adjacency stack and
    # runs as ONE lax.scan program like the centralized algorithms
    supports_fused = True

    def cost_trained_clients_per_round(self) -> int:
        # gossip rounds train the whole cohort (dpsgd_api.py:41-103)
        return self.num_clients

    def __init__(self, *args, neighbor_mode: str = "random", **kwargs):
        self.neighbor_mode = neighbor_mode
        super().__init__(*args, **kwargs)

    def _build(self) -> None:
        self.client_update = make_client_update(
            self.apply_fn, self.loss_type, self.hp,
            mask_grads=False, mask_params_post_step=False,
            remat=self.remat_local, full_batches=self._full_batches(),
            augment_fn=self.augment_fn,
        )

        def round_fn(state: DPSGDState, adjacency, round_idx,
                     x_train, y_train, n_train):
            rng, round_key = jax.random.split(state.rng)
            # gossip: uniform average over the neighborhood (incl. self)
            row_sum = jnp.maximum(adjacency.sum(axis=1, keepdims=True), 1.0)
            mixed = mix_over_clients(adjacency / row_sum,
                                     state.personal_params)
            params, _, losses = self._train_stacked(
                self.client_update, mixed, mixed, round_idx, round_key,
                x_train, y_train, n_train,
            )
            return DPSGDState(personal_params=params, rng=rng), jnp.mean(losses)

        self._round_jit = jax.jit(round_fn)
        self._eval_global = self._make_global_eval()
        self._eval_personal = self._make_personal_eval()

    def init_state(self, rng: jax.Array) -> DPSGDState:
        p_rng, s_rng = jax.random.split(rng)
        params = self.init_model_params(p_rng)
        return DPSGDState(
            personal_params=broadcast_tree(params, self.num_clients),
            rng=s_rng,
        )

    def _fused_host_inputs(self, round_idx: int):
        # the round's adjacency, with the exact seeded draw of the unfused
        # path (neighbor_adjacency reseeds from round_idx internally)
        return (neighbor_adjacency(
            round_idx, self.num_clients, self.clients_per_round,
            mode=self.neighbor_mode,
        ),)

    def run_round(self, state: DPSGDState, round_idx: int):
        (adj,) = self._fused_host_inputs(round_idx)
        state, loss = self._round_jit(
            state, jnp.asarray(adj), jnp.asarray(round_idx, jnp.float32),
            self.data.x_train, self.data.y_train, self.data.n_train,
        )
        return state, {"train_loss": loss}

    def eval_metrics(self, state: DPSGDState, x_test, y_test,
                     n_test) -> Dict[str, Any]:
        # global average model (dpsgd_api.py:85 _avg_aggregate) + personal;
        # fully traceable, so the fused block evals in-graph too
        avg = jax.tree_util.tree_map(
            lambda x: jnp.mean(x, axis=0), state.personal_params
        )
        ev_g = self._eval_global(avg, x_test, y_test, n_test)
        ev_p = self._eval_personal(
            state.personal_params, x_test, y_test, n_test)
        return {
            "global_acc": ev_g["acc"], "global_loss": ev_g["loss"],
            "personal_acc": ev_p["acc"], "personal_loss": ev_p["loss"],
            "acc_per_client": ev_p["acc_per_client"],
        }
