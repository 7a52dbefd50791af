"""FedFomo — personalized aggregation by first-order model optimization.

Re-design of ``fedml_api/standalone/fedfomo/fedfomo_api.py:53-217``: each
round every client (1) trains its personal model, (2) picks a neighbor set
(biased toward accumulated helpfulness ``p_choose`` with probability 1/2,
else uniform — ``_benefit_choose`` :130-144), (3) scores each neighbor j by
``w_ij = (L_i(own pre-round model) - L_i(model_j)) / ||theta_j - theta_i||``
on its own *validation* split (``_updates_weight_local`` :147-171; j=self
uses the freshly trained model), and (4) applies the positively-clipped,
normalized weighted deltas to its pre-round model (``_aggregate_func``
:200-217 — if no neighbor helps, the client keeps its pre-round model).

Requires per-client validation shards (the reference's 9-element
``data_val_loader`` tuple, ``cifar10/data_val_loader.py:275-326``).

TPU-native: the neighbor evaluation is a [C, K] gather of stacked models
evaluated by a doubly-vmapped loss pass — the O(C*K) cross-evaluation the
reference does sequentially becomes one jitted program.
"""
from __future__ import annotations

import random as _pyrandom
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..core.state import broadcast_tree, tree_index
from ..core.trainer import make_client_update
from .base import FedAlgorithm


@struct.dataclass
class FedFomoState:
    personal_params: Any     # [C, ...]
    p_choose: jax.Array      # [C, C] accumulated helpfulness
    rng: jax.Array


class FedFomo(FedAlgorithm):
    name = "fedfomo"

    def cost_trained_clients_per_round(self) -> int:
        # every client trains its own model each round (fedfomo_api.py:53-118)
        return self.num_clients

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        if self.data.x_val is None:
            raise ValueError(
                "FedFomo needs per-client validation shards "
                "(FederatedData.x_val; see data_val_loader in the reference)"
            )

    def _build(self) -> None:
        self.client_update = make_client_update(
            self.apply_fn, self.loss_type, self.hp,
            mask_grads=False, mask_params_post_step=False,
            remat=self.remat_local, full_batches=self._full_batches(),
            augment_fn=self.augment_fn,
        )
        self._n_nei = min(self.clients_per_round, self.num_clients - 1)

        def val_loss(params, x, y, n_valid):
            _, loss_sum, total = self.eval_client(params, x, y, n_valid)
            return loss_sum / jnp.maximum(total, 1)

        def round_fn(state: FedFomoState, nei_idx, round_idx,
                     x_train, y_train, n_train, x_val, y_val, n_val):
            rng, k_train = jax.random.split(state.rng)
            lstrd = state.personal_params  # pre-round snapshot

            # (1) every client trains its own model
            trained, _, losses = self._train_stacked(
                self.client_update, lstrd, lstrd, round_idx, k_train,
                x_train, y_train, n_train,
            )

            # (2+3+4) fused per-(client, neighbor) pass: build each
            # neighbor's delta once, score it, and aggregate the
            # positively-clipped normalized deltas
            c = nei_idx.shape[0]
            self_loss = jax.vmap(val_loss)(lstrd, x_val, y_val, n_val)

            def client_round(i, js):
                base = jax.tree_util.tree_map(lambda l: l[i], lstrd)

                # scan over neighbors, accumulating the positively-clipped
                # weighted delta sum in the carry — normalization by the
                # weight sum is linear, so dividing once at the end equals
                # weighting by w/wsum per neighbor. Keeps exactly one
                # neighbor delta live instead of a [K+1, |model|] stack
                # (which at AlexNet3D scale would hold C*(K+1) model copies
                # in HBM at once).
                def per_neighbor(carry, j):
                    acc, wsum = carry
                    model_j = jax.tree_util.tree_map(
                        lambda t, l: jnp.where(j == i, t[i], l[j]),
                        trained, lstrd,
                    )
                    delta = jax.tree_util.tree_map(
                        lambda mj, b: mj - b, model_j, base
                    )
                    l_j = val_loss(model_j, x_val[i], y_val[i], n_val[i])
                    nrm = jnp.sqrt(sum(
                        jnp.sum(jnp.square(d))
                        for d in jax.tree_util.tree_leaves(delta)
                    ))
                    w = jnp.where(
                        nrm > 0,
                        (self_loss[i] - l_j) / jnp.maximum(nrm, 1e-12),
                        0.0,
                    )
                    w_pos = jnp.maximum(w, 0.0)
                    acc = jax.tree_util.tree_map(
                        lambda a, d: a + w_pos.astype(d.dtype) * d,
                        acc, delta,
                    )
                    return (acc, wsum + w_pos), w

                zeros = jax.tree_util.tree_map(jnp.zeros_like, base)
                (acc, wsum), ws = jax.lax.scan(
                    per_neighbor, (zeros, jnp.float32(0.0)), js
                )
                new_p = jax.tree_util.tree_map(
                    lambda b, a: jnp.where(
                        wsum > 0,
                        b + a / jnp.maximum(wsum, 1e-12).astype(a.dtype),
                        b,
                    ),
                    base, acc,
                )
                return new_p, ws

            new_personal, nei_w = jax.vmap(client_round)(
                jnp.arange(c), nei_idx
            )

            # p_choose accumulation over visited neighbors (:93)
            upd = jnp.zeros_like(state.p_choose)
            upd = upd.at[jnp.arange(c)[:, None], nei_idx].add(nei_w)
            return (
                FedFomoState(personal_params=new_personal,
                             p_choose=state.p_choose + upd, rng=rng),
                jnp.mean(losses),
            )

        self._round_jit = jax.jit(round_fn)
        self._eval_personal = self._make_personal_eval()

    def init_state(self, rng: jax.Array) -> FedFomoState:
        p_rng, s_rng = jax.random.split(rng)
        params = self.init_model_params(p_rng)
        return FedFomoState(
            personal_params=broadcast_tree(params, self.num_clients),
            p_choose=jnp.ones((self.num_clients, self.num_clients)),
            rng=s_rng,
        )

    def _choose_neighbors(self, round_idx: int,
                          p_choose: np.ndarray) -> np.ndarray:
        """Host-side neighbor choice (fedfomo_api.py:130-144): with prob 1/2
        the top-p_choose clients, else uniform (self excluded); self always
        appended."""
        c, k = self.num_clients, self._n_nei
        rng = np.random.RandomState(round_idx)
        coin = _pyrandom.Random(round_idx)
        out = np.zeros((c, k + 1), dtype=np.int32)
        for i in range(c):
            p = p_choose[i].copy()
            p[i] = 0
            if coin.random() >= 0.5:
                idx = np.argsort(p)[-k:]
            else:
                others = np.delete(np.arange(c), i)
                idx = rng.choice(others, k, replace=False)
            out[i, :k] = idx
            out[i, k] = i
        return out

    def run_round(self, state: FedFomoState, round_idx: int):
        nei = self._choose_neighbors(round_idx, np.asarray(state.p_choose))
        state, loss = self._round_jit(
            state, jnp.asarray(nei), jnp.asarray(round_idx, jnp.float32),
            self.data.x_train, self.data.y_train, self.data.n_train,
            self.data.x_val, self.data.y_val, self.data.n_val,
        )
        return state, {"train_loss": loss}

    def evaluate(self, state: FedFomoState) -> Dict[str, Any]:
        ev = self._eval_personal(
            state.personal_params, self.data.x_test, self.data.y_test,
            self.data.n_test,
        )
        return {"personal_acc": ev["acc"], "personal_loss": ev["loss"],
                "acc_per_client": ev["acc_per_client"]}
