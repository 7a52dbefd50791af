"""TurboAggregate — secure aggregation over additive secret shares.

Re-design of ``fedml_api/standalone/turboaggregate/`` (arXiv:2002.04156
scaffold): the reference provides finite-field MPC primitives
(``mpc_function.py:4-275``) and a trainer whose round is FedAvg with a
topology placeholder between train and aggregate (``TA_trainer.py:38-72``).
Here the protocol is actually wired end-to-end for the centralized-sum case:
each client's locally-trained model is fixed-point quantized into F_p,
split into additive secret shares (one per simulated aggregation group),
the shares are summed share-wise (no party sees a plaintext model), and the
reconstructed field sum is dequantized into the sample-weighted average.

The local-training leg is the same jitted SPMD program as FedAvg; the
secret-sharing transport is host-side numpy int64 (correctness-only, per
SURVEY.md §7.7 — TPUs have no native int64 modular arithmetic path worth
building for this).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..core.state import broadcast_tree, zeros_like_tree
from ..core.trainer import make_client_update
from ..ops import mpc
from .base import FedAlgorithm, sample_client_indexes


@struct.dataclass
class TurboAggregateState:
    global_params: Any
    rng: jax.Array


class TurboAggregate(FedAlgorithm):
    name = "turboaggregate"

    def __init__(self, *args, n_groups: int = 3, quant_scale: int = 2 ** 16,
                 prime: int = mpc.DEFAULT_PRIME, **kwargs):
        self.n_groups = n_groups
        self.quant_scale = quant_scale
        self.prime = prime
        super().__init__(*args, **kwargs)

    def _build(self) -> None:
        self.client_update = make_client_update(
            self.apply_fn, self.loss_type, self.hp,
            mask_grads=False, mask_params_post_step=False,
            remat=self.remat_local, full_batches=self._full_batches(),
            augment_fn=self.augment_fn,
        )

        def local_fn(global_params, sel_idx, round_idx, round_key,
                     x_train, y_train, n_train):
            n_sel = jnp.take(n_train, sel_idx)
            x_sel = jnp.take(x_train, sel_idx, axis=0)
            y_sel = jnp.take(y_train, sel_idx, axis=0)
            s = sel_idx.shape[0]
            params0 = broadcast_tree(global_params, s)
            mom0 = zeros_like_tree(params0)
            keys = jax.random.split(round_key, s)
            params_out, _, losses = self._vmap_clients(
                self.client_update, in_axes=(0, 0, 0, 0, 0, 0, 0, None, 0)
            )(params0, mom0, params0, keys, x_sel, y_sel, n_sel, round_idx,
              params0)
            return params_out, n_sel, jnp.mean(losses)

        self._local_jit = jax.jit(local_fn)
        self._eval_global = self._make_global_eval()

    def _secure_weighted_sum(self, stacked_locals: Any,
                             weights: np.ndarray) -> Any:
        """Sum pre-weighted local models through additive secret shares."""
        p, scale = self.prime, self.quant_scale
        leaves, treedef = jax.tree_util.tree_flatten(stacked_locals)
        out = []
        rng = np.random.RandomState(0)
        for leaf in leaves:
            arr = np.asarray(leaf, np.float64)
            weighted = arr * weights.reshape((-1,) + (1,) * (arr.ndim - 1))
            # each client secret-shares its quantized weighted model
            share_sum = np.zeros((self.n_groups,) + arr.shape[1:], np.int64)
            for c in range(arr.shape[0]):
                q = mpc.quantize(weighted[c], scale, p)
                shares = mpc.additive_shares(q, self.n_groups, p, rng)
                share_sum = np.mod(share_sum + shares, p)
            # groups reveal only their share totals; the sum reconstructs
            total = np.mod(share_sum.sum(axis=0), p)
            out.append(jnp.asarray(
                mpc.dequantize(total, scale, p).astype(np.float32)
            ))
        return jax.tree_util.tree_unflatten(treedef, out)

    def init_state(self, rng: jax.Array) -> TurboAggregateState:
        p_rng, s_rng = jax.random.split(rng)
        params = self.init_model_params(p_rng)
        return TurboAggregateState(global_params=params, rng=s_rng)

    def run_round(self, state: TurboAggregateState, round_idx: int):
        sel = sample_client_indexes(
            round_idx, self.num_clients, self.clients_per_round
        )
        rng, round_key = jax.random.split(state.rng)
        params_out, n_sel, loss = self._local_jit(
            state.global_params, jnp.asarray(sel),
            jnp.asarray(round_idx, jnp.float32), round_key,
            self.data.x_train, self.data.y_train, self.data.n_train,
        )
        w = np.asarray(n_sel, np.float64)
        w = w / w.sum()
        new_global = self._secure_weighted_sum(params_out, w)
        return (
            TurboAggregateState(global_params=new_global, rng=rng),
            {"train_loss": loss},
        )

    def evaluate(self, state: TurboAggregateState) -> Dict[str, Any]:
        ev = self._eval_global(
            state.global_params, self.data.x_test, self.data.y_test,
            self.data.n_test,
        )
        return {"global_acc": ev["acc"], "global_loss": ev["loss"],
                "acc_per_client": ev["acc_per_client"]}
