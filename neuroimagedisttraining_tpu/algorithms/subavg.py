"""SubAvg — iterative-magnitude-pruning federated averaging.

Re-design of ``fedml_api/standalone/subavg/``: each sampled client trains
with masked gradients from the masked global model
(``my_model_trainer.py:48-82``), derives candidate masks by magnitude
percentile after the first and last local epoch (``fake_prune``,
``prune_func.py:9-30``), and accepts the new mask only if the two candidates
differ by more than ``dist_thresh`` hamming, the current density is above
``dense_ratio``, and post-prune local accuracy clears ``acc_thresh``
(``subavg/client.py:36-63``). The server then does mask-count-weighted
averaging, keeping its previous value where no client had a live weight
(``subavg_api.py:123-140`` — the ``isfinite`` guard).

TPU-native: the accept decision is a traced three-way AND selecting between
mask pytrees; the count-weighted aggregate is two contractions over the
selected-client axis.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import struct

from ..core.state import (
    HyperParams,
    broadcast_tree,
    tree_index,
    tree_scatter_update,
)
from ..core.trainer import make_client_update
from ..ops.sparsity import (
    magnitude_prune_mask,
    mask_density,
    mask_distance,
)
from .base import FedAlgorithm, sample_client_indexes


@struct.dataclass
class SubAvgState:
    global_params: Any
    masks: Any  # [C, ...] per-client masks
    rng: jax.Array


class SubAvg(FedAlgorithm):
    name = "subavg"
    supports_fused = True
    masks_evolve = True  # pruning changes per-client density

    def __init__(self, *args, each_prune_ratio: float = 0.2,
                 dist_thresh: float = 0.001, acc_thresh: float = 0.5,
                 dense_ratio: float = 0.5, **kwargs):
        self.each_prune_ratio = each_prune_ratio
        self.dist_thresh = dist_thresh
        self.acc_thresh = acc_thresh
        self.dense_ratio = dense_ratio
        super().__init__(*args, **kwargs)

    def _build(self) -> None:
        hp = self.hp
        hp_first = hp.replace(local_epochs=1)
        hp_rest = hp.replace(local_epochs=max(0, hp.local_epochs - 1))
        self._update_first = make_client_update(
            self.apply_fn, self.loss_type, hp_first,
            mask_grads=True, mask_params_post_step=False,
            remat=self.remat_local, full_batches=self._full_batches(hp_first),
            augment_fn=self.augment_fn,
        )
        self._update_rest = (
            make_client_update(
                self.apply_fn, self.loss_type, hp_rest,
                mask_grads=True, mask_params_post_step=False,
                remat=self.remat_local,
                full_batches=self._full_batches(hp_rest),
                augment_fn=self.augment_fn,
            )
            if hp_rest.local_epochs > 0 else None
        )

        def client_round(params, mask, rng, x, y, n_valid, round_idx):
            mom0 = jax.tree_util.tree_map(jnp.zeros_like, params)
            p1, mom1, loss1 = self._update_first(
                params, mom0, mask, rng, x, y, n_valid, round_idx, params
            )
            m1 = magnitude_prune_mask(mask, p1, self.each_prune_ratio)
            if self._update_rest is not None:
                p2, _, loss2 = self._update_rest(
                    p1, mom1, mask, jax.random.fold_in(rng, 1), x, y,
                    n_valid, round_idx, p1,
                )
                loss = (loss1 + loss2) / 2
            else:
                p2, loss = p1, loss1
            m2 = magnitude_prune_mask(mask, p2, self.each_prune_ratio)

            # accept gates (subavg/client.py:50-60)
            dist = mask_distance(m1, m2)
            density = mask_density(p2)  # nonzero fraction of the weights themselves
            correct, _, total = self.eval_client(
                jax.tree_util.tree_map(jnp.multiply, p2, m2), x, y, n_valid
            )
            acc = correct.astype(jnp.float32) / jnp.maximum(total, 1)
            accept = (
                (dist > self.dist_thresh)
                & (density > self.dense_ratio)
                & (acc > self.acc_thresh)
            )
            new_mask = jax.tree_util.tree_map(
                lambda a, b: jnp.where(accept, a, b), m2, mask
            )
            new_params = jax.tree_util.tree_map(
                lambda p, m: jnp.where(accept, p * m, p), p2, new_mask
            )
            return new_params, new_mask, loss

        def round_fn(state: SubAvgState, sel_idx, round_idx,
                     x_train, y_train, n_train):
            rng, round_key = jax.random.split(state.rng)
            s = sel_idx.shape[0]
            masks_sel = tree_index(state.masks, sel_idx)
            # client starts from the mask-pruned global (client.py:40-42)
            params0 = jax.tree_util.tree_map(
                jnp.multiply, broadcast_tree(state.global_params, s),
                masks_sel,
            )
            keys = jax.random.split(round_key, s)
            trained, new_masks, losses = self._vmap_clients(
                client_round, in_axes=(0, 0, 0, 0, 0, 0, None)
            )(params0, masks_sel, keys,
              jnp.take(x_train, sel_idx, axis=0),
              jnp.take(y_train, sel_idx, axis=0),
              jnp.take(n_train, sel_idx), round_idx)

            # mask-count-weighted server update (subavg_api.py:123-140).
            # Counts use the PRE-round masks: the reference appends
            # (mask_pers[idx], w_client) to w_locals BEFORE the post-
            # aggregation mask update loop (subavg_api.py:66-70,83-84), so
            # freshly pruned coordinates count in the denominator there too.
            counts = jax.tree_util.tree_map(
                lambda m: jnp.sum(m, axis=0), masks_sel
            )
            sums = jax.tree_util.tree_map(
                lambda w: jnp.sum(w, axis=0), trained
            )
            new_global = jax.tree_util.tree_map(
                lambda srv, s_, c: jnp.where(c > 0, s_ / jnp.maximum(c, 1e-9),
                                             srv),
                state.global_params, sums, counts,
            )
            all_masks = tree_scatter_update(state.masks, sel_idx, new_masks)
            return (
                SubAvgState(global_params=new_global, masks=all_masks,
                            rng=rng),
                jnp.mean(losses),
            )

        self._round_jit = jax.jit(round_fn)
        self._eval_global = self._make_global_eval()
        self._eval_personal = self._make_personal_eval()

    def init_state(self, rng: jax.Array) -> SubAvgState:
        p_rng, s_rng = jax.random.split(rng)
        params = self.init_model_params(p_rng)
        # all clients start from the SAME all-ones mask (subavg_api.py:45-47)
        masks = broadcast_tree(
            jax.tree_util.tree_map(jnp.ones_like, params), self.num_clients
        )
        return SubAvgState(global_params=params, masks=masks, rng=s_rng)

    def run_round(self, state: SubAvgState, round_idx: int):
        sel = sample_client_indexes(
            round_idx, self.num_clients, self.clients_per_round
        )
        state, loss = self._round_jit(
            state, jnp.asarray(sel), jnp.asarray(round_idx, jnp.float32),
            self.data.x_train, self.data.y_train, self.data.n_train,
        )
        return state, {"train_loss": loss}

    def eval_metrics(self, state: SubAvgState, x_test, y_test,
                     n_test) -> Dict[str, Any]:
        # reference evaluates the global model through each client's mask
        # (subavg_api.py _local_test_on_all_clients)
        c = self.num_clients
        per_client = jax.tree_util.tree_map(
            jnp.multiply, broadcast_tree(state.global_params, c), state.masks
        )
        ev = self._eval_personal(per_client, x_test, y_test, n_test)
        dens = jax.vmap(mask_density)(state.masks)
        return {
            "personal_acc": ev["acc"], "personal_loss": ev["loss"],
            "mean_mask_density": jnp.mean(dens),
            "acc_per_client": ev["acc_per_client"],
        }
