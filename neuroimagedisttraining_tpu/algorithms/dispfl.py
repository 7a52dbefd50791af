"""DisPFL — decentralized sparse personalized FL (CVPR'22).

Re-design of ``fedml_api/standalone/DisPFL/dispfl_api.py:46-184``:
  * per-client random masks at ERK-allocated layer sparsities
    (``my_model_trainer.py:28-38,40-114``)
  * per round: client dropout coin-flips (``--active``, :96), neighbor
    choice random/ring/full (``_benefit_choose`` :196-220),
    count-mask-weighted aggregation of neighbors' sparse personal models
    re-masked by the local mask (``_aggregate_func`` :222-240),
    masked-gradient local SGD (trainer :147-172), then mask evolution:
    screen one dense gradient batch (:128-144), cosine-annealed magnitude
    fire + gradient-magnitude regrow (``client.py:71-99``)
  * mask hamming-distance tracking (``slim_util.py:14-19``).

TPU-native: masks and personal models are [C, ...] stacked pytrees; the
count-mask aggregation is two adjacency contractions (weights and mask
counts) + a safe reciprocal — all inside one jitted round program. Inactive
clients keep their previous state via a select, preserving the reference's
dropout-simulation semantics without host branching.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..core.losses import make_loss_fn
from ..core.state import broadcast_tree, mix_over_clients
from ..core.trainer import make_client_update
from ..ops.sparsity import (
    cosine_annealing,
    erk_sparsities,
    uniform_sparsities,
    fire_mask,
    kernel_flags,
    live_counts,
    mask_density,
    param_shapes,
    random_masks_from_sparsities,
    regrow_mask,
)
from ..parallel.topology import neighbor_adjacency
from .base import FedAlgorithm


@struct.dataclass
class DisPFLState:
    personal_params: Any  # [C, ...] sparse personal models
    masks: Any            # [C, ...] personal masks
    rng: jax.Array


class DisPFL(FedAlgorithm):
    name = "dispfl"

    def cost_trained_clients_per_round(self) -> int:
        # inactive clients skip only the aggregation; all train
        # (dispfl_api.py:96,105-142)
        return self.num_clients

    def __init__(self, *args, dense_ratio: float = 0.5,
                 anneal_factor: float = 0.5, neighbor_mode: str = "random",
                 active: float = 1.0, static_masks: bool = False,
                 total_rounds: int = 100, erk_power_scale: float = 1.0,
                 sparsity_distribution: str = "erk",
                 different_initial: bool = False, diff_spa: bool = False,
                 dis_gradient_check: bool = False,
                 record_local_tests: bool = True,
                 **kwargs):
        """Mask-init variants (``dispfl_api.py:48-71``):
        ``sparsity_distribution``: "erk" (default) or "uniform"
        (``--uniform``). ``different_initial``: per-client independent
        initial masks (reference default is one shared initial mask).
        ``diff_spa``: clients cycle dense ratios [0.2,0.4,0.6,0.8,1.0]
        (implies different_initial); densities persist through fire/regrow
        because evolution preserves per-client live counts."""
        self.dense_ratio = dense_ratio
        self.anneal_factor = anneal_factor
        self.neighbor_mode = neighbor_mode
        self.active = active
        self.static_masks = static_masks
        self.masks_evolve = not static_masks  # fire/regrow changes density
        self.total_rounds = total_rounds
        self.erk_power_scale = erk_power_scale
        if sparsity_distribution not in ("erk", "uniform"):
            raise ValueError(
                f"sparsity_distribution {sparsity_distribution!r} not in "
                "('erk', 'uniform')")
        self.sparsity_distribution = sparsity_distribution
        self.different_initial = different_initial or diff_spa
        self.diff_spa = diff_spa
        # --dis_gradient_check: regrow uniformly at random among dead
        # weights instead of by |grad| (and skip the screening batch) —
        # DisPFL/client.py:54,91-98
        self.dis_gradient_check = dis_gradient_check
        # record_local_tests: the reference tests every client locally
        # around local training EVERY round (dispfl_api.py:150-155) — kept
        # as the default; disable to drop the two per-round full-cohort
        # test passes when eval cost matters (the runner turns it off at
        # --frequency_of_the_test 0)
        self.record_local_tests = record_local_tests
        super().__init__(*args, **kwargs)

    def _build(self) -> None:
        self.client_update = make_client_update(
            self.apply_fn, self.loss_type, self.hp,
            mask_grads=True, mask_params_post_step=True,
            remat=self.remat_local, full_batches=self._full_batches(),
            augment_fn=self.augment_fn,
        )
        loss_fn = make_loss_fn(self.loss_type)

        def screen_gradients(params, x, y, n_valid, rng):
            """One dense-batch gradient for regrow scoring
            (DisPFL/my_model_trainer.py:128-144); the reference feeds it
            train-loader batches, so augmentation applies like training."""
            k_idx, k_drop = jax.random.split(rng)
            idx = jax.random.randint(
                k_idx, (self.hp.batch_size,), 0, jnp.maximum(n_valid, 1)
            )
            xb = jnp.take(x, idx, axis=0)
            yb = jnp.take(y, idx, axis=0)
            if self.augment_fn is not None:
                k_aug, k_drop = jax.random.split(k_drop)
                xb = self.augment_fn(k_aug, xb)
            return jax.grad(
                lambda p: loss_fn(self.apply_fn(p, xb, train=True,
                                                rng=k_drop), yb)
            )(params)

        eval_client = self.eval_client

        def local_test_means(params_stack, x_test, y_test, n_test):
            """Per-client local test, reported as the reference's means:
            acc = mean_c(correct_c/total_c), loss = mean_c(loss_c/total_c)
            (dispfl_api.py:242-301). Chunked like the training vmap so the
            two default-on eval passes respect the same --client_chunk HBM
            bound as training (ADVICE r3)."""
            correct, loss_sum, total = self._vmap_clients(
                eval_client, in_axes=(0, 0, 0, 0))(
                params_stack, x_test, y_test, n_test)
            totals = jnp.maximum(total, 1).astype(jnp.float32)
            return (jnp.mean(correct.astype(jnp.float32) / totals),
                    jnp.mean(loss_sum / totals))

        def round_fn(state: DisPFLState, adjacency, active_vec, round_idx,
                     x_train, y_train, n_train, x_test, y_test, n_test):
            rng, k_train, k_screen = jax.random.split(state.rng, 3)
            params, masks = state.personal_params, state.masks

            # --- count-mask-weighted neighbor aggregation (:222-240) ------
            counts = mix_over_clients(adjacency, masks)
            inv = jax.tree_util.tree_map(
                lambda c: jnp.where(c != 0, 1.0 / jnp.maximum(c, 1e-9), 0.0),
                counts,
            )
            sums = mix_over_clients(adjacency, params)
            consensus = jax.tree_util.tree_map(jnp.multiply, sums, inv)
            w_agg = jax.tree_util.tree_map(jnp.multiply, consensus, masks)

            # inactive clients skip ONLY the aggregation — they still train
            # from their own previous personal model and evolve their masks
            # (dispfl_api.py:105-142: w_local falls back to the lstrd copy,
            # client.train runs unconditionally)
            def pick_active(agg, own):
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(
                        active_vec.reshape((-1,) + (1,) * (a.ndim - 1)) > 0,
                        a, b,
                    ),
                    agg, own,
                )

            w_local = pick_active(w_agg, params)

            # per-round local test of the aggregated model BEFORE local
            # training ("new mask" series, dispfl_api.py:150-151,271-301)
            nanv = jnp.float32(jnp.nan)
            pre_acc = pre_loss = nanv
            if self.record_local_tests:
                pre_acc, pre_loss = local_test_means(
                    w_local, x_test, y_test, n_test)

            # --- masked local SGD ----------------------------------------
            trained, _, losses = self._train_stacked(
                self.client_update, w_local, masks, round_idx, k_train,
                x_train, y_train, n_train,
            )

            # per-round local test AFTER local training, before mask
            # evolution — the tst_results each client.train returns
            # ("old mask" series, dispfl_api.py:154-155,242-269)
            post_acc = post_loss = nanv
            if self.record_local_tests:
                post_acc, post_loss = local_test_means(
                    trained, x_test, y_test, n_test)

            # --- mask evolution (fire/regrow, client.py:55-99) -----------
            if self.static_masks:
                new_masks = masks
            else:
                c = x_train.shape[0]
                keys = jax.random.split(k_screen, c)
                if self.dis_gradient_check:
                    # random regrow: uniform scores stand in for |grad| —
                    # top-n random dead == multinomial without replacement
                    # (DisPFL/client.py:96-98); no screening batch runs
                    def rand_tree(p, key):
                        leaves, treedef = jax.tree_util.tree_flatten(p)
                        ks = jax.random.split(key, len(leaves))
                        return jax.tree_util.tree_unflatten(
                            treedef,
                            [jax.random.uniform(k2, l.shape)
                             for l, k2 in zip(leaves, ks)])

                    grads = jax.vmap(rand_tree)(trained, keys)
                else:
                    grads = self._vmap_clients(
                        screen_gradients, in_axes=(0, 0, 0, 0, 0)
                    )(trained, x_train, y_train, n_train, keys)
                rate = cosine_annealing(
                    self.anneal_factor, round_idx, self.total_rounds
                )
                before = jax.vmap(live_counts)(masks)  # per-client counts
                fired = jax.vmap(partial(fire_mask, drop_rate=rate))(
                    masks, trained
                )
                n_regrow = jax.tree_util.tree_map(
                    lambda b, f: b - f, before, jax.vmap(live_counts)(fired)
                )
                new_masks = jax.vmap(regrow_mask)(fired, grads, n_regrow)
                trained = jax.tree_util.tree_map(
                    jnp.multiply, trained, new_masks
                )

            # mask-change tracking (hamming fraction, slim_util.py:14-19)
            ham = _hamming_fraction(masks, new_masks)
            out = (
                DisPFLState(personal_params=trained, masks=new_masks,
                            rng=rng),
                jnp.mean(losses), ham,
            )
            if self.record_local_tests:
                out += (pre_acc, pre_loss, post_acc, post_loss)
            return out

        self._round_jit = jax.jit(round_fn)
        self._eval_personal = self._make_personal_eval()

    def _client_sparsities(self, shapes, client_idx: int):
        """Per-layer sparsities for one client's initial mask."""
        ratio = self.dense_ratio
        if self.diff_spa:
            # dispfl_api.py:63-71: cycle dense ratios over clients
            ratio = (0.2, 0.4, 0.6, 0.8, 1.0)[client_idx % 5]
        if self.sparsity_distribution == "uniform":
            return uniform_sparsities(shapes, ratio)
        return erk_sparsities(shapes, ratio, self.erk_power_scale)

    def init_state(self, rng: jax.Array) -> DisPFLState:
        p_rng, m_rng, s_rng = jax.random.split(rng, 3)
        params = self.init_model_params(p_rng)
        shapes = param_shapes(params)
        if self.different_initial:
            mask_keys = jax.random.split(m_rng, self.num_clients)
            per_client = [
                random_masks_from_sparsities(
                    params,
                    (lambda sp: lambda n, s: sp[n])(
                        self._client_sparsities(shapes, i)),
                    mask_keys[i],
                )
                for i in range(self.num_clients)
            ]
            masks = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per_client)
        else:
            # reference default: ONE shared initial mask — compute once
            # and broadcast (not num_clients identical recomputations)
            sp = self._client_sparsities(shapes, 0)
            one = random_masks_from_sparsities(
                params, lambda n, s: sp[n], m_rng)
            masks = broadcast_tree(one, self.num_clients)
        stacked = broadcast_tree(params, self.num_clients)
        personal = jax.tree_util.tree_map(jnp.multiply, stacked, masks)
        return DisPFLState(personal_params=personal, masks=masks, rng=s_rng)

    # every per-round host input is a pure function of round_idx (the
    # reference's np.random.seed(round_idx) dropout coin-flips,
    # dispfl_api.py:96, and the seeded _benefit_choose adjacency,
    # :196-220) — data-INDEPENDENT host RNG, so a K-round block can
    # precompute the (adjacency, active) stacks and fuse like DPSGD.
    # Mask evolution (fire/regrow) is data-dependent but lives entirely
    # in-graph, so it scans fine.
    supports_fused = True

    @property
    def _round_metric_names(self):
        names = ("train_loss", "mask_change")
        if self.record_local_tests:
            # reference stat_info key names (dispfl_api.py:269,301):
            # "old_mask" = after local training, "new_mask" = the
            # aggregated model under the refreshed shared mask, before
            # local training
            names += ("new_mask_test_acc", "new_mask_test_loss",
                      "old_mask_test_acc", "old_mask_test_loss")
        return names

    def _fused_host_inputs(self, round_idx: int):
        # exact unfused draw order: seed, coin-flip the active vector,
        # then the adjacency (which reseeds its own RandomState)
        np.random.seed(round_idx)
        active_vec = np.random.choice(
            [0, 1], size=self.num_clients,
            p=[1.0 - self.active, self.active],
        )
        adj = neighbor_adjacency(
            round_idx, self.num_clients, self.clients_per_round,
            mode=self.neighbor_mode, active=active_vec,
        )
        return (adj, active_vec)

    def _fused_data_args(self):
        d = self.data
        # the round program itself consumes the test arrays (the two
        # per-round local-test passes); the fused driver appends them
        # again for the eval branch — same buffers, no copies
        return (d.x_train, d.y_train, d.n_train,
                d.x_test, d.y_test, d.n_test)

    def run_round(self, state: DisPFLState, round_idx: int):
        adj, active_vec = self._fused_host_inputs(round_idx)
        out = self._round_jit(
            state, jnp.asarray(adj), jnp.asarray(active_vec),
            jnp.asarray(round_idx, jnp.float32),
            self.data.x_train, self.data.y_train, self.data.n_train,
            self.data.x_test, self.data.y_test, self.data.n_test,
        )
        return out[0], dict(zip(self._round_metric_names, out[1:]))

    def eval_metrics(self, state: DisPFLState, x_test, y_test,
                     n_test) -> Dict[str, Any]:
        ev = self._eval_personal(
            state.personal_params, x_test, y_test, n_test)
        dens = jax.vmap(mask_density)(state.masks)
        return {
            "personal_acc": ev["acc"], "personal_loss": ev["loss"],
            "mean_mask_density": jnp.mean(dens),
            "acc_per_client": ev["acc_per_client"],
        }

    def mask_distance_matrix(self, state: DisPFLState) -> np.ndarray:
        """Pairwise hamming-fraction matrix over client masks — the end-of-
        run diagnostic the reference stores (dispfl_api.py:170-175)."""
        flat = jnp.concatenate([
            m.reshape(m.shape[0], -1)
            for m, k in zip(jax.tree_util.tree_leaves(state.masks),
                            jax.tree_util.tree_leaves(
                                kernel_flags(state.masks)))
            if k
        ], axis=1)
        a = (flat != 0).astype(jnp.float32)
        return np.asarray(
            jnp.mean(jnp.abs(a[:, None, :] - a[None, :, :]), axis=-1)
        )


def _hamming_fraction(masks_a: Any, masks_b: Any) -> jax.Array:
    # only kernel leaves evolve (fire/regrow gate on kernel_flags); counting
    # bias/scale leaves in the denominator would dilute the metric
    flags = jax.tree_util.tree_leaves(kernel_flags(masks_a))
    num = sum(
        jnp.sum((a != 0) != (b != 0))
        for a, b, k in zip(jax.tree_util.tree_leaves(masks_a),
                           jax.tree_util.tree_leaves(masks_b), flags)
        if k
    )
    tot = sum(a.size
              for a, k in zip(jax.tree_util.tree_leaves(masks_a), flags)
              if k)
    return num / tot
