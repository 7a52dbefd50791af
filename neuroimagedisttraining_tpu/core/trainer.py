"""Jittable local-training and evaluation kernels.

This replaces the reference's per-client Python training loop
(``sailentgrads/my_model_trainer.py:185-219``: SGD(lr*decay**round) + BCE +
clip(10) + post-step ``param *= mask``) with a pure function over one client's
state that is `vmap`ed over the leading client axis and `lax.scan`ned over
local steps — so a whole cohort's local epoch is one XLA program with no
host round-trips (the reference pays a GPU→CPU ``state_dict`` deepcopy per
client per round, ``my_model_trainer.py:131-132``).

Batching model: each client's local shard lives padded at ``[n_max, ...]``
with a valid-count scalar. The default ``hp.batching == "epoch"`` draws
per-epoch shuffled batches — each client consumes exactly its own
``ceil(n_i/batch)`` batches per epoch, the last one partial, matching the
reference's ``DataLoader(shuffle=True, drop_last=False)`` iteration
(``ABCD/data_loader.py:202``, ``my_model_trainer.py:194-216``); steps past a
client's own count are masked no-ops so shapes stay static under jit/vmap.
``hp.batching == "replacement"`` keeps the round-1/2 uniform
with-replacement draws (also unbiased; marginally cheaper per step).
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .losses import PER_EXAMPLE_LOSSES, make_loss_fn, predictions
from .optim import clip_by_global_norm, sgd_momentum_step
from .state import HyperParams


ApplyFn = Callable[..., Any]  # apply_fn(params, x, train: bool, rng) -> logits


def epoch_permutations(rng: jax.Array, n_valid: jax.Array, epochs: int,
                       length: int, n_rows: int = 0) -> jax.Array:
    """``[epochs, length]`` shuffles for epoch batching: per epoch, the first
    ``min(n_valid, length)`` entries are a uniform draw without replacement
    from ALL valid row indices ``[0, n_valid)`` — a full permutation when
    ``length >= n_valid``; the remaining entries point at padded rows
    (``>= n_valid``) and are masked out by the per-example batch weights.
    Static-shape replacement for the reference's per-epoch DataLoader
    shuffle (``ABCD/data_loader.py:202``).

    ``n_rows`` is the padded shard size; the draw domain is
    ``max(length, n_rows)`` so a caller-truncated epoch (``steps_per_epoch *
    batch_size < n_i``) consumes a fresh random subset of the WHOLE shard
    each epoch rather than a fixed prefix."""
    domain = max(length, int(n_rows))
    positions = jnp.arange(domain)

    def one(key):
        scores = jnp.where(positions < n_valid,
                           jax.random.uniform(key, (domain,)), jnp.inf)
        return jnp.argsort(scores)[:length].astype(jnp.int32)

    return jax.vmap(one)(jax.random.split(rng, epochs))


def make_client_update(
    apply_fn: ApplyFn,
    loss_type: str,
    hp: HyperParams,
    mask_grads: bool = False,
    mask_params_post_step: bool = True,
    prox_lambda: float = 0.0,
    remat: bool = False,
    full_batches: bool = False,
    augment_fn: Callable = None,
):
    """Build the per-client local-training function.

    ``mask_grads``: also zero gradients through the mask (DisPFL/SubAvg-style
    masked SGD, ``DisPFL/my_model_trainer.py:147-172``).
    ``mask_params_post_step``: multiply params by mask after each optimizer
    step (SalientGrads, ``my_model_trainer.py:213-216``).
    ``prox_lambda``: Ditto's personalization pull — after each step,
    ``w -= lr * lambda * (w - w_global)`` (``ditto/my_model_trainer.py:63-64``).
    ``remat``: rematerialize the per-batch loss (activations recomputed in
    the backward pass) — trades FLOPs for HBM so more clients fit
    concurrently under the vmap (``client_chunk`` can rise).
    ``augment_fn``: jittable ``(rng, xb) -> xb`` training-time augmentation
    (e.g. :func:`data.cifar.random_crop_flip`), applied to every gathered
    training batch inside the scanned step — the device-side equivalent of
    the reference's torchvision train transform running in the DataLoader
    (``cifar10/data_loader.py:46-50``). Eval paths never see it.
    ``full_batches``: caller-asserted static guarantee that EVERY client's
    ``n_valid >= steps_per_epoch * batch_size`` (checkable host-side from
    the concrete shard counts). Epoch mode then skips the provably-no-op
    machinery — per-example batch weights, active-step selects — with
    bit-identical semantics (every batch is full, every step active).

    Returns ``client_update(params, momentum, mask, rng, x, y, n_valid,
    round_idx, prox_target) -> (params, momentum, mean_loss)``; vmap over a
    leading client axis on everything except ``round_idx``. ``prox_target``
    is ignored (and DCE'd) unless ``prox_lambda > 0``. ``momentum`` may be
    None where ``hp.momentum == 0``: no buffer rides the step loop then
    (a model's worth of memory; the folding round of ``algorithms/base.py``
    passes none).
    """
    per_example = PER_EXAMPLE_LOSSES[loss_type]
    epoch_mode = hp.batching == "epoch"

    def batch_loss(params, xb, yb, wb, dropout_rng):
        logits = apply_fn(params, xb, train=True, rng=dropout_rng)
        if wb is None:
            # full batch: plain mean, reduced in f32 like the masked
            # branch so the full_batches fast path and the masked path
            # keep identical reduction precision under bf16 compute
            return jnp.mean(per_example(logits, yb).astype(jnp.float32))
        # partial final epoch batch: mean over the batch's own valid
        # examples, exactly the reference's smaller-last-batch loss.mean()
        w = wb.astype(jnp.float32)
        per_ex = per_example(logits, yb).astype(jnp.float32)
        return jnp.sum(per_ex * w) / jnp.maximum(jnp.sum(w), 1.0)

    if remat:
        batch_loss = jax.checkpoint(
            batch_loss, policy=jax.checkpoint_policies.nothing_saveable)
    grad_fn = jax.value_and_grad(batch_loss)

    @jax.named_scope("optimizer")
    def apply_update(params, momentum, grads, mask, prox_target, lr):
        """One optimizer step: clip + (masked) SGD + prox pull + re-mask.
        ``momentum`` None: the caller carries no buffer (sound at
        ``hp.momentum == 0`` only, where the step never reads it)."""
        grads = clip_by_global_norm(grads, hp.grad_clip)
        carried = momentum is not None
        if not carried:
            momentum = grads    # a stand-in of the right shape, never read
        if mask_grads:
            grads = jax.tree_util.tree_map(lambda g, m: g * m, grads, mask)
        params, momentum = sgd_momentum_step(
            params, momentum, grads, lr, hp.momentum, hp.weight_decay
        )
        if prox_lambda:
            params = jax.tree_util.tree_map(
                lambda p, g: p - lr.astype(p.dtype) * prox_lambda * (p - g),
                params, prox_target,
            )
        if mask_params_post_step:
            params = jax.tree_util.tree_map(lambda p, m: p * m, params, mask)
        return params, (momentum if carried else None)

    def client_update(params, momentum, mask, rng, x, y, n_valid, round_idx,
                      prox_target):
        if momentum is None and hp.momentum:
            raise ValueError("momentum=None needs hp.momentum == 0")
        lr = hp.lr * jnp.power(hp.lr_decay, round_idx.astype(jnp.float32))

        if epoch_mode:
            spe, bs = hp.steps_per_epoch, hp.batch_size
            k_perm, k_steps = jax.random.split(rng)
            # [E, spe*bs] per-epoch shuffles, flattened for dynamic slicing
            flat_perms = epoch_permutations(
                k_perm, n_valid, hp.local_epochs, spe * bs,
                n_rows=x.shape[0]).reshape(-1)

            def step(carry, s):
                params, momentum = carry
                k_drop = jax.random.fold_in(k_steps, s)
                pos = s % spe
                start = (s // spe) * (spe * bs) + pos * bs
                idx = lax.dynamic_slice(flat_perms, (start,), (bs,))
                # perm slots past n_valid point past the padded shard when
                # spe*bs > n_rows; clamp (their loss terms are masked by wb
                # anyway, but jnp.take's default OOB fill is NaN)
                idx = jnp.minimum(idx, x.shape[0] - 1)
                with jax.named_scope("batch_gather"):
                    xb = jnp.take(x, idx, axis=0)
                    yb = jnp.take(y, idx, axis=0)
                if augment_fn is not None:
                    k_aug, k_drop = jax.random.split(k_drop)
                    xb = augment_fn(k_aug, xb)
                if full_batches:
                    # statically guaranteed: every batch full, every step
                    # active — same math without the masking machinery
                    loss, grads = grad_fn(params, xb, yb, None, k_drop)
                    params, momentum = apply_update(
                        params, momentum, grads, mask, prox_target, lr)
                    return (params, momentum), (loss, jnp.bool_(True))
                # validity of this batch's slots within the client's epoch
                offs = pos * bs + jnp.arange(bs)
                wb = offs < n_valid
                loss, grads = grad_fn(params, xb, yb, wb, k_drop)
                new_params, new_momentum = apply_update(
                    params, momentum, grads, mask, prox_target, lr)
                # steps past this client's own ceil(n_i/bs) are no-ops
                active = (pos * bs) < n_valid
                params = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(active, a, b), new_params, params)
                momentum = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(active, a, b), new_momentum,
                    momentum)
                return (params, momentum), (loss, active)

            (params, momentum), (losses, actives) = lax.scan(
                step, (params, momentum), jnp.arange(hp.local_steps))
            act = actives.astype(jnp.float32)
            mean_loss = jnp.sum(losses * act) / jnp.maximum(jnp.sum(act), 1.0)
            return params, momentum, mean_loss

        def step(carry, key):
            params, momentum = carry
            k_idx, k_drop = jax.random.split(key)
            idx = jax.random.randint(k_idx, (hp.batch_size,), 0,
                                     jnp.maximum(n_valid, 1))
            with jax.named_scope("batch_gather"):
                xb = jnp.take(x, idx, axis=0)
                yb = jnp.take(y, idx, axis=0)
            if augment_fn is not None:
                k_aug, k_drop = jax.random.split(k_drop)
                xb = augment_fn(k_aug, xb)
            loss, grads = grad_fn(params, xb, yb, None, k_drop)
            params, momentum = apply_update(
                params, momentum, grads, mask, prox_target, lr)
            return (params, momentum), loss

        keys = jax.random.split(rng, hp.local_steps)
        (params, momentum), losses = lax.scan(step, (params, momentum), keys)
        return params, momentum, jnp.mean(losses)

    return client_update


def make_eval_fn(apply_fn: ApplyFn, loss_type: str, eval_batch: int = 32):
    """Build the per-client evaluation function.

    Implements the reference's test protocol (``my_model_trainer.py:222-260``:
    sigmoid>=.5 / argmax accuracy + summed loss over the local test set) over a
    padded ``[m_max, ...]`` shard; entries at index >= n_valid are ignored.
    Returns ``eval_client(params, x, y, n_valid) -> (correct, loss_sum, total)``.
    """
    loss_fn = make_loss_fn(loss_type)

    def eval_client(params, x, y, n_valid):
        m_max = x.shape[0]
        # never batch wider than the shard: tiny test shards (small ABCD
        # sites) would otherwise be padded up to eval_batch and burn a
        # full-width forward on padding rows (floor 1 keeps the zero-row
        # shard edge well-defined: nb = 0, empty scan, zero totals)
        eb = max(1, min(eval_batch, m_max))
        pad = (-m_max) % eb
        if pad:  # static — pad the shard so chunking is exact
            x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
            y = jnp.pad(y, [(0, pad)])
            m_max += pad
        nb = m_max // eb

        def body(carry, i):
            correct, loss_sum = carry
            start = i * eb
            xb = lax.dynamic_slice_in_dim(x, start, eb, axis=0)
            yb = lax.dynamic_slice_in_dim(y, start, eb, axis=0)
            logits = apply_fn(params, xb, train=False, rng=None)
            preds = predictions(logits, loss_type)
            valid = (start + jnp.arange(eb)) < n_valid
            correct += jnp.sum((preds == yb.astype(jnp.int32)) & valid)
            # per-example loss, masked by validity
            per_ex = PER_EXAMPLE_LOSSES[loss_type](logits, yb)
            loss_sum += jnp.sum(per_ex * valid.astype(per_ex.dtype))
            return (correct, loss_sum), None

        (correct, loss_sum), _ = lax.scan(
            body, (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)),
            jnp.arange(nb),
        )
        return correct, loss_sum, n_valid

    return eval_client
