"""Loss functions.

Reference semantics: ABCD sex classification uses ``nn.BCEWithLogitsLoss`` on a
single logit with float labels (``sailentgrads/my_model_trainer.py:191-206``);
CIFAR paths use ``nn.CrossEntropyLoss`` (``fedavg/my_model_trainer.py:38-67``).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def _first_output(out):
    # Several reference models return [logits, features]
    # (salient_models.py:139,297); losses consume only the logits.
    if isinstance(out, (tuple, list)):
        return out[0]
    return out


def bce_with_logits_per_example(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-example binary cross-entropy with logits; logits [B,1] or [B]."""
    logits = _first_output(logits)
    logits = logits.reshape(logits.shape[0], -1)[:, 0]
    labels = labels.astype(logits.dtype)
    # x*(1-y) + softplus(-x): same value as the max/abs stable form but
    # smooth, so the gradient is sigmoid(x)-y EVERYWHERE — the max/abs
    # form's subgradient at x == 0 is -1 (not torch's analytic -0.5) from
    # JAX's tie-splitting through maximum() and abs()
    return logits * (1.0 - labels) + jax.nn.softplus(-logits)


def softmax_ce_per_example(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-example softmax cross-entropy; logits [B, K], labels [B] int."""
    logits = _first_output(logits)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(
        logp, labels[:, None].astype(jnp.int32), axis=-1
    )[:, 0]


def mse_per_example(preds: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-example squared error (AlexNet3D_Dropout_Regression head,
    salient_models.py:248-297)."""
    preds = _first_output(preds)
    preds = preds.reshape(preds.shape[0], -1)[:, 0]
    return jnp.square(preds - targets.astype(preds.dtype))


def token_ce_per_example(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-sequence cross-entropy of a language model: ``logits [B, S, V]``
    over the vocabulary rows held, ``labels [B, S]`` the next ids. A label
    below 0 carries no target (the last position of a shard's sequence) and
    has weight 0; the value is the float32 mean over a sequence's positions
    that have one. The head's product (``models/decoder.py``) and this
    reduction share the scope ``lm_head``."""
    logits = _first_output(logits)
    with jax.named_scope("lm_head"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None].astype(jnp.int32),
            axis=-1)[..., 0]
        w = (labels >= 0).astype(jnp.float32)
        return -jnp.sum(picked * w, axis=-1) / jnp.maximum(
            jnp.sum(w, axis=-1), 1.0)


PER_EXAMPLE_LOSSES = {
    "bce": bce_with_logits_per_example,
    "ce": softmax_ce_per_example,
    "mse": mse_per_example,
    "token_ce": token_ce_per_example,
}


def bce_with_logits_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean(bce_with_logits_per_example(logits, labels))


def softmax_ce_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean(softmax_ce_per_example(logits, labels))


def mse_loss(preds: jax.Array, targets: jax.Array) -> jax.Array:
    return jnp.mean(mse_per_example(preds, targets))


def predictions(logits: jax.Array, loss_type: str) -> jax.Array:
    """Hard predictions matching the reference's eval rules.

    BCE: sigmoid >= 0.5 (``my_model_trainer.py:243-248``); CE: argmax
    (``token_ce``: per token, ``[B, S]``).
    """
    logits = _first_output(logits)
    if loss_type == "bce":
        logits = logits.reshape(logits.shape[0], -1)[:, 0]
        return (logits >= 0.0).astype(jnp.int32)  # sigmoid(x) >= .5  <=>  x >= 0
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def make_loss_fn(loss_type: str) -> Callable[[jax.Array, jax.Array], jax.Array]:
    if loss_type not in PER_EXAMPLE_LOSSES:
        raise ValueError(f"unknown loss type: {loss_type!r}")
    per_ex = PER_EXAMPLE_LOSSES[loss_type]
    return lambda logits, labels: jnp.mean(per_ex(logits, labels))
