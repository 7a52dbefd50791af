"""Unit tests: losses, optimizer semantics, state utilities."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.core.losses import (
    bce_with_logits_loss,
    predictions,
    softmax_ce_loss,
)
from neuroimagedisttraining_tpu.core.optim import (
    clip_by_global_norm,
    global_norm,
    sgd_momentum_step,
)
from neuroimagedisttraining_tpu.core.state import (
    HyperParams,
    broadcast_tree,
    weighted_tree_sum,
)
from neuroimagedisttraining_tpu.core.trainer import make_client_update


def test_bce_matches_reference_formula():
    logits = jnp.array([0.5, -1.2, 3.0])
    labels = jnp.array([1, 0, 1])
    expected = -np.mean(
        np.array(labels) * np.log(1 / (1 + np.exp(-np.array(logits))))
        + (1 - np.array(labels)) * np.log(1 - 1 / (1 + np.exp(-np.array(logits))))
    )
    got = bce_with_logits_loss(logits[:, None], labels)
    assert np.allclose(got, expected, rtol=1e-4)


def test_ce_matches_nll():
    logits = jnp.array([[2.0, 0.5, -1.0], [0.0, 1.0, 0.0]])
    labels = jnp.array([0, 2])
    p = np.exp(np.array(logits))
    p /= p.sum(-1, keepdims=True)
    expected = -np.mean(np.log(p[np.arange(2), np.array(labels)]))
    assert np.allclose(softmax_ce_loss(logits, labels), expected, rtol=1e-5)


def test_predictions_bce_threshold():
    logits = jnp.array([[0.01], [-0.01], [0.0]])
    preds = predictions(logits, "bce")
    assert preds.tolist() == [1, 0, 1]  # sigmoid>=0.5 <=> logit>=0


def test_clip_by_global_norm_matches_torch_semantics():
    grads = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    norm = float(global_norm(grads))
    assert np.isclose(norm, np.sqrt(10 * 9 + 10 * 16))
    clipped = clip_by_global_norm(grads, 1.0)
    assert np.isclose(float(global_norm(clipped)), 1.0, rtol=1e-4)
    # below threshold: untouched
    small = {"a": jnp.array([0.1]), "b": jnp.array([0.1])}
    out = clip_by_global_norm(small, 10.0)
    assert np.allclose(out["a"], small["a"])


def test_sgd_momentum_matches_torch_update_order():
    # torch: g += wd*p; buf = mu*buf + g; p -= lr*buf
    p = {"w": jnp.array([1.0])}
    m = {"w": jnp.array([0.0])}
    g = {"w": jnp.array([2.0])}
    lr, mu, wd = jnp.float32(0.1), 0.9, 0.01
    p1, m1 = sgd_momentum_step(p, m, g, lr, mu, wd)
    g_eff = 2.0 + 0.01 * 1.0
    assert np.allclose(m1["w"], g_eff)
    assert np.allclose(p1["w"], 1.0 - 0.1 * g_eff)
    # second step accumulates momentum
    p2, m2 = sgd_momentum_step(p1, m1, g, lr, mu, wd)
    g_eff2 = 2.0 + 0.01 * float(p1["w"][0])
    buf2 = 0.9 * g_eff + g_eff2
    assert np.allclose(m2["w"], buf2, rtol=1e-5)
    assert np.allclose(p2["w"], p1["w"] - 0.1 * buf2, rtol=1e-5)


# ---------------------------------------------------------------------------
# the masked SGD step as every cell runs it: ONE optimizer step of
# core/trainer.py's client update (clip, masked SGD, re-mask), driven through
# a linear model whose MSE gradient NumPy knows in closed form
# ---------------------------------------------------------------------------

def _ref_update(p, m, g, k, lr, mom, wd, mask_grads):
    g = np.asarray(g, np.float64)
    p = np.asarray(p, np.float64)
    m = np.asarray(m, np.float64)
    k = np.asarray(k, np.float64)
    if mask_grads:
        g = g * k
    g = g + wd * p
    m_new = mom * m + g
    p_new = p - lr * m_new
    if not mask_grads:
        p_new = p_new * k
    return p_new, m_new


def _linear_apply(params, xb, train, rng):
    """One prediction per example: ``xb`` against every parameter of the
    tree, raveled in leaf order."""
    del train, rng
    flat = jnp.concatenate(
        [leaf.ravel() for leaf in jax.tree_util.tree_leaves(params)])
    return xb @ flat.astype(xb.dtype)


def _mse_grads(params, x, y):
    """d mean((x @ w - y)^2) / dw in float64, cut back into the tree."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    w = np.concatenate([np.asarray(leaf, np.float64).ravel()
                        for leaf in leaves])
    x = np.asarray(x, np.float64)
    g = 2.0 / x.shape[0] * x.T @ (x @ w - np.asarray(y, np.float64))
    cuts = np.cumsum([leaf.size for leaf in leaves])[:-1]
    return jax.tree_util.tree_unflatten(
        treedef, [part.reshape(leaf.shape)
                  for part, leaf in zip(np.split(g, cuts), leaves)])


def _one_step(params, mom, mask, x, y, lr, momentum, wd, mask_grads):
    """One full-batch optimizer step of the product's client update.
    ``mask_grads`` False: SalientGrads (``p *= mask`` after the step); True:
    DisPFL's masked-gradient SGD."""
    hp = HyperParams(lr=lr, lr_decay=1.0, momentum=momentum,
                     weight_decay=wd, grad_clip=1e9, local_epochs=1,
                     steps_per_epoch=1, batch_size=x.shape[0],
                     batching="epoch")
    update = make_client_update(_linear_apply, "mse", hp,
                                mask_grads=mask_grads,
                                mask_params_post_step=not mask_grads)
    new_p, new_m, _ = jax.jit(update)(
        params, mom, mask, jax.random.PRNGKey(0), x, y,
        jnp.int32(x.shape[0]), jnp.int32(0), params)
    return new_p, new_m


@pytest.mark.parametrize("shape", [(7,), (5, 3), (4, 4, 4, 2), (300, 7)])
@pytest.mark.parametrize("mask_grads", [False, True])
def test_masked_sgd_step_matches_reference(shape, mask_grads):
    rng = np.random.RandomState(0)
    p = rng.randn(*shape).astype(np.float32)
    m = rng.randn(*shape).astype(np.float32)
    k = (rng.rand(*shape) > 0.5).astype(np.float32)
    x = (rng.randn(8, p.size) / np.sqrt(p.size)).astype(np.float32)
    y = rng.randn(8).astype(np.float32)
    lr, mom, wd = 0.05, 0.9, 1e-4
    p2, m2 = _one_step({"w": jnp.asarray(p)}, {"w": jnp.asarray(m)},
                       {"w": jnp.asarray(k)}, jnp.asarray(x),
                       jnp.asarray(y), lr, mom, wd, mask_grads)
    g = _mse_grads({"w": p}, x, y)["w"]
    rp, rm = _ref_update(p, m, g, k, lr, mom, wd, mask_grads)
    np.testing.assert_allclose(np.asarray(p2["w"]), rp, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(m2["w"]), rm, rtol=1e-5,
                               atol=1e-6)
    assert p2["w"].shape == shape and p2["w"].dtype == jnp.float32


def test_masked_sgd_step_pytree():
    rng = np.random.RandomState(1)

    def tree(f):
        return {"a": {"kernel": jnp.asarray(f((33, 9))),
                      "bias": jnp.asarray(f((9,)))},
                "b": jnp.asarray(f((2, 3, 4)))}

    params = tree(lambda s: rng.randn(*s).astype(np.float32))
    mom = tree(lambda s: np.zeros(s, np.float32))
    mask = tree(lambda s: np.ones(s, np.float32))
    n = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    x = jnp.asarray((rng.randn(8, n) / np.sqrt(n)).astype(np.float32))
    y = jnp.asarray(rng.randn(8).astype(np.float32))
    p2, _ = _one_step(params, mom, mask, x, y, 0.1, 0.9, 0.0, False)
    # plain SGD when the mask is all-ones and the buffer starts at zero
    grads = _mse_grads(params, x, y)
    expect = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        p2, expect)


def test_masked_sgd_step_keeps_master_and_momentum_dtype():
    """bf16 compute over f32 masters (what ``--compute_dtype bfloat16``
    runs): parameters and momentum buffer both leave the step in f32."""
    rng = np.random.RandomState(2)
    p = rng.randn(33).astype(np.float32)
    x = (rng.randn(8, 33) / np.sqrt(33)).astype(np.float32)
    y = rng.randn(8).astype(np.float32)
    p2, m2 = _one_step({"w": jnp.asarray(p)}, {"w": jnp.zeros(33)},
                       {"w": jnp.ones(33)},
                       jnp.asarray(x, jnp.bfloat16), jnp.asarray(y),
                       0.1, 0.9, 0.0, False)
    assert p2["w"].dtype == jnp.float32
    assert m2["w"].dtype == jnp.float32
    g = _mse_grads({"w": p}, x, y)["w"]
    np.testing.assert_allclose(np.asarray(m2["w"]), g, rtol=0.05, atol=0.02)
    np.testing.assert_allclose(np.asarray(p2["w"]), p - 0.1 * g, rtol=0.05,
                               atol=0.02)


def test_weighted_tree_sum_is_fedavg_aggregate():
    # mirrors fedavg_api.py:102-117: w_global[k] = sum_i (n_i/N) local_i[k]
    stacked = {"w": jnp.array([[1.0, 1.0], [3.0, 3.0]])}
    weights = jnp.array([0.25, 0.75])
    out = weighted_tree_sum(stacked, weights)
    assert np.allclose(out["w"], [2.5, 2.5])


def test_broadcast_tree():
    t = {"w": jnp.ones((2, 3))}
    b = broadcast_tree(t, 4)
    assert b["w"].shape == (4, 2, 3)
