"""The round that never stacks the locals (algorithms/base.py:
_train_selected_folded) against the stacked round, and the build-time refusal
of a combination that needs the stack where it cannot be held."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import neuroimagedisttraining_tpu.algorithms.base as base_mod
from neuroimagedisttraining_tpu.algorithms import FedAvg, SalientGrads
from neuroimagedisttraining_tpu.core.state import HyperParams
from neuroimagedisttraining_tpu.data.types import FederatedData
from neuroimagedisttraining_tpu.models import create_model


def _images(sites=6, rows=8, uneven=False):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    counts = np.full((sites,), rows, np.int32)
    if uneven:
        counts = np.asarray([8, 5, 8, 3, 8, 6], np.int32)[:sites]
    return FederatedData(
        x_train=jax.random.normal(k[0], (sites, rows, 28, 28, 1)),
        y_train=jax.random.randint(k[1], (sites, rows), 0, 10),
        n_train=jnp.asarray(counts),
        x_test=jax.random.normal(k[2], (sites, 4, 28, 28, 1)),
        y_test=jax.random.randint(k[3], (sites, 4), 0, 10),
        n_test=jnp.full((sites,), 4, jnp.int32), class_num=10)


def _fedavg(data, momentum=0.0, **kw):
    hp = HyperParams(lr=0.05, momentum=momentum, local_epochs=2,
                     steps_per_epoch=2, batch_size=4)
    return FedAvg(create_model("lenet5", num_classes=10), data, hp,
                  loss_type="ce", frac=0.5, seed=3, client_chunk=1, **kw)


def _two_rounds(algo):
    state = algo.init_state(jax.random.PRNGKey(5))
    losses = []
    for r in range(2):
        state, rec = algo.run_round(state, r)
        losses.append(np.asarray(rec["train_loss"]))
    return state, losses


@pytest.mark.parametrize("momentum,uneven", [(0.0, False), (0.9, False),
                                             (0.0, True)])
def test_folding_round_against_stacked_round(momentum, uneven):
    """Same selection, same keys: the loss equal, the new global within
    float32 summation order. The personal stack is what keeps the second
    algorithm on the stacked body."""
    data = _images(uneven=uneven)
    folded = _fedavg(data, momentum, track_personal=False)
    stacked = _fedavg(data, momentum, track_personal=True)
    assert folded._stack_readers() == []
    assert [r.split()[0] for r in stacked._stack_readers()] \
        == ["--track_personal"]
    s_f, l_f = _two_rounds(folded)
    s_s, l_s = _two_rounds(stacked)
    # round 0 starts from the same model: its loss is the same number; round
    # 1 starts from globals that differ by summation order
    np.testing.assert_array_equal(l_f[0], l_s[0])
    np.testing.assert_allclose(l_f[1], l_s[1], rtol=1e-5)
    assert s_f.personal_params is None and s_s.personal_params is not None
    for a, b in zip(jax.tree_util.tree_leaves(s_f.global_params),
                    jax.tree_util.tree_leaves(s_s.global_params)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    # the folded program holds no [S, model] stack: its largest f32 buffer
    # with a leading axis of the 3 selected clients is a batch, not a model
    hlo = folded._round_jit.lower(
        folded.init_state(jax.random.PRNGKey(5)),
        jnp.arange(3, dtype=jnp.int32), jnp.asarray(0, jnp.float32),
        data.x_train, data.y_train, data.n_train).as_text()
    big = max(p.size for p in jax.tree_util.tree_leaves(s_f.global_params))
    assert f"tensor<3x{big}" not in hlo.replace("x1x", "x")


def test_folding_round_keeps_the_mask_and_needs_one_at_a_time():
    data = _images()
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=2,
                     batch_size=4)
    kw = dict(loss_type="ce", frac=0.5, seed=3, dense_ratio=0.5,
              track_personal=False)
    model = create_model("lenet5", num_classes=10)
    folded = SalientGrads(model, data, hp, client_chunk=1, **kw)
    vmapped = SalientGrads(model, data, hp, **kw)   # clients side by side
    s_f, l_f = _two_rounds(folded)
    s_v, l_v = _two_rounds(vmapped)
    np.testing.assert_allclose(l_f, l_v, rtol=1e-6)
    for p, m, q in zip(jax.tree_util.tree_leaves(s_f.global_params),
                       jax.tree_util.tree_leaves(s_f.mask),
                       jax.tree_util.tree_leaves(s_v.global_params)):
        assert not np.any(np.asarray(p)[np.asarray(m) == 0])
        np.testing.assert_allclose(p, q, rtol=0, atol=1e-6)


def test_momentum_none_is_for_momentum_zero_only():
    from neuroimagedisttraining_tpu.core.trainer import make_client_update
    from neuroimagedisttraining_tpu.models import init_params, make_apply_fn

    model = create_model("lenet5", num_classes=10)
    data = _images(sites=1)
    params = init_params(model, jax.random.PRNGKey(0), (28, 28, 1))
    args = (params, jax.random.PRNGKey(1), data.x_train[0], data.y_train[0],
            data.n_train[0], jnp.asarray(0.0), params)
    for momentum in (0.0, 0.9):
        hp = HyperParams(lr=0.05, momentum=momentum, local_epochs=1,
                         steps_per_epoch=2, batch_size=4)
        update = make_client_update(make_apply_fn(model), "ce", hp)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        with_buffer = update(params, zeros, *args)
        if momentum:
            with pytest.raises(ValueError, match="momentum == 0"):
                update(params, None, *args)
            continue
        without = update(params, None, *args)
        assert without[1] is None
        for a, b in zip(jax.tree_util.tree_leaves(with_buffer[0]),
                        jax.tree_util.tree_leaves(without[0])):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(with_buffer[2], without[2])


@pytest.mark.parametrize("kw,flag", [
    (dict(track_personal=True), "--track_personal 1"),
    (dict(track_personal=False, robust_agg="median"), "--robust_agg median"),
    (dict(track_personal=False, agg_impl="bf16"), "--agg_impl bf16"),
    (dict(track_personal=False, fault_spec="nan=0.5"), "--fault_spec"),
])
def test_build_refuses_a_stack_the_device_cannot_hold(monkeypatch, kw, flag):
    """On a device that reports a memory limit below the stacked body's
    (2 S + 1) models, a combination that reads the stack fails at build and
    the message names the option that asked for it; the folding body's two
    models are never refused."""
    data = _images()
    assert _fedavg(data, **kw) is not None      # the CPU reports no limit
    monkeypatch.setattr(base_mod, "_device_memory_limit", lambda: 400_000)
    with pytest.raises(ValueError, match="stacked local models") as err:
        _fedavg(data, **kw)
    assert flag in str(err.value)
    assert _fedavg(data, track_personal=False) is not None
    monkeypatch.setattr(base_mod, "_device_memory_limit", lambda: 2 ** 34)
    assert _fedavg(data, **kw) is not None      # and one it can hold passes
