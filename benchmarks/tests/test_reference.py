"""The plain references against the zoo's dense float32 models on the CPU,
same weights, at the smallest volumes the models take. The parameters go
dense model -> the program's converter -> phased twin -> ``from_system``, so
the benchmark's own phase arithmetic is on the path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import phase
from benchmarks.reference import alexnet3d, ops, resnet3d, small3dcnn
from neuroimagedisttraining_tpu.models import (create_model, init_params,
                                               make_apply_fn)
from neuroimagedisttraining_tpu.models.alexnet3d import (
    convert_smallcnn3d_params)
from neuroimagedisttraining_tpu.models.resnet3d import convert_resnet3d_params
from neuroimagedisttraining_tpu.ops.s2d import convert_alexnet3d_params

CASES = [
    ("3dcnn", alexnet3d, convert_alexnet3d_params, 5, 0, (69, 71, 69)),
    ("3dresnet", resnet3d, convert_resnet3d_params, 3, 3, (33, 35, 33)),
    ("small3dcnn", small3dcnn, convert_smallcnn3d_params, 3, 1, (8, 10, 8)),
]


@pytest.mark.parametrize("model,ref,convert,kernel,pad,volume", CASES,
                         ids=[c[0] for c in CASES])
def test_reference_agrees_with_the_dense_zoo_model(model, ref, convert,
                                                   kernel, pad, volume):
    dense_model = create_model(model, num_classes=1)
    params = init_params(dense_model, jax.random.PRNGKey(1), volume + (1,))
    # biases and norm offsets start at zero: move them, or they test nothing
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        p + 0.1 * jax.random.normal(k, p.shape) for p, k in zip(leaves, keys)])
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (2,) + volume))
    y = jnp.asarray([0, 1])
    with jax.default_matmul_precision("highest"):
        out = make_apply_fn(dense_model)(params, jnp.asarray(x)[..., None],
                                         train=False, rng=None)
        want = (out[0] if isinstance(out, list) else out)[:, 0]
        r_params = ref.from_system(
            jax.device_get(convert(params)),
            lambda w: phase.dense_stem_kernel(w, kernel))
        got = ref.forward(r_params, jnp.asarray(x))
        # and the phased twin on the benchmark's own decomposition
        twin = create_model(model + "_s2d", num_classes=1)
        out = make_apply_fn(twin)(
            convert(params), jnp.asarray(phase.decompose(x, kernel, pad)),
            train=False, rng=None)
        twin_z = (out[0] if isinstance(out, list) else out)[:, 0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(twin_z, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.std(want)) > 1e-3       # the two volumes differ
    loss = ops.bce_with_logits(got, y)
    z = np.asarray(want, np.float64)
    by_hand = np.mean(np.log1p(np.exp(-z * (2 * np.asarray(y) - 1))))
    assert float(loss) == pytest.approx(by_hand, rel=1e-5)


def test_phase_arithmetic_round_trip():
    rng = np.random.default_rng(0)
    for kernel, pad, volume in ((5, 0, (13, 15, 11)), (3, 3, (12, 9, 10)),
                                (3, 1, (8, 8, 8))):
        x = rng.random((2,) + volume, dtype=np.float32)
        p = phase.decompose(x, kernel, pad)
        assert p.shape == (2,) + phase.phased_shape(volume, kernel, pad)
        np.testing.assert_array_equal(phase.recompose(p, volume, pad), x)
    assert phase.phased_shape((121, 145, 121), 5, 0) == (61, 73, 8, 61)
    assert phase.phased_shape((121, 145, 121), 3, 3) == (64, 76, 8, 64)
