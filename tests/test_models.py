"""Model zoo shape/parity tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.ops.s2d import phased_sample_shape

from neuroimagedisttraining_tpu.models import (
    create_model,
    init_params,
    make_apply_fn,
)


def _n_params(params):
    return sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params))


def test_small3dcnn_forward():
    model = create_model("small3dcnn", num_classes=1)
    params = init_params(model, jax.random.PRNGKey(0), (8, 8, 8, 1))
    apply_fn = make_apply_fn(model)
    x = jnp.ones((4, 8, 8, 8, 1))
    out = apply_fn(params, x, train=False, rng=None)
    assert out.shape == (4, 1)
    out_t = apply_fn(params, x, train=True, rng=jax.random.PRNGKey(1))
    assert out_t.shape == (4, 1)


@pytest.mark.slow
def test_alexnet3d_flatten_width_matches_reference():
    """On the canonical ABCD volume the feature stack flattens to 256
    (the reference's hard-coded Linear(256, 64), salient_models.py:180)."""
    model = create_model("3dcnn", num_classes=1)
    params = init_params(model, jax.random.PRNGKey(0), (121, 145, 121, 1))
    # classifier first Dense kernel must have input dim 256
    dense_kernels = [
        p for path, p in jax.tree_util.tree_flatten_with_path(params)[0]
        if path[-1].key == "kernel" and p.ndim == 2
    ]
    first_dense = min(dense_kernels, key=lambda k: -k.shape[0])
    assert first_dense.shape[0] == 256


def test_alexnet3d_runs_on_smallest_valid_volume():
    # 77^3 is the smallest cube surviving three k3/s3 floor-mode pools
    model = create_model("3dcnn", num_classes=1)
    params = init_params(model, jax.random.PRNGKey(0), (77, 77, 77, 1))
    apply_fn = make_apply_fn(model)
    out = apply_fn(params, jnp.ones((1, 77, 77, 77, 1)), train=False, rng=None)
    assert out.shape == (1, 1)


@pytest.mark.slow
def test_multi_output_models_return_pairs():
    model = create_model("3dresnet", num_classes=2)
    params = init_params(model, jax.random.PRNGKey(0), (32, 32, 32, 1))
    apply_fn = make_apply_fn(model)
    out = apply_fn(params, jnp.ones((2, 32, 32, 32, 1)), train=False, rng=None)
    assert isinstance(out, list) and len(out) == 2
    assert out[0].shape == (2, 2)
    assert out[1].shape == (2, 512)


@pytest.mark.slow
def test_cifar_models_shapes():
    for name, nc in [("cnn_cifar10", 10), ("resnet18", 10), ("lenet5", 10)]:
        shape = (32, 32, 3) if name != "lenet5" else (28, 28, 1)
        model = create_model(name, num_classes=nc)
        params = init_params(model, jax.random.PRNGKey(0), shape)
        apply_fn = make_apply_fn(model)
        out = apply_fn(params, jnp.ones((2,) + shape), train=False, rng=None)
        assert out.shape == (2, nc), name


def test_cnn_cifar10_flatten_width():
    """cnn_cifar10 flattens to 64*5*5=1600 on 32x32 (cnn_cifar10.py:19)."""
    model = create_model("cnn_cifar10", num_classes=10)
    params = init_params(model, jax.random.PRNGKey(0), (32, 32, 3))
    kernels = [
        p for path, p in jax.tree_util.tree_flatten_with_path(params)[0]
        if path[-1].key == "kernel" and p.ndim == 2
    ]
    assert sorted(k.shape[0] for k in kernels) == [192, 384, 1600]


@pytest.mark.slow
def test_new_zoo_models_shapes():
    """CNN_DropOut / VGG16 / meta CNN / ImageNet GN-ResNets forward shapes."""
    cases = [
        ("cnn_dropout", 62, (28, 28, 1)),
        ("vgg16", 10, (32, 32, 3)),
        ("cnn_cifar10_meta", 10, (32, 32, 3)),
        ("resnet18_gn", 7, (64, 64, 3)),
        ("resnet50_gn", 7, (64, 64, 3)),
    ]
    for name, nc, shape in cases:
        model = create_model(name, num_classes=nc)
        params = init_params(model, jax.random.PRNGKey(0), shape)
        apply_fn = make_apply_fn(model)
        out = apply_fn(params, jnp.ones((2,) + shape), train=False, rng=None)
        assert out.shape == (2, nc), name
        out_t = apply_fn(params, jnp.ones((2,) + shape), train=True,
                         rng=jax.random.PRNGKey(1))
        assert out_t.shape == (2, nc), name


def test_cnn_cifar10_meta_fc_width():
    """VALID 5x5 convs + 3s2 pools on 32x32 -> 4x4x64 fc input
    (cnn_meta.py:100: fc1 is Linear(64*4*4, 10))."""
    model = create_model("cnn_cifar10_meta", num_classes=10)
    params = init_params(model, jax.random.PRNGKey(0), (32, 32, 3))
    fc = params["meta_fc1"]["kernel"]
    assert fc.shape == (64 * 4 * 4, 10)


def test_meta_net_generates_target_shape():
    from neuroimagedisttraining_tpu.models.meta import (
        MetaNet,
        init_random_mask,
    )

    target = (5, 5, 3, 64)
    mask = init_random_mask(jax.random.PRNGKey(0), target, dense_ratio=0.2)
    density = float(mask.mean())
    assert abs(density - 0.2) < 0.01
    net = MetaNet(target_shape=target)
    variables = net.init(jax.random.PRNGKey(1), mask)
    w = net.apply(variables, mask)
    assert w.shape == target


def test_sync_batch_norm_cross_device_stats():
    """SyncBatchNorm with axis_name psums batch stats over the mesh axis:
    per-device outputs must equal single-device BN over the concatenated
    batch (the batchnorm_utils.py:150-396 master/slave sync, done by XLA)."""
    import numpy as np
    from neuroimagedisttraining_tpu.models.layers import SyncBatchNorm

    n_dev = min(4, jax.local_device_count())
    x = jax.random.normal(jax.random.PRNGKey(0), (n_dev, 8, 6))

    m_sync = SyncBatchNorm(axis_name="clients")
    variables = SyncBatchNorm().init(jax.random.PRNGKey(1), x[0], train=True)

    def step(xs):
        y, _ = m_sync.apply(variables, xs, train=True,
                            mutable=["batch_stats"])
        return y

    y_pmap = jax.pmap(step, axis_name="clients")(x)
    # single-device reference over the concatenated batch
    y_ref, _ = SyncBatchNorm().apply(
        variables, x.reshape(-1, 6), train=True, mutable=["batch_stats"])
    np.testing.assert_allclose(
        np.asarray(y_pmap).reshape(-1, 6), np.asarray(y_ref),
        rtol=1e-4, atol=1e-5)


def test_resnet_gn_zero_init_residual():
    """Residual branches start as identity: the last GN scale in each block
    is zero at init (resnet_gn.py:143-146 parity)."""
    model = create_model("resnet18_gn", num_classes=4)
    params = init_params(model, jax.random.PRNGKey(0), (32, 32, 3))
    import numpy as np

    zero_scales = [
        p for path, p in jax.tree_util.tree_flatten_with_path(params)[0]
        if path[-1].key == "scale" and float(np.abs(np.asarray(p)).sum()) == 0
    ]
    assert len(zero_scales) == 8  # 2 blocks x 4 stages


@pytest.mark.slow
def test_resnet_ip_dual_params_forward():
    """resnet_ip (reference resnet_ip.py:179-289): forward uses w_g + w_v;
    zeroing every personal leg must give the g-only function."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuroimagedisttraining_tpu.models import create_model, init_params

    model = create_model("resnet_ip", num_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
    params = init_params(model, jax.random.PRNGKey(1), (32, 32, 3))
    y = model.apply({"params": params}, x, train=False)
    assert y.shape == (2, 10)
    assert np.all(np.isfinite(np.asarray(y)))
    # v-legs init to zero, so perturbing v changes the function
    perturbed = jax.tree_util.tree_map_with_path(
        lambda path, l: l + 0.01 if "_v" in str(path[-1]) else l, params)
    y2 = model.apply({"params": perturbed}, x, train=False)
    assert not np.allclose(np.asarray(y), np.asarray(y2))
    # g and v leaves exist pairwise (the federated aggregation split)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    names = {str(p[-1]) for p, _ in flat}
    assert any("kernel_g" in n for n in names)
    assert any("kernel_v" in n for n in names)


@pytest.mark.slow
def test_resnet_meta_hypernetwork_scales():
    """resnet_meta (reference resnet_meta_2.py behavior): conv kernels come
    from per-layer hypernetworks conditioned on channel scales; narrower
    scales zero the inactive channels."""
    import jax
    import numpy as np

    from neuroimagedisttraining_tpu.models import create_model, init_params

    model = create_model("resnet_meta", num_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
    params = init_params(model, jax.random.PRNGKey(1), (32, 32, 3))
    y_full = model.apply({"params": params}, x, train=False)
    assert y_full.shape == (2, 10)
    # half-width everywhere still runs and differs from full width
    y_half = model.apply({"params": params}, x,
                         stage_scale_ids=[1, 1, 1],
                         mid_scale_ids=[1] * 6, train=False)
    assert np.all(np.isfinite(np.asarray(y_half)))
    assert not np.allclose(np.asarray(y_full), np.asarray(y_half))


@pytest.mark.slow
def test_original_resnet18_bn_forward():
    """original_resnet18 (resnet.py:42-89): BatchNorm variant; train mode
    mutates batch_stats, eval mode uses the running averages."""
    import jax
    import numpy as np

    from neuroimagedisttraining_tpu.models import create_model

    model = create_model("original_resnet18", num_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(1), x, train=False)
    assert "batch_stats" in variables
    y, updated = model.apply(variables, x, train=True,
                             mutable=["batch_stats"])
    assert y.shape == (2, 10)
    y_eval = model.apply({"params": variables["params"],
                          "batch_stats": updated["batch_stats"]},
                         x, train=False)
    assert np.all(np.isfinite(np.asarray(y_eval)))


@pytest.mark.parametrize("name,shape", [
    ("small3dcnn", (8, 8, 8, 1)),
    ("3dcnn_s2d", phased_sample_shape((69, 69, 69), 5, 0)),
    ("3dresnet_s2d", phased_sample_shape((29, 33, 29), 3, 3)),
], ids=["small3dcnn", "3dcnn_s2d", "3dresnet_s2d"])
def test_init_params_is_jitted_and_bit_equal_to_eager_init(name, shape):
    """``init_params`` runs ``model.init`` as one program (an eager init
    runs the forward pass op by op at the sample's size): the parameters
    must be the ones the eager init made, bit for bit."""
    model = create_model(name, num_classes=1)
    rng = jax.random.PRNGKey(3)
    x = jnp.zeros((1,) + tuple(shape), jnp.float32)
    eager = model.init({"params": rng, "dropout": rng}, x,
                       train=False)["params"]
    got = init_params(model, rng, shape)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(eager)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(eager)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
