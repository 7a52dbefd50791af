"""Spatial (volume) parallelism tests — the context-parallel slot.

Verifies on the 8-device virtual CPU mesh that
  * explicit halo exchange reproduces zero-padding semantics,
  * the shard_map halo-exchange conv matches the dense conv bit-for-bit,
  * a GSPMD depth-sharded forward of the real 3D model matches the
    unsharded forward,
  * the hybrid clients x space layout compiles and matches too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
from neuroimagedisttraining_tpu.parallel import spatial as sp


def space_mesh(n, devices):
    return Mesh(np.array(devices[:n]), (sp.SPACE_AXIS,))


def test_halo_exchange_matches_zero_padding(eight_devices):
    n = 4
    mesh = space_mesh(n, eight_devices)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 3, 3, 1))

    f = shard_map(
        lambda xb: sp.halo_exchange(xb, halo=2),
        mesh=mesh,
        in_specs=P(None, sp.SPACE_AXIS),
        out_specs=P(None, sp.SPACE_AXIS),
        check_vma=False,
    )
    out = jax.jit(f)(x)
    # each local block (depth 4) grows to 8; global result is the blocks'
    # concatenation. Reconstruct expected from dense zero-padded x.
    xp = jnp.pad(x, [(0, 0), (2, 2), (0, 0), (0, 0), (0, 0)])
    expected = jnp.concatenate(
        [xp[:, i * 4:i * 4 + 8] for i in range(n)], axis=1
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected))


def test_sharded_conv3d_matches_dense(eight_devices):
    mesh = space_mesh(4, eight_devices)
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (2, 12, 6, 6, 3))
    w = jax.random.normal(k2, (3, 3, 3, 3, 5)) * 0.1
    b = jax.random.normal(k3, (5,)) * 0.1

    f = sp.make_sharded_conv3d(mesh)
    out = jax.jit(f)(x, w, b)

    dense = lax.conv_general_dilated(
        x, w, (1, 1, 1), [(1, 1)] * 3,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
    ) + b
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=1e-5)


def test_gspmd_spatial_forward_matches_unsharded(eight_devices):
    from neuroimagedisttraining_tpu.models import (
        create_model, init_params, make_apply_fn,
    )

    mesh = space_mesh(4, eight_devices)
    model = create_model("small3dcnn", num_classes=2)
    params = init_params(model, jax.random.PRNGKey(0), (16, 8, 8, 1))
    apply_fn = make_apply_fn(model)

    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 8, 8, 1))
    dense = apply_fn(params, x, train=False, rng=None)

    fwd = sp.make_spatial_forward(apply_fn, mesh)
    xs = sp.shard_spatial(x, mesh)
    out = fwd(params, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=1e-5)


def test_gspmd_spatial_uneven_depth_pads(eight_devices):
    """Depth not divisible by the space axis: pad_depth_to makes it work and
    parity holds on the padded volume."""
    from neuroimagedisttraining_tpu.models import (
        create_model, init_params, make_apply_fn,
    )

    mesh = space_mesh(4, eight_devices)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 15, 8, 8, 1))
    with pytest.raises(ValueError, match="pad_depth_to"):
        sp.shard_spatial(x, mesh)

    xp = sp.pad_depth_to(x, 4)
    assert xp.shape[1] == 16

    model = create_model("small3dcnn", num_classes=1)
    params = init_params(model, jax.random.PRNGKey(0), xp.shape[1:])
    apply_fn = make_apply_fn(model)
    dense = apply_fn(params, xp, train=False, rng=None)
    out = sp.make_spatial_forward(apply_fn, mesh)(
        params, sp.shard_spatial(xp, mesh)
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=1e-5)


def test_hybrid_clients_space_grad_step(eight_devices):
    """clients x space hybrid: grads of a depth-sharded per-client batch match
    the fully replicated computation."""
    from neuroimagedisttraining_tpu.models import (
        create_model, init_params, make_apply_fn,
    )

    mesh = make_mesh(2, n_space=4, devices=eight_devices)
    model = create_model("small3dcnn", num_classes=1)
    params = init_params(model, jax.random.PRNGKey(0), (8, 4, 4, 1))
    apply_fn = make_apply_fn(model)

    n_clients = 2
    x = jax.random.normal(jax.random.PRNGKey(4), (n_clients, 4, 8, 4, 4, 1))
    y = jnp.array([[0, 1, 0, 1], [1, 1, 0, 0]], jnp.float32)

    def client_loss(params, xc, yc):
        logits = apply_fn(params, xc, train=False, rng=None)[..., 0]
        return jnp.mean(
            jnp.maximum(logits, 0) - logits * yc
            + jnp.log1p(jnp.exp(-jnp.abs(logits)))
        )

    def total_loss(params, x, y):
        losses = jax.vmap(client_loss, in_axes=(None, 0, 0))(params, x, y)
        return jnp.mean(losses)

    grads_dense = jax.grad(total_loss)(params, x, y)

    xs = sp.shard_hybrid(x, mesh)
    grads_sharded = jax.jit(jax.grad(total_loss))(params, xs, y)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        grads_dense,
        grads_sharded,
    )


def test_ring_mix_matches_adjacency_contraction(eight_devices):
    """ppermute ring gossip == the dense ring-adjacency einsum the
    general-graph path uses (uniform 1/3 weighting)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuroimagedisttraining_tpu.parallel import make_mesh, ring_mix
    from neuroimagedisttraining_tpu.parallel import shard_over_clients

    n = 8
    mesh = make_mesh(n, devices=eight_devices)
    tree = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (n, 4, 3)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (n, 5)),
    }
    sharded = shard_over_clients(tree, mesh)
    mixed = ring_mix(sharded, mesh)

    adj = np.zeros((n, n), np.float32)
    for i in range(n):
        adj[i, i] = adj[i, (i - 1) % n] = adj[i, (i + 1) % n] = 1 / 3
    for k, leaf in tree.items():
        ref = jnp.einsum("ij,j...->i...", jnp.asarray(adj), leaf)
        np.testing.assert_allclose(np.asarray(mixed[k]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    # weighted variant (self-heavy gossip)
    mixed2 = ring_mix(sharded, mesh, weights=(0.5, 0.25, 0.25))
    adj2 = np.zeros((n, n), np.float32)
    for i in range(n):
        adj2[i, i] = 0.5
        adj2[i, (i - 1) % n] = adj2[i, (i + 1) % n] = 0.25
    ref2 = jnp.einsum("ij,j...->i...", jnp.asarray(adj2), tree["w"])
    np.testing.assert_allclose(np.asarray(mixed2["w"]), np.asarray(ref2),
                               rtol=1e-5, atol=1e-6)


def test_ring_mix_direction_semantics(eight_devices):
    """Asymmetric weights pin the left/right neighbor convention:
    left = i-1, right = i+1 (mod N)."""
    from neuroimagedisttraining_tpu.parallel import make_mesh, ring_mix
    from neuroimagedisttraining_tpu.parallel import shard_over_clients

    n = 8
    mesh = make_mesh(n, devices=eight_devices)
    x = {"v": jnp.arange(n, dtype=jnp.float32)[:, None]}
    mixed = ring_mix(shard_over_clients(x, mesh), mesh,
                     weights=(0.0, 1.0, 0.0))  # pure left-neighbor copy
    expect = jnp.roll(x["v"], 1, axis=0)  # out_i = x_{i-1}
    np.testing.assert_allclose(np.asarray(mixed["v"]), np.asarray(expect))


def test_mesh_space_cli_product_path(tmp_path):
    """--mesh_space is a product feature (VERDICT r1 item 6): a real
    algorithm trains through the CLI runner on a hybrid clients x space
    mesh, with volume depth zero-padded to divide the space axis."""
    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_experiment,
    )

    argv = ["--model", "small3dcnn", "--dataset", "synthetic",
            "--client_num_in_total", "4", "--batch_size", "8",
            "--epochs", "1", "--comm_round", "2", "--lr", "0.05",
            "--mesh_space", "2", "--final_finetune", "0",
            "--log_dir", str(tmp_path / "LOG"),
            "--results_dir", str(tmp_path / "results")]
    args = parse_args(argv, algo="fedavg")
    out = run_experiment(args, "fedavg")
    rounds = [h for h in out["history"] if h["round"] >= 0]
    assert len(rounds) == 2
    assert all(np.isfinite(h["train_loss"]) for h in rounds)
    assert np.isfinite(rounds[-1]["global_acc"])


def test_mesh_space_pads_odd_depth(tmp_path):
    """Odd-depth volumes (the canonical 121 has no factors of 2) must be
    zero-padded so the space axis divides the depth — checked via the
    padding helper the runner uses."""
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.data import make_synthetic_federated
    from neuroimagedisttraining_tpu.parallel.spatial import (
        pad_federated_depth,
    )

    data = make_synthetic_federated(
        n_clients=4, samples_per_client=8, test_per_client=4,
        sample_shape=(7, 8, 8, 1), loss_type="bce", class_num=2)
    padded = pad_federated_depth(data, 4)
    assert padded.x_train.shape[2] == 8
    assert padded.x_test.shape[2] == 8
    # padding is zeros (background), data preserved
    assert jnp.allclose(padded.x_train[:, :, :7], data.x_train)
    assert jnp.all(padded.x_train[:, :, 7:] == 0)
