"""Multi-host helpers on the single-process 8-virtual-device CPU mesh.

Single-process is the degenerate case of the multi-host path (process
count 1 owns every client); these tests pin the indexing/assembly logic
that multi-process runs rely on.
"""
import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.parallel import (
    local_client_indices,
    make_global_client_array,
    make_multihost_mesh,
    shard_federated_data_global,
)


def test_multihost_mesh_covers_all_devices():
    mesh = make_multihost_mesh()
    assert mesh.shape["clients"] == len(jax.devices())

    mesh2 = make_multihost_mesh(n_space=2)
    assert mesh2.shape == {"clients": len(jax.devices()) // 2, "space": 2}


def test_local_client_indices_single_process_owns_all():
    mesh = make_multihost_mesh()
    idx = local_client_indices(16, mesh)
    np.testing.assert_array_equal(idx, np.arange(16))


def test_local_client_indices_rejects_ragged():
    mesh = make_multihost_mesh()
    try:
        local_client_indices(len(jax.devices()) + 1, mesh)
        raise AssertionError("expected ValueError")
    except ValueError:
        pass


def test_make_global_client_array_roundtrip():
    mesh = make_multihost_mesh()
    n = len(jax.devices())
    rows = np.arange(n * 6, dtype=np.float32).reshape(n, 6)
    arr = make_global_client_array(rows, (n, 6), mesh)
    assert arr.shape == (n, 6)
    np.testing.assert_array_equal(np.asarray(arr), rows)
    # sharded over clients: each device holds one row
    assert len(arr.sharding.device_set) == n


def test_shard_federated_data_global_runs_a_round():
    """Globally-assembled data must drive the standard FedAvg round."""
    from neuroimagedisttraining_tpu.algorithms import FedAvg
    from neuroimagedisttraining_tpu.core.state import HyperParams
    from neuroimagedisttraining_tpu.data import make_synthetic_federated
    from neuroimagedisttraining_tpu.models import create_model

    mesh = make_multihost_mesh()
    n = len(jax.devices())
    data = make_synthetic_federated(
        n_clients=n, samples_per_client=16, test_per_client=8,
        sample_shape=(8, 8, 8, 1), loss_type="bce", class_num=2)
    gdata = shard_federated_data_global(data, n, mesh)
    assert len(gdata.x_train.sharding.device_set) == n

    model = create_model("small3dcnn", num_classes=1)
    hp = HyperParams(lr=0.05, lr_decay=1.0, momentum=0.9, weight_decay=0.0,
                     grad_clip=10.0, local_epochs=1, steps_per_epoch=2,
                     batch_size=8)
    algo = FedAvg(model, gdata, hp, loss_type="bce", frac=1.0, seed=0)
    state = algo.init_state(jax.random.PRNGKey(0))
    state, metrics = algo.run_round(state, 0)
    assert np.isfinite(float(metrics["train_loss"]))


def test_make_multihost_mesh_shrinks_to_divide_clients():
    n_dev = len(jax.devices())
    mesh = make_multihost_mesh(num_clients=n_dev // 2)
    assert mesh.shape["clients"] == n_dev // 2
    # indivisible client count shrinks to the largest divisor
    mesh = make_multihost_mesh(num_clients=6)
    assert 6 % mesh.shape["clients"] == 0
    mesh = make_multihost_mesh(max_client_devices=2)
    assert mesh.shape["clients"] == 2


def test_abcd_client_filter_loads_subset(tmp_path):
    from neuroimagedisttraining_tpu.data.abcd import (
        abcd_site_count,
        load_partition_data_abcd,
        load_partition_data_abcd_rescale,
        write_abcd_h5,
    )

    rng = np.random.RandomState(0)
    X = rng.rand(40, 5, 6, 5).astype(np.float32)
    y = rng.randint(0, 2, size=40)
    site = np.repeat(np.arange(4), 10)
    path = str(tmp_path / "c.h5")
    write_abcd_h5(path, X, y, site)

    assert abcd_site_count(path) == 4
    full = load_partition_data_abcd(path)
    sub = load_partition_data_abcd(path, client_filter=[1, 3])
    assert sub.num_clients == 2
    np.testing.assert_array_equal(
        np.asarray(sub.x_train[0, : int(sub.n_train[0])]),
        np.asarray(full.x_train[1, : int(full.n_train[1])]))

    full_r = load_partition_data_abcd_rescale(path, client_number=4)
    sub_r = load_partition_data_abcd_rescale(path, client_number=4,
                                             client_filter=[2])
    np.testing.assert_array_equal(
        np.asarray(sub_r.x_train[0, : int(sub_r.n_train[0])]),
        np.asarray(full_r.x_train[2, : int(full_r.n_train[2])]))


def test_abcd_client_filter_uneven_sites_pad_globally(tmp_path):
    """Filtered (per-process) loads must pad to the GLOBAL maxima so every
    process computes the same global array shapes (sites are unequal)."""
    from neuroimagedisttraining_tpu.data.abcd import (
        load_partition_data_abcd,
        write_abcd_h5,
    )

    rng = np.random.RandomState(0)
    site = np.concatenate([np.zeros(14), np.ones(14), np.full(8, 2),
                           np.full(8, 3)]).astype(np.int64)
    n = len(site)
    X = rng.rand(n, 5, 6, 5).astype(np.float32)
    y = rng.randint(0, 2, size=n)
    path = str(tmp_path / "c.h5")
    write_abcd_h5(path, X, y, site)

    full = load_partition_data_abcd(path)
    a = load_partition_data_abcd(path, client_filter=[0, 1])
    b = load_partition_data_abcd(path, client_filter=[2, 3])
    # same padded extents on both "processes", equal to the global ones
    assert a.x_train.shape[1:] == b.x_train.shape[1:] == \
        full.x_train.shape[1:]
    assert a.x_test.shape[1:] == b.x_test.shape[1:] == full.x_test.shape[1:]
    # and with a val split too
    av = load_partition_data_abcd(path, client_filter=[0, 1],
                                  val_fraction=0.25)
    bv = load_partition_data_abcd(path, client_filter=[2, 3],
                                  val_fraction=0.25)
    fv = load_partition_data_abcd(path, val_fraction=0.25)
    assert av.x_train.shape[1:] == bv.x_train.shape[1:] == \
        fv.x_train.shape[1:]
    assert av.x_val.shape[1:] == bv.x_val.shape[1:] == fv.x_val.shape[1:]


def test_abcd_client_filter_val_membership_matches_full(tmp_path):
    """Filtered loads must carve the SAME train/val membership per client
    as the full load (per-client RNG keyed by global id)."""
    from neuroimagedisttraining_tpu.data.abcd import (
        load_partition_data_abcd,
        write_abcd_h5,
    )

    rng = np.random.RandomState(0)
    site = np.repeat(np.arange(4), 12)
    X = rng.rand(len(site), 5, 6, 5).astype(np.float32)
    y = rng.randint(0, 2, size=len(site))
    path = str(tmp_path / "c.h5")
    write_abcd_h5(path, X, y, site)

    full = load_partition_data_abcd(path, val_fraction=0.25)
    sub = load_partition_data_abcd(path, client_filter=[2, 3],
                                   val_fraction=0.25)
    for local_i, gid in enumerate([2, 3]):
        nv = int(sub.n_val[local_i])
        assert nv == int(full.n_val[gid])
        np.testing.assert_array_equal(
            np.asarray(sub.x_val[local_i, :nv]),
            np.asarray(full.x_val[gid, :nv]))
        nt = int(sub.n_train[local_i])
        np.testing.assert_array_equal(
            np.asarray(sub.x_train[local_i, :nt]),
            np.asarray(full.x_train[gid, :nt]))


def test_sync_retry_wrapper_retries_transient_then_succeeds():
    """Bounded-retry host-sync wrapper (ISSUE 2 multihost hardening):
    transient failures retry with backoff; the budget is bounded."""
    from neuroimagedisttraining_tpu.parallel import multihost as mh

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient DCN hiccup")
        return "ok"

    assert mh._with_retries("probe", flaky, max_retries=3,
                            backoff_s=0.0) == "ok"
    assert len(calls) == 3

    calls.clear()
    try:
        mh._with_retries("probe", flaky, max_retries=1, backoff_s=0.0)
        raise AssertionError("expected the bounded budget to propagate")
    except RuntimeError:
        pass
    assert len(calls) == 2  # initial try + 1 retry, then gave up


def test_initialize_distributed_single_process_still_degrades(monkeypatch):
    """The hardened wrapper keeps the auto-detect degradation contract:
    no cluster environment -> False, no retry storm, no raise. jax checks
    "backend already initialized" BEFORE it looks for a cluster, and by
    this point of the suite the backend is up, so the no-cluster outcome
    (the ValueError jax raises when auto-detection finds nothing) is
    stubbed in: the contract under test is the wrapper's classification."""
    import jax

    from neuroimagedisttraining_tpu.parallel import initialize_distributed

    calls = []

    def no_cluster(**kw):
        calls.append(kw)
        raise ValueError("coordinator_address should be defined.")

    monkeypatch.setattr(jax.distributed, "initialize", no_cluster)
    assert initialize_distributed(timeout_s=4.2, max_retries=2) is False
    assert calls == [{"initialization_timeout": 5}]  # ceil, one attempt
