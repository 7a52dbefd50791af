"""Aggregation-subsystem parity (parallel/collectives.py).

The agg_impl contract (ISSUE 1): the default dense path keeps today's
numerics bit-for-bit; bucketed is bit-equal to dense off-mesh; the
low-precision wires agree within their precision; mask-aware sparse
aggregation is bit-equal to the dense (mask-weighted) aggregate when
masks are honored; every impl composes with the Byzantine-robust
defenses; and the shard_map mesh paths agree with the unsharded dense
reference on the 8-device CPU mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.core.state import HyperParams, weighted_tree_sum
from neuroimagedisttraining_tpu.parallel import collectives as coll
from neuroimagedisttraining_tpu.parallel import (
    make_mesh,
    mesh_of,
    shard_over_clients,
)


def _tree(c=5, key=0):
    k = jax.random.PRNGKey(key)
    return {
        "conv": {"kernel": jax.random.normal(k, (c, 3, 5, 7)),
                 "bias": jax.random.normal(jax.random.fold_in(k, 1), (c, 7))},
        # odd-sized leaf so the bucket padding path is exercised
        "head": {"kernel": jax.random.normal(
            jax.random.fold_in(k, 2), (c, 11, 13))},
    }


def _weights(c=5, seed=0):
    w = np.random.RandomState(seed).rand(c).astype(np.float32)
    return jnp.asarray(w / w.sum())


def _global_mask(density=0.4, key=9):
    k = jax.random.PRNGKey(key)
    return {
        "conv": {"kernel": (jax.random.uniform(k, (3, 5, 7))
                            < density).astype(jnp.float32),
                 "bias": jnp.ones((7,))},
        "head": {"kernel": (jax.random.uniform(jax.random.fold_in(k, 1),
                                               (11, 13))
                            < density).astype(jnp.float32)},
    }


def _leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _max_err(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _assert_reassociated_sum_close(dense, other, what):
    """The contract of a bucketed (or hier, off-mesh) aggregate against the
    dense per-leaf contraction: the same f32 sum of C terms, associated in
    the order XLA picks for each operand shape (one [C, N_total] bucket
    against a [C, n_leaf] dot per leaf), so equal to a few ulp at the scale
    of the parameters, not bit for bit. Measured on XLA:CPU 0.9.0 after two
    rounds: one ulp (6e-8) in an 8-element leaf of O(1) values."""
    scale = max(1.0, max(float(jnp.max(jnp.abs(x)))
                         for x in jax.tree_util.tree_leaves(dense)))
    atol = 4 * float(np.finfo(np.float32).eps) * scale
    err = _max_err(dense, other)
    assert err <= atol, (
        f"{what}: max |difference| {err:.3g} over {atol:.3g} (4 ulp of "
        f"f32 at scale {scale:.3g}): more than a re-associated sum of C "
        "terms explains")


def test_bucketed_bit_equal_dense():
    tree, w = _tree(), _weights()
    dense = weighted_tree_sum(tree, w)
    # bucket_size 16 forces multiple buckets AND tail padding
    assert _leaves_equal(dense, coll.weighted_mean(tree, w, bucket_size=16))
    # one giant bucket too (no padding split)
    assert _leaves_equal(dense, coll.weighted_mean(tree, w))


def test_flatten_roundtrip():
    tree = _tree(c=1)
    spec = coll.flat_spec(tree)
    assert _leaves_equal(tree, coll.vec_to_tree(coll.tree_to_vec(tree),
                                                spec))


def test_bf16_wire_tolerance():
    tree, w = _tree(), _weights()
    dense = weighted_tree_sum(tree, w)
    bf = coll.weighted_mean(tree, w, bucket_size=16, wire="bf16")
    assert 0 < _max_err(dense, bf) < 2e-2  # bf16 wire: ~8 mantissa bits


def test_int8_wire_tolerance():
    tree, w = _tree(), _weights()
    dense = weighted_tree_sum(tree, w)
    i8 = coll.weighted_mean(tree, w, bucket_size=16, wire="int8",
                            rng=jax.random.PRNGKey(3))
    # per-bucket scale = amax/127; values here are O(1) normals
    assert _max_err(dense, i8) < 6e-2
    with pytest.raises(ValueError):
        coll.weighted_mean(tree, w, wire="int8")  # rng required


def test_sparse_bit_equal_dense_when_masks_honored():
    tree, w = _tree(), _weights()
    gm = _global_mask()
    honored = jax.tree_util.tree_map(lambda x, m: x * m[None], tree, gm)
    plan = coll.build_sparse_plan(gm)
    assert 0.2 < plan.density < 0.8  # kernels compressed, bias dense
    sparse = coll.sparse_weighted_mean(honored, w, plan, bucket_size=16)
    assert _leaves_equal(weighted_tree_sum(honored, w), sparse)


def test_sparse_masked_bit_equal_dense_masked():
    """Per-client masks: numerator AND the sum(masks) denominator reduced
    on the compressed representation == the dense mask-weighted mean."""
    tree, w = _tree(), _weights()
    k = jax.random.PRNGKey(4)
    masks = jax.tree_util.tree_map(
        lambda x: (jax.random.uniform(
            jax.random.fold_in(k, x.size), x.shape) < 0.5
        ).astype(jnp.float32), tree)
    honored = jax.tree_util.tree_map(lambda x, m: x * m, tree, masks)
    plan = coll.build_sparse_plan(masks, stacked=True)
    ref = coll.masked_weighted_mean(honored, w, masks)
    sp = coll.sparse_weighted_mean(honored, w, plan, masks=masks,
                                   bucket_size=16)
    assert _leaves_equal(ref, sp)


def test_sparse_plan_tree_mismatch_raises():
    tree, w = _tree(), _weights()
    plan = coll.build_sparse_plan(_global_mask())
    bad = {"only": tree["head"]}
    with pytest.raises(ValueError):
        coll.sparse_weighted_mean(bad, w, plan)


def test_mesh_shardmap_paths_match_dense(eight_devices):
    """All wires on the 8-device clients mesh: per-bucket psum (f32) and
    the all_gather low-precision wires agree with the unsharded dense
    reference (f32 only reassociates across devices)."""
    mesh = make_mesh(8)
    tree, w = _tree(c=8, key=1), _weights(c=8, seed=1)
    sharded = shard_over_clients(tree, mesh)
    assert mesh_of(sharded) is not None
    assert mesh_of(tree) is None
    dense = weighted_tree_sum(tree, w)
    f32 = coll.weighted_mean(sharded, w, mesh=mesh, bucket_size=16)
    assert _max_err(dense, f32) < 1e-5
    bf = coll.weighted_mean(sharded, w, mesh=mesh, bucket_size=16,
                            wire="bf16")
    assert _max_err(dense, bf) < 2e-2
    i8 = coll.weighted_mean(sharded, w, mesh=mesh, bucket_size=16,
                            wire="int8", rng=jax.random.PRNGKey(7))
    assert _max_err(dense, i8) < 6e-2
    # sparse on-mesh: compressed psum + scatter
    gm = _global_mask()
    honored = jax.tree_util.tree_map(lambda x, m: x * m[None], sharded, gm)
    plan = coll.build_sparse_plan(gm)
    sp = coll.sparse_weighted_mean(honored, w, plan, mesh=mesh,
                                   bucket_size=16)
    ref = weighted_tree_sum(
        jax.tree_util.tree_map(lambda x, m: x * m[None], tree, gm), w)
    assert _max_err(ref, sp) < 1e-5
    # per-client masks ON-MESH: num/den both reduced compressed inside
    # shard_map (the agg_masked branch) vs the dense masked reference
    k2 = jax.random.PRNGKey(11)
    masks = jax.tree_util.tree_map(
        lambda x: (jax.random.uniform(
            jax.random.fold_in(k2, x.size), x.shape) < 0.5
        ).astype(jnp.float32), tree)
    honored_m = jax.tree_util.tree_map(lambda x, m: x * m, sharded, masks)
    mplan = coll.build_sparse_plan(masks, stacked=True)
    spm = coll.sparse_weighted_mean(honored_m, w, mplan, masks=masks,
                                    mesh=mesh, bucket_size=16)
    refm = coll.masked_weighted_mean(
        jax.tree_util.tree_map(lambda x, m: x * m, tree, masks), w, masks)
    assert _max_err(refm, spm) < 1e-5
    # C not divisible by the mesh axis -> static fallback to the exact
    # off-mesh contraction (partial-participation rounds)
    t5, w5 = _tree(c=5), _weights(c=5)
    assert _leaves_equal(weighted_tree_sum(t5, w5),
                         coll.weighted_mean(t5, w5, mesh=mesh,
                                            bucket_size=16))


# ---------------------------------------------------------------------------
# end-to-end: agg_impl through the algorithms
# ---------------------------------------------------------------------------

def _small_setup():
    from neuroimagedisttraining_tpu.data import make_synthetic_federated
    from neuroimagedisttraining_tpu.models import create_model

    data = make_synthetic_federated(
        n_clients=8, samples_per_client=12, test_per_client=4,
        sample_shape=(8, 8, 8, 1))
    model = create_model("small3dcnn", num_classes=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=3,
                     batch_size=4)
    return model, data, hp


def _run2(cls, agg_impl, model, data, hp, **kw):
    algo = cls(model, data, hp, loss_type="bce", frac=1.0, seed=0,
               agg_impl=agg_impl, **kw)
    state = algo.init_state(jax.random.PRNGKey(0))
    for r in range(2):
        state, m = algo.run_round(state, r)
    return algo, state, float(m["train_loss"])


def test_salientgrads_agg_impl_round_parity():
    """Two SalientGrads rounds per impl: sparse is bit-equal to the dense
    default (locals honor the static SNIP mask, so the compressed reduce
    loses nothing, and it contracts leaf by leaf as dense does); bucketed
    re-associates the sum and stays within a few ulp; bf16 stays within
    wire precision; int8 trains finite."""
    from neuroimagedisttraining_tpu.algorithms import SalientGrads

    model, data, hp = _small_setup()
    kw = dict(dense_ratio=0.5, itersnip_iterations=1)
    _, sd, loss_d = _run2(SalientGrads, "dense", model, data, hp, **kw)
    assert np.isfinite(loss_d)
    _, s, _ = _run2(SalientGrads, "bucketed", model, data, hp, **kw)
    _assert_reassociated_sum_close(sd.global_params, s.global_params,
                                   "bucketed")
    algo, s, _ = _run2(SalientGrads, "sparse", model, data, hp, **kw)
    assert _leaves_equal(sd.global_params, s.global_params), "sparse"
    assert algo._agg_sparse_plan is not None
    assert algo._agg_sparse_plan.density < 1.0
    _, sb, loss_b = _run2(SalientGrads, "bf16", model, data, hp, **kw)
    assert np.isfinite(loss_b)
    assert _max_err(sd.global_params, sb.global_params) < 2e-2
    _, si, loss_i = _run2(SalientGrads, "int8", model, data, hp, **kw)
    assert np.isfinite(loss_i)


def test_salientgrads_sparse_fused_matches_unfused():
    """agg_impl='sparse' under the fused K-round scan: the plan is built
    before the fused program traces, and the block matches the unfused
    rounds bit-for-bit (the fused-vs-unfused contract extends to the
    compressed aggregation path)."""
    from neuroimagedisttraining_tpu.algorithms import SalientGrads

    model, data, hp = _small_setup()
    kw = dict(dense_ratio=0.5, itersnip_iterations=1,
              agg_impl="sparse", loss_type="bce", frac=1.0, seed=0)
    algo = SalientGrads(model, data, hp, **kw)
    s0 = algo.init_state(jax.random.PRNGKey(0))
    s_loop = s0
    for r in range(2):
        s_loop, _ = algo.run_round(s_loop, r)
    algo2 = SalientGrads(model, data, hp, **kw)
    s_fused, ys = algo2.run_rounds_fused(s0, 0, 2)
    assert np.isfinite(np.asarray(ys["train_loss"])).all()
    assert _leaves_equal(s_loop.global_params, s_fused.global_params)


def test_robust_defense_composes_with_agg_impls():
    """Defenses transform the stacked locals BEFORE aggregation, so they
    compose with every agg_impl: the deterministic clipping defense is
    bit-equal across dense/sparse and within a few ulp for bucketed (a
    re-associated sum), and weak-DP + sparse keeps
    the mask invariant (noise on dead coordinates is dropped by the
    compressed reduce)."""
    from neuroimagedisttraining_tpu.algorithms import SalientGrads
    from neuroimagedisttraining_tpu.ops.sparsity import mask_density
    from neuroimagedisttraining_tpu.robust import RobustAggregator

    model, data, hp = _small_setup()
    kw = dict(dense_ratio=0.5, itersnip_iterations=1)
    clip = dict(defense_type="norm_diff_clipping", norm_bound=0.5)
    _, sd, _ = _run2(SalientGrads, "dense", model, data, hp,
                     defense=RobustAggregator(**clip), **kw)
    _, s, _ = _run2(SalientGrads, "bucketed", model, data, hp,
                    defense=RobustAggregator(**clip), **kw)
    _assert_reassociated_sum_close(sd.global_params, s.global_params,
                                   "bucketed behind the clipping defense")
    _, s, _ = _run2(SalientGrads, "sparse", model, data, hp,
                    defense=RobustAggregator(**clip), **kw)
    assert _leaves_equal(sd.global_params, s.global_params), "sparse"
    _, sw, loss = _run2(
        SalientGrads, "sparse", model, data, hp,
        defense=RobustAggregator("weak_dp", norm_bound=0.5, stddev=0.01),
        **kw)
    assert np.isfinite(loss)
    dens = float(mask_density(sw.mask))
    gp = sw.global_params
    # global params keep the SNIP sparsity despite the dense noise
    from neuroimagedisttraining_tpu.ops.sparsity import kernel_flags

    flags = kernel_flags(gp)
    for p, m, k in zip(jax.tree_util.tree_leaves(gp),
                       jax.tree_util.tree_leaves(sw.mask),
                       jax.tree_util.tree_leaves(flags)):
        if k:
            assert np.all(np.asarray(p)[np.asarray(m) == 0] == 0)
    assert 0 < dens < 1


def test_fedavg_bucketed_bit_equal_and_sparse_rejected():
    # the name is the driver's record of this test; the contract is a few
    # ulp (see _assert_reassociated_sum_close)
    from neuroimagedisttraining_tpu.algorithms import FedAvg

    model, data, hp = _small_setup()
    _, sd, _ = _run2(FedAvg, "dense", model, data, hp,
                     track_personal=False)
    _, sb, _ = _run2(FedAvg, "bucketed", model, data, hp,
                     track_personal=False)
    _assert_reassociated_sum_close(sd.global_params, sb.global_params,
                                   "fedavg bucketed")
    with pytest.raises(ValueError, match="static-mask"):
        _run2(FedAvg, "sparse", model, data, hp, track_personal=False)
    with pytest.raises(ValueError, match="agg_impl"):
        FedAvg(model, data, hp, loss_type="bce", agg_impl="nope")


def test_full_participation_guard(monkeypatch):
    """ADVICE r5 base.py:388: a permuted draw at full participation must
    fail fast instead of silently misaligning the skipped gathers."""
    import neuroimagedisttraining_tpu.algorithms.base as base_mod
    from neuroimagedisttraining_tpu.algorithms import FedAvg

    model, data, hp = _small_setup()
    algo = FedAvg(model, data, hp, loss_type="bce", frac=1.0, seed=0,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(0))
    monkeypatch.setattr(
        base_mod, "sample_client_indexes",
        lambda r, n, k: np.arange(n, dtype=np.int32)[::-1].copy())
    with pytest.raises(ValueError, match="arange"):
        algo.run_round(state, 0)
    with pytest.raises(ValueError, match="arange"):
        algo._fused_host_inputs(0)


def test_fused_metric_contract_raises():
    """ADVICE r5 base.py:649: the fused-loop contract checks are explicit
    raises (python -O must not strip them)."""
    from neuroimagedisttraining_tpu.algorithms import FedAvg

    model, data, hp = _small_setup()

    class Drifted(FedAvg):
        _round_metric_names = ("train_loss", "phantom")

    algo = Drifted(model, data, hp, loss_type="bce", frac=1.0, seed=0,
                   track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="_round_metric_names"):
        algo.run_rounds_fused(state, 0, 2)


