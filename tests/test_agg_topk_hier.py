"""Pod-scale aggregation (ISSUE 7): error-feedback top-k, hierarchical
two-stage reduce, compute/comm overlap.

The parity matrix the existing impls carry (tests/test_collectives.py /
test_guard.py) extended over the two new wires plus the scheduling knob:

* topk density=1.0 degrades to the dense weighted mean; at low density
  the error-feedback residual carries the unsent remainder exactly;
* guard-quarantine survivor parity: a NaN-poisoned client's compensated
  delta never reaches the aggregate AND its residual row keeps the
  previous value (no leak into later rounds);
* fused-vs-unfused bit parity for topk and hier;
* mesh/shard_map paths agree with the off-mesh spellings;
* overlap on/off is bit-identical (scheduling freedom only);
* WireCostModel prices the topk payload EXACTLY against real
  ``Message.to_bytes`` serialization (residual-free wire), and topk at
  10% density models >= 4x fewer bytes than dense;
* obs/devtrace.py measures collective-vs-compute interval overlap.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.core.state import (
    HyperParams,
    weighted_tree_sum,
)
from neuroimagedisttraining_tpu.parallel import collectives as coll
from neuroimagedisttraining_tpu.parallel import (
    make_mesh,
    shard_over_clients,
)
from neuroimagedisttraining_tpu.robust import guard


def _tree(c=6, key=0, scale=1.0):
    k = jax.random.PRNGKey(key)
    return {
        "conv": {"kernel": jax.random.normal(k, (c, 3, 5, 7)) * scale,
                 "bias": jax.random.normal(
                     jax.random.fold_in(k, 1), (c, 7)) * scale},
        "head": {"kernel": jax.random.normal(
            jax.random.fold_in(k, 2), (c, 11, 13)) * scale},
    }


def _weights(c=6, seed=0):
    w = np.random.RandomState(seed).rand(c).astype(np.float32)
    return jnp.asarray(w / w.sum())


def _leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _max_err(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------------------
# topk kernel semantics
# ---------------------------------------------------------------------------

def test_topk_count_rule():
    assert coll.topk_count(100, 0.1) == 10
    assert coll.topk_count(5, 0.1) == 1          # floor of 1
    assert coll.topk_count(7, 0.5) == 4          # ceil
    assert coll.topk_count(10, 1.0) == 10        # keeps everything
    with pytest.raises(ValueError):
        coll.topk_count(10, 0.0)
    with pytest.raises(ValueError):
        coll.topk_count(10, 1.5)


def test_topk_sparsify_keeps_top_magnitudes_per_group():
    # one leaf-group (huge bucket): exact top-k of the flat row
    tree = {"a": jnp.asarray([[3.0, -7.0, 0.5, 2.0, -1.0,
                               9.0, 0.1, -4.0, 6.0, 0.2]])}
    sp = coll.topk_sparsify(tree, 0.3)  # k = ceil(0.3*10) = 3
    row = np.asarray(sp["a"])[0]
    assert np.count_nonzero(row) == 3
    np.testing.assert_array_equal(
        np.flatnonzero(row), [1, 5, 8])  # |-7|, |9|, |6|
    np.testing.assert_array_equal(row[[1, 5, 8]], [-7.0, 9.0, 6.0])


def test_topk_density_one_is_dense_mean():
    tree, w = _tree(), _weights()
    agg, sp = coll.topk_weighted_mean(tree, w, 1.0, bucket_size=16)
    assert _leaves_equal(sp, tree)  # nothing dropped
    assert _max_err(agg, weighted_tree_sum(tree, w)) < 1e-6


def test_topk_residual_is_exact_remainder():
    tree, w = _tree(), _weights()
    sp = coll.topk_sparsify(tree, 0.2, bucket_size=16)
    # the residual identity the EF round body relies on: comp - sp holds
    # exactly the coordinates selection dropped
    res = jax.tree_util.tree_map(lambda c, s: c - s, tree, sp)
    for r, s, x in zip(jax.tree_util.tree_leaves(res),
                       jax.tree_util.tree_leaves(sp),
                       jax.tree_util.tree_leaves(tree)):
        r, s, x = np.asarray(r), np.asarray(s), np.asarray(x)
        assert np.array_equal(r + s, x)
        assert not np.any((r != 0) & (s != 0))  # disjoint supports


def test_topk_selection_within_plan_live_coords():
    """SalientGrads composition: with a plan, k is a fraction of the
    LIVE set and dead coordinates are never selected."""
    tree, w = _tree(), _weights()
    mask = {
        "conv": {"kernel": (jax.random.uniform(
            jax.random.PRNGKey(9), (3, 5, 7)) < 0.4).astype(jnp.float32),
            "bias": jnp.ones((7,))},
        "head": {"kernel": (jax.random.uniform(
            jax.random.PRNGKey(10), (11, 13)) < 0.4).astype(jnp.float32)},
    }
    honored = jax.tree_util.tree_map(lambda x, m: x * m[None], tree, mask)
    plan = coll.build_sparse_plan(mask)
    sp = coll.topk_sparsify(honored, 0.25, plan=plan, bucket_size=16)
    for s, m in zip(jax.tree_util.tree_leaves(sp),
                    jax.tree_util.tree_leaves(mask)):
        s = np.asarray(s)
        mm = np.broadcast_to(np.asarray(m), s.shape)
        assert np.all(s[mm == 0] == 0)  # dead coords never ship
    # plan_dead_select: zeroes dead coords of an arbitrary stacked tree
    dirty = jax.tree_util.tree_map(lambda x: x + 1.0, tree)
    clean = coll.plan_dead_select(dirty, plan)
    for c, m in zip(jax.tree_util.tree_leaves(clean),
                    jax.tree_util.tree_leaves(mask)):
        c = np.asarray(c)
        mm = np.broadcast_to(np.asarray(m), c.shape)
        assert np.all(c[mm == 0] == 0)
        assert np.all(c[mm == 1] != 0)


def test_topk_sampled_threshold_is_deterministic_and_close():
    """The DGC sampling trick: a strided-subsample threshold estimate
    ships approximately k coordinates, deterministically (no RNG) — EF
    absorbs the approximation, so only determinism and rough calibration
    are contracts."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 4096))
    tree = {"a": x}
    exact = coll.topk_sparsify(tree, 0.1, bucket_size=1 << 20)
    samp1 = coll.topk_sparsify(tree, 0.1, bucket_size=1 << 20,
                               sample=256)
    samp2 = coll.topk_sparsify(tree, 0.1, bucket_size=1 << 20,
                               sample=256)
    assert _leaves_equal(samp1, samp2)  # deterministic
    k = coll.topk_count(4096, 0.1)
    for row_e, row_s in zip(np.asarray(exact["a"]),
                            np.asarray(samp1["a"])):
        assert np.count_nonzero(row_e) == k
        ns = np.count_nonzero(row_s)
        # calibrated within 2x on gaussian magnitudes
        assert k / 2 <= ns <= 2 * k, ns
    # sample >= n falls back to the exact selection
    assert _leaves_equal(
        exact, coll.topk_sparsify(tree, 0.1, bucket_size=1 << 20,
                                  sample=8192))
    # residual identity still exact under sampling: comp == sp + (comp-sp)
    res = jax.tree_util.tree_map(lambda c, s: c - s, tree, samp1)
    assert _leaves_equal(
        tree, jax.tree_util.tree_map(lambda s, r: s + r, samp1, res))


# ---------------------------------------------------------------------------
# hier kernel semantics
# ---------------------------------------------------------------------------

def test_resolve_hier_inner():
    assert coll.resolve_hier_inner(8) == 2      # balanced auto: 2x4
    assert coll.resolve_hier_inner(16) == 4
    assert coll.resolve_hier_inner(8, 4) == 4
    assert coll.resolve_hier_inner(8, 8) == 0   # one slice = no stage 2
    assert coll.resolve_hier_inner(8, 1) == 0
    assert coll.resolve_hier_inner(2) == 0
    with pytest.raises(ValueError):
        coll.resolve_hier_inner(8, 3)
    # invalid requests fail on SMALL axes too (the dev-mesh typo must
    # not silently disable hier and then surface only when promoted)
    with pytest.raises(ValueError):
        coll.resolve_hier_inner(2, 3)
    with pytest.raises(ValueError):
        coll.resolve_hier_inner(2, -1)


def test_hier_off_mesh_is_exact_dense():
    tree, w = _tree(), _weights()
    dense = weighted_tree_sum(tree, w)
    for wire in ("f32", "bf16"):
        h = coll.weighted_mean(tree, w, bucket_size=16, wire=wire,
                               hier_inner=-1)
        assert _leaves_equal(dense, h), wire  # one slice: wire never fires


def test_hier_one_slice_on_mesh_is_exact_dense(eight_devices):
    """hier_inner == axis size ON-mesh: everything is inside the fast
    domain, the cross-slice wire must never fire — bit-equal to the
    exact f32 bucketed reduce, NOT a whole-axis bf16/int8 reduce."""
    mesh = make_mesh(8)
    tree, w = _tree(c=8, key=5, scale=100.0), _weights(c=8, seed=5)
    sharded = shard_over_clients(tree, mesh)
    exact = coll.weighted_mean(sharded, w, mesh=mesh, bucket_size=16,
                               wire="f32")
    for wire, rng in (("bf16", None), ("int8", jax.random.PRNGKey(9))):
        h = coll.weighted_mean(sharded, w, mesh=mesh, bucket_size=16,
                               wire=wire, rng=rng, hier_inner=8)
        assert _leaves_equal(exact, h), wire


def test_hier_mesh_paths_match_dense(eight_devices):
    mesh = make_mesh(8)
    tree, w = _tree(c=8, key=1), _weights(c=8, seed=1)
    sharded = shard_over_clients(tree, mesh)
    dense = weighted_tree_sum(tree, w)
    # f32 cross-slice: reassociation only
    h32 = coll.weighted_mean(sharded, w, mesh=mesh, bucket_size=16,
                             wire="f32", hier_inner=-1)
    assert _max_err(dense, h32) < 1e-5
    # bf16 cross-slice at both slice splits
    for inner in (2, 4):
        hb = coll.weighted_mean(sharded, w, mesh=mesh, bucket_size=16,
                                wire="bf16", hier_inner=inner)
        assert _max_err(dense, hb) < 2e-2, inner
    # int8 cross-slice (per-slice stochastic-rounding keys)
    hi = coll.weighted_mean(sharded, w, mesh=mesh, bucket_size=16,
                            wire="int8", hier_inner=2,
                            rng=jax.random.PRNGKey(7))
    assert _max_err(dense, hi) < 6e-2
    # sparse (compressed-plan) payload through the hier reduce
    gm = {
        "conv": {"kernel": (jax.random.uniform(
            jax.random.PRNGKey(3), (3, 5, 7)) < 0.5).astype(jnp.float32),
            "bias": jnp.ones((7,))},
        "head": {"kernel": (jax.random.uniform(
            jax.random.PRNGKey(4), (11, 13)) < 0.5).astype(jnp.float32)},
    }
    honored = jax.tree_util.tree_map(lambda x, m: x * m[None], sharded,
                                     gm)
    plan = coll.build_sparse_plan(gm)
    hs = coll.sparse_weighted_mean(honored, w, plan, mesh=mesh,
                                   bucket_size=16, hier_inner=2)
    ref = weighted_tree_sum(
        jax.tree_util.tree_map(lambda x, m: x * m[None], tree, gm), w)
    assert _max_err(ref, hs) < 1e-5


def test_topk_mesh_matches_off_mesh(eight_devices):
    mesh = make_mesh(8)
    tree, w = _tree(c=8, key=2), _weights(c=8, seed=2)
    sharded = shard_over_clients(tree, mesh)
    t_on, sp_on = coll.topk_weighted_mean(sharded, w, 0.2, mesh=mesh,
                                          bucket_size=16)
    t_off, sp_off = coll.topk_weighted_mean(tree, w, 0.2, bucket_size=16)
    # selection is per-client-local: bit-equal on and off mesh
    assert _leaves_equal(sp_on, sp_off)
    assert _max_err(t_on, t_off) < 1e-5


def test_overlap_on_off_bit_identical(eight_devices):
    """The group-ordered dispatch is scheduling freedom only: per-bucket
    math is identical, so results are bit-equal with overlap on or
    off — on every wire."""
    mesh = make_mesh(8)
    tree, w = _tree(c=8, key=3), _weights(c=8, seed=3)
    sharded = shard_over_clients(tree, mesh)
    for kw in (dict(wire="f32"), dict(wire="bf16"),
               dict(wire="int8", rng=jax.random.PRNGKey(11)),
               dict(wire="bf16", hier_inner=2)):
        on = coll.weighted_mean(sharded, w, mesh=mesh, bucket_size=16,
                                overlap=True, **kw)
        off = coll.weighted_mean(sharded, w, mesh=mesh, bucket_size=16,
                                 overlap=False, **kw)
        assert _leaves_equal(on, off), kw


# ---------------------------------------------------------------------------
# end-to-end: the new impls through the algorithms
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


def _run(cls, agg_impl, model, data, hp, rounds=2, **kw):
    algo = cls(model, data, hp, loss_type="bce", frac=1.0, seed=0,
               agg_impl=agg_impl, **kw)
    state = algo.init_state(jax.random.PRNGKey(0))
    for r in range(rounds):
        state, m = algo.run_round(state, r)
    return algo, state, float(m["train_loss"])


def test_fedavg_topk_density_one_matches_dense():
    from neuroimagedisttraining_tpu.algorithms import FedAvg

    model, data, hp = _small_setup()
    _, sd, _ = _run(FedAvg, "dense", model, data, hp,
                    track_personal=False)
    _, st, _ = _run(FedAvg, "topk", model, data, hp,
                    track_personal=False, agg_topk_density=1.0)
    # g + sum(w*(loc-g)) == sum(w*loc) up to f32 round-off (w sums to 1)
    assert _max_err(sd.global_params, st.global_params) < 1e-5
    # nothing deferred at density 1.0
    assert max(float(jnp.max(jnp.abs(x))) for x in
               jax.tree_util.tree_leaves(st.agg_residual)) == 0.0


def test_fedavg_topk_low_density_trains_and_accumulates_residual():
    from neuroimagedisttraining_tpu.algorithms import FedAvg

    model, data, hp = _small_setup()
    _, st, loss = _run(FedAvg, "topk", model, data, hp,
                       track_personal=False, agg_topk_density=0.1)
    assert np.isfinite(loss)
    assert all(np.all(np.isfinite(np.asarray(x)))
               for x in jax.tree_util.tree_leaves(st.global_params))
    assert max(float(jnp.max(jnp.abs(x))) for x in
               jax.tree_util.tree_leaves(st.agg_residual)) > 0.0


def test_topk_rejected_without_residual_support():
    from neuroimagedisttraining_tpu.algorithms import Ditto

    model, data, hp = _small_setup()
    with pytest.raises(ValueError, match="residual"):
        Ditto(model, data, hp, loss_type="bce", frac=1.0, seed=0,
              agg_impl="topk")


def test_negative_hier_inner_rejected_at_construction():
    # the collectives layer's -1 is an INTERNAL auto sentinel; from
    # config a negative is a typo that would silently run the auto
    # split while run_identity records the never-applied request
    from neuroimagedisttraining_tpu.algorithms import FedAvg

    model, data, hp = _small_setup()
    with pytest.raises(ValueError, match="agg_hier_inner"):
        FedAvg(model, data, hp, loss_type="bce", frac=1.0, seed=0,
               agg_impl="hier", agg_hier_inner=-4)
    # density is validated on EVERY impl (the --obs_comm what-if table
    # prices topk on every run), not only when agg_impl == 'topk'
    with pytest.raises(ValueError, match="density"):
        FedAvg(model, data, hp, loss_type="bce", frac=1.0, seed=0,
               agg_impl="dense", agg_topk_density=0.0)


def test_salientgrads_topk_keeps_mask_invariants():
    from neuroimagedisttraining_tpu.algorithms import SalientGrads
    from neuroimagedisttraining_tpu.ops.sparsity import kernel_flags

    model, data, hp = _small_setup()
    algo, s, loss = _run(SalientGrads, "topk", model, data, hp,
                         rounds=3, dense_ratio=0.5,
                         itersnip_iterations=1, agg_topk_density=0.2)
    assert np.isfinite(loss)
    assert algo._agg_sparse_plan is not None  # selection ran compressed
    flags = kernel_flags(s.global_params)
    for g, r, m, kf in zip(jax.tree_util.tree_leaves(s.global_params),
                           jax.tree_util.tree_leaves(s.agg_residual),
                           jax.tree_util.tree_leaves(s.mask),
                           jax.tree_util.tree_leaves(flags)):
        if not kf:
            continue
        mm = np.asarray(m)
        # global keeps the SNIP sparsity; the residual holds nothing on
        # dead coordinates (round 0's dense init must not linger there)
        assert np.all(np.asarray(g)[mm == 0] == 0)
        rm = np.asarray(r)
        assert np.all(rm[np.broadcast_to(mm, rm.shape) == 0] == 0)


def test_salientgrads_hier_off_mesh_bit_equal_dense():
    """Off-mesh there is one slice: the cross-slice wire never fires and
    the hier reduce IS the bucketed contraction (the sparse one under
    ``agg_hier_wire='sparse'``), bit for bit. The sparse wire contracts
    leaf by leaf as dense does, so it equals dense bitwise too; the
    bucketed sum is a re-association of the dense one and is held to a few
    ulp of it in tests/test_collectives.py, which has the reason."""
    from neuroimagedisttraining_tpu.algorithms import SalientGrads

    model, data, hp = _small_setup()
    kw = dict(dense_ratio=0.5, itersnip_iterations=1)
    _, sd, _ = _run(SalientGrads, "dense", model, data, hp, **kw)
    _, sb, _ = _run(SalientGrads, "bucketed", model, data, hp, **kw)
    for hkw, twin in ((dict(), sb), (dict(agg_hier_wire="f32"), sb),
                      (dict(agg_hier_wire="sparse"), sd)):
        _, sh, _ = _run(SalientGrads, "hier", model, data, hp, **kw,
                        **hkw)
        assert _leaves_equal(twin.global_params, sh.global_params), hkw


def test_fused_vs_unfused_bit_parity_topk_and_hier():
    """The fused-vs-unfused contract extends to the new impls: the
    residual rides the scan carry bit-exactly."""
    from neuroimagedisttraining_tpu.algorithms import SalientGrads

    model, data, hp = _small_setup()
    for impl, extra in (("topk", dict(agg_topk_density=0.2)),
                        ("hier", dict())):
        kw = dict(dense_ratio=0.5, itersnip_iterations=1,
                  agg_impl=impl, loss_type="bce", frac=1.0, seed=0,
                  **extra)
        algo = SalientGrads(model, data, hp, **kw)
        s0 = algo.init_state(jax.random.PRNGKey(0))
        s_loop = s0
        for r in range(2):
            s_loop, _ = algo.run_round(s_loop, r)
        algo2 = SalientGrads(model, data, hp, **kw)
        s_fused, ys = algo2.run_rounds_fused(s0, 0, 2)
        assert np.isfinite(np.asarray(ys["train_loss"])).all()
        assert _leaves_equal(s_loop.global_params,
                             s_fused.global_params), impl
        if impl == "topk":
            assert _leaves_equal(s_loop.agg_residual,
                                 s_fused.agg_residual)


def test_topk_guard_quarantine_survivor_parity():
    """A NaN-poisoned client under the guard: (a) the topk aggregate is
    finite and equals the survivor-only aggregate, (b) the poisoned
    client's residual row keeps its previous value (no leak), (c) a
    clean guarded round is bit-identical to the unguarded one."""
    from neuroimagedisttraining_tpu.algorithms import FedAvg
    from neuroimagedisttraining_tpu.robust.faults import (
        make_fault_fn,
        parse_fault_spec,
    )

    model, data, hp = _small_setup()

    def build(**kw):
        return FedAvg(model, data, hp, loss_type="bce", frac=1.0,
                      seed=0, agg_impl="topk", agg_topk_density=0.2,
                      track_personal=False, **kw)

    # clean guarded == clean unguarded, bit-for-bit
    a_g = build(guard=True)
    a_u = build(guard=False)
    s0 = a_g.init_state(jax.random.PRNGKey(0))
    sg, _ = a_g.run_round(s0, 0)
    su, _ = a_u.run_round(s0, 0)
    assert _leaves_equal(sg.global_params, su.global_params)
    assert _leaves_equal(sg.agg_residual, su.agg_residual)

    # NaN-poison one client via the deterministic injector: the guard
    # quarantines it; its residual row must stay at the previous value
    a_f = build(fault_spec="nan=0.3", guard=True)
    s1 = a_f.init_state(jax.random.PRNGKey(0))
    prev_res = s1.agg_residual
    found = False
    for r in range(4):
        s_next, m = a_f.run_round(s1, r)
        nq = float(m["clients_quarantined"]) + float(m["clients_dropped"])
        assert all(np.all(np.isfinite(np.asarray(x))) for x in
                   jax.tree_util.tree_leaves(s_next.global_params))
        assert all(np.all(np.isfinite(np.asarray(x))) for x in
                   jax.tree_util.tree_leaves(s_next.agg_residual))
        if nq > 0:
            found = True
            # replay the injector host-side to find the poisoned rows
            fn = make_fault_fn(parse_fault_spec("nan=0.3"), 0)
            sel = np.arange(8, dtype=np.int32)
            poisoned, _ = fn(
                jax.tree_util.tree_map(
                    lambda x: jnp.zeros((8,) + x.shape),
                    s1.global_params),
                s1.global_params, jnp.asarray(sel),
                jnp.asarray(float(r), jnp.float32))
            bad = np.asarray(~guard.finite_screen(poisoned))
            for newr, oldr in zip(
                    jax.tree_util.tree_leaves(s_next.agg_residual),
                    jax.tree_util.tree_leaves(prev_res)):
                np.testing.assert_array_equal(
                    np.asarray(newr)[bad], np.asarray(oldr)[bad])
        s1, prev_res = s_next, s_next.agg_residual
    assert found, "nan=0.3 never fired in 4 rounds (spec/seed drifted?)"


def test_topk_error_feedback_convergence_ab():
    """The convergence A/B of the acceptance gate, at CI scale: topk at
    10% density WITH error feedback tracks dense final accuracy within
    noise; the same wire with the residual zeroed every round (feedback
    ablated) must not beat it — the residual is what preserves
    convergence (DGC, Lin et al. 2018)."""
    from neuroimagedisttraining_tpu.algorithms import FedAvg
    from neuroimagedisttraining_tpu.data import make_synthetic_federated
    from neuroimagedisttraining_tpu.models import create_model

    data = make_synthetic_federated(
        n_clients=8, samples_per_client=24, test_per_client=8,
        sample_shape=(8, 8, 8, 1))
    model = create_model("small3dcnn", num_classes=1)
    hp = HyperParams(lr=0.05, momentum=0.9, local_epochs=1,
                     steps_per_epoch=4, batch_size=8)

    def final_acc(agg_impl, **kw):
        algo = FedAvg(model, data, hp, loss_type="bce", frac=1.0,
                      seed=0, agg_impl=agg_impl, track_personal=False,
                      **kw)
        state, _ = algo.run(comm_rounds=10, eval_every=0,
                            finalize=False)
        return float(algo.evaluate(state)["global_acc"])

    acc_dense = final_acc("dense")
    acc_topk = final_acc("topk", agg_topk_density=0.1)
    # measured on this seeded cell: dense and topk-EF land within a few
    # points of each other (both well above chance); the gate is that EF
    # keeps topk within noise of dense at 10x fewer modeled bytes
    assert acc_dense > 0.6, acc_dense
    assert acc_topk > acc_dense - 0.1, (acc_topk, acc_dense)


# ---------------------------------------------------------------------------
# wire-cost model + Message serialization pins (concrete — no hypothesis)
# ---------------------------------------------------------------------------

def test_wire_model_topk_hier_bytes():
    from neuroimagedisttraining_tpu.obs.comm import WireCostModel

    sizes = (1000, 50)
    m = WireCostModel(sizes, (None, None), ("A", "B"), (0, 1),
                      agg_impl="topk", topk_density=0.1)
    # 8 bytes per selected coordinate, topk_count per leaf
    assert m.bytes_for("topk") == 8.0 * (100 + 5)
    assert m.bytes_for("dense") == 4.0 * 1050
    # >= 4x reduction vs dense at 10% density (the acceptance floor;
    # exact ratio here: 4200 / 840 = 5x)
    assert m.bytes_for("dense") / m.bytes_for("topk") >= 4.0
    assert m.round_metrics()["comm_bytes_wire"] == m.bytes_for("topk")
    # hier prices the cross-slice hop at the configured wire
    for wire, expect in (("bf16", 2.0 * 1050), ("f32", 4.0 * 1050)):
        mh = WireCostModel(sizes, (None, None), ("A", "B"), (0, 1),
                           agg_impl="hier", hier_wire=wire)
        assert mh.bytes_for("hier") == expect, wire
    # live-set composition: topk counts a fraction of LIVE coords
    ml = WireCostModel(sizes, (200, None), ("A", "B"), (0, 1),
                       agg_impl="topk", topk_density=0.1)
    assert ml.bytes_for("topk") == 8.0 * (20 + 5)
    # hier sparse wire needs a known density for the what-if
    mhs = WireCostModel(sizes, (None, None), ("A", "B"), (0, 1),
                        hier_wire="sparse")
    assert "hier" not in mhs.what_if()
    assert "topk" in mhs.what_if()
    with pytest.raises(ValueError):
        WireCostModel(sizes, (None, None), ("A", "B"), (0, 1),
                      topk_density=0.0)
    with pytest.raises(ValueError):
        WireCostModel(sizes, (None, None), ("A", "B"), (0, 1),
                      hier_wire="fp4")


def test_topk_payload_pins_message_bytes_exactly():
    """The property-pinned acceptance gate, concrete spelling (the
    hypothesis variant lives in test_comm_model_properties.py): the
    model's topk leaf bytes == message_payload_nbytes(topk_payload)
    EXACTLY, and real Message.to_bytes lands within the documented
    header budget on top."""
    from neuroimagedisttraining_tpu.comm.message import Message
    from neuroimagedisttraining_tpu.obs.comm import (
        message_overhead_budget,
        message_payload_nbytes,
        topk_payload,
    )
    from neuroimagedisttraining_tpu.parallel.collectives import topk_count

    rs = np.random.RandomState(0)
    tree = {"conv": rs.randn(4, 5, 6).astype(np.float32),
            "head": rs.randn(37).astype(np.float32),
            "bias": rs.randn(3).astype(np.float32)}
    for frac in (0.05, 0.1, 0.5, 1.0):
        payload = topk_payload(tree, frac)
        pred = sum(topk_count(int(np.prod(l.shape)), frac) * (4 + 4)
                   for l in tree.values())
        assert message_payload_nbytes(payload) == pred
        msg = Message("topk_update", 0, 1)
        msg.add_tensor("delta", payload)
        raw = msg.to_bytes()
        n_leaves = 2 * len(tree)  # idx + val per leaf
        assert pred <= len(raw) <= pred + message_overhead_budget(
            n_leaves)
        # round-trip: indices ascend, values match the source leaves
        back = Message.from_bytes(raw).get_tensor("delta")
        for key, leaf in tree.items():
            idx = back[key]["idx"]
            assert np.all(np.diff(idx) > 0) or idx.size <= 1
            np.testing.assert_array_equal(
                back[key]["val"], leaf.reshape(-1)[idx])
    # masked composition: selection restricted to live coordinates
    mask = {"conv": (rs.rand(4, 5, 6) < 0.5).astype(np.float32),
            "head": (rs.rand(37) < 0.5).astype(np.float32),
            "bias": np.ones(3, np.float32)}
    payload = topk_payload(tree, 0.2, mask=mask)
    for key in tree:
        live = np.flatnonzero(mask[key].reshape(-1))
        assert np.all(np.isin(payload[key]["idx"], live))
        assert payload[key]["idx"].size == topk_count(live.size, 0.2)


def test_algorithm_wire_model_covers_new_impls():
    """WireCostModel.from_algorithm prices topk/hier from the algo's
    own knobs, and the what-if table covers the new wires."""
    from neuroimagedisttraining_tpu.algorithms import FedAvg
    from neuroimagedisttraining_tpu.obs.comm import WireCostModel

    model, data, hp = _small_setup()
    algo = FedAvg(model, data, hp, loss_type="bce", frac=1.0, seed=0,
                  agg_impl="topk", agg_topk_density=0.25,
                  track_personal=False)
    m = WireCostModel.from_algorithm(algo)
    assert m.topk_density == 0.25
    metrics = m.round_metrics()
    assert metrics["comm_bytes_wire"] == metrics["comm_bytes_topk"]
    assert metrics["comm_bytes_topk"] < metrics["comm_bytes_dense"]
    assert "comm_bytes_hier" in metrics  # bf16 default cross-slice wire
    assert metrics["comm_bytes_hier"] == metrics["comm_bytes_bf16"]


# ---------------------------------------------------------------------------
# devtrace overlap attribution
# ---------------------------------------------------------------------------

def _trace_doc(events):
    meta = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 9,
         "args": {"name": "Steps"}},
    ]
    return {"traceEvents": meta + events}


def test_devtrace_overlap_attribution():
    from neuroimagedisttraining_tpu.obs import devtrace

    # compute 0..100us on tid 1; all-reduce 50..90us on tid 2 (a
    # separate stream): 40us of the 40us collective overlaps compute
    doc = _trace_doc([
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1",
         "ts": 0, "dur": 100},
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-reduce.7",
         "ts": 50, "dur": 40},
        # aggregate row must NOT count (same pid, "Steps" tid)
        {"ph": "X", "pid": 1, "tid": 9, "name": "step-row",
         "ts": 0, "dur": 1000},
    ])
    att = devtrace.attribute_trace(doc)
    t = att["totals"]
    assert t["busy_s"] == pytest.approx(140e-6)
    assert t["collective_s"] == pytest.approx(40e-6)
    assert t["overlap_s"] == pytest.approx(40e-6)
    assert t["overlap_frac"] == pytest.approx(1.0)


def test_devtrace_overlap_zero_when_serialized():
    from neuroimagedisttraining_tpu.obs import devtrace

    # the serialized schedule: collective strictly after compute
    doc = _trace_doc([
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1",
         "ts": 0, "dur": 50},
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-reduce.7",
         "ts": 60, "dur": 40},
    ])
    t = devtrace.attribute_trace(doc)["totals"]
    assert t["overlap_s"] == 0.0
    assert t["overlap_frac"] == 0.0
    # partial overlap folds correctly across files in a profile dir
    doc2 = _trace_doc([
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.2",
         "ts": 0, "dur": 30},
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-gather.1",
         "ts": 20, "dur": 20},
    ])
    t2 = devtrace.attribute_trace(doc2)["totals"]
    assert t2["overlap_s"] == pytest.approx(10e-6)
    assert t2["overlap_frac"] == pytest.approx(0.5)


def test_devtrace_dir_fold_carries_overlap(tmp_path):
    import json

    from neuroimagedisttraining_tpu.obs import devtrace

    doc = _trace_doc([
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1",
         "ts": 0, "dur": 100},
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-reduce.7",
         "ts": 50, "dur": 40},
    ])
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.trace.json").write_text(json.dumps(doc))
    out = devtrace.analyze_profile_dir(str(tmp_path),
                                       modeled_bytes=1e6)
    assert out["present"]
    assert out["totals"]["overlap_s"] == pytest.approx(40e-6)
    assert out["totals"]["overlap_frac"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# identity / lineage
# ---------------------------------------------------------------------------

def test_run_identity_splits_topk_and_hier():
    from neuroimagedisttraining_tpu.experiments.config import (
        parse_args,
        run_identity,
    )

    base = parse_args(["--algo", "fedavg"])
    topk = parse_args(["--algo", "fedavg", "--agg_impl", "topk",
                       "--agg_topk_density", "0.05"])
    hier = parse_args(["--algo", "fedavg", "--agg_impl", "hier",
                       "--agg_hier_wire", "int8",
                       "--agg_hier_inner", "4"])
    # metric identity splits for both; density / wire / inner ride it
    assert "aggtopk" in run_identity(topk)
    assert "tk0.05" in run_identity(topk)
    assert "agghier" in run_identity(hier)
    assert "hwint8" in run_identity(hier) and "hi4" in run_identity(hier)
    # CHECKPOINT identity: topk splits (residual state structure), the
    # other impls stay interchangeable with dense lineages
    assert run_identity(base, for_checkpoint=True) == \
        run_identity(hier, for_checkpoint=True)
    ck = run_identity(topk, for_checkpoint=True)
    assert "aggtopk" in ck and "tk0.05" in ck


def test_topk_checkpoint_roundtrip(tmp_path):
    """The residual stack checkpoints and restores (the state-schema
    migration contract: topk states are self-consistent lineages)."""
    pytest.importorskip("orbax.checkpoint")
    from neuroimagedisttraining_tpu.algorithms import FedAvg
    from neuroimagedisttraining_tpu.utils.checkpoint import (
        CheckpointManager,
    )

    model, data, hp = _small_setup()
    algo = FedAvg(model, data, hp, loss_type="bce", frac=1.0, seed=0,
                  agg_impl="topk", agg_topk_density=0.2,
                  track_personal=False)
    s = algo.init_state(jax.random.PRNGKey(0))
    s, _ = algo.run_round(s, 0)
    mgr = CheckpointManager(str(tmp_path), "topk-run")
    assert mgr.save(1, s, force=True)
    restored, step = mgr.restore_latest(
        algo.init_state(jax.random.PRNGKey(0)))
    assert step == 1
    assert _leaves_equal(s.agg_residual, restored.agg_residual)
    assert _leaves_equal(s.global_params, restored.global_params)
    mgr.close()
