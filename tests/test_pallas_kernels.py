"""The ``--agg_kernels pallas`` leg vs its XLA spellings and reference
math (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


# ---------------------------------------------------------------------------
# kernel leg (--agg_kernels): threshold selection / fused quantize+reduce /
# SNIP mask ops — pallas-interpret == XLA == reference, bitwise where the
# tie-break contract promises it (ops/topk_select.py module docstring)
# ---------------------------------------------------------------------------

def _sort_threshold(av, k):
    """The legacy sort spelling the threshold search replaced."""
    return jax.lax.top_k(av, k)[0][..., -1:]


def _threshold_cases():
    rng = np.random.RandomState(7)
    cont = rng.randn(4, 1000).astype(np.float32) * 0.01
    ties = rng.randint(0, 5, (3, 640)).astype(np.float32)  # tie-heavy
    ties[0, :17] = 0.0
    zeros = np.zeros((2, 256), np.float32)  # all-zero rows
    single = np.abs(rng.randn(1, 128)).astype(np.float32)
    return [(np.abs(cont), 100), (np.abs(cont), 1), (np.abs(cont), 1000),
            (ties, 64), (zeros, 8), (single, 128)]


@pytest.mark.parametrize("case", range(6))
def test_threshold_backends_bit_identical(case):
    """exact_threshold (XLA) == threshold_topk (pallas interpret) ==
    lax.top_k (sort) == the f64 sorted reference, BITWISE — including
    tie-heavy and all-zero rows (the k-th largest of f32 values is one
    of them; every backend converges to the same integer bit pattern)."""
    from neuroimagedisttraining_tpu.ops.pallas_kernels import threshold_topk
    from neuroimagedisttraining_tpu.ops.topk_select import exact_threshold

    av, k = _threshold_cases()[case]
    ref = np.sort(av.astype(np.float64), axis=-1)[:, ::-1][:, k - 1:k]
    srt = np.asarray(_sort_threshold(jnp.asarray(av), k))
    xla = np.asarray(exact_threshold(jnp.asarray(av), k))
    pls = np.asarray(threshold_topk(jnp.asarray(av), k))
    assert srt.tobytes() == xla.tobytes()
    assert srt.tobytes() == pls.tobytes()
    np.testing.assert_array_equal(xla.astype(np.float64), ref)


def test_select_threshold_routing_and_validation():
    from neuroimagedisttraining_tpu.ops import topk_select as ts

    av = jnp.abs(jnp.asarray(
        np.random.RandomState(0).randn(2, 512).astype(np.float32)))
    outs = [np.asarray(ts.select_threshold(av, 50, kernels=kb))
            for kb in ("sort", "xla", "pallas")]
    assert outs[0].tobytes() == outs[1].tobytes() == outs[2].tobytes()
    with pytest.raises(ValueError, match="agg_kernels"):
        ts.check_kernels("cuda")
    # a row VMEM cannot hold is refused with the reason — a pallas
    # request never runs the XLA search in silence
    from neuroimagedisttraining_tpu.ops.pallas_kernels import (
        THRESHOLD_MAX_N,
    )

    big = jax.ShapeDtypeStruct((1, THRESHOLD_MAX_N + 1), jnp.float32)
    with pytest.raises(ValueError, match="THRESHOLD_MAX_N"):
        jax.eval_shape(
            lambda a: ts.select_threshold(a, 5, kernels="pallas"), big)


def test_topk_sparsify_backends_select_identical_sets():
    """The acceptance contract: threshold selection (xla and pallas)
    picks a BIT-IDENTICAL coordinate set to the legacy sort path."""
    from neuroimagedisttraining_tpu.parallel import collectives as C

    key = jax.random.PRNGKey(3)
    tree = {"k": jax.random.normal(key, (5, 33, 9)) * 0.01,
            "b": jax.random.normal(jax.random.fold_in(key, 1),
                                   (5, 270)) * 0.01}
    ref = C.topk_sparsify(tree, 0.1, bucket_size=128, kernels="sort")
    for kb in ("xla", "pallas"):
        got = C.topk_sparsify(tree, 0.1, bucket_size=128, kernels=kb)
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(got)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), kb


def test_sampled_threshold_calibration_band():
    """The strided estimator (hoisted into ops/topk_select) stays the
    DGC calibration the sampled path always had: same spelling as the
    old inline block, and the selected count lands within a 2x band of
    exact k on smooth magnitudes (drift the EF residual absorbs)."""
    from neuroimagedisttraining_tpu.ops import topk_select as ts

    av = jnp.abs(jnp.asarray(
        np.random.RandomState(1).randn(2, 8192).astype(np.float32)))
    k, sample = 819, 1024
    thr = ts.sampled_threshold(av, k, sample)
    # the pre-dedupe inline spelling, verbatim
    stride = max(1, av.shape[-1] // sample)
    cand = av[:, ::stride]
    ks = min(cand.shape[1], max(1, int(round(k / stride))))
    legacy = jax.lax.top_k(cand, ks)[0][:, -1:]
    assert np.asarray(thr).tobytes() == np.asarray(legacy).tobytes()
    # routed through select_threshold on EVERY backend (sampling is
    # backend-independent: the subsample's top_k is already tiny)
    for kb in ("sort", "xla", "pallas"):
        got = ts.select_threshold(av, k, kernels=kb, sample=sample)
        assert np.asarray(got).tobytes() == np.asarray(thr).tobytes()
    counts = np.sum(np.asarray(av) >= np.asarray(thr), axis=1)
    assert ((counts >= k / 2) & (counts <= 2 * k)).all(), counts


def test_fused_quantize_reduce_bitwise_vs_xla_chain():
    """weighted_mean(wire='int8', kernels='pallas') is BIT-identical to
    the untouched XLA chain (same rng draw, same _int8_scale spelling,
    same dot-contraction primitive), and within quantization tolerance
    of the f64 accumulation of the same dequantized values."""
    from neuroimagedisttraining_tpu.parallel import collectives as C

    key = jax.random.PRNGKey(11)
    tree = {"a": jax.random.normal(key, (6, 3, 3, 4, 8)) * 0.01,
            "b": jax.random.normal(jax.random.fold_in(key, 1),
                                   (6, 2048)) * 0.01,
            "c": jax.random.normal(jax.random.fold_in(key, 2),
                                   (6, 17)) * 0.01}
    w = jnp.asarray(np.random.RandomState(2).rand(6).astype(np.float32))
    w = w / w.sum()
    rng = jax.random.PRNGKey(5)
    run = {kb: jax.jit(lambda st, wv, _kb=kb: C.weighted_mean(
        st, wv, wire="int8", rng=rng, bucket_size=1024,
        kernels=_kb))(tree, w) for kb in ("xla", "pallas")}
    for k in tree:
        a = np.asarray(run["xla"][k])
        b = np.asarray(run["pallas"][k])
        assert a.tobytes() == b.tobytes(), k
    # f64 reference of the reduce over the SAME dequantized f32 values
    mat = np.asarray(C.stacked_to_mat(tree))
    pad = (-mat.shape[1]) % 1024
    mb = np.pad(mat, ((0, 0), (0, pad))).reshape(6, -1, 1024)
    q, s = C._quantize_int8(jnp.asarray(mb), rng)
    deq = np.asarray(q).astype(np.float64) * np.asarray(s).astype(
        np.float64)
    ref = np.tensordot(np.asarray(w).astype(np.float64), deq, axes=1)
    got = np.concatenate([np.asarray(run["pallas"][k]).ravel()
                          for k in tree])
    np.testing.assert_allclose(
        got, ref.reshape(-1)[:mat.shape[1]], rtol=1e-5, atol=1e-7)


def test_quantize_reduce_any_bucket_runs_the_kernel():
    """Buckets that don't tile the kernel's 1024-element panel are
    zero-padded inside the kernel wrapper — still the pallas kernel,
    still bit-identical to the XLA chain."""
    from neuroimagedisttraining_tpu.parallel import collectives as C

    tree = {"x": jax.random.normal(jax.random.PRNGKey(0), (3, 40))}
    w = jnp.asarray([0.5, 0.25, 0.25], jnp.float32)
    rng = jax.random.PRNGKey(1)
    a = C.weighted_mean(tree, w, wire="int8", rng=rng, bucket_size=16,
                        kernels="pallas")
    b = C.weighted_mean(tree, w, wire="int8", rng=rng, bucket_size=16,
                        kernels="xla")
    assert np.asarray(a["x"]).tobytes() == np.asarray(b["x"]).tobytes()


def test_fused_mask_ops_bitwise():
    """fused_mask_apply == p*m and fused_score_mask == (s/norm >= thr),
    bitwise (pure elementwise ops — IEEE-exact per op in interpret
    mode), across leaf shapes that exercise the panel padding."""
    from neuroimagedisttraining_tpu.ops.pallas_kernels import (
        fused_mask_apply,
        fused_score_mask_leaf,
    )

    rng = np.random.RandomState(4)
    for shape in [(7,), (33, 9), (3, 3, 4, 8), (1030,)]:
        p = jnp.asarray(rng.randn(*shape).astype(np.float32))
        m = jnp.asarray((rng.rand(*shape) > 0.5).astype(np.float32))
        got = fused_mask_apply({"l": p}, {"l": m})["l"]
        assert np.asarray(got).tobytes() == np.asarray(p * m).tobytes()
        s = jnp.abs(jnp.asarray(rng.randn(*shape).astype(np.float32)))
        norm = jnp.sum(s)
        thr = jnp.float32(0.3) / jnp.maximum(norm, 1e-9)
        got = fused_score_mask_leaf(s, norm, thr)
        ref = (s / norm >= thr).astype(jnp.float32)
        assert np.asarray(got).tobytes() == np.asarray(ref).tobytes()


def test_mask_from_scores_backends_bit_identical():
    """SNIP mask construction: sort == xla == pallas bitwise, including
    a tie-heavy score tree (integer-valued scores)."""
    from neuroimagedisttraining_tpu.ops.sparsity import mask_from_scores

    rng = np.random.RandomState(5)
    smooth = {"conv": {"kernel": jnp.asarray(
        np.abs(rng.randn(3, 3, 4, 8)).astype(np.float32)),
        "bias": jnp.asarray(np.abs(rng.randn(8)).astype(np.float32))}}
    ties = {"conv": {"kernel": jnp.asarray(
        rng.randint(0, 4, (8, 8, 2, 2)).astype(np.float32))}}
    for scores, ratio in [(smooth, 0.3), (ties, 0.5)]:
        ref = mask_from_scores(scores, ratio, kernels="sort")
        for kb in ("xla", "pallas"):
            got = mask_from_scores(scores, ratio, kernels=kb)
            for a, b in zip(jax.tree_util.tree_leaves(ref),
                            jax.tree_util.tree_leaves(got)):
                assert np.asarray(a).tobytes() == \
                    np.asarray(b).tobytes(), kb


def test_salientgrads_agg_kernels_round_bit_identical():
    """A full SalientGrads topk round under agg_kernels='pallas' equals
    the 'xla' round BITWISE — mask build, selection, and re-mask all
    route through the kernel leg and the tie-break contract holds
    end-to-end."""
    from neuroimagedisttraining_tpu.algorithms import SalientGrads
    from neuroimagedisttraining_tpu.core.state import HyperParams
    from neuroimagedisttraining_tpu.data import make_synthetic_federated
    from neuroimagedisttraining_tpu.models import create_model

    data = make_synthetic_federated(
        n_clients=4, samples_per_client=16, test_per_client=4,
        sample_shape=(8, 8, 8, 1), loss_type="bce", class_num=2)
    model = create_model("small3dcnn", num_classes=1)
    hp = HyperParams(lr=0.05, lr_decay=1.0, momentum=0.9,
                     weight_decay=5e-4, grad_clip=10.0, local_epochs=1,
                     steps_per_epoch=2, batch_size=8)
    states = {}
    for kb in ("xla", "pallas"):
        a = SalientGrads(model, data, hp, loss_type="bce", frac=1.0,
                         seed=0, dense_ratio=0.5, agg_impl="topk",
                         agg_kernels=kb)
        s = a.init_state(jax.random.PRNGKey(0))
        s, _ = a.run_round(s, 0)
        states[kb] = s
    for la, lb in zip(
            jax.tree_util.tree_leaves(states["xla"].global_params),
            jax.tree_util.tree_leaves(states["pallas"].global_params)):
        assert np.asarray(la).tobytes() == np.asarray(lb).tobytes()


def test_base_rejects_unknown_agg_kernels():
    from neuroimagedisttraining_tpu.algorithms import FedAvg
    from neuroimagedisttraining_tpu.core.state import HyperParams
    from neuroimagedisttraining_tpu.data import make_synthetic_federated
    from neuroimagedisttraining_tpu.models import create_model

    data = make_synthetic_federated(
        n_clients=2, samples_per_client=8, test_per_client=4,
        sample_shape=(8, 8, 8, 1), loss_type="bce", class_num=2)
    model = create_model("small3dcnn", num_classes=1)
    hp = HyperParams(lr=0.05, lr_decay=1.0, momentum=0.9,
                     weight_decay=5e-4, grad_clip=10.0, local_epochs=1,
                     steps_per_epoch=1, batch_size=8)
    with pytest.raises(ValueError, match="agg_kernels"):
        FedAvg(model, data, hp, loss_type="bce", agg_kernels="cuda")


def test_runner_agg_kernels_twin_identical(tmp_path):
    """Acceptance gate: agg_kernels=pallas vs =xla twin runs diff
    `identical` through obs/diff.py on the int8 AND topk wires, with
    the varied flag landing in the census's INERT bucket (it never
    enters run identity)."""
    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_experiment,
    )
    from neuroimagedisttraining_tpu.experiments.config import run_identity
    from neuroimagedisttraining_tpu.obs import diff as obs_diff

    def argv(tag, impl, kernels):
        return ["--model", "small3dcnn", "--dataset", "synthetic",
                "--client_num_in_total", "4", "--batch_size", "8",
                "--epochs", "1", "--comm_round", "2", "--lr", "0.05",
                "--frac", "1.0", "--frequency_of_the_test", "1",
                "--agg_impl", impl, "--agg_bucket_size", "1024",
                "--agg_kernels", kernels, "--obs", "1",
                "--results_dir", str(tmp_path / tag / "results"),
                "--log_dir", str(tmp_path / f"LOG{tag}")]

    for impl in ("int8", "topk"):
        outs = {}
        for kb in ("xla", "pallas"):
            tag = f"{impl}-{kb}"
            outs[kb] = run_experiment(
                parse_args(argv(tag, impl, kb), algo="fedavg"), "fedavg")
        assert outs["xla"]["identity"] == outs["pallas"]["identity"]
        assert "kernel" not in run_identity(
            parse_args(argv("i", impl, "pallas"), algo="fedavg"),
            "fedavg")
        doc = obs_diff.diff_runs(
            obs_diff.load_run(str(tmp_path / f"{impl}-xla" / "results" /
                                  "synthetic")),
            obs_diff.load_run(str(tmp_path / f"{impl}-pallas" /
                                  "results" / "synthetic")))
        assert obs_diff.expect_exit_code(doc, "identical") == 0, \
            (impl, obs_diff.render_diff(doc))
        assert "agg_kernels" in doc["planes"]["config"]["inert"]
        pd = obs_diff.params_diff(outs["xla"]["state"].global_params,
                                  outs["pallas"]["state"].global_params)
        assert pd["identical"], (impl, pd["diverged"][:3])


# ---------------------------------------------------------------------------
# real-TPU tier: every kernel compiled through Mosaic (non-interpret) at the
# flagship's shapes, each against its jax.numpy spelling. Run on a TPU host:
#     JAX_PLATFORMS=tpu python -m pytest -m tpu tests/test_pallas_kernels.py
# (tests/conftest.py deselects the tier on the CPU platform). The CPU tier
# above pins bit-identity in interpret mode; across two compilers (Mosaic
# and XLA:TPU) the float contracts are tolerances, the integer fixed point
# of the threshold search stays bitwise.
# ---------------------------------------------------------------------------

ABCD_VOLUME = (121, 145, 121)


def _alexnet_tree(key, lead=()):
    """Random f32 arrays in the shape of the full-volume AlexNet3D-s2d
    parameter tree (2.57 M parameters), optionally client-stacked."""
    from neuroimagedisttraining_tpu.models import create_model, init_params
    from neuroimagedisttraining_tpu.ops.s2d import phased_sample_shape

    model = create_model("3dcnn_s2d", num_classes=1)
    shapes = jax.eval_shape(
        lambda k: init_params(model, k, phased_sample_shape(ABCD_VOLUME)),
        jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    return jax.tree_util.tree_unflatten(treedef, [
        jax.random.normal(jax.random.fold_in(key, i),
                          tuple(lead) + l.shape, jnp.float32) * 0.01
        for i, l in enumerate(leaves)])


def _flat(tree):
    return np.concatenate([np.asarray(x).ravel()
                           for x in jax.tree_util.tree_leaves(tree)])


def _assert_trees_close(got, want, rtol, atol):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


@pytest.mark.tpu
@pytest.mark.parametrize("c", [8, 32])
def test_tpu_weighted_tree_sum_is_f32_exact(c):
    """The weighted sum the round ships (core.state.weighted_tree_sum, a
    tensordot) against an f64 host reference: on the chip an f32 contraction must not be rounded
    through bf16. Terms are ~1e-2 * w; atol 2e-8 admits the f32 rounding
    of a cancelling c-term sum and sits 50x under one bf16 rounding of a
    term (1e-2 * 2^-9 * w ~ 1e-6 at c=8)."""
    from neuroimagedisttraining_tpu.core.state import weighted_tree_sum

    tree = _alexnet_tree(jax.random.PRNGKey(1), lead=(c,))
    w = np.random.RandomState(c).rand(c).astype(np.float32)
    w = jnp.asarray(w / w.sum())
    w64 = np.asarray(w, np.float64)
    want = jax.tree_util.tree_map(
        lambda x: np.tensordot(w64, np.asarray(x, np.float64),
                               axes=1).astype(np.float32), tree)
    _assert_trees_close(jax.jit(weighted_tree_sum)(tree, w), want,
                        rtol=2e-6, atol=2e-8)


@pytest.mark.tpu
@pytest.mark.parametrize("c,n,k", [
    (1, 2_570_000, 1_285_000),   # the SNIP score row: whole model, half
    (8, 1 << 20, 104_857),       # topk wire group, 8 / 32 clients
    (32, 1 << 20, 104_857),
    (4, 4096, 50),
])
def test_tpu_threshold_topk_bitwise(c, n, k):
    from neuroimagedisttraining_tpu.ops.pallas_kernels import threshold_topk
    from neuroimagedisttraining_tpu.ops.topk_select import exact_threshold

    av = jnp.abs(jax.random.normal(jax.random.PRNGKey(n % 97), (c, n)))
    got = np.asarray(threshold_topk(av, k))
    assert got.tobytes() == np.asarray(exact_threshold(av, k)).tobytes()
    assert (np.sum(np.asarray(av) >= got, axis=1) >= k).all()


@pytest.mark.tpu
@pytest.mark.parametrize("c", [8, 32])
@pytest.mark.parametrize("bucket", [1024, 1 << 18])
def test_tpu_fused_quantize_reduce_alexnet_tree(c, bucket):
    """int8 wire, pallas vs the XLA chain on the same draw. The two
    compilers may round x/scale differently in the last bit, which flips
    a stochastic rounding only where the draw lands within an ulp of the
    fraction: nearly every element agrees to f32 tolerance, and no
    element is off by more than one quantum of its bucket."""
    from neuroimagedisttraining_tpu.parallel import collectives as C

    tree = _alexnet_tree(jax.random.PRNGKey(2), lead=(c,))
    w = jnp.full((c,), 1.0 / c, jnp.float32)
    rng = jax.random.PRNGKey(3)
    run = {kb: jax.jit(lambda st, wv, _kb=kb: C.weighted_mean(
        st, wv, wire="int8", rng=rng, bucket_size=bucket,
        kernels=_kb))(tree, w) for kb in ("xla", "pallas")}
    a, b = _flat(run["xla"]), _flat(run["pallas"])
    amax = float(np.max(np.abs(_flat(tree))))
    quantum = amax / 127.0 / c          # one int8 step of one client
    diff = np.abs(a - b)
    assert diff.max() <= quantum * 1.01, (diff.max(), quantum)
    assert np.mean(diff > 1e-6 * amax) < 1e-4, np.mean(diff > 1e-6 * amax)


@pytest.mark.tpu
def test_tpu_mask_kernels_alexnet_tree():
    """fused_mask_apply (bitwise: one f32 multiply), fused_score_mask_leaf
    and the whole pallas SNIP mask build at the flagship's 2.57 M scores:
    masks agree with the XLA spelling except where a score sits within an
    ulp of the threshold, and keep exactly the requested density."""
    from neuroimagedisttraining_tpu.ops.pallas_kernels import (
        fused_mask_apply,
    )
    from neuroimagedisttraining_tpu.ops.sparsity import (
        mask_density,
        mask_from_scores,
    )

    tree = _alexnet_tree(jax.random.PRNGKey(4))
    mask = jax.tree_util.tree_map(
        lambda x: (x > 0).astype(jnp.float32), tree)
    got = jax.jit(fused_mask_apply)(tree, mask)
    want = jax.tree_util.tree_map(lambda p, m: p * m, tree, mask)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

    scores = jax.tree_util.tree_map(jnp.abs, tree)
    masks = {kb: jax.jit(lambda s, _kb=kb: mask_from_scores(
        s, 0.5, kernels=_kb))(scores) for kb in ("xla", "pallas")}
    a, b = _flat(masks["xla"]), _flat(masks["pallas"])
    assert np.mean(a != b) < 1e-5, np.mean(a != b)
    assert abs(float(mask_density(masks["pallas"])) - 0.5) < 1e-3
