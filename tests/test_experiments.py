"""L5 experiments/CLI layer: flags, identity, runner, checkpoint/resume,
cost accounting."""
import json
import os
import pickle

import numpy as np
import pytest

from neuroimagedisttraining_tpu.experiments import (
    ALGO_NAMES,
    parse_args,
    run_experiment,
    run_identity,
)


def _argv(tmp_path, algo="fedavg", **over):
    base = {
        "--model": "small3dcnn",
        "--dataset": "synthetic",
        "--client_num_in_total": "4",
        "--batch_size": "8",
        "--epochs": "1",
        "--comm_round": "2",
        "--lr": "0.05",
        "--log_dir": str(tmp_path / "LOG"),
        "--results_dir": str(tmp_path / "results"),
    }
    base.update({k: str(v) for k, v in over.items()})
    argv = []
    for k, v in base.items():
        argv += [k, v]
    return argv


def test_parse_and_identity(tmp_path):
    args = parse_args(_argv(tmp_path) + ["--frac", "0.5"], algo="salientgrads")
    assert args.client_num_per_round == 2
    ident = run_identity(args, "salientgrads")
    assert "salientgrads" in ident and "synthetic" in ident
    assert "seed0" in ident


def test_ci_mode_caps_rounds(tmp_path):
    args = parse_args(_argv(tmp_path, **{"--comm_round": 50, "--ci": 1}))
    assert args.comm_round == 2


@pytest.mark.parametrize("algo", ["fedavg", "salientgrads", "ditto"])
def test_run_experiment_smoke(tmp_path, algo):
    args = parse_args(_argv(tmp_path), algo=algo)
    out = run_experiment(args, algo)
    rounds = [h for h in out["history"] if h["round"] >= 0]
    assert len(rounds) == 2
    losses = [h["train_loss"] for h in rounds]
    assert all(np.isfinite(l) for l in losses)
    if algo == "fedavg":  # final fine-tune record (fedavg_api.py:79-88)
        assert out["history"][-1]["round"] == -1
    # per-round cost counters accumulate (sailentgrads_api.py:137-138)
    assert rounds[-1]["sum_training_flops"] > rounds[0]["sum_training_flops"]
    assert rounds[-1]["sum_comm_params"] > 0
    # stat_info artifact written (subavg_api.py:218-221 semantics)
    assert out["stat_path"] and os.path.exists(out["stat_path"])
    with open(out["stat_path"], "rb") as f:
        stat = pickle.load(f)
    assert stat["config"]["model"] == "small3dcnn"
    assert len(stat["history"]) == len(out["history"])
    assert stat["sum_training_flops"] > 0
    assert stat["sum_comm_params"] > 0
    # record_avg_inference_flops (sailentgrads_api.py:319-332)
    assert stat["avg_inference_flops"] > 0
    # per-run file log exists, keyed by identity
    assert os.path.exists(
        os.path.join(str(tmp_path / "LOG"), out["identity"] + ".log"))


def test_fedfomo_via_cli(tmp_path):
    args = parse_args(_argv(tmp_path, **{"--val_fraction": 0.2}),
                      algo="fedfomo")
    out = run_experiment(args, "fedfomo")
    assert np.isfinite(out["history"][-1]["train_loss"])


def test_unified_main_algo_flag(tmp_path):
    args = parse_args(_argv(tmp_path) + ["--algo", "local"])
    out = run_experiment(args)
    assert len(out["history"]) == 2


def test_checkpoint_resume(tmp_path):
    ck = str(tmp_path / "ckpt")
    argv = _argv(tmp_path, **{"--comm_round": 3, "--checkpoint_dir": ck})
    args = parse_args(argv, algo="fedavg")
    out1 = run_experiment(args, "fedavg")
    # resume with a larger total budget: picks up at round 3, runs 3..4
    args2 = parse_args(argv + ["--resume", "--comm_round", "5"],
                       algo="fedavg")
    out2 = run_experiment(args2, "fedavg")
    rounds2 = [h["round"] for h in out2["history"] if h["round"] >= 0]
    assert rounds2 == [3, 4], f"resume should continue at round 3, got {rounds2}"
    # checkpoint lineage is shared even though r{comm_round} differs
    from neuroimagedisttraining_tpu.experiments.config import run_identity as ri
    assert ri(args, "fedavg", for_checkpoint=True) == \
        ri(args2, "fedavg", for_checkpoint=True)


def test_identity_stable_across_entry_points(tmp_path):
    """Unified --algo CLI and per-algo main must agree on identity, else
    resume/log/stat paths diverge."""
    argv = _argv(tmp_path)
    unified = parse_args(argv + ["--algo", "fedavg"])
    per_algo = parse_args(argv, algo="fedavg")
    assert run_identity(unified, "fedavg") == run_identity(per_algo, "fedavg")
    assert run_identity(unified, "fedavg", for_checkpoint=True) == \
        run_identity(per_algo, "fedavg", for_checkpoint=True)


def test_sequential_runs_no_log_crosstalk(tmp_path):
    """Per-run file handlers are detached after each run."""
    args1 = parse_args(_argv(tmp_path) + ["--tag", "one"], algo="local")
    args2 = parse_args(_argv(tmp_path) + ["--tag", "two"], algo="local")
    out1 = run_experiment(args1, "local")
    out2 = run_experiment(args2, "local")
    log1 = os.path.join(str(tmp_path / "LOG"), out1["identity"] + ".log")
    with open(log1) as f:
        content = f.read()
    assert out2["identity"] not in content, "run 2 wrote into run 1's log"


def test_all_algos_parse(tmp_path):
    for algo in ALGO_NAMES:
        args = parse_args(_argv(tmp_path), algo=algo)
        assert args.comm_round == 2


def test_flops_counter_3d():
    import jax

    from neuroimagedisttraining_tpu.models import create_model, init_params
    from neuroimagedisttraining_tpu.utils.flops import (
        count_communication_params,
        count_params,
        inference_flops,
        per_layer_flops,
    )

    model = create_model("small3dcnn", num_classes=1)
    params = init_params(model, jax.random.PRNGKey(0), (8, 8, 8, 1))
    layers = per_layer_flops(model, params, (8, 8, 8, 1))
    assert layers, "expected conv/dense layers counted"
    dense_total = inference_flops(model, params, (8, 8, 8, 1))
    assert dense_total > 0
    # masking half the weights must reduce counted FLOPs
    mask = jax.tree_util.tree_map(
        lambda x: (jax.random.uniform(jax.random.PRNGKey(1), x.shape) > 0.5
                   ).astype(x.dtype),
        params,
    )
    sparse_total = inference_flops(model, params, (8, 8, 8, 1), mask=mask)
    assert sparse_total < dense_total
    assert count_communication_params(params, mask) < count_params(params)


def test_flops_xla_matches_analytical_order():
    import jax

    from neuroimagedisttraining_tpu.models import (
        create_model,
        init_params,
        make_apply_fn,
    )
    from neuroimagedisttraining_tpu.utils.flops import (
        inference_flops,
        inference_flops_xla,
    )

    model = create_model("small3dcnn", num_classes=1)
    params = init_params(model, jax.random.PRNGKey(0), (8, 8, 8, 1))
    analytical = inference_flops(model, params, (8, 8, 8, 1))
    xla = inference_flops_xla(make_apply_fn(model), params, (8, 8, 8, 1))
    if xla > 0:  # cost model availability varies by backend
        assert xla >= analytical * 0.5  # same order: XLA counts all ops


def test_cost_tracker_accumulates():
    import jax

    from neuroimagedisttraining_tpu.models import create_model, init_params
    from neuroimagedisttraining_tpu.utils.flops import CostTracker

    model = create_model("small3dcnn", num_classes=1)
    params = init_params(model, jax.random.PRNGKey(0), (8, 8, 8, 1))
    tracker = CostTracker(model, (8, 8, 8, 1))
    r1 = tracker.record_round(params, n_clients=4, samples_per_client=8)
    r2 = tracker.record_round(params, n_clients=4, samples_per_client=8)
    assert r2["sum_training_flops"] == pytest.approx(
        2 * r1["training_flops"])
    assert r2["sum_comm_params"] == 2 * r1["comm_params"]


@pytest.mark.slow
def test_cli_abcd_s2d_layout(tmp_path):
    """End-to-end CLI on a real cohort .h5 with the s2d layout: the runner
    must pick the phased-stem model twin and train a round."""
    import numpy as np

    from neuroimagedisttraining_tpu.data.abcd import write_abcd_h5

    rng = np.random.RandomState(0)
    # stem-viable small volume: every dim >= 69 is too slow for CI, so use
    # the small3dcnn path for flat and just exercise s2d data plumbing via
    # the full 3dcnn on a minimum-viable 69^3 volume with 1 round, 1 step
    n = 12
    X = rng.rand(n, 69, 69, 69).astype(np.float32)
    y = rng.randint(0, 2, size=n)
    site = rng.randint(0, 2, size=n)
    path = str(tmp_path / "cohort.h5")
    write_abcd_h5(path, X, y, site)

    args = parse_args(_argv(tmp_path, **{
        "--model": "3dcnn",
        "--dataset": "abcd_site",
        "--data_dir": path,
        "--layout": "s2d",
        "--compute_dtype": "bfloat16",
        "--client_num_in_total": "0",
        "--batch_size": "2",
        "--comm_round": "1",
        "--frequency_of_the_test": "1",
        "--final_finetune": "0",  # layout plumbing under test, not the pass
    }))
    out = run_experiment(args, "fedavg")
    assert len(out["history"]) == 1
    assert np.isfinite(out["history"][0]["train_loss"])


def test_dispfl_cli_variant_flags(tmp_path):
    """--uniform/--different_initial/--save_masks/--record_mask_diff flow
    through the CLI to the algorithm and stat_info."""
    import pickle

    args = parse_args(_argv(tmp_path) + [
        "--uniform", "--different_initial", "--save_masks",
        "--record_mask_diff", "--comm_round", "1"], algo="dispfl")
    out = run_experiment(args, "dispfl")
    with open(out["stat_path"], "rb") as f:
        stat = pickle.load(f)
    assert "final_masks" in stat
    assert stat["mask_distance_matrix"].shape == (4, 4)
    # inert reference-compat flags parse too
    args = parse_args(_argv(tmp_path) + [
        "--strict_avg", "--public_portion", "0.1",
        "--logfile", "custom_run"], algo="dispfl")
    assert args.strict_avg and args.public_portion == 0.1


@pytest.mark.slow
def test_checkpoint_resume_dispfl_preserves_masks(tmp_path):
    """DisPFL state (personal params + evolving masks + rng) must survive
    checkpoint/resume — the reference's DisPFL runs are the ones that died
    at SLURM TIME LIMIT with no resume (DisPFL/error3469448.err)."""
    import jax

    ck = str(tmp_path / "ckpt")
    argv = _argv(tmp_path, **{"--comm_round": 2, "--checkpoint_dir": ck})
    args = parse_args(argv, algo="dispfl")
    out1 = run_experiment(args, "dispfl")
    masks1 = out1["state"].masks

    # resume with NO extra rounds: the restored state must equal the
    # checkpointed one bit-for-bit (a re-initialized mask would have the
    # same shapes/live-counts by construction, so identity is the only
    # assertion that catches a discarded-state bug)
    args_same = parse_args(argv + ["--resume"], algo="dispfl")
    out_same = run_experiment(args_same, "dispfl")
    assert out_same["history"] == []
    for m1, m2 in zip(jax.tree_util.tree_leaves(masks1),
                      jax.tree_util.tree_leaves(out_same["state"].masks)):
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))

    args2 = parse_args(argv + ["--resume", "--comm_round", "3"],
                       algo="dispfl")
    out2 = run_experiment(args2, "dispfl")
    assert [h["round"] for h in out2["history"]] == [2]
    # the resumed run evolved masks FROM the checkpointed ones: densities
    # (live counts) are preserved by fire/regrow
    for m1, m2 in zip(jax.tree_util.tree_leaves(masks1),
                      jax.tree_util.tree_leaves(out2["state"].masks)):
        np.testing.assert_allclose(np.asarray(m1).sum(),
                                   np.asarray(m2).sum())


def test_cost_tracker_sparse_vs_dense_ratio(tmp_path):
    """stat_info cost accounting is mask-aware: a salientgrads run at
    dense_ratio=0.25 reports fewer training FLOPs and comm params than a
    dense fedavg run of the same model/schedule (model_trainer.py:49-53 +
    sailentgrads_api.py:137-138 semantics)."""
    # --final_finetune 0 so both runs count exactly 2 rounds x 4 clients
    dense_args = parse_args(
        _argv(tmp_path, **{"--final_finetune": 0}), algo="fedavg")
    sparse_args = parse_args(
        _argv(tmp_path, algo="salientgrads", **{"--dense_ratio": 0.25}),
        algo="salientgrads")
    dense = run_experiment(dense_args, "fedavg")
    sparse = run_experiment(sparse_args, "salientgrads")

    def totals(out):
        import pickle as pkl
        with open(out["stat_path"], "rb") as f:
            s = pkl.load(f)
        return s["sum_training_flops"], s["sum_comm_params"]

    fd, cd = totals(dense)
    fs, cs = totals(sparse)
    assert fs < fd  # masked kernels skip FLOPs
    assert cs < cd  # only nonzero params ship
    # comm ratio tracks overall nonzero density: strictly below dense,
    # above the kernel-only dense_ratio since biases/norm params stay dense
    assert 0.2 < cs / cd < 0.9



def test_avg_inference_flops_per_client_masks(tmp_path):
    """record_avg_inference_flops (sailentgrads_api.py:319-332): with
    per-client masks at mixed densities (--diff_spa), the recorded value
    is the cohort MEAN, not client 0's count."""
    import pickle as pkl

    args = parse_args(_argv(tmp_path) + ["--diff_spa", "--comm_round", "1"],
                      algo="dispfl")
    out = run_experiment(args, "dispfl")
    with open(out["stat_path"], "rb") as f:
        stat = pkl.load(f)
    avg = stat["avg_inference_flops"]
    assert avg > 0 and np.isfinite(avg)
    # the cohort mean must differ from any single client's count: diff_spa
    # cycles densities, so client 0 (lowest) and the last client (highest)
    # bracket the mean strictly
    import jax

    from neuroimagedisttraining_tpu.utils.flops import (
        inference_flops,
    )

    state = out["state"]

    from neuroimagedisttraining_tpu.models import create_model

    model = create_model("small3dcnn", num_classes=1)

    def client_count(c):
        params = jax.tree_util.tree_map(lambda l: l[c],
                                        state.personal_params)
        mask = jax.tree_util.tree_map(lambda l: l[c], state.masks)
        return inference_flops(model, params, (8, 8, 8, 1), mask=mask)

    lo = client_count(0)
    hi = client_count(3)
    assert lo < avg < hi, (lo, avg, hi)


def test_non_sgd_optimizer_rejected(tmp_path):
    """--client_optimizer adam: the reference implements only SGD (anything
    else crashes there with an undefined optimizer); fail with a message
    instead of silently training with SGD."""
    args = parse_args(_argv(tmp_path) + ["--client_optimizer", "adam"],
                      algo="fedavg")
    with pytest.raises(SystemExit):
        run_experiment(args, "fedavg")
