"""The in-state incremental personal eval (``--eval_cache`` — ISSUE 9).

The cache moves the per-client (correct, loss_sum, total) eval terms
into algorithm state: the round body refreshes only the trained
clients' rows (O(clients_per_round) forwards, pinned here by counting
the traced eval width), evals re-reduce the [C] cache with ZERO
forwards, the cache rides the fused scan carry bit-identically, it
checkpoints/resumes, and guard-quarantined rounds can never leave a
poisoned row behind. Accuracies are bit-equal to the full O(C) eval
(integer counts over identical params); losses agree to f32 round-off
(the subset-width reassociation tolerance every eval parity gate in
this repo uses)."""
import jax
import numpy as np
import pytest

from neuroimagedisttraining_tpu.algorithms import FedAvg, SalientGrads
from neuroimagedisttraining_tpu.core.state import HyperParams
from neuroimagedisttraining_tpu.data import make_synthetic_federated
from neuroimagedisttraining_tpu.models import create_model


def _data(n_clients=8):
    return make_synthetic_federated(
        n_clients=n_clients, samples_per_client=8, test_per_client=4,
        sample_shape=(8, 8, 8, 1),
    )


def _hp():
    return HyperParams(lr=0.05, lr_decay=0.998, momentum=0.9,
                       local_epochs=1, steps_per_epoch=1, batch_size=4)


def _mk(cls, frac=0.25, seed=0, **kw):
    return cls(create_model("small3dcnn", num_classes=1), _data(),
               _hp(), loss_type="bce", frac=frac, seed=seed,
               donate_state=False, eval_cache=True, **kw)


def _loss_close(a, b):
    return abs(a - b) <= 4e-7 * max(1.0, abs(b))


def test_per_round_forwards_are_o_clients_per_round():
    """The acceptance pin: at frac<1, the ONLY per-round personal-eval
    compute is the in-graph row refresh — traced at width
    clients_per_round, not C — and evaluate() runs ZERO forwards (the
    full-eval path is never invoked after the init seeding)."""
    algo = _mk(FedAvg, frac=0.25)  # S=2 of C=8
    widths = []
    orig_rows = algo._eval_cache_rows

    def counting_rows(p, x, y, n):
        widths.append(jax.tree_util.tree_leaves(x)[0].shape[0])
        return orig_rows(p, x, y, n)

    algo._eval_cache_rows = counting_rows
    full_evals = []
    orig_full = algo._eval_personal
    algo._eval_personal = (
        lambda *a, **k: full_evals.append(1) or orig_full(*a, **k))

    state = algo.init_state(jax.random.PRNGKey(0))
    assert full_evals == [1]  # the one-time O(C) seeding pass
    evs = []
    for r in range(4):
        state, _ = algo.run_round(state, r)
        evs.append(algo.evaluate(state))
    # the row refresh traced ONCE at exactly S (every round replays the
    # compiled program: S forwards/round), and no full eval ran
    assert widths == [algo.clients_per_round] == [2]
    assert full_evals == [1]
    # and the metrics are bit-equal (acc) / ulp-equal (loss) to a full
    # O(C) eval of the same states
    d = algo.data
    full = orig_full(state.personal_params, d.x_test, d.y_test,
                     d.n_test)
    assert float(evs[-1]["personal_acc"]) == float(full["acc"])
    assert _loss_close(float(evs[-1]["personal_loss"]),
                       float(full["loss"]))


@pytest.mark.parametrize("cls,kw", [
    (FedAvg, {}),
    (SalientGrads, dict(dense_ratio=0.5, itersnip_iterations=1)),
])
def test_cached_metrics_bit_equal_full_eval(cls, kw):
    algo = _mk(cls, frac=0.25, **kw)
    state = algo.init_state(jax.random.PRNGKey(0))
    for r in range(3):
        state, _ = algo.run_round(state, r)
        ev = algo.evaluate(state)
        full = algo._eval_personal(
            state.personal_params, algo.data.x_test, algo.data.y_test,
            algo.data.n_test)
        assert float(ev["personal_acc"]) == float(full["acc"]), r
        # per client, from the cache's own rows: the integer counts the
        # accuracies divide. (``ev["acc_per_client"]`` is the GLOBAL
        # model's per-site accuracy, ``_eval_impl``: another quantity.)
        for k in ("correct", "total"):
            np.testing.assert_array_equal(
                np.asarray(state.eval_cache[k]), np.asarray(full[k]), k)
        assert _loss_close(float(ev["personal_loss"]),
                           float(full["loss"])), r


def test_fused_carry_matches_unfused_with_cache():
    """The cache rides the fused scan carry: fused and unfused runs
    produce bit-identical cache contents and per-round eval series."""
    algo = _mk(SalientGrads, frac=0.5, seed=1, dense_ratio=0.5,
               itersnip_iterations=1)
    s0 = algo.init_state(jax.random.PRNGKey(1))
    s_u = algo.clone_state(s0)
    pers, glob = [], []
    for r in range(4):
        s_u, _ = algo.run_round(s_u, r)
        ev = algo.evaluate(s_u)
        pers.append(float(ev["personal_acc"]))
        glob.append(float(ev["global_acc"]))
    s_f, ys = algo.run_rounds_fused(s0, 0, 4, eval_every=1)
    np.testing.assert_array_equal(
        np.asarray(ys["eval"]["personal_acc"]), pers)
    np.testing.assert_array_equal(
        np.asarray(ys["eval"]["global_acc"]), glob)
    for k in ("correct", "loss_sum", "total"):
        np.testing.assert_array_equal(
            np.asarray(s_u.eval_cache[k]), np.asarray(s_f.eval_cache[k]))


def test_quarantined_round_leaves_no_poisoned_row():
    """NaN-faulted clients are quarantined by the guard; their personal
    rows keep the previous models, so the refreshed cache rows
    reproduce the previous values — the cached metrics stay finite and
    bit-equal to a full eval of the (guarded) state."""
    algo = _mk(FedAvg, frac=0.5, fault_spec="nan=0.5", guard=True)
    state = algo.init_state(jax.random.PRNGKey(0))
    quarantined = 0.0
    for r in range(3):
        state, rec = algo.run_round(state, r)
        quarantined += float(rec["clients_quarantined"])
        ev = algo.evaluate(state)
        assert np.isfinite(float(ev["personal_loss"])), r
        full = algo._eval_personal(
            state.personal_params, algo.data.x_test, algo.data.y_test,
            algo.data.n_test)
        assert float(ev["personal_acc"]) == float(full["acc"]), r
    assert quarantined > 0  # the fault really fired
    for k in ("correct", "loss_sum", "total"):
        assert np.all(np.isfinite(np.asarray(state.eval_cache[k]))), k


def test_cache_checkpoints_and_resumes(tmp_path):
    """Resume: the cache restores with the state and the continued run
    is bit-identical to an uninterrupted one — no reseeding, no stale
    rows."""
    from neuroimagedisttraining_tpu.utils.checkpoint import (
        CheckpointManager,
    )

    algo = _mk(FedAvg, frac=0.5, seed=2)
    s = algo.init_state(jax.random.PRNGKey(2))
    for r in range(2):
        s, _ = algo.run_round(s, r)
    mgr = CheckpointManager(str(tmp_path), "evcache")
    mgr.save(2, s)
    s_ref = s
    for r in range(2, 4):
        s_ref, _ = algo.run_round(s_ref, r)
    ev_ref = algo.evaluate(s_ref)
    restored, step = mgr.restore_latest(
        algo.init_state(jax.random.PRNGKey(2)))
    mgr.close()
    assert step == 2
    s_res = restored
    for r in range(2, 4):
        s_res, _ = algo.run_round(s_res, r)
    ev_res = algo.evaluate(s_res)
    assert float(ev_res["personal_acc"]) == float(ev_ref["personal_acc"])
    assert float(ev_res["personal_loss"]) == float(
        ev_ref["personal_loss"])
    for k in ("correct", "loss_sum", "total"):
        np.testing.assert_array_equal(
            np.asarray(s_res.eval_cache[k]),
            np.asarray(s_ref.eval_cache[k]))


def test_finalize_invalidates_and_fresh_state_seeds():
    """FedAvg's final fine-tune retrains EVERY personal row: finalize
    drops the stale cache (eval falls back to the full pass and stays
    correct); a fresh init_state seeds the cache from a full eval."""
    algo = _mk(FedAvg, frac=0.5)
    state = algo.init_state(jax.random.PRNGKey(0))
    # fresh-state seeding == a direct full eval of the fresh stack
    full0 = algo._eval_personal(
        state.personal_params, algo.data.x_test, algo.data.y_test,
        algo.data.n_test)
    np.testing.assert_array_equal(
        np.asarray(state.eval_cache["correct"]),
        np.asarray(full0["correct"]))
    state, _ = algo.run_round(state, 0)
    state, rec = algo.finalize(state)
    assert state.eval_cache is None and rec is not None
    full = algo._eval_personal(
        state.personal_params, algo.data.x_test, algo.data.y_test,
        algo.data.n_test)
    assert float(rec["personal_acc"]) == float(full["acc"])


def test_identity_splits_and_refusals(tmp_path):
    """'evcache' splits BOTH identities (state-structure change — the
    r5/topk rule); unsupported combinations are refused at the right
    layer."""
    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.experiments.config import (
        run_identity,
    )
    from neuroimagedisttraining_tpu.experiments.runner import (
        run_experiment,
    )

    base = ["--model", "small3dcnn", "--dataset", "synthetic",
            "--client_num_in_total", "4", "--comm_round", "1",
            "--results_dir", "", "--log_dir", str(tmp_path / "LOG")]
    args = parse_args(base + ["--eval_cache", "1"], algo="fedavg")
    assert "evcache" in run_identity(args, "fedavg")
    assert "evcache" in run_identity(args, "fedavg",
                                     for_checkpoint=True)
    off = parse_args(base, algo="fedavg")
    assert "evcache" not in run_identity(off, "fedavg")
    # non-consuming algorithm: no split, and the runner refuses it
    assert "evcache" not in run_identity(
        parse_args(base + ["--eval_cache", "1"], algo="local"), "local")
    with pytest.raises(SystemExit, match="eval_cache"):
        run_experiment(parse_args(
            base + ["--eval_cache", "1"], algo="local"), "local")
    with pytest.raises(SystemExit, match="track_personal"):
        run_experiment(parse_args(
            base + ["--eval_cache", "1", "--track_personal", "0"],
            algo="fedavg"), "fedavg")
    with pytest.raises(SystemExit, match="eval_clients"):
        run_experiment(parse_args(
            base + ["--eval_cache", "1", "--eval_clients", "2"],
            algo="fedavg"), "fedavg")
    # constructor-level contracts (library users)
    with pytest.raises(ValueError, match="personal"):
        _mk(FedAvg, track_personal=False)
    with pytest.raises(ValueError, match="eval_clients|subset"):
        _mk(FedAvg, eval_clients=2)


def test_runner_eval_cache_matches_plain_run(tmp_path):
    """End-to-end CLI A/B: --eval_cache 1 reproduces the plain run's
    eval series (acc bitwise, loss to f32 round-off) through both the
    unfused and fused drivers."""
    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_experiment,
    )

    def argv(tag, *extra):
        return ["--model", "small3dcnn", "--dataset", "synthetic",
                "--client_num_in_total", "4", "--batch_size", "8",
                "--epochs", "1", "--comm_round", "4", "--lr", "0.05",
                "--frac", "0.5", "--frequency_of_the_test", "1",
                "--results_dir", "",
                "--log_dir", str(tmp_path / f"LOG{tag}"),
                *extra]

    ref = run_experiment(parse_args(argv("ref"), algo="fedavg"),
                         "fedavg")
    # the fused driver leg: the fused-carry cache parity is pinned
    # bitwise at library level (test_fused_carry_matches_unfused_
    # with_cache); one fused CLI run covers the runner wiring
    ec = run_experiment(parse_args(
        argv("ec", "--eval_cache", "1", "--fuse_rounds", "2"),
        algo="fedavg"), "fedavg")
    h_ref = [h for h in ref["history"] if h["round"] >= 0]
    h = [x for x in ec["history"] if x["round"] >= 0]
    assert len(h) == len(h_ref) == 4
    for a, b in zip(h_ref, h):
        assert float(a["train_loss"]) == float(b["train_loss"])
        assert float(a["personal_acc"]) == float(b["personal_acc"])
        assert _loss_close(float(b["personal_loss"]),
                           float(a["personal_loss"]))
