"""The ``tokens_selected`` family and its one configuration
(``keye_vl2_fed``): the manifest loads the cell, the configuration's file says
what the program's own constant and cut say, the reference's layer table adds
up to the model and to hand counts, and a tiny configuration of the family
runs a whole traced cell on the CPU against the plain reference, then with
four planted faults."""
import json
import os
import time

import pytest
from conftest import BENCH, MANIFEST, write_manifest
from test_reduce_trace import hand_built_trace

from benchmarks.families import tokens_selected as family
from benchmarks.lib import flops, harness, manifest, peaks, reduce_trace
from benchmarks.reference import keye_vl2

CELL = "keye_vl2_fed.longctx"
SEQ, TOPK = 32, 8


@pytest.fixture
def tiny_manifest(tmp_path):
    from neuroimagedisttraining_tpu.models import decoder

    held = decoder.held_config("keye_tiny", decoder.Share(4, 4, 2, 0, 4))
    config = {
        "name": "tiny_selected", "source": "test fixture",
        "family": "tokens_selected", "reference": "keye_vl2",
        "published": held.pop("published"),
        "first_expert": held.pop("first_expert"),
        "held": {k: held.pop(k) for k in family.HELD_KEYS},
        "flags": {"algo": "fedavg", "model": "keye_tiny", "lm_layers": 4,
                  "lm_expert_shards": 4, "lm_tensor_shards": 2,
                  "lm_vocab_shards": 4, "dataset": "token_shards",
                  "track_personal": 0, "client_chunk": 1, "batch_size": 1,
                  "epochs": 1, "lr": 0.5, "momentum": 0.0, "grad_clip": 10.0},
        "cohort": {"n_sites": 8, "train_per_site": 1, "test_per_site": 1,
                   "sequence_length": SEQ},
        **held}
    return write_manifest(tmp_path, config, (("longctx", 1),))


def test_manifest_loads_the_new_cell():
    cell = manifest.load_cell(MANIFEST, CELL)
    assert cell.chips == 1 and cell.family is family
    assert cell.cohort == {"n_sites": 8, "train_per_site": 1,
                           "test_per_site": 1, "sequence_length": 16384}
    assert cell.traffic["block_rounds"] == 2
    names = {e["name"] for e, _ in cell.per_layer}
    new = {"attention_indexer_ms_per_round", "attention_select_ms_per_round",
           "attention_selected_ms_per_round", "selected_attention_roofline",
           "selected_key_share"}
    shared = {"router_ms_per_round", "experts_ms_per_round",
              "experts_roofline", "lm_head_ms_per_round",
              "embed_ms_per_round", "expert_load_max_over_mean"}
    assert new | shared <= names
    # on the chip XLA fuses the slice of a site's one sequence into its
    # consumers: no instruction is left under cohort_gather (my chip run,
    # PR 33), so the cell is not on that metric's list
    assert "cohort_gather_ms_per_round" not in names
    # what this model has no layer for is not asked of it
    assert not names & {
        "attention_full_ms_per_round", "attention_window_ms_per_round",
        "attention_roofline", "dense_mlp_ms_per_round",
        "shared_expert_ms_per_round", "batch_gather_ms_per_round",
        "personal_update_ms_per_round"}
    assert not {n for n in names if n.startswith("stem_")}
    argv = harness.program_flags(cell, 7)
    assert argv[argv.index("--model") + 1] == "keye_vl2"
    assert argv[argv.index("--lm_vocab_shards") + 1] == "8"
    # and no accepted cell asks for the new metrics
    for other in ("laguna_s21_fed.train", "alexnet3d_abcd.train"):
        assert not new & {e["name"] for e, _ in
                          manifest.load_cell(MANIFEST, other).per_layer}


def test_configuration_file_is_the_programs_constant_and_cut():
    """Every published key under its own name as ``CONFIGS["keye_vl2"]`` has
    it; the held counts as the program's ``held_config`` cuts them for the
    cell's four share flags; no width among the cut keys."""
    from neuroimagedisttraining_tpu.models import decoder

    with open(os.path.join(BENCH, "configs", "keye_vl2_fed.json")) as f:
        doc = json.load(f)
    flags = doc["flags"]
    held = decoder.held_config("keye_vl2", decoder.Share(
        flags["lm_layers"], flags["lm_expert_shards"],
        flags["lm_tensor_shards"], 0, flags["lm_vocab_shards"]))
    merged = family.model_config(doc)
    published = decoder.CONFIGS["keye_vl2"]
    assert set(published) <= set(merged)
    for key, value in published.items():
        assert merged[key] == held[key], key
        if key in family.HELD_KEYS:
            assert key in doc["reduced"] and key not in doc, key
            assert doc["published"][key] == value, key
        else:
            assert doc[key] == value, key
    assert doc["held"] == {
        "num_hidden_layers": 4, "num_experts": 16, "num_local_experts": 16,
        "num_attention_heads": 8, "num_key_value_heads": 1,
        "vocab_size": 18992}
    assert doc["first_expert"] == held["first_expert"] == 0
    assert doc["published"] == held["published"]
    assert not [k for k in family.HELD_KEYS - {"vocab_size"}
                if k.endswith(("_size", "_dim", "_rank"))]
    with open(MANIFEST) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}["keye_vl2_fed"]
    assert set(entry["reduced"]) == set(doc["reduced"])
    assert entry["source"] == doc["source"]
    with pytest.raises(ValueError, match="'held' states"):
        family.model_config({**doc, "held": {"num_experts": 16}})


def test_layer_table_adds_up_to_the_model_and_the_hand_counts():
    cell = manifest.load_cell(MANIFEST, CELL)
    rows = {r["name"]: r for r in family.layers(keye_vl2, cell.config)}
    assert sum(r["params"] for r in rows.values()) == 408_768_000
    assert rows["attention_indexer"]["params"] == 4 * 2_261_120
    assert rows["attention_proj"]["params"] == 4 * (4_718_592 + 256)
    assert rows["experts"]["params"] == 4 * 75_497_472
    seq = 16384
    square = seq * (seq + 1) // 2
    pairs = 2048 * 2049 // 2 + 14336 * 2048
    assert keye_vl2.selected_pairs(seq, 2048) == pairs
    assert abs(pairs / square - 0.2344) < 1e-4
    assert rows["attention_selected"]["forward"]["flops"] \
        == 4 * 2 * 2 * pairs * 128 * 8
    assert rows["attention_indexer"]["forward"]["flops"] == 4 * (
        2 * seq * 2_261_120 + 2 * square * 16 * 64)
    assert rows["attention_select"]["forward"] == {
        "flops": 0.0, "elements": 4 * 2 * square, "step_elements": 0}
    # nothing of the indexer or the choice is differentiated
    for name in ("attention_indexer", "attention_select"):
        assert rows[name]["backward"] == {"flops": 0.0, "elements": 0,
                                          "step_elements": 0}
    assert rows["attention_selected"]["backward"]["flops"] \
        == 2 * rows["attention_selected"]["forward"]["flops"]
    assert rows["experts"]["forward"]["flops"] \
        == 2.0 * (seq * 8 * 16 / 128) * 4 * 3 * 2048 * 768
    # a small size by hand: 2 layers, 12 tokens, top-4 of an 8-head indexer
    small = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
             "num_key_value_heads": 1, "vocab_size": 10, "num_experts": 2,
             "published": {"num_experts": 4}, "num_experts_per_tok": 2,
             "num_hidden_layers": 2, "moe_intermediate_size": 6,
             "sa_config": {"indexer_num_heads": 8, "indexer_head_dim": 2,
                           "topk": 4}}
    table = {r["name"]: r for r in keye_vl2.layers(small, 12)}
    assert keye_vl2.selected_pairs(12, 4) == 10 + 8 * 4 == 42
    assert keye_vl2.selected_pairs(3, 4) == 6
    assert table["attention_selected"]["forward"]["flops"] \
        == 2 * 2 * 2 * 42 * 4 * 2
    index = 8 * (16 + 2 + 8) + 4
    assert table["attention_indexer"]["params"] == 2 * index
    assert table["attention_indexer"]["forward"]["flops"] == 2 * (
        2 * 12 * index + 2 * 78 * 8 * 2)
    assert table["attention_select"]["forward"]["elements"] == 2 * 2 * 78
    assert table["attention_proj"]["params"] == 2 * (8 * (16 + 8) + 8)
    step = flops.train_flops_per_sample(list(rows.values()))
    assert 9e12 < step < 12e12
    floor_s, parts = flops.step_floor(list(rows.values()), 1, 2,
                                      peaks.PEAKS["TPU v5 lite"])
    assert 0.04 < floor_s < 0.2 and len(parts) == 2 * len(rows)


def test_tiny_configuration_runs_a_traced_cell(tiny_manifest, tmp_path,
                                               monkeypatch):
    """``run_cell`` whole on the CPU with a tiny configuration of the family
    (float32 on both sides here: every error is rounding)."""
    monkeypatch.setattr(reduce_trace, "load",
                        lambda trace_dir, devices, rounds, op_names:
                        hand_built_trace(rounds))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    result, details = harness.run_cell(
        tiny_manifest, "tiny_selected.longctx", seed=2147484005,
        seconds=0.5, trace=True, t0=time.perf_counter(),
        trace_dir=str(tmp_path / "trace"))
    assert result["failed"] == 0 and result["attempted"] >= 2
    check = details["reference_check"]
    assert check["ok"], check
    assert set(family.TOLERANCE) <= set(check)
    assert check["routing"]["error"] == 0.0 == check["selection"]["error"]
    assert check["logits"]["error"] < 1e-4
    assert max(check[n]["error"] for n in keye_vl2.GRAD_LEAVES) < 1e-3
    assert all(check[n]["error"] == 0.0 for n in keye_vl2.INDEXER_LEAVES)
    assert check["agreeing_positions"] == check["compared_positions"]
    assert result["correct"] is True, details["state_check"]
    assert set(family.TOLERANCE) <= set(result["compared"])
    # the gauges the program set are what the readers read
    want = keye_vl2.selected_pairs(SEQ, TOPK) / (SEQ * (SEQ + 1) / 2)
    assert result["metrics"]["selected_key_share"]["value"] \
        == check["expert_load"]["selected_key_share"] \
        == pytest.approx(want, abs=1e-6)
    assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    assert result["metrics"]["train_mfu"]["value"] > 0


@pytest.mark.parametrize("fault", [
    "state_unchanged", "half_the_clients", "full_attention_in_its_place",
    "half_the_topk"])
def test_check_catches_planted_faults(tiny_manifest, fault, monkeypatch):
    """A round that leaves the state as it was and one that folds half the
    clients are not correct by the round's leaves (and the indexer still
    reads 0: nothing moved it); full attention in the selection's place and
    a selection of half ``topk`` are not correct by ``selection`` AND by the
    round's leaves: the program is built anew with the fault in it, so the
    compiled round (the selection and the attention's output kept across
    the block's remat) trains on the wrong keys too."""
    import jax
    import jax.numpy as jnp
    from control_selected import every_visible_key

    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.models import decoder

    cell = manifest.load_cell(tiny_manifest, "tiny_selected.longctx")
    algo = harness.build(cell, parse_args(harness.program_flags(cell, 3)), 3)
    state = algo.init_state(jax.random.PRNGKey(3))
    ref, real = harness.reference_of(cell), algo._round_jit
    sound = family.reference_check(algo, state.global_params, ref,
                                   cell.config)
    assert sound["ok"], sound
    leaves, frozen = list(keye_vl2.GRAD_LEAVES), list(keye_vl2.INDEXER_LEAVES)
    assert max(sound[n]["error"] for n in leaves) < 1e-4

    def faulty(st, sel, round_idx, x, y, n):
        if fault == "state_unchanged":
            return st, jnp.float32(sound["round_loss"]["error"])
        return real(st, sel, round_idx, x, y,       # the later half: nothing
                    n.at[sel[len(sel) // 2:]].set(0))

    if fault in ("state_unchanged", "half_the_clients"):
        algo._round_jit = faulty
    else:
        choose = decoder.select_keys
        monkeypatch.setattr(decoder, "select_keys", {
            "full_attention_in_its_place": every_visible_key(choose),
            "half_the_topk":
                lambda scores, start, topk: choose(scores, start, topk // 2),
        }[fault])
        # the sound check compiled and cached the sound round: a program
        # built after the fault, nothing traced before it
        jax.clear_caches()
        algo = harness.build(
            cell, parse_args(harness.program_flags(cell, 3)), 3)
    report = family.reference_check(algo, state.global_params, ref,
                                    cell.config)
    assert not report["ok"]
    assert all(report[n]["ok"] for n in frozen), {n: report[n] for n in frozen}
    if fault in ("state_unchanged", "half_the_clients"):
        assert all(report[n]["ok"] for n in ("loss", "logits", "routing",
                                             "selection"))
        # an unchanged state fails by every leaf; half the clients by the
        # dense ones at least (the held experts' and the router's limits
        # stand wide: their readings swing on the chip)
        dense = ("q_proj", "o_proj", "q_norm", "lm_head", "embed")
        failing = leaves if fault == "state_unchanged" else dense
        assert not any(report[n]["ok"] for n in failing), report
    if fault == "state_unchanged":
        assert all(abs(report[n]["error"] - 1.0) < 1e-5 for n in leaves)
    if fault == "half_the_clients":
        control = sound["round_controls"]["half_the_clients"]
        for n in leaves:    # what the report says such a fold would read
            assert abs(report[n]["error"] - control[n]) < 1e-3, n
    if fault == "full_attention_in_its_place":
        # a query at t keeps t + 1 keys where the reference keeps 8
        want = 1 - sum(min(1.0, TOPK / (t + 1)) for t in range(SEQ)) / SEQ
        assert report["selection"]["error"] == pytest.approx(want, abs=1e-5)
        assert not report["selection"]["ok"]
    if fault in ("full_attention_in_its_place", "half_the_topk"):
        # the compiled round attended the wrong keys: the leaves the
        # attention's gradient reaches first are not the reference's
        assert not report["q_proj"]["ok"] and not report["o_proj"]["ok"], \
            {n: report[n] for n in leaves}
    if fault == "half_the_topk":
        # the first layer keeps 4 of the reference's 8 (its input is the
        # reference's); later layers choose on other hidden states
        assert report["selection"]["error"] > 0.3
        assert not report["selection"]["ok"]


@pytest.mark.parametrize("control", ["e4m3", "full_attention"])
def test_controls_read_not_correct(tiny_manifest, control, monkeypatch):
    """The two controls of ``control_selected.py`` at the tiny size: the
    reference with its matrices rounded to e4m3, and with full attention in
    the selection's place, each in the place of the program's forward pass:
    not correct, the second by ``selection`` (the closed form) while its
    routing, sown as the program sows it, is read as the program's is. The
    second is planted in the program too, as ``main`` plants it: its compiled
    round is not correct by ``q_proj`` and ``o_proj``; under ``e4m3`` the
    round is the program's own and reads as the program does."""
    import jax
    from control_selected import every_visible_key, stand_in

    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.models import decoder

    if control == "full_attention":
        monkeypatch.setattr(decoder, "select_keys",
                            every_visible_key(decoder.select_keys))
        jax.clear_caches()
    cell = manifest.load_cell(tiny_manifest, "tiny_selected.longctx")
    algo = harness.build(cell, parse_args(harness.program_flags(cell, 3)), 3)
    state = algo.init_state(jax.random.PRNGKey(3))
    ref = harness.reference_of(cell)
    algo.apply_fn = stand_in(ref, family.model_config(cell.config), control)
    report = family.reference_check(algo, state.global_params, ref,
                                    cell.config)
    assert not report["ok"]
    failed = {n for n in ("loss", "logits", "routing", "selection")
              if not report[n]["ok"]}
    assert failed
    round_failed = {n for n in keye_vl2.GRAD_LEAVES if not report[n]["ok"]}
    if control == "full_attention":
        want = 1 - sum(min(1.0, TOPK / (t + 1)) for t in range(SEQ)) / SEQ
        assert report["selection"]["error"] == pytest.approx(want, abs=1e-5)
        assert "selection" in failed
        assert {"q_proj", "o_proj"} <= round_failed, report
    else:
        assert not round_failed, report
    assert all(report[n]["ok"] for n in keye_vl2.INDEXER_LEAVES)


def test_e4m3_in_float32_arithmetic_is_the_casts_rounding():
    """The control's rounding against the cast to ``float8_e4m3fn`` (which
    the CPU performs): normal weights of the model's scale, the subnormal
    range, ties, powers of two, the largest value."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from control_selected import to_e4m3

    a = jnp.concatenate([
        0.02 * jax.random.normal(jax.random.PRNGKey(0), (4096,)),
        jax.random.normal(jax.random.PRNGKey(1), (1024,)) * 100.0,
        jnp.asarray([0.0, 2.0 ** -9, 2.0 ** -10, 3 * 2.0 ** -10, 2.0 ** -6,
                     1.0, 1.0625, 1.1875, 448.0, 440.0, 1e-5, -0.017])])
    want = np.asarray(a.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(jax.jit(to_e4m3)(a)), want)
    assert np.mean(want[:4096] != np.asarray(a[:4096])) > 0.99
