"""The ``tokens_shortconv`` family and its one configuration
(``lfm2_8b_a1b_fed``): the manifest loads the cell, the configuration's file
says what the program's own constant and cut say, the reference's layer table
adds up to the model and to hand counts, and a tiny configuration of the
family runs a whole traced cell on the CPU against the plain reference, then
with four planted faults and under the three controls."""
import json
import os
import time

import numpy as np
import pytest
from conftest import BENCH, MANIFEST, write_manifest
from test_reduce_trace import hand_built_trace

from benchmarks.families import tokens_shortconv as family
from benchmarks.lib import flops, harness, manifest, peaks, reduce_trace
from benchmarks.reference import lfm2_moe

CELL = "lfm2_8b_a1b_fed.longctx"
SEQ = 32
CONV_LEAVES = ("conv_in_proj", "conv_taps", "conv_out_proj")


@pytest.fixture
def tiny_manifest(tmp_path):
    from neuroimagedisttraining_tpu.models import decoder

    held = decoder.held_config("lfm2_tiny", decoder.Share(6, 4, 2))
    config = {
        "name": "tiny_shortconv", "source": "test fixture",
        "family": "tokens_shortconv", "reference": "lfm2_moe",
        "published": held.pop("published"),
        "first_expert": held.pop("first_expert"),
        "held": {k: held.pop(k) for k in family.HELD_KEYS},
        "flags": {"algo": "fedavg", "model": "lfm2_tiny", "lm_layers": 6,
                  "lm_expert_shards": 4, "lm_tensor_shards": 2,
                  "dataset": "token_shards", "track_personal": 0,
                  "client_chunk": 1, "batch_size": 1, "epochs": 1, "lr": 0.5,
                  "momentum": 0.0, "grad_clip": 10.0},
        "cohort": {"n_sites": 8, "train_per_site": 1, "test_per_site": 1,
                   "sequence_length": SEQ},
        **held}
    assert set(held) <= family.CONFIG_KEYS
    return write_manifest(tmp_path, config, (("longctx", 1),))


def _built(path, seed=3):
    import jax

    from neuroimagedisttraining_tpu.experiments import parse_args

    cell = manifest.load_cell(path, "tiny_shortconv.longctx")
    algo = harness.build(
        cell, parse_args(harness.program_flags(cell, seed)), seed)
    state = algo.init_state(jax.random.PRNGKey(seed))
    return cell, algo, state, harness.reference_of(cell)


def test_manifest_loads_the_new_cell():
    cell = manifest.load_cell(MANIFEST, CELL)
    assert cell.chips == 1 and cell.family is family
    assert cell.cohort == {"n_sites": 8, "train_per_site": 1,
                           "test_per_site": 1, "sequence_length": 16384}
    assert cell.traffic["block_rounds"] == 2
    assert cell.traffic["flags"] == {"frac": 0.25,
                                     "frequency_of_the_test": 0}
    names = {e["name"] for e, _ in cell.per_layer}
    new = {"short_conv_ms_per_round", "short_conv_roofline",
           "expert_bias_swap_share"}
    shared = {"attention_full_ms_per_round", "attention_roofline",
              "router_ms_per_round", "experts_ms_per_round",
              "experts_roofline", "lm_head_ms_per_round",
              "dense_mlp_ms_per_round", "embed_ms_per_round",
              "expert_load_max_over_mean"}
    assert new | shared <= names
    # what this model has no layer for is not asked of it
    assert not names & {
        "attention_window_ms_per_round", "shared_expert_ms_per_round",
        "attention_indexer_ms_per_round", "attention_select_ms_per_round",
        "attention_selected_ms_per_round", "selected_attention_roofline",
        "selected_key_share", "cohort_gather_ms_per_round",
        "batch_gather_ms_per_round", "personal_update_ms_per_round"}
    assert not {n for n in names if n.startswith("stem_")}
    argv = harness.program_flags(cell, 7)
    assert argv[argv.index("--model") + 1] == "lfm2_8b_a1b"
    assert argv[argv.index("--lm_tensor_shards") + 1] == "4"
    assert "--lm_vocab_shards" not in argv
    # and no accepted cell asks for the new metrics
    for other in ("laguna_s21_fed.train", "keye_vl2_fed.longctx",
                  "alexnet3d_abcd.train"):
        assert not new & {e["name"] for e, _ in
                          manifest.load_cell(MANIFEST, other).per_layer}
    with open(MANIFEST) as f:
        cells = json.load(f)["workloads"]
    assert len(cells) == 7 and sum(w["chips"] == 4 for w in cells) == 1
    assert [w["name"] for w in cells
            if w["config"] == "lfm2_8b_a1b_fed"] == [CELL]


def test_configuration_file_is_the_programs_constant_and_cut():
    """Every published key under its own name as ``CONFIGS["lfm2_8b_a1b"]``
    has it; the held counts as the program's ``held_config`` cuts them for
    the cell's share flags; no width among the cut keys."""
    from neuroimagedisttraining_tpu.models import decoder

    with open(os.path.join(BENCH, "configs", "lfm2_8b_a1b_fed.json")) as f:
        doc = json.load(f)
    flags = doc["flags"]
    held = decoder.held_config("lfm2_8b_a1b", decoder.Share(
        flags["lm_layers"], flags["lm_expert_shards"],
        flags["lm_tensor_shards"]))
    merged = family.model_config(doc)
    published = decoder.CONFIGS["lfm2_8b_a1b"]
    assert set(published) <= set(merged)
    for key, value in published.items():
        assert merged[key] == held[key], key
        if key in family.HELD_KEYS:
            assert key in doc["reduced"] and key not in doc, key
            assert doc["published"][key] == value, key
        else:
            assert doc[key] == value, key
    assert doc["held"] == {
        "num_hidden_layers": 6, "num_experts": 8, "num_attention_heads": 8,
        "num_key_value_heads": 2, "vocab_size": 16384, "conv_channels": 512,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv"]}
    assert doc["held"]["conv_channels"] == held["conv_channels"]
    assert doc["first_expert"] == held["first_expert"] == 0
    assert {k: doc["published"][k] for k in held["published"]} \
        == held["published"]
    assert doc["published"]["layer_types"] == published["layer_types"]
    assert not [k for k in family.HELD_KEYS - {"vocab_size"}
                if k.endswith(("_size", "_dim", "_rank"))]
    assert set(doc["assumed"]) >= {"block", "conv_operator", "qk_norm",
                                   "router", "tied_head", "expert_bias"}
    with open(MANIFEST) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[
            "lfm2_8b_a1b_fed"]
    assert set(entry["reduced"]) == set(doc["reduced"]) \
        == family.HELD_KEYS | {"cohort"}
    assert entry["source"] == doc["source"]
    with pytest.raises(ValueError, match="'held' states"):
        family.model_config({**doc, "held": {"num_experts": 8}})


def test_layer_table_adds_up_to_the_model_and_the_hand_counts():
    cell = manifest.load_cell(MANIFEST, CELL)
    rows = {r["name"]: r for r in family.layers(lfm2_moe, cell.config)}
    assert sum(r["params"] for r in rows.values()) == 497_846_016
    assert rows["experts"]["params"] == 352_321_536
    assert rows["dense_mlp"]["params"] == 88_080_384
    assert rows["embed"]["params"] == 33_554_432
    assert rows["lm_head"]["params"] == 0           # tied: the embedding's
    assert rows["conv_proj"]["params"] + rows["conv_mix"]["params"] \
        == 20_979_200
    assert rows["attention_proj"]["params"] == 2_621_568
    assert rows["router"]["params"] == 262_272      # with the four biases
    assert rows["norms"]["params"] == 26_624
    seq = 16384
    pairs = seq * (seq + 1) // 2
    assert rows["attention_full"]["forward"]["flops"] \
        == 2 * 2 * pairs * 64 * 8
    assert rows["conv_proj"]["forward"]["flops"] \
        == 5 * 2 * seq * (2048 * 1536 + 512 * 2048)
    # seven operations a channel and token; three streams read, one written
    assert rows["conv_mix"]["forward"]["flops"] == 5 * seq * 512 * 7
    assert rows["conv_mix"]["forward"]["elements"] == 5 * seq * 4 * 512
    assert rows["conv_mix"]["backward"]["elements"] == 2 * 5 * seq * 4 * 512
    # a held expert sees the deployment's 2,048 tokens a step: T slots of 4T
    assert rows["experts"]["forward"]["flops"] \
        == 2.0 * seq * 4 * 3 * 2048 * 1792
    assert rows["lm_head"]["forward"]["flops"] == 2.0 * seq * 2048 * 16384
    assert rows["lm_head"]["forward"]["step_elements"] == 16384 * 2048
    # a small size by hand: 3 layers (1 dense; conv, attention, conv), 12
    # tokens
    small = {"hidden_size": 8, "num_attention_heads": 2,
             "num_key_value_heads": 1, "vocab_size": 10, "num_experts": 2,
             "published": {"num_experts": 4, "num_attention_heads": 4},
             "num_experts_per_tok": 2, "num_hidden_layers": 3,
             "num_dense_layers": 1, "moe_intermediate_size": 6,
             "intermediate_size": 5, "conv_channels": 4, "conv_L_cache": 3,
             "layer_types": ["conv", "full_attention", "conv"]}
    table = {r["name"]: r for r in lfm2_moe.layers(small, 12)}
    assert table["conv_proj"]["params"] == 2 * (8 * 12 + 4 * 8)
    assert table["conv_mix"]["params"] == 2 * 4 * 3
    assert table["conv_mix"]["forward"]["flops"] == 2 * 12 * 4 * 7
    assert table["attention_proj"]["params"] == 8 * (2 * 2 * 2 + 2 * 2) + 4
    assert table["attention_full"]["forward"]["flops"] == 2 * 2 * 78 * 2 * 2
    assert table["router"]["params"] == 2 * (8 * 4 + 4)
    assert table["experts"]["params"] == 2 * 2 * 3 * 8 * 6
    assert table["dense_mlp"]["params"] == 3 * 8 * 5
    step = flops.train_flops_per_sample(list(rows.values()))
    assert 19e12 < step < 20e12
    floor_s, parts = flops.step_floor(list(rows.values()), 1, 2,
                                      peaks.PEAKS["TPU v5 lite"])
    assert 0.04 < floor_s < 0.2 and len(parts) == 2 * len(rows)
    bound = {(p["layer"], p["pass"]): p["bound"] for p in parts}
    assert bound["conv_mix", "forward"] == "memory"
    assert bound["conv_proj", "forward"] == "compute"


def test_tiny_configuration_runs_a_traced_cell(tiny_manifest, tmp_path,
                                               monkeypatch):
    """``run_cell`` whole on the CPU with a tiny configuration of the family
    (float32 on both sides here: every error is rounding)."""
    monkeypatch.setattr(reduce_trace, "load",
                        lambda trace_dir, devices, rounds, op_names:
                        hand_built_trace(rounds))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    result, details = harness.run_cell(
        tiny_manifest, "tiny_shortconv.longctx", seed=2147484005,
        seconds=0.5, trace=True, t0=time.perf_counter(),
        trace_dir=str(tmp_path / "trace"))
    assert result["failed"] == 0 and result["attempted"] >= 2
    check = details["reference_check"]
    assert check["ok"], check
    frozen = [f"expert_bias_layer{i}" for i in range(2, 6)]
    assert set(family.TOLERANCE) | set(frozen) <= set(check)
    assert check["routing"]["error"] == 0.0
    assert check["logits"]["error"] < 1e-4
    assert max(check[n]["error"] for n in lfm2_moe.GRAD_LEAVES) < 1e-3
    assert all(check[n]["error"] == 0.0 and check[n]["tolerance"] == 0.0
               for n in frozen)
    assert check["agreeing_positions"] == check["compared_positions"]
    assert result["correct"] is True, details["state_check"]
    assert set(family.TOLERANCE) | set(frozen) <= set(result["compared"])
    # the gauge the program set is what the reader reads
    assert result["metrics"]["expert_bias_swap_share"]["value"] \
        == check["expert_load"]["expert_bias_swap_share"] > 0.1
    assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    assert result["metrics"]["train_mfu"]["value"] > 0


@pytest.mark.parametrize("fault", [
    "state_unchanged", "half_the_clients", "no_bias", "no_taps"])
def test_check_catches_planted_faults(tiny_manifest, fault, monkeypatch):
    """A round that leaves the state as it was and one that folds half the
    clients are not correct by the round's leaves (and every bias still
    reads 0: nothing moved it), the half fold by ``fold`` too, which reads 1
    there whatever the sites' updates have in common; the bias left out of the choice is not
    correct by ``routing``, the taps left out by the conv leaves: the
    program is built anew with the fault in it, so the compiled round
    holds it too (without the bias it moves the router's leaf elsewhere,
    within that leaf's wide limit)."""
    import jax
    import jax.numpy as jnp
    from control_shortconv import first_half_alone, plant

    from neuroimagedisttraining_tpu.models import decoder

    cell, algo, state, ref = _built(tiny_manifest)
    sound = family.reference_check(algo, state.global_params, ref,
                                   cell.config)
    assert sound["ok"], sound
    leaves = list(lfm2_moe.GRAD_LEAVES)
    frozen = [f"expert_bias_layer{i}" for i in range(2, 6)]
    assert max(sound[n]["error"] for n in leaves) < 1e-4
    assert sound["fold"]["error"] < 1e-3

    def unchanged(st, sel, round_idx, x, y, n):
        return st, jnp.float32(sound["round_loss"]["error"])

    if fault == "state_unchanged":
        algo._round_jit = unchanged
    elif fault == "half_the_clients":
        algo._round_jit = first_half_alone(algo._round_jit)
    else:
        plant(decoder, fault, monkeypatch.setattr)
        # the sound check compiled and cached the sound round: a program
        # built after the fault, nothing traced before it
        jax.clear_caches()
        cell, algo, _, ref = _built(tiny_manifest)
    report = family.reference_check(algo, state.global_params, ref,
                                    cell.config)
    assert not report["ok"]
    assert all(report[n]["ok"] for n in frozen), {n: report[n] for n in frozen}
    if fault in ("state_unchanged", "half_the_clients"):
        assert all(report[n]["ok"] for n in ("loss", "logits", "routing"))
        # an unchanged state fails by every leaf; half the clients by the
        # dense ones at least (the held experts' and the router's limits
        # stand wider: their readings swing on the chip)
        dense = CONV_LEAVES + ("q_proj", "q_layernorm", "dense_up", "embed")
        failing = leaves if fault == "state_unchanged" else dense
        assert not any(report[n]["ok"] for n in failing), report
    if fault == "state_unchanged":
        assert all(abs(report[n]["error"] - 1.0) < 1e-5 for n in leaves)
    if fault == "half_the_clients":
        control = sound["round_controls"]["half_the_clients"]
        for n in leaves:    # what the report says such a fold would read
            assert abs(report[n]["error"] - control[n]) < 1e-3, n
        # and the number that reads the fold itself: all the way to the half
        assert not report["fold"]["ok"]
        assert abs(report["fold"]["error"] - 1.0) < 1e-2
        assert all(abs(v - 1.0) < 1e-2 for v in
                   report["round_controls"]["toward_half"].values())
    if fault == "no_bias":
        # the program routed by the scores alone: as often as the gauge of
        # the sound program says the bias changes the choice, at least
        assert not report["routing"]["ok"]
        assert report["routing"]["error"] >= 0.9 * sound["expert_load"][
            "expert_bias_swap_share"] > 0.1
        assert report["expert_load"]["expert_bias_swap_share"] == 0.0
        # the compiled round routed by the scores alone too: the router's
        # leaf is far from where rounding leaves it (its limit stands wide:
        # on the chip it swings, and ``routing`` is this fault's witness)
        assert report["router_first"]["error"] \
            > 100 * sound["router_first"]["error"]
    if fault == "no_taps":
        # the compiled round mixed no tokens: the conv leaves are not the
        # reference's, the taps' least of all
        assert not any(report[n]["ok"] for n in CONV_LEAVES), report
        assert report["conv_taps"]["error"] > 0.5


@pytest.mark.parametrize("control", ["e4m3", "no_bias", "no_taps"])
def test_controls_read_not_correct(tiny_manifest, control, monkeypatch):
    """The three controls of ``control_shortconv.py`` at the tiny size: the
    reference with its matrices rounded to e4m3, without the bias in the
    choice and without the taps, each in the place of the program's forward
    pass: not correct by a forward number. The last two are planted in the
    program too, as ``main`` plants them: the compiled round moves the
    router's leaf (within its wide limit) and is not correct by the conv
    leaves; under ``e4m3`` the round is the program's own and reads as the
    program does."""
    import jax
    from control_shortconv import plant, stand_in

    from neuroimagedisttraining_tpu.models import decoder

    plant(decoder, control, monkeypatch.setattr)
    jax.clear_caches()
    cell, algo, state, ref = _built(tiny_manifest)
    algo.apply_fn = stand_in(ref, family.model_config(cell.config), control)
    # the matrices scaled up, or every output of the tiny model is too small
    # for a forward limit to see a fault; not where the bias is the fault:
    # beside scores that far apart it changes too little
    scale = 1.0 if control == "no_bias" else 8.0
    params = jax.tree_util.tree_map(
        lambda a: a * scale if a.ndim > 1 else a, state.global_params)
    report = family.reference_check(algo, params, ref, cell.config)
    assert not report["ok"]
    failed = {n for n in ("loss", "logits", "routing")
              if not report[n]["ok"]}
    assert failed
    round_failed = {n for n in lfm2_moe.GRAD_LEAVES if not report[n]["ok"]}
    if control == "no_bias":
        assert "routing" in failed
        assert report["router_first"]["error"] > 0.01   # rounding: 1e-6
    elif control == "no_taps":
        assert set(CONV_LEAVES) <= round_failed, report
    else:
        assert not round_failed, report
    assert all(report[f"expert_bias_layer{i}"]["ok"] for i in range(2, 6))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.slow
def test_round_program_fits_the_chip(one_chip):
    """The cell's round at its real size compiled for a described v5e (about
    a minute; a size, never a time): it folds, it fits, it holds the scopes
    the metric files ask for."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.algorithms.fedavg import FedAvgState
    from neuroimagedisttraining_tpu.data.types import FederatedData
    from neuroimagedisttraining_tpu.experiments import parse_args, runner

    cell = manifest.load_cell(MANIFEST, CELL)
    c, n, m, seq = (cell.cohort[k] for k in (
        "n_sites", "train_per_site", "test_per_site", "sequence_length"))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    data = FederatedData(
        x_train=shape((c, n, seq), jnp.int32),
        y_train=shape((c, n, seq), jnp.int32),
        n_train=np.full((c,), n, np.int32),
        x_test=shape((c, m, seq), jnp.int32),
        y_test=shape((c, m, seq), jnp.int32),
        n_test=np.full((c,), m, np.int32),
        class_num=cell.config["held"]["vocab_size"])
    args = parse_args(harness.program_flags(cell, 0))
    algo, _ = runner.build_algorithm(args, args.algo, data=data)
    assert algo.client_chunk == 1 and algo._stack_readers() == []
    params = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype),
                                    algo.params_template())
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == 497_846_016
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = FedAvgState(global_params=params, personal_params=None,
                        rng=shape(key.shape, key.dtype))
    compiled = algo._round_jit.lower(
        state, shape((algo.clients_per_round,), jnp.int32),
        shape((), jnp.float32), data.x_train, data.y_train,
        shape((c,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    print(f"{CELL}: round program {gib:.2f} GiB (arguments "
          f"{mem.argument_size_in_bytes}, temporaries "
          f"{mem.temp_size_in_bytes}, output {mem.output_size_in_bytes}, "
          f"code {mem.generated_code_size_in_bytes}), compiled for a "
          "described v5e")
    assert gib < 15.75
    names = set(reduce_trace.hlo_op_names(compiled.as_text()).values())
    for scope in ("local_train", "aggregate", "short_conv", "attention/full",
                  "router", "experts", "dense_mlp", "lm_head", "embed"):
        assert any(f"/{scope}/" in f"/{s}/" for s in names), scope
