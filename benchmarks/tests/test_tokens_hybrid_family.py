"""The ``tokens_hybrid`` family and its one configuration
(``falcon_h1_34b_fed``): the manifest loads the cell, the configuration's
file says what the program's own constant and cut say, the reference's layer
table adds up to the model and to hand counts, and a tiny configuration of
the family runs a whole traced cell on the CPU against the plain reference,
then with five planted faults and under the four controls."""
import json
import os
import time

import numpy as np
import pytest
from conftest import BENCH, MANIFEST, write_manifest
from test_reduce_trace import hand_built_trace

from benchmarks.families import tokens_hybrid as family
from benchmarks.lib import flops, harness, manifest, peaks, reduce_trace
from benchmarks.reference import falcon_h1

CELL = "falcon_h1_34b_fed.longctx"
SEQ = 256       # 32 chunks of 8; logits compared at positions 0 and 128
SSM_LEAVES = ("ssm_in_proj", "ssm_conv_taps", "ssm_A_log", "ssm_dt_bias",
              "ssm_D", "ssm_norm", "ssm_out_proj")


@pytest.fixture
def tiny_manifest(tmp_path):
    from neuroimagedisttraining_tpu.models import decoder

    held = decoder.held_config("falcon_h1_tiny",
                               decoder.Share(4, 1, 2, 0, 4, 2, 2))
    config = {
        "name": "tiny_hybrid", "source": "test fixture",
        "family": "tokens_hybrid", "reference": "falcon_h1",
        "published": held.pop("published"),
        "held": {k: held.pop(k) for k in family.HELD_KEYS},
        "flags": {"algo": "fedavg", "model": "falcon_h1_tiny", "lm_layers": 4,
                  "lm_tensor_shards": 2, "lm_ssm_shards": 2,
                  "lm_mlp_shards": 2, "lm_vocab_shards": 4,
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

    cell = manifest.load_cell(path, "tiny_hybrid.longctx")
    algo = harness.build(
        cell, parse_args(harness.program_flags(cell, seed)), seed)
    state = algo.init_state(jax.random.PRNGKey(seed))
    return cell, algo, state, harness.reference_of(cell)


def _scaled(params, scale=4.0):
    """The matrices scaled up, or every output of the tiny model is too
    small for a forward limit to see a fault (the mixer's small leaves are
    drawn as Mamba-2 draws them and stay)."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * scale if a.ndim > 1 and path[-1].key != "conv"
        else a, params)


def test_manifest_loads_the_new_cell():
    cell = manifest.load_cell(MANIFEST, CELL)
    assert cell.chips == 1 and cell.family is family
    assert cell.cohort == {"n_sites": 8, "train_per_site": 1,
                           "test_per_site": 1, "sequence_length": 8192}
    assert cell.traffic["block_rounds"] == 2
    assert cell.traffic["flags"] == {"frac": 0.25,
                                     "frequency_of_the_test": 0}
    names = {e["name"] for e, _ in cell.per_layer}
    new = {"ssm_ms_per_round", "ssm_scan_ms_per_round", "ssm_roofline",
           "ssm_scan_roofline", "ssm_chunk_carry"}
    shared = {"attention_full_ms_per_round", "attention_roofline",
              "dense_mlp_ms_per_round", "lm_head_ms_per_round",
              "embed_ms_per_round"}
    assert new | shared <= names
    # what this model has no layer for is not asked of it
    assert not names & {
        "attention_window_ms_per_round", "shared_expert_ms_per_round",
        "attention_indexer_ms_per_round", "attention_select_ms_per_round",
        "attention_selected_ms_per_round", "selected_attention_roofline",
        "selected_key_share", "cohort_gather_ms_per_round",
        "batch_gather_ms_per_round", "personal_update_ms_per_round",
        "router_ms_per_round", "experts_ms_per_round", "experts_roofline",
        "expert_load_max_over_mean", "expert_bias_swap_share",
        "short_conv_ms_per_round", "short_conv_roofline"}
    assert not {n for n in names if n.startswith("stem_")}
    argv = harness.program_flags(cell, 7)
    assert argv[argv.index("--model") + 1] == "falcon_h1_34b"
    for flag, value in (("lm_layers", 4), ("lm_tensor_shards", 4),
                        ("lm_ssm_shards", 2), ("lm_mlp_shards", 8),
                        ("lm_vocab_shards", 8)):
        assert argv[argv.index("--" + flag) + 1] == str(value)
    assert "--lm_expert_shards" not in argv
    # and no accepted cell asks for the new metrics
    for other in ("laguna_s21_fed.train", "keye_vl2_fed.longctx",
                  "lfm2_8b_a1b_fed.longctx", "alexnet3d_abcd.train"):
        assert not new & {e["name"] for e, _ in
                          manifest.load_cell(MANIFEST, other).per_layer}
    with open(MANIFEST) as f:
        cells = json.load(f)["workloads"]
    assert sum(w["chips"] == 4 for w in cells) == 1
    assert [w["name"] for w in cells
            if w["config"] == "falcon_h1_34b_fed"] == [CELL]
    assert len(cells[-1]["why"]) <= 200


def test_configuration_file_is_the_programs_constant_and_cut():
    """Every published key under its own name as ``CONFIGS["falcon_h1_34b"]``
    has it (all twelve multipliers and the MLP's two among them); the held
    counts as the program's ``held_config`` cuts them for the cell's share
    flags; no width among the cut keys."""
    from neuroimagedisttraining_tpu.models import decoder

    with open(os.path.join(BENCH, "configs", "falcon_h1_34b_fed.json")) as f:
        doc = json.load(f)
    flags = doc["flags"]
    held = decoder.held_config("falcon_h1_34b", decoder.Share(
        flags["lm_layers"], 1, flags["lm_tensor_shards"], 0,
        flags["lm_vocab_shards"], flags["lm_ssm_shards"],
        flags["lm_mlp_shards"]))
    merged = family.model_config(doc)
    published = decoder.CONFIGS["falcon_h1_34b"]
    assert set(published) <= set(merged)
    for key, value in published.items():
        assert merged[key] == held[key], key
        if key in family.HELD_KEYS:
            assert key in doc["reduced"] and key not in doc, key
            assert doc["published"][key] == value, key
        else:
            assert doc[key] == value, key
    assert doc["held"] == {
        "num_hidden_layers": 4, "num_attention_heads": 5,
        "num_key_value_heads": 1, "mamba_n_heads": 16, "mamba_n_groups": 1,
        "mlp_columns": 2688, "vocab_size": 32640}
    assert merged["mlp_columns"] == held["mlp_columns"]
    assert doc["intermediate_size"] == 21504 and doc["mamba_d_ssm"] == 4096
    assert doc["published"] == held["published"]
    multipliers = [k for k in doc if k.endswith("_multiplier")]
    assert len(multipliers) == 7 and len(doc["ssm_multipliers"]) == 5 \
        and len(doc["mlp_multipliers"]) == 2
    assert not [k for k in family.HELD_KEYS - {"vocab_size"}
                if k.endswith(("_size", "_dim", "_rank"))]
    assert set(doc["assumed"]) >= {"equations", "block", "attention", "ssm",
                                   "mlp", "ssm_leaves_draw", "optimiser",
                                   "keys_that_change_nothing"}
    assert doc["cohort"]["sequence_length"] == 8192
    with open(MANIFEST) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[
            "falcon_h1_34b_fed"]
    assert set(entry["reduced"]) == set(doc["reduced"]) \
        == family.HELD_KEYS | {"cohort"}
    assert entry["source"] == doc["source"]
    with pytest.raises(ValueError, match="'held' states"):
        family.model_config({**doc, "held": {"mamba_n_heads": 16}})


def test_layer_table_adds_up_to_the_model_and_the_hand_counts():
    cell = manifest.load_cell(MANIFEST, CELL)
    rows = {r["name"]: r for r in family.layers(falcon_h1, cell.config)}
    assert sum(r["params"] for r in rows.values()) == 667_589_824
    assert rows["embed"]["params"] == rows["lm_head"]["params"] \
        == 32640 * 5120
    assert rows["dense_mlp"]["params"] == 4 * 3 * 5120 * 2688
    assert rows["ssm_proj"]["params"] == 4 * (5120 * 4624 + 2048 * 5120)
    assert rows["ssm_conv"]["params"] == 4 * (2560 * 4 + 2560)
    assert rows["ssm_scan"]["params"] == 4 * 48
    assert rows["ssm_norm"]["params"] == 4 * 2048
    assert rows["attention_proj"]["params"] == 4 * (2 * 5120 * 640
                                                    + 2 * 5120 * 128)
    assert rows["norms"]["params"] == 4 * 10240 + 5120
    seq = 8192
    pairs = seq * (seq + 1) // 2
    # the causal pairs by operations; q, k, v and the output by bytes: no
    # score crosses HBM
    assert rows["attention_full"]["forward"]["flops"] \
        == 4 * 2 * 2 * pairs * 128 * 5
    assert rows["attention_full"]["forward"]["elements"] \
        == 4 * seq * 128 * (2 * 5 + 2 * 1)
    assert rows["attention_full"]["params"] == 0
    # the chunked algorithm at chunk 128: per token 2 * 128 * 256 for the one
    # group's scores, per head 2 * 128 * 128 + 4 * 128 * 256
    per_token = 2 * 128 * 256 + 16 * (2 * 128 * 128 + 4 * 128 * 256)
    assert per_token == 2_686_976
    assert rows["ssm_scan"]["forward"]["flops"] == 4 * seq * per_token
    assert falcon_h1.scan_flops(seq, 16, 1, 128, 256, 128) == seq * per_token
    # x, z and y of 2048 channels, B and C of 256, dt of 16
    assert rows["ssm_scan"]["forward"]["elements"] \
        == 4 * seq * (3 * 2048 + 2 * 256 + 16)
    assert rows["ssm_conv"]["forward"]["flops"] == 4 * seq * 2560 * 13
    assert rows["ssm_conv"]["forward"]["elements"] == 4 * seq * 2 * 2560
    assert rows["ssm_proj"]["forward"]["flops"] \
        == 4 * 2.0 * seq * (5120 * 4624 + 2048 * 5120)
    assert rows["dense_mlp"]["forward"]["flops"] \
        == 4 * 2.0 * seq * 3 * 5120 * 2688
    assert rows["lm_head"]["forward"]["flops"] == 2.0 * seq * 5120 * 32640
    # a small size by hand: 2 layers, 12 tokens
    small = {"hidden_size": 8, "head_dim": 2, "num_attention_heads": 2,
             "num_key_value_heads": 1, "vocab_size": 10,
             "num_hidden_layers": 2, "mamba_n_heads": 2, "mamba_n_groups": 1,
             "mamba_d_head": 3, "mamba_d_state": 5, "mamba_d_conv": 4,
             "mamba_chunk_size": 4, "intermediate_size": 7}
    table = {r["name"]: r for r in falcon_h1.layers(small, 12)}
    width = 2 * 6 + 2 * 5 + 2
    assert table["ssm_proj"]["params"] == 2 * (8 * width + 6 * 8)
    assert table["ssm_conv"]["params"] == 2 * (16 * 4 + 16)
    assert table["ssm_scan"]["params"] == 2 * 6
    assert table["ssm_scan"]["forward"]["flops"] == 2 * 12 * (
        2 * 4 * 5 + 2 * (2 * 4 * 3 + 4 * 3 * 5))
    assert table["attention_full"]["forward"]["flops"] == 2 * 2 * 2 * 78 * 2 * 2
    assert table["dense_mlp"]["params"] == 2 * 3 * 8 * 7
    assert table["dense_mlp"]["params"] \
        == {r["name"]: r for r in falcon_h1.layers(
            {**small, "mlp_columns": 7}, 12)}["dense_mlp"]["params"]
    step = flops.train_flops_per_sample(list(rows.values()))
    assert 25e12 < step < 27e12
    floor_s, parts = flops.step_floor(list(rows.values()), 1, 2,
                                      peaks.PEAKS["TPU v5 lite"])
    assert 0.1 < floor_s < 0.2 and len(parts) == 2 * len(rows)
    bound = {(p["layer"], p["pass"]): p["bound"] for p in parts}
    assert bound["ssm_conv", "forward"] == "memory"
    assert bound["ssm_norm", "forward"] == "memory"
    assert bound["ssm_proj", "forward"] == "compute"
    assert bound["attention_full", "forward"] == "compute"


def test_tiny_configuration_runs_a_traced_cell(tiny_manifest, tmp_path,
                                               monkeypatch):
    """``run_cell`` whole on the CPU with a tiny configuration of the family
    (float32 on both sides here: every error is rounding)."""
    monkeypatch.setattr(reduce_trace, "load",
                        lambda trace_dir, devices, rounds, op_names:
                        hand_built_trace(rounds))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    result, details = harness.run_cell(
        tiny_manifest, "tiny_hybrid.longctx", seed=2147484005,
        seconds=0.5, trace=True, t0=time.perf_counter(),
        trace_dir=str(tmp_path / "trace"))
    assert result["failed"] == 0 and result["attempted"] >= 2
    check = details["reference_check"]
    assert check["ok"], check
    assert set(family.TOLERANCE) <= set(check)
    assert check["logits"]["error"] < 1e-3
    assert max(check[n]["error"] for n in falcon_h1.GRAD_LEAVES) < 2e-3
    assert check["fold"]["error"] < 1e-2
    assert check["compared_positions"] == 2
    assert result["correct"] is True, details["state_check"]
    assert set(family.TOLERANCE) <= set(result["compared"])
    # the gauge the program set is what the reader reads
    assert result["metrics"]["ssm_chunk_carry"]["value"] \
        == check["ssm_carry"]["ssm_chunk_carry"] > 0.1
    assert result["metrics"]["train_mfu"]["value"] > 0


@pytest.mark.parametrize("fault", [
    "state_unchanged", "half_the_clients", "no_carry", "no_ssm_multipliers",
    "no_key_multiplier"])
def test_check_catches_planted_faults(tiny_manifest, fault, monkeypatch):
    """A round that leaves the state as it was is not correct by every leaf
    (each reads 1), one that folds half the clients by ``fold`` (which reads
    1); the carry between chunks left out and the mixer's multipliers left
    out by the mixer's leaves, ``key_multiplier`` left out by ``q_proj`` and
    ``k_proj``: the program is built anew with the fault in it, so the
    compiled round holds it."""
    import jax
    import jax.numpy as jnp
    from control_hybrid import plant
    from control_shortconv import first_half_alone

    from neuroimagedisttraining_tpu.models import decoder

    cell, algo, state, ref = _built(tiny_manifest)
    params = _scaled(state.global_params)
    sound = family.reference_check(algo, params, ref, cell.config)
    assert sound["ok"], sound
    leaves = list(falcon_h1.GRAD_LEAVES)
    assert max(sound[n]["error"] for n in leaves) < 2e-3
    assert sound["fold"]["error"] < 1e-2

    def unchanged(st, sel, round_idx, x, y, n):
        return st, jnp.float32(sound["round_loss"]["error"])

    if fault == "state_unchanged":
        algo._round_jit = unchanged
    elif fault == "half_the_clients":
        algo._round_jit = first_half_alone(algo._round_jit)
    else:
        plant(decoder, fault, "falcon_h1_tiny", monkeypatch.setattr)
        # the sound check compiled and cached the sound round: a program
        # built after the fault, nothing traced before it
        jax.clear_caches()
        cell, algo, _, ref = _built(tiny_manifest)
    report = family.reference_check(algo, params, ref, cell.config)
    assert not report["ok"]
    failed = {n for n in leaves if not report[n]["ok"]}
    if fault == "state_unchanged":
        assert failed == set(leaves)
        assert all(abs(report[n]["error"] - 1.0) < 1e-5 for n in leaves)
        assert report["logits"]["ok"] and report["loss"]["ok"]
    if fault == "half_the_clients":
        assert not report["fold"]["ok"]
        assert abs(report["fold"]["error"] - 1.0) < 1e-2
        control = sound["round_controls"]["half_the_clients"]
        for n in leaves:    # what the report says such a fold would read
            assert abs(report[n]["error"] - control[n]) < 2e-3, n
        assert report["logits"]["ok"] and report["loss"]["ok"]
    if fault == "no_ssm_multipliers":
        # the forward pass the check compares is the faulty program's too
        assert not report["logits"]["ok"]
        assert set(SSM_LEAVES) <= failed
    if fault == "no_carry":
        # what a chunk's entering state adds to an output is small beside
        # the chunk's own part and D's skip, and the gated norm rescales it:
        # logits and the matrices move by half a percent. The leaves that
        # set the decays feel it whole: their gradient is what the state
        # remembers
        assert {"ssm_A_log", "ssm_dt_bias"} <= failed
        assert min(report[n]["error"] for n in ("ssm_A_log",
                                                "ssm_dt_bias")) > 0.4
        # the gauge still says what the sound scan would have carried
        assert report["ssm_carry"]["ssm_chunk_carry"] > 0.1
    if fault == "no_key_multiplier":
        assert {"q_proj", "k_proj"} <= failed
        assert min(report[n]["error"] for n in ("q_proj", "k_proj")) > 0.3


@pytest.mark.parametrize("control", ["e4m3", "no_carry", "no_ssm_multipliers",
                                     "no_key_multiplier"])
def test_controls_read_not_correct(tiny_manifest, control, monkeypatch):
    """The four controls of ``control_hybrid.py`` at the tiny size: the
    reference with its matrices rounded to e4m3, with every chunk started
    from a zero state, without the mixer's multipliers and without
    ``key_multiplier``, each in the place of the program's forward pass: not
    correct, ``e4m3`` and the multipliers by ``logits``. The last three are planted in the program too, as
    ``main`` plants them: the compiled round is not correct by the leaves
    named for the fault; under ``e4m3`` the round is the program's own and
    reads as the program does."""
    import jax
    from control_hybrid import plant, stand_in

    from neuroimagedisttraining_tpu.models import decoder

    plant(decoder, control, "falcon_h1_tiny", monkeypatch.setattr)
    jax.clear_caches()
    cell, algo, state, ref = _built(tiny_manifest)
    algo.apply_fn = stand_in(ref, family.model_config(cell.config), control)
    report = family.reference_check(algo, _scaled(state.global_params), ref,
                                    cell.config)
    assert not report["ok"]
    assert report["ssm_carry"] == {}        # the stand-in sows nothing
    round_failed = {n for n in falcon_h1.GRAD_LEAVES if not report[n]["ok"]}
    if control == "e4m3":
        assert not report["logits"]["ok"], report["logits"]
        assert not round_failed, report
    elif control == "no_key_multiplier":
        assert {"q_proj", "k_proj"} <= round_failed, report
    elif control == "no_carry":
        assert {"ssm_A_log", "ssm_dt_bias"} <= round_failed, report
    else:
        assert not report["logits"]["ok"], report["logits"]
        assert set(SSM_LEAVES) <= round_failed, report


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
    half a minute; a size, never a time): it folds, it fits, it holds the
    scopes the metric files ask for."""
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
        == 667_589_824
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
    for scope in ("local_train", "aggregate", "ssm", "ssm/conv", "ssm/scan",
                  "ssm/norm", "attention/full", "dense_mlp", "lm_head",
                  "embed"):
        assert any(f"/{scope}/" in f"/{s}/" for s in names), scope
