"""The ``tokens_flash`` family and its one configuration
(``phi4_mini_flash_fed``): the manifest loads the cell, the configuration's
file says what the program's own constant and cut say, the reference's layer
table adds up to the model and to hand counts, and a tiny configuration of
the family runs a whole traced cell on the CPU against the plain reference,
then with planted faults and under the controls."""
import json
import os
import time

import numpy as np
import pytest
from conftest import BENCH, MANIFEST, write_manifest
from test_reduce_trace import hand_built_trace

from benchmarks.families import tokens_flash as family
from benchmarks.lib import flops, harness, manifest, peaks, reduce_trace
from benchmarks.reference import phi4_flash

CELL = "phi4_mini_flash_fed.longctx"
SEQ = 256       # 32 chunks of 8; logits compared at positions 0 and 128
NEW = ["mamba_ms_per_round", "mamba_scan_ms_per_round",
       "mamba_scan_roofline", "gmu_ms_per_round",
       "attention_cross_ms_per_round", "mamba_chunk_carry"]
SHARED = {"attention_full_ms_per_round", "attention_window_ms_per_round",
          "attention_roofline", "dense_mlp_ms_per_round",
          "lm_head_ms_per_round", "embed_ms_per_round"}
# the limits of a float32 program at the tiny size, where every error of a
# sound program is rounding (at most 2.2e-5 over the leaves: 45 times of
# room): a scan state in bfloat16 reads 4e-3 to 2e-2 there, inside the
# chip's limits, which stand over a bfloat16 program's readings
FLOAT32_TOLERANCE = {**{name: 1e-3 for name in family.TOLERANCE},
                     "loss": 1e-5, "logits": 1e-4, "routing": 0.0,
                     "round_loss": 1e-5}
# the leaves held to a limit one by one (the lambda vectors are held together,
# as ``lambda``)
LEAVES = [n for n in phi4_flash.GRAD_LEAVES
          if n not in phi4_flash.LAMBDA_LEAVES]
# the tiny preset's stage: layers 8-17 of 24, the published stage's pattern
SHARE = dict(lm_first_layer=8, lm_layers=10, lm_tensor_shards=2,
             lm_mlp_shards=8, lm_vocab_shards=4)


@pytest.fixture
def tiny_manifest(tmp_path):
    from neuroimagedisttraining_tpu.models import decoder

    held = decoder.held_config("phi4_flash_tiny", decoder.Share(
        layers=10, tensor_shards=2, vocab_shards=4, mlp_shards=8,
        first_layer=8))
    config = {
        "name": "tiny_flash", "source": "test fixture",
        "family": "tokens_flash", "reference": "phi4_flash",
        "published": {k: held["published"][k] for k in family.HELD_KEYS},
        "held": {k: held.pop(k) for k in family.HELD_KEYS},
        "first_layer": held.pop("first_layer"),
        "first_gmu_channel": held.pop("first_gmu_channel"),
        "flags": {"algo": "fedavg", "model": "phi4_flash_tiny", **SHARE,
                  "dataset": "token_shards", "track_personal": 0,
                  "client_chunk": 1, "batch_size": 1, "epochs": 1, "lr": 0.05,
                  "momentum": 0.0, "grad_clip": 10.0},
        "cohort": {"n_sites": 8, "train_per_site": 1, "test_per_site": 1,
                   "sequence_length": SEQ},
        **{k: v for k, v in held.items()
           if k not in ("layer_types", "published")}}
    assert set(config) - manifest.COMMON_CONFIG_KEYS <= family.CONFIG_KEYS
    return write_manifest(tmp_path, config, (("longctx", 1),))


def _built(path, seed=3):
    import jax

    from neuroimagedisttraining_tpu.experiments import parse_args

    cell = manifest.load_cell(path, "tiny_flash.longctx")
    algo = harness.build(
        cell, parse_args(harness.program_flags(cell, seed)), seed)
    state = algo.init_state(jax.random.PRNGKey(seed))
    return cell, algo, state, harness.reference_of(cell)


def _scaled(params, scale=4.0):
    """The matrices scaled up, or every output of the tiny model is too
    small for a forward limit to see a fault (the mixers' small leaves are
    drawn so that states outlive their chunks, and stay)."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * scale if a.ndim > 1 and path[-1].key not in (
            "conv", "A_log") else a, params)


def test_manifest_loads_the_new_cell():
    cell = manifest.load_cell(MANIFEST, CELL)
    assert cell.chips == 1 and cell.family is family
    assert cell.cohort == {"n_sites": 8, "train_per_site": 1,
                           "test_per_site": 1, "sequence_length": 16384}
    assert cell.traffic["block_rounds"] == 2
    assert cell.traffic["flags"] == {"frac": 0.25,
                                     "frequency_of_the_test": 0}
    names = {e["name"] for e, _ in cell.per_layer}
    assert set(NEW) | SHARED <= names
    # what this model has no layer for is not asked of it
    assert not names & {
        "router_ms_per_round", "experts_ms_per_round", "experts_roofline",
        "shared_expert_ms_per_round", "expert_load_max_over_mean",
        "attention_indexer_ms_per_round", "selected_key_share",
        "short_conv_ms_per_round", "ssm_ms_per_round", "ssm_scan_roofline",
        "ssm_chunk_carry", "linear_attention_ms_per_round",
        "linear_chunk_carry", "cohort_gather_ms_per_round"}
    argv = harness.program_flags(cell, 7)
    assert argv[argv.index("--model") + 1] == "phi4_mini_flash"
    for flag, value in (("lm_first_layer", 12), ("lm_layers", 10),
                        ("lm_tensor_shards", 2), ("lm_mlp_shards", 8),
                        ("lm_vocab_shards", 8)):
        assert argv[argv.index("--" + flag) + 1] == str(value)
    # and no accepted cell asks for the new metrics
    for other in ("laguna_s21_fed.train", "falcon_h1_34b_fed.longctx",
                  "qwen3_next_80b_fed.longctx", "alexnet3d_abcd.train"):
        assert not set(NEW) & {e["name"] for e, _ in
                               manifest.load_cell(MANIFEST, other).per_layer}
    with open(MANIFEST) as f:
        doc = json.load(f)
    cells = doc["workloads"]
    assert sum(w["chips"] == 4 for w in cells) == 1
    assert cells[-1]["name"] == CELL and len(cells[-1]["why"]) <= 200
    assert doc["configs"][-1]["name"] == "phi4_mini_flash_fed"
    # the new metrics stand last; the shared ones had the cell appended
    assert [e["name"] for e in doc["per_layer"][-len(NEW):]] == NEW
    for entry in doc["per_layer"]:
        if entry["name"] in SHARED:
            assert entry["workloads"][-1] == CELL


def test_configuration_file_is_the_programs_constant_and_cut():
    """Every published key of the catalog's row under its own name as
    ``CONFIGS["phi4_mini_flash"]`` has it; the held counts as the program's
    ``held_config`` cuts them for the cell's share flags; the stated keys
    beside them."""
    from neuroimagedisttraining_tpu.models import decoder

    with open(os.path.join(BENCH, "configs", "phi4_mini_flash_fed.json")) as f:
        doc = json.load(f)
    flags = doc["flags"]
    held = decoder.held_config("phi4_mini_flash", decoder.Share(
        layers=flags["lm_layers"], tensor_shards=flags["lm_tensor_shards"],
        vocab_shards=flags["lm_vocab_shards"],
        mlp_shards=flags["lm_mlp_shards"],
        first_layer=flags["lm_first_layer"]))
    merged = family.model_config(doc)
    published = decoder.CONFIGS["phi4_mini_flash"]
    assert set(published) <= set(merged)
    for key, value in published.items():
        assert merged[key] == held[key], key
        if key in family.HELD_KEYS:
            assert key in doc["reduced"] and key not in doc, key
            assert doc["published"][key] == value, key
        else:
            assert doc[key] == value, key
    assert doc["held"] == {k: held[k] for k in family.HELD_KEYS} == {
        "num_hidden_layers": 10, "num_attention_heads": 20,
        "num_key_value_heads": 10, "mlp_columns": 1280,
        "gmu_channels": 640, "vocab_size": 25008}
    assert doc["published"] == {k: held["published"][k]
                                for k in family.HELD_KEYS}
    assert held["layer_types"] == [
        "mamba", "sliding_attention", "mamba", "sliding_attention",
        "mamba_memory", "full_attention", "gmu", "cross_attention", "gmu",
        "cross_attention"]
    assert [{"window": "sliding_attention", "memory": "mamba_memory",
             "full": "full_attention", "cross": "cross_attention"}.get(k, k)
            for k in phi4_flash.layer_types(merged)] == held["layer_types"]
    assert doc["first_layer"] == held["first_layer"] == 12
    assert doc["first_gmu_channel"] == held["first_gmu_channel"] == 0
    assert doc["differential_attention"] is True
    assert (doc["mamba_d_state"], doc["mamba_d_conv"], doc["mamba_expand"],
            doc["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert set(doc["assumed"]) >= {
        "equations", "layer_types", "block", "mlp", "mamba", "memory", "gmu",
        "differential_attention", "chunk", "init", "optimiser"}
    assert doc["cohort"]["sequence_length"] == 16384
    with open(MANIFEST) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[
            "phi4_mini_flash_fed"]
    assert set(entry["reduced"]) == set(doc["reduced"]) \
        == family.HELD_KEYS | {"cohort"}
    assert entry["source"] == doc["source"]
    # the catalog's row, number for number
    row = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}
    for key, value in row.items():
        assert (doc["published"][key] if key in doc["reduced"]
                else doc[key]) == value, key
    with pytest.raises(ValueError, match="'held' states"):
        family.model_config({**doc, "held": {"num_hidden_layers": 10}})


def test_layer_table_adds_up_to_the_model_and_the_hand_counts():
    cell = manifest.load_cell(MANIFEST, CELL)
    rows = {r["name"]: r for r in family.layers(phi4_flash, cell.config)}
    assert sum(r["params"] for r in rows.values()) == 335_310_720
    assert rows["embed"]["params"] == 25008 * 2560
    assert rows["lm_head"]["params"] == 0            # tied
    # three Mamba layers of 5120 channels, state 16, rank 160
    mat = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert rows["mamba_proj"]["params"] == 3 * (mat + 5120 * 5 + 5120 * 16
                                                + 2 * 5120)
    assert rows["gmu"]["params"] == 2 * 2 * 2560 * 640
    assert rows["dense_mlp"]["params"] == 10 * 3 * 2560 * 1280
    seq = 16384
    # the recurrence: two multiply-adds a channel, state and token; u,
    # delta, y of 5120 channels and B, C of 16 states by bytes
    assert rows["mamba_scan"]["forward"]["flops"] == 3 * 4 * seq * 5120 * 16
    assert rows["mamba_scan"]["forward"]["elements"] \
        == 3 * seq * (3 * 5120 + 32)
    pairs = seq * (seq + 1) // 2
    # the full layer and the two cross layers, 20 heads of 64
    assert rows["attention_full"]["forward"]["flops"] \
        == 3 * 2 * 2 * pairs * 64 * 20
    band = 512 * 513 // 2 + (seq - 512) * 512
    assert rows["attention_window"]["forward"]["flops"] \
        == 2 * 2 * 2 * band * 64 * 20
    assert rows["lm_head"]["forward"]["flops"] == 2.0 * seq * 2560 * 25008
    step = flops.train_flops_per_sample(list(rows.values()))
    assert 35e12 < step < 45e12
    floor_s, parts = flops.step_floor(list(rows.values()), 1, 2,
                                      peaks.PEAKS["TPU v5 lite"])
    assert 0.1 < floor_s < 0.4 and len(parts) == 2 * len(rows)
    bound = {(p["layer"], p["pass"]): p["bound"] for p in parts}
    assert bound["mamba_scan", "forward"] == "memory"
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
        tiny_manifest, "tiny_flash.longctx", seed=2147484105,
        seconds=0.5, trace=True, t0=time.perf_counter(),
        trace_dir=str(tmp_path / "trace"))
    assert result["failed"] == 0 and result["attempted"] >= 2
    check = details["reference_check"]
    assert check["ok"], check
    assert set(family.TOLERANCE) <= set(check)
    assert check["logits"]["error"] < 1e-4
    assert check["routing"]["error"] == 0.0
    assert max(check[n]["error"] for n in LEAVES + ["lambda"]) < 2e-3
    assert not set(phi4_flash.LAMBDA_LEAVES) & set(check)
    assert check["fold"]["error"] < 1e-2
    assert check["compared_positions"] == 2
    assert result["correct"] is True, details["state_check"]
    assert set(family.TOLERANCE) <= set(result["compared"])
    # the gauge the program set is what the reader reads
    carry = check["mamba_carry"]["mamba_chunk_carry"]
    assert result["metrics"]["mamba_chunk_carry"]["value"] == carry
    assert 0.05 < carry < 1.0
    assert result["metrics"]["train_mfu"]["value"] > 0


@pytest.mark.parametrize("fault", [
    "state_unchanged", "half_the_clients", "no_carry", "bf16_state",
    "frozen_lambda"])
def test_check_catches_planted_faults(tiny_manifest, fault, monkeypatch):
    """A round that leaves the state as it was is not correct by every leaf
    and by ``lambda`` (each reads 1), one that folds half the clients by
    ``fold`` (which reads 1); the carry between chunks left out and a scan
    state in bfloat16 (by float32's limits: both sides run float32 here) by
    the Mamba mixer's leaves; no gradient through differential attention's
    ``lam`` by ``lambda`` alone (it reads 1). The last three: the program is
    built anew with the fault in it, so the compiled round holds it."""
    import jax
    import jax.numpy as jnp
    from control_flash import plant
    from control_shortconv import first_half_alone

    from neuroimagedisttraining_tpu.models import decoder

    if fault == "bf16_state":
        monkeypatch.setattr(family, "TOLERANCE", FLOAT32_TOLERANCE)
    cell, algo, state, ref = _built(tiny_manifest)
    params = _scaled(state.global_params)
    sound = family.reference_check(algo, params, ref, cell.config)
    assert sound["ok"], sound
    leaves = LEAVES
    assert max(sound[n]["error"] for n in leaves + ["lambda"]) < 2e-3

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
    report = family.reference_check(algo, params, ref, cell.config)
    assert not report["ok"]
    failed = {n for n in leaves if not report[n]["ok"]}
    if fault == "state_unchanged":
        assert failed == set(leaves)
        assert all(abs(report[n]["error"] - 1.0) < 1e-5
                   for n in leaves + ["lambda"])
        assert report["logits"]["ok"] and report["loss"]["ok"]
    if fault == "frozen_lambda":
        assert not failed and abs(report["lambda"]["error"] - 1.0) < 1e-5
        assert report["logits"]["ok"] and report["loss"]["ok"]
    if fault == "half_the_clients":
        assert not report["fold"]["ok"]
        assert abs(report["fold"]["error"] - 1.0) < 1e-2
        assert report["logits"]["ok"] and report["loss"]["ok"]
    if fault in ("no_carry", "bf16_state"):
        assert {"mamba_A_log", "mamba_dt_bias"} & failed, report


@pytest.mark.parametrize("control", ["e4m3", "bf16_state", "no_carry",
                                     "one_map", "no_memory"])
def test_controls_read_not_correct(tiny_manifest, control, monkeypatch):
    """The controls of ``control_flash.py`` at the tiny size, each the
    reference changed in one way in the place of the program's forward
    pass: not correct, by ``logits`` or ``loss``. ``bf16_state`` and
    ``no_carry`` are planted in the program too, as ``main`` plants them:
    the compiled round is then not correct by the Mamba mixer's leaves;
    otherwise the round is the program's own and reads as the program
    does."""
    import jax
    from control_flash import PLANTED, plant, stand_in

    from neuroimagedisttraining_tpu.models import decoder

    if control == "bf16_state":
        monkeypatch.setattr(family, "TOLERANCE", FLOAT32_TOLERANCE)
    plant(decoder, control, monkeypatch.setattr)
    jax.clear_caches()
    cell, algo, state, ref = _built(tiny_manifest)
    algo.apply_fn = stand_in(ref, family.model_config(cell.config), control)
    report = family.reference_check(algo, _scaled(state.global_params), ref,
                                    cell.config)
    assert not report["ok"]
    assert not (report["logits"]["ok"] and report["loss"]["ok"]), report
    assert report["mamba_carry"] == {}      # the stand-in sows no carry
    round_failed = {n for n in LEAVES if not report[n]["ok"]}
    if control in PLANTED:
        assert {"mamba_A_log", "mamba_dt_bias"} & round_failed, report
    else:
        assert not round_failed, report


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
    """The cell's round at its real size compiled for a described v5e (a
    size, never a time): it folds, it fits, it holds the scopes the metric
    files ask for, and its attention products are the flash kernels."""
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
    params = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype),
                                    algo.params_template())
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == 335_310_720
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
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    names = set(reduce_trace.hlo_op_names(text).values())
    for scope in ("local_train", "aggregate", "mamba", "mamba/scan", "gmu",
                  "attention/window", "attention/full", "attention/cross",
                  "dense_mlp", "lm_head", "embed"):
        assert any(f"/{scope}/" in f"/{s}/" for s in names), scope
