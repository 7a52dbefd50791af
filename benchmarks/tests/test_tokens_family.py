"""The ``tokens`` family and its one configuration (``laguna_s21_fed``): the
manifest loads the cell, the configuration's file says what the program's own
cut says, the cohort repeats for a seed, the reference's layer table adds up
to the model and to the hand count of ISSUE 28, and a tiny configuration of
the family runs a whole traced cell on the CPU against the plain reference."""
import json
import os
import time

import numpy as np
import pytest
from conftest import BENCH, MANIFEST, write_manifest
from test_reduce_trace import hand_built_trace

from benchmarks.families import tokens
from benchmarks.lib import flops, harness, manifest, peaks, reduce_trace
from benchmarks.reference import laguna_s

CELL = "laguna_s21_fed.train"


def family_config(name, held: dict, flags: dict, cohort: dict) -> dict:
    """The program's ``held_config`` in the family's file format."""
    held = dict(held)
    doc = {"name": name, "source": "test fixture", "family": "tokens",
           "reference": "laguna_s",
           "published": held.pop("published"),
           "first_expert": held.pop("first_expert"),
           "held": {k: held.pop(k) for k in tokens.HELD_KEYS},
           "flags": flags, "cohort": cohort}
    return {**doc, **held}


@pytest.fixture
def tiny_tokens_manifest(tmp_path):
    from neuroimagedisttraining_tpu.models import decoder

    held = decoder.held_config("laguna_tiny", decoder.Share(5, 4, 2))
    config = family_config(
        "tiny_tokens", held,
        {"algo": "fedavg", "model": "laguna_tiny", "lm_layers": 5,
         "lm_expert_shards": 4, "lm_tensor_shards": 2,
         "dataset": "token_shards", "track_personal": 0, "client_chunk": 1,
         "batch_size": 1, "epochs": 1, "lr": 0.5, "momentum": 0.0,
         "grad_clip": 10.0},
        {"n_sites": 4, "train_per_site": 2, "test_per_site": 1,
         "sequence_length": 32})
    return write_manifest(tmp_path, config, (("train", 1),))


def test_manifest_loads_the_new_cell():
    cell = manifest.load_cell(MANIFEST, CELL)
    assert cell.chips == 1 and cell.family is tokens
    assert cell.cohort == {"n_sites": 8, "train_per_site": 2,
                           "test_per_site": 1, "sequence_length": 8192}
    names = {e["name"] for e, _ in cell.per_layer}
    new = {"attention_full_ms_per_round", "attention_window_ms_per_round",
           "router_ms_per_round", "experts_ms_per_round",
           "lm_head_ms_per_round", "experts_roofline", "attention_roofline",
           "expert_load_max_over_mean", "dense_mlp_ms_per_round",
           "shared_expert_ms_per_round", "embed_ms_per_round"}
    assert new <= names
    # what cannot exist in a language model's round is not asked of it
    assert not {n for n in names if n.startswith("stem_")}
    assert "personal_update_ms_per_round" not in names
    # on the chip XLA fuses the gather of one sequence into its consumers:
    # no instruction is left under batch_gather (my chip run, PR 28)
    assert "batch_gather_ms_per_round" not in names
    assert {"train_mfu", "local_step_roofline", "aggregate_ms_per_round",
            "local_train_ms_per_round", "optimizer_ms_per_round",
            "cohort_gather_ms_per_round", "round_unscoped_share",
            "device_idle_share"} <= names
    argv = harness.program_flags(cell, 7)
    assert argv[argv.index("--model") + 1] == "laguna_s"
    # and the four accepted cells ask for none of the new metrics
    for other in ("alexnet3d_abcd.train", "alexnet3d_abcd.mesh4"):
        assert not new & {e["name"] for e, _ in
                          manifest.load_cell(MANIFEST, other).per_layer}


def test_configuration_file_is_the_programs_cut():
    """Every published key under its own name, as the program's constant has
    it; the held counts as the program's ``held_config`` cuts them for the
    cell's three share flags; no width among the cut keys."""
    from neuroimagedisttraining_tpu.models import decoder

    with open(os.path.join(BENCH, "configs", "laguna_s21_fed.json")) as f:
        doc = json.load(f)
    flags = doc["flags"]
    held = decoder.held_config("laguna_s", decoder.Share(
        flags["lm_layers"], flags["lm_expert_shards"],
        flags["lm_tensor_shards"]))
    merged = tokens.model_config(doc)
    for key, value in decoder.CONFIGS["laguna_s"].items():
        assert merged[key] == held[key], key
        if key not in tokens.HELD_KEYS:
            assert doc[key] == value, key
        else:
            assert key in doc["reduced"], key
    assert doc["first_expert"] == held["first_expert"] == 0
    assert {k: doc["published"][k] for k in held["published"]} \
        == held["published"]
    # no width among the cut keys (the vocabulary's rows are a count)
    assert not [k for k in tokens.HELD_KEYS - {"vocab_size"}
                if k.endswith(("_size", "_dim", "_rank"))]


def test_cohort_is_the_seeds():
    cohort = {"n_sites": 3, "train_per_site": 2, "test_per_site": 1,
              "sequence_length": 64}
    config = {"held": {"vocab_size": 50}}
    a = tokens.make_cohort(cohort, config, 2147484001)
    b = tokens.make_cohort(cohort, config, 2147484001)
    c = tokens.make_cohort(cohort, config, 2147484002)
    assert a.x_train.shape == (3, 2, 64) and a.x_train.dtype == np.int32
    assert a.x_test.shape == (3, 1, 64) and a.class_num == 50
    np.testing.assert_array_equal(a.x_train, b.x_train)
    np.testing.assert_array_equal(a.y_test, b.y_test)
    assert (np.asarray(a.x_train) != np.asarray(c.x_train)).any()
    x, y = np.asarray(a.x_train), np.asarray(a.y_train)
    assert x.min() >= 0 and x.max() < 50
    np.testing.assert_array_equal(y[..., :-1], x[..., 1:])
    assert (y[..., -1] == -1).all()
    # a first-order structure: most positions follow one of four successors
    big = np.asarray(tokens.make_cohort(
        {**cohort, "sequence_length": 2048}, config, 5).x_train).reshape(-1, 2048)
    successors = {}
    for row in big:
        for cur, nxt in zip(row[:-1], row[1:]):
            successors.setdefault(int(cur), []).append(int(nxt))
    top4 = sum(sum(sorted(np.bincount(v).tolist())[-4:])
               for v in successors.values())
    assert top4 / (big.size - len(big)) > 0.85


def test_layer_table_adds_up_to_the_model_and_the_hand_count():
    cell = manifest.load_cell(MANIFEST, CELL)
    rows = tokens.layers(laguna_s, cell.config)
    assert sum(r["params"] for r in rows) == 567_957_504
    seq = cell.cohort["sequence_length"]
    per_token = {r["name"]: r["forward"]["flops"] / seq / 1e6 for r in rows}
    # ISSUE 28's count, MFLOP forward a token
    hand = {"dense_mlp": 226, "lm_head": 77, "shared_expert": 75,
            "attention_proj": 69, "experts": 24, "router": 6}
    for name, want in hand.items():
        assert round(per_token[name]) == want, (name, per_token[name])
    scores = per_token["attention_full"] + per_token["attention_window"]
    assert round(scores) == 32, scores
    step = flops.train_flops_per_sample(rows)
    assert 12.4e12 < step < 12.8e12         # "a step is 12.7 TFLOP"
    scopes = {r["scope"] for r in rows}
    assert {"embed", "attention", "attention/full", "attention/window",
            "router", "experts", "shared_expert", "dense_mlp",
            "lm_head"} <= scopes
    floor_s, table = flops.step_floor(rows, 1, 2, peaks.PEAKS["TPU v5 lite"])
    assert 0.05 < floor_s < 0.2 and len(table) == 2 * len(rows)


def test_tiny_configuration_runs_a_traced_cell(tiny_tokens_manifest, tmp_path,
                                               monkeypatch):
    """``run_cell`` whole on the CPU with a tiny configuration of the family:
    cohort, build, the folding round, the reference check (float32 on both
    sides here: every error is rounding), the window, ``state_check``, and
    the traced path with the hand-built trace in the profiler's place."""
    monkeypatch.setattr(reduce_trace, "load",
                        lambda trace_dir, devices, rounds, op_names:
                        hand_built_trace(rounds))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    result, details = harness.run_cell(
        tiny_tokens_manifest, "tiny_tokens.train", seed=2147484005,
        seconds=0.5, trace=True, t0=time.perf_counter(),
        trace_dir=str(tmp_path / "trace"))
    assert result["failed"] == 0 and result["attempted"] >= 4
    check = details["reference_check"]
    assert check["ok"], check
    assert set(tokens.TOLERANCE) <= set(check)
    assert check["routing"]["error"] == 0.0 and check["logits"]["error"] < 1e-4
    assert max(check[n]["error"] for n in laguna_s.GRAD_LEAVES) < 1e-3
    assert check["agreeing_positions"] == check["compared_positions"]
    assert result["correct"] is True, details["state_check"]
    assert details["state_check"]["loss_ok"]
    assert set(tokens.TOLERANCE) <= set(result["compared"])
    # the gauge the program set is what the new reader reads
    load = check["expert_load"]
    assert result["metrics"]["expert_load_max_over_mean"]["value"] \
        == load["expert_load_max_over_mean"] >= 1.0
    assert 0.0 < load["held_slot_share"] < 1.0
    assert result["metrics"]["train_mfu"]["value"] > 0


def test_reference_check_fails_the_next_precision_down(tiny_tokens_manifest,
                                                       monkeypatch):
    """The control: the program's model computed in bfloat16 where the
    configuration states float32 is not correct, by at least one limit."""
    import jax

    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.models import make_apply_fn

    cell = manifest.load_cell(tiny_tokens_manifest, "tiny_tokens.train")
    algo = harness.build(cell, parse_args(harness.program_flags(cell, 3)), 3)
    state = algo.init_state(jax.random.PRNGKey(3))
    tight = {k: 1e-3 for k in tokens.TOLERANCE}
    monkeypatch.setattr(tokens, "TOLERANCE", {**tight, "routing": 0.0})
    ref = harness.reference_of(cell)
    assert tokens.reference_check(algo, state.global_params, ref,
                                  cell.config)["ok"]
    monkeypatch.setattr(algo, "apply_fn",
                        make_apply_fn(algo.model, compute_dtype="bfloat16"))
    control = tokens.reference_check(algo, state.global_params, ref,
                                     cell.config)
    assert not control["ok"]
    assert [n for n in tokens.TOLERANCE if not control[n]["ok"]]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_clients",
                                   "a_third_off_the_step"])
def test_round_check_catches_planted_faults(tiny_tokens_manifest, fault):
    """``reference_check`` drives the program's own compiled round; a round
    that leaves the state as it was, one that folds half the clients, and
    one whose step is a third short each come out as not correct, by the
    round's limits and not by the forward pass's."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.experiments import parse_args

    cell = manifest.load_cell(tiny_tokens_manifest, "tiny_tokens.train")
    algo = harness.build(cell, parse_args(harness.program_flags(cell, 3)), 3)
    state = algo.init_state(jax.random.PRNGKey(3))
    ref, real = harness.reference_of(cell), algo._round_jit
    sound = tokens.reference_check(algo, state.global_params, ref,
                                   cell.config)
    assert sound["ok"], sound
    leaves = list(laguna_s.GRAD_LEAVES)
    assert max(sound[n]["error"] for n in leaves) < 1e-4

    def faulty(st, sel, round_idx, x, y, n):
        if fault == "state_unchanged":
            return st, jnp.float32(sound["round_loss"]["error"])
        if fault == "half_the_clients":     # the later half trains nothing
            return real(st, sel, round_idx, x, y,
                        n.at[sel[len(sel) // 2:]].set(0))
        # lr x 0.998 ** 200: two thirds of the step
        return real(st, sel, jnp.asarray(200, jnp.float32), x, y, n)

    algo._round_jit = faulty
    report = tokens.reference_check(algo, state.global_params, ref,
                                    cell.config)
    assert not report["ok"]
    assert all(report[n]["ok"] for n in ("loss", "logits", "routing"))
    assert not any(report[n]["ok"] for n in leaves), report
    if fault == "state_unchanged":
        assert all(abs(report[n]["error"] - 1.0) < 1e-5 for n in leaves)
    if fault == "half_the_clients":
        control = sound["round_controls"]["half_the_clients"]
        for n in leaves:    # what the report says such a fold would read
            assert abs(report[n]["error"] - control[n]) < 1e-3, n
    if fault == "a_third_off_the_step":
        assert min(report[n]["error"] for n in leaves) > 0.3


def test_scope_kernels_reader_adds_the_nameless_kernels():
    """The reader of the experts' two metrics on a hand-built trace: ops
    under the scope, a ``ragged-dot`` kernel that carries no scope, and an
    op of neither; the union per round, and the rows' floor over it."""
    from benchmarks.readers import scope_kernels

    def op(name, start, end, op_name):
        return reduce_trace.Op(name, start, end, "jit_round_fn", op_name)

    ops = [op("fusion.1", 0, 4e6, "jit(round_fn)/local_train/experts/add"),
           op("ragged-dot-none.3", 3e6, 9e6, "ragged-dot-none"),
           op("fusion.2", 9e6, 20e6, "jit(round_fn)/local_train/mul")]
    reduce_trace.nest(ops)
    cell = manifest.load_cell(MANIFEST, CELL)
    ctx = {"trace": reduce_trace.Trace({"/device:TPU:0": ops}, [], 3),
           "counters": {"steps_per_round_per_chip": 8},
           "layers": tokens.layers(laguna_s, cell.config), "batch": 1,
           "itemsize": 2, "peaks": peaks.PEAKS["TPU v5 lite"], "details": {}}
    args = {"program": "jit_round_fn", "scope": "experts",
            "kernels": ["ragged-dot"]}
    assert scope_kernels.read(ctx, **args) == pytest.approx(9.0 / 3)  # ms
    share = scope_kernels.read(ctx, layers=["experts"], **args)
    floor = ctx["details"]["scope_kernels"]["experts"]["floor_s_per_round"]
    assert share == pytest.approx(100 * floor / 3e-3) and floor > 0
    assert scope_kernels.read(ctx, "jit_round_fn", "nothing", ["no-such"]) \
        is None
    specs = {e["name"]: s for e, s in cell.per_layer}
    assert specs["experts_ms_per_round"]["args"] == args
    assert specs["experts_roofline"]["args"] == {**args,
                                                 "layers": ["experts"]}


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
        == 567_957_504
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
    print(f"{CELL}: round program {gib:.2f} GiB, compiled for a described "
          "v5e")
    assert gib < 15.75
    names = set(reduce_trace.hlo_op_names(compiled.as_text()).values())
    for scope in ("local_train", "aggregate", "attention/full",
                  "attention/window", "router", "experts", "lm_head"):
        assert any(f"/{scope}/" in f"/{s}/" for s in names), scope
