"""What the benchmark needs of the program, held in tier-1 (the driver's
tier-1 run does not collect benchmarks/tests).

One case of the family seam (ISSUE 27's, left out by PR 27; the whole
rehearsal is benchmarks/tests/test_family_seam.py): a configuration of a
family that ships with no cell (2-D images, ten classes, softmax CE:
benchmarks/tests/images2d) loads, builds through the program's own entry
points and passes its own reference check, from files alone.

And the guard a deleting PR needs (PR 31): everything the files under
``benchmarks/`` import from the package, read off ``algo``, pass to the
program's functions or put on its command line still exists. The names come
from the benchmark's source text and data files; nothing under
``benchmarks/`` is executed for it."""
import ast
import glob
import importlib
import importlib.util
import inspect
import json
import os
import re
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "neuroimagedisttraining_tpu"


def _bench_conftest():
    path = os.path.join(REPO, "benchmarks", "tests", "conftest.py")
    spec = importlib.util.spec_from_file_location("_bench_conftest", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_family_without_a_cell_loads_builds_and_checks(tmp_path,
                                                         monkeypatch):
    import json

    bench = _bench_conftest()
    from benchmarks.lib import harness, manifest
    from neuroimagedisttraining_tpu.experiments import parse_args

    for name in ("families.images2d", "reference.lenet5_plain"):
        name = "benchmarks." + name
        monkeypatch.setitem(sys.modules, name, bench._module_from(
            os.path.join(bench.IMAGES, name.rpartition(".")[2] + ".py"),
            name))
    with open(os.path.join(bench.IMAGES, "lenet5_images.json")) as f:
        config = json.load(f)
    path = bench.write_manifest(tmp_path, config, (("train", 1),))
    cell = manifest.load_cell(path, "lenet5_images.train")
    assert cell.family.__name__ == "benchmarks.families.images2d"
    assert cell.config["image"] == [28, 28, 1]
    algo = harness.build(cell, parse_args(harness.program_flags(cell, 3)), 3)
    assert algo.data.x_train.shape == (4, 8, 28, 28, 1)
    assert algo.loss_type == "ce"
    state = algo.init_state(jax.random.PRNGKey(3))
    report = cell.family.reference_check(
        algo, state.global_params, harness.reference_of(cell), cell.config)
    assert report["ok"], report
    assert set(cell.family.TOLERANCE) < set(report)
    # and the shipped families are found by the same lookup
    assert manifest.family_of({}).__name__ == "benchmarks.families.volumes"
    assert manifest.family_of({"family": "tokens"}).__name__ \
        == "benchmarks.families.tokens"


def test_the_selecting_family_loads_builds_and_checks(tmp_path):
    """One case of the ``tokens_selected`` family (the whole rehearsal is
    benchmarks/tests/test_tokens_selected_family.py): a tiny configuration
    of it under the cell's own traffic mix is found by name, builds through
    the program's entry points with ``--lm_vocab_shards``, and passes its
    own reference check: routing and selection the reference's, the indexer
    not moved by the round."""
    bench = _bench_conftest()
    from benchmarks.lib import harness, manifest
    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.models import decoder

    family = manifest.family_of({"family": "tokens_selected"})
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
                   "sequence_length": 32},
        **held}
    assert set(held) <= family.CONFIG_KEYS
    path = bench.write_manifest(tmp_path, config, (("longctx", 1),))
    cell = manifest.load_cell(path, "tiny_selected.longctx")
    assert cell.family is family
    algo = harness.build(cell, parse_args(harness.program_flags(cell, 3)), 3)
    assert algo.data.x_train.shape == (8, 1, 32)
    assert algo.clients_per_round == 2 and algo.data.class_num == 16
    state = algo.init_state(jax.random.PRNGKey(3))
    report = family.reference_check(
        algo, state.global_params, harness.reference_of(cell), cell.config)
    assert report["ok"], report
    assert set(family.TOLERANCE) < set(report)
    assert report["selection"]["error"] == 0.0 == report["routing"]["error"]
    assert report["indexer_q_proj"]["error"] == 0.0
    assert 0.3 < report["expert_load"]["selected_key_share"] < 0.5
    with pytest.raises(ValueError, match="unknown key"):
        manifest.load_cell(bench.write_manifest(
            tmp_path / "bad", {**config, "rope_parameters": {}},
            (("longctx", 1),)), "tiny_selected.longctx")


def test_the_short_conv_family_loads_builds_and_checks(tmp_path):
    """One case of the ``tokens_shortconv`` family (the whole rehearsal is
    benchmarks/tests/test_tokens_shortconv_family.py): a tiny configuration
    of it under the cell's own traffic mix is found by name, builds through
    the program's entry points, and passes its own reference check: the
    routing the reference's, no selection bias moved by the round, the
    gauge of what the bias does set on the way."""
    bench = _bench_conftest()
    from benchmarks.lib import harness, manifest
    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.models import decoder

    family = manifest.family_of({"family": "tokens_shortconv"})
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
                   "sequence_length": 32},
        **held}
    assert set(held) <= family.CONFIG_KEYS
    path = bench.write_manifest(tmp_path, config, (("longctx", 1),))
    cell = manifest.load_cell(path, "tiny_shortconv.longctx")
    assert cell.family is family
    assert {"short_conv_ms_per_round", "short_conv_roofline",
            "expert_bias_swap_share"} <= {e["name"] for e, _ in cell.per_layer}
    algo = harness.build(cell, parse_args(harness.program_flags(cell, 3)), 3)
    assert algo.data.x_train.shape == (8, 1, 32)
    assert algo.clients_per_round == 2 and algo.data.class_num == 32
    state = algo.init_state(jax.random.PRNGKey(3))
    assert "lm_head" not in state.global_params
    report = family.reference_check(
        algo, state.global_params, harness.reference_of(cell), cell.config)
    assert report["ok"], report
    assert set(family.TOLERANCE) < set(report)
    assert report["routing"]["error"] == 0.0
    for layer in range(2, 6):
        assert report[f"expert_bias_layer{layer}"] == {
            "error": 0.0, "tolerance": 0.0, "ok": True}
    assert report["expert_load"]["expert_bias_swap_share"] > 0.1
    with pytest.raises(ValueError, match="unknown key"):
        manifest.load_cell(bench.write_manifest(
            tmp_path / "bad", {**config, "sa_config": {}},
            (("longctx", 1),)), "tiny_shortconv.longctx")


def test_the_hybrid_family_loads_builds_and_checks(tmp_path):
    """One case of the ``tokens_hybrid`` family (the whole rehearsal is
    benchmarks/tests/test_tokens_hybrid_family.py): a tiny configuration of
    it under the cell's own traffic mix is found by name, builds through the
    program's entry points with ``--lm_ssm_shards`` and ``--lm_mlp_shards``,
    and passes its own reference check (the program's chunked scan against
    the reference's token-by-token recurrence in the forward pass and in one
    compiled round), the carry's gauge set on the way."""
    bench = _bench_conftest()
    from benchmarks.lib import harness, manifest
    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.models import decoder

    family = manifest.family_of({"family": "tokens_hybrid"})
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
                   "sequence_length": 32},
        **held}
    assert set(held) <= family.CONFIG_KEYS
    path = bench.write_manifest(tmp_path, config, (("longctx", 1),))
    cell = manifest.load_cell(path, "tiny_hybrid.longctx")
    assert cell.family is family
    assert {"ssm_ms_per_round", "ssm_scan_ms_per_round", "ssm_roofline",
            "ssm_scan_roofline", "ssm_chunk_carry"} <= {
                e["name"] for e, _ in cell.per_layer}
    argv = harness.program_flags(cell, 3)
    assert argv[argv.index("--lm_ssm_shards") + 1] == "2"
    assert argv[argv.index("--lm_mlp_shards") + 1] == "2"
    algo = harness.build(cell, parse_args(argv), 3)
    assert algo.data.x_train.shape == (8, 1, 32)
    assert algo.clients_per_round == 2 and algo.data.class_num == 16
    state = algo.init_state(jax.random.PRNGKey(3))
    report = family.reference_check(
        algo, state.global_params, harness.reference_of(cell), cell.config)
    assert report["ok"], report
    assert set(family.TOLERANCE) < set(report)
    assert report["compared_positions"] == 1
    assert max(report[n]["error"] for n in family.FOLD_LEAVES) < 1e-3
    assert 0.1 < report["ssm_carry"]["ssm_chunk_carry"] < 1.0
    with pytest.raises(ValueError, match="unknown key"):
        manifest.load_cell(bench.write_manifest(
            tmp_path / "bad", {**config, "num_experts": 8},
            (("longctx", 1),)), "tiny_hybrid.longctx")


# ---------------------------------------------------------------------------
# the static guard
# ---------------------------------------------------------------------------

def _benchmark_files():
    """Every module under ``benchmarks/`` (its own tests apart): path
    relative to the repo -> source."""
    out = {}
    for path in sorted(glob.glob(os.path.join(REPO, "benchmarks", "**",
                                              "*.py"), recursive=True)):
        rel = os.path.relpath(path, REPO)
        if not rel.startswith(os.path.join("benchmarks", "tests")):
            with open(path) as f:
                out[rel] = f.read()
    return out


_FILES = _benchmark_files()
#: those whose text names the package
_SOURCES = {rel: src for rel, src in _FILES.items() if PACKAGE in src}


@pytest.mark.parametrize("rel", sorted(_SOURCES))
def test_what_a_benchmark_module_names_of_the_package_exists(rel):
    """Each ``from neuroimagedisttraining_tpu... import name`` and ``import
    neuroimagedisttraining_tpu...`` resolves, wherever in the module it
    stands, and each file of the package its text cites is there."""
    source = _SOURCES[rel]
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] == PACKAGE:
                    importlib.import_module(alias.name)
        elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                and (node.module or "").split(".")[0] == PACKAGE:
            module = importlib.import_module(node.module)
            for alias in node.names:
                if not hasattr(module, alias.name):   # a submodule, then
                    importlib.import_module(f"{node.module}.{alias.name}")
    for cited in re.findall(PACKAGE + r"/[\w/]+\.py", source):
        assert os.path.exists(os.path.join(REPO, cited)), (rel, cited)


def _calls_on(tree, owner: str, attr: str):
    """The ``owner.attr(...)`` calls of a module."""
    return [node for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == attr
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == owner]


def _binds(call, fn, skip_self=False):
    """The call's positional count and keywords fit ``fn``'s signature."""
    args = [None] * (len(call.args) + int(skip_self))
    inspect.signature(fn).bind(*args, **{k.arg: None for k in call.keywords})


def test_what_the_harness_reads_off_the_program_is_still_there():
    """The attributes the benchmark reads off ``algo``, the way it calls
    ``run``, ``build_algorithm`` and ``maybe_shard``, and every flag its
    data files put on the program's command line."""
    from neuroimagedisttraining_tpu.algorithms import SalientGrads
    from neuroimagedisttraining_tpu.analysis.identity import collect_flags
    from neuroimagedisttraining_tpu.core.state import HyperParams
    from neuroimagedisttraining_tpu.data import make_synthetic_federated
    from neuroimagedisttraining_tpu.experiments import runner
    from neuroimagedisttraining_tpu.models import create_model

    assert os.path.join("benchmarks", "lib", "harness.py") in _SOURCES
    trees = {rel: ast.parse(src) for rel, src in _FILES.items()}
    read = {node.attr for tree in trees.values() for node in ast.walk(tree)
            if isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name) and node.value.id == "algo"}
    # the seam as ISSUE 31 found it: a benchmark PR may read more or less
    assert {"run", "init_state", "clone_state", "_round_jit", "_donate",
            "eval_cache", "client_chunk", "apply_fn", "hp", "data",
            "clients_per_round", "loss_type", "compute_dtype"} <= read
    algo = SalientGrads(
        create_model("small3dcnn", num_classes=1),
        make_synthetic_federated(n_clients=2, samples_per_client=4,
                                 test_per_client=2,
                                 sample_shape=(8, 8, 8, 1)),
        HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                    batch_size=4),
        loss_type="bce", frac=1.0, seed=0, dense_ratio=0.5)
    assert not [name for name in sorted(read) if not hasattr(algo, name)]

    harness = trees[os.path.join("benchmarks", "lib", "harness.py")]
    runs = _calls_on(harness, "algo", "run")
    builds = _calls_on(harness, "runner", "build_algorithm")
    shards = _calls_on(harness, "runner", "maybe_shard")
    assert runs and builds and shards
    for call in runs:
        _binds(call, type(algo).run, skip_self=True)
        assert "fuse_rounds" in {k.arg for k in call.keywords}
    for call in builds:
        _binds(call, runner.build_algorithm)
        assert "data" in {k.arg for k in call.keywords}
    for call in shards:
        _binds(call, runner.maybe_shard)

    with open(os.path.join(REPO, PACKAGE, "experiments", "config.py")) as f:
        known = set(collect_flags(f.read()))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    passed = {"client_num_in_total", "mesh_devices", "seed"}  # program_flags
    for entry in bench["configs"]:
        with open(os.path.join(REPO, entry["file"])) as f:
            passed |= set(json.load(f)["flags"])
    for path in glob.glob(os.path.join(REPO, "benchmarks", "traffic",
                                       "*.json")):
        with open(path) as f:
            passed |= set(json.load(f)["flags"])
    assert not sorted(passed - known)
