"""One tier-1 case of the benchmark's family seam (ISSUE 27's, left out by
PR 27; the whole rehearsal is benchmarks/tests/test_family_seam.py, which the
driver's tier-1 run does not collect): a configuration of a family that ships
with no cell (2-D images, ten classes, softmax CE: benchmarks/tests/images2d)
loads, builds through the program's own entry points and passes its own
reference check, from files alone."""
import importlib.util
import os
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
