"""The benchmark's own tests run on the CPU, on four virtual devices (the
mesh path), at sizes no cell uses: nothing they print is a device number.

    python -m pytest benchmarks/tests -q            # about two minutes
    python -m pytest benchmarks/tests -q -m slow    # compiles every cell for a described v5e
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import pytest  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
MANIFEST = os.path.join(REPO, "BENCHMARK.json")

TINY_CONFIG = {
    "name": "tiny", "source": "test fixture", "reference": "small3dcnn",
    "volume": [8, 8, 8], "stem": {"kernel": 3, "pad": 1},
    "flags": {"algo": "salientgrads", "model": "small3dcnn",
              "dataset": "abcd_site", "layout": "s2d",
              "compute_dtype": "bfloat16", "batch_size": 4, "epochs": 2,
              "lr": 0.01, "dense_ratio": 0.5},
    "cohort": {"n_sites": 4, "train_per_site": 8, "test_per_site": 4},
}


@pytest.fixture
def tiny_manifest(tmp_path):
    """A throw-away benchmark beside the real one: a new configuration at
    8^3 (the zoo's CI model) under the real traffic mixes and per-layer
    metrics. Only data files and manifest entries are new."""
    with open(MANIFEST) as f:
        manifest = json.load(f)
    root = tmp_path / "benchmarks"
    (root / "configs").mkdir(parents=True)
    for shared in ("traffic", "metrics"):
        os.symlink(os.path.join(BENCH, shared), root / shared)
    (root / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    old = {w["name"]: "tiny." + w["traffic"] for w in manifest["workloads"]}
    manifest["configs"] = [{"name": "tiny", "source": "test fixture",
                            "file": "benchmarks/configs/tiny.json",
                            "reduced": [], "why": "CPU rehearsal"}]
    manifest["workloads"] = [
        {"name": "tiny." + t, "config": "tiny", "traffic": t, "chips": c,
         "why": "CPU rehearsal"}
        for t, c in (("train", 1), ("protocol", 1), ("mesh4", 4))]
    for entry in manifest["per_layer"]:
        if "workloads" in entry:
            entry["workloads"] = sorted({old[w] for w in entry["workloads"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)
