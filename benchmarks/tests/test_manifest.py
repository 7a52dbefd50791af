"""The manifest and every data file it names load, and stay inside the
contract's limits; a new cell needs new files and entries only."""
import glob
import importlib
import json
import os

import pytest
from conftest import BENCH, MANIFEST, REPO

from benchmarks.lib import manifest as mf


def _manifest():
    with open(MANIFEST) as f:
        return json.load(f)


def test_contract_shape():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(MANIFEST) <= 64 * 1024
    assert 1 <= m["run_seconds"] <= 51
    names = [e["name"] for s in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in m[s]]
    assert len(names) == len(set(names))
    assert all(mf.NAME.match(n) for n in names)
    assert all(len(e["why"]) <= 200 for e in m["configs"] + m["workloads"])
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {c["name"] for c in m["configs"]} == {w["config"]
                                                 for w in m["workloads"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    assert all(0.01 <= e["bound"] <= 0.1 and e["source"] in
               ("host_clock", "device_trace") for e in e2e.values())
    assert all(p["moves"] in e2e and p["source"] in (
        "device_trace", "program_span", "program_counter", "host_clock")
        for p in m["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in _manifest()["workloads"]])
def test_every_cell_loads(cell):
    c = mf.load_cell(MANIFEST, cell)
    assert {e["name"] for e in c.end_to_end} >= {"setup_s", "rounds_per_s"}
    assert c.per_layer
    importlib.import_module("benchmarks.reference." + c.config["reference"])
    for entry, spec in c.per_layer:
        reader = importlib.import_module("benchmarks.readers." + spec["reader"])
        assert callable(reader.read)


@pytest.mark.parametrize("kind,keys", [("configs", mf.CONFIG_KEYS),
                                       ("traffic", mf.TRAFFIC_KEYS),
                                       ("metrics", mf.METRIC_KEYS)])
def test_every_data_file_is_named_and_known(kind, keys):
    m = _manifest()
    known = {"configs": {c["name"] for c in m["configs"]},
             "traffic": {w["traffic"] for w in m["workloads"]},
             "metrics": {p["name"] for p in m["per_layer"]}}[kind]
    paths = glob.glob(os.path.join(BENCH, kind, "*.json"))
    assert {os.path.basename(p)[:-5] for p in paths} == known
    for path in paths:
        name = os.path.basename(path)[:-5]
        assert mf.NAME.match(name)
        assert mf._load(path, keys, name)["name"] == name


def test_config_files_state_their_cut():
    for c in _manifest()["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            doc = json.load(f)
        assert doc["source"] == c["source"]
        assert set(doc["reduced"]) == set(c["reduced"])
        assert doc["volume"] == [121, 145, 121]


def test_new_cell_needs_only_files_and_entries(tiny_manifest):
    cell = mf.load_cell(tiny_manifest, "tiny.protocol")
    assert cell.config["reference"] == "small3dcnn"
    assert cell.traffic["flags"]["frequency_of_the_test"] == 1
    assert "eval_ms_per_round" in {e["name"] for e, _ in cell.per_layer}
    assert "eval_ms_per_round" not in {
        e["name"] for e, _ in mf.load_cell(tiny_manifest, "tiny.train").per_layer}


def test_unknown_parameters_are_errors(tiny_manifest, tmp_path):
    root = tmp_path / "benchmarks"
    os.unlink(root / "traffic")
    (root / "traffic").mkdir()
    traffic = {"name": "train", "flags": {"frac": 0.5}, "block_rounds": 2}
    for bad, match in (({"burst": 3}, "unknown key"),
                       ({"flags": {"batch_size": 8}}, "belong to the config")):
        (root / "traffic" / "train.json").write_text(
            json.dumps({**traffic, **bad}))
        with pytest.raises(ValueError, match=match):
            mf.load_cell(tiny_manifest, "tiny.train")
    with pytest.raises(ValueError, match="no workload"):
        mf.load_cell(tiny_manifest, "tiny.nothing")
