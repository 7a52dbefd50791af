"""Fleet run catalog (obs/catalog.py): entries, dedupe, rebuild.

Covers the catalog contract surface: byte-deterministic entry lines
(sorted keys, no timestamps), the keep-last ``(dataset, identity)``
rerun semantics of the read path, the identity-flags-only ``flags``
block (inert/unkeyed knobs never enter the entry), the final-metrics
fold ordering (the round=-1 final record folds LAST, matching the
live session), the two completion signals the rebuild path reads
(round=-1 record OR metrics.json), scan/rebuild over on-disk run
dirs, and the ObsSession close-path append (crashed runs catalog
with completed=False; finished runs with True).
"""
import json
import os

from neuroimagedisttraining_tpu.obs import catalog, export


def _write_jsonl(path, records):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


# ---------------------------------------------------------------------------
# entry construction
# ---------------------------------------------------------------------------

def test_build_entry_keeps_only_identity_flags():
    config = {"dataset": "synthetic", "algo": "fedavg",
              "fault_spec": "nan=0.4", "watchdog": 1,
              "obs_catalog": 1, "fuse_rounds": 4,
              "checkpoint_dir": "/tmp/x"}
    e = catalog.build_entry("run-a", config=config)
    assert "fault_spec" in e["flags"] and "watchdog" in e["flags"]
    # inert knobs (hard-rule obs_ prefix, census-inert fuse_rounds,
    # unkeyed checkpoint_dir) stay out of the entry
    for absent in ("obs_catalog", "fuse_rounds", "checkpoint_dir"):
        assert absent not in e["flags"]
    assert e["dataset"] == "synthetic" and e["algo"] == "fedavg"
    assert e["catalog_schema"] == catalog.CATALOG_SCHEMA_VERSION


def test_build_entry_json_safe_config_stringifies():
    e = catalog.build_entry(
        "run-a", config={"dataset": "s", "fault_spec": ("a", "b")})
    assert e["flags"]["fault_spec"] == str(("a", "b"))


def test_final_metrics_fold_final_record_last():
    # the round=-1 final-eval record sorts FIRST in a deduped stream
    # but was recorded LAST — its values must win the fold
    records = [
        {"round": -1, "global_acc": 0.9},
        {"round": 0, "train_loss": 1.0, "global_acc": 0.1},
        {"round": 1, "train_loss": 0.5, "global_acc": 0.2},
    ]
    fm = catalog.final_metrics_from_records(records)
    assert fm == {"train_loss": 0.5, "global_acc": 0.9}


def test_final_metrics_ignore_non_numeric_and_bools():
    fm = catalog.final_metrics_from_records(
        [{"round": 0, "train_loss": "oops", "global_acc": True}])
    assert fm == {}


# ---------------------------------------------------------------------------
# append / read: keep-last rerun semantics, byte determinism
# ---------------------------------------------------------------------------

def test_append_and_read_keep_last_per_dataset_identity(tmp_path):
    path = str(tmp_path / "runs_index.jsonl")
    e1 = catalog.build_entry("run-a", config={"dataset": "synthetic"},
                             rounds_recorded=2)
    e2 = catalog.build_entry("run-a", config={"dataset": "synthetic"},
                             rounds_recorded=5)
    e3 = catalog.build_entry("run-b", config={"dataset": "synthetic"})
    for e in (e1, e2, e3):
        assert catalog.append_entry(path, e, force=True)
    raw = catalog.read_catalog(path, dedupe=False)
    assert len(raw) == 3
    deduped = catalog.read_catalog(path)
    assert [e["identity"] for e in deduped] == ["run-a", "run-b"]
    assert deduped[0]["rounds_recorded"] == 5  # the rerun superseded


def test_append_is_byte_deterministic(tmp_path):
    p1, p2 = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    e = catalog.build_entry("run-a", config={"dataset": "s"},
                            final_metrics={"train_loss": 0.25},
                            event_counts={"SLO_BREACH": 2})
    catalog.append_entry(p1, e, force=True)
    catalog.append_entry(p2, e, force=True)
    with open(p1, "rb") as f1, open(p2, "rb") as f2:
        assert f1.read() == f2.read()


def test_read_catalog_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "runs_index.jsonl")
    e = catalog.build_entry("run-a", config={"dataset": "s"})
    catalog.append_entry(path, e, force=True)
    with open(path, "a") as f:
        f.write('{"identity": "torn')  # killed mid-append
    assert [x["identity"] for x in catalog.read_catalog(path)] == \
        ["run-a"]


def test_read_catalog_missing_file_is_empty(tmp_path):
    assert catalog.read_catalog(str(tmp_path / "nope.jsonl")) == []


# ---------------------------------------------------------------------------
# rebuild path: entry_from_run / scan / rebuild
# ---------------------------------------------------------------------------

def _seed_run(run_dir, identity, with_final=False,
              with_metrics_json=False, health=None):
    records = [{"round": r, "obs_schema": export.OBS_SCHEMA_VERSION,
                "train_loss": 1.0 / (r + 1)} for r in range(3)]
    if health:
        for rec, h in zip(records, health):
            rec["slo_health"] = h
    if with_final:
        records.append({"round": -1, "global_acc": 0.75,
                        "obs_schema": export.OBS_SCHEMA_VERSION})
    _write_jsonl(os.path.join(run_dir, identity + ".obs.jsonl"),
                 records)
    _write_jsonl(os.path.join(run_dir, identity + ".events.jsonl"),
                 [{"round": 1, "event_type": "SLO_BREACH",
                   "severity": "warning"},
                  {"round": 2, "event_type": "SLO_BREACH",
                   "severity": "warning"},
                  {"round": 2, "event_type": "SLO_RECOVERY",
                   "severity": "info"}])
    with open(os.path.join(run_dir, identity + ".json"), "w") as f:
        json.dump({"config": {"dataset": "synthetic", "algo": "fedavg",
                              "fault_spec": "nan=0.1"}}, f)
    if with_metrics_json:
        with open(os.path.join(run_dir,
                               identity + ".metrics.json"), "w") as f:
            json.dump({}, f)


def test_entry_from_run_reads_artifacts(tmp_path):
    run_dir = str(tmp_path / "synthetic")
    os.makedirs(run_dir)
    _seed_run(run_dir, "run-a", with_final=True,
              health=["ok", "degraded", "degraded"])
    e = catalog.entry_from_run(run_dir, "run-a")
    assert e["rounds_recorded"] == 3  # round=-1 does not count
    assert e["completed"] is True  # the -1 record is the signal
    assert e["final_metrics"]["global_acc"] == 0.75
    assert e["slo_health"] == "degraded"
    assert e["event_counts"] == {"SLO_BREACH": 2, "SLO_RECOVERY": 1}
    assert e["flags"]["fault_spec"] == "nan=0.1"
    assert e["flags"]["dataset"] == "synthetic"  # identity flag
    assert e["obs_schema_version"] == export.OBS_SCHEMA_VERSION
    arts = e["artifacts"]
    assert os.path.exists(arts["obs_jsonl"])
    assert os.path.exists(arts["events_jsonl"])


def test_entry_from_run_completion_signals(tmp_path):
    run_dir = str(tmp_path / "synthetic")
    os.makedirs(run_dir)
    # neither a -1 record nor metrics.json: the run died mid-flight
    _seed_run(run_dir, "crashed")
    assert catalog.entry_from_run(run_dir, "crashed")["completed"] \
        is False
    # metrics.json alone marks completion (final eval disabled —
    # finish() always writes the snapshot before closing)
    _seed_run(run_dir, "no-eval", with_metrics_json=True)
    assert catalog.entry_from_run(run_dir, "no-eval")["completed"] \
        is True


def test_scan_and_rebuild(tmp_path):
    results = str(tmp_path / "results")
    run_dir = os.path.join(results, "synthetic")
    os.makedirs(run_dir)
    _seed_run(run_dir, "run-a", with_final=True)
    _seed_run(run_dir, "run-b")
    entries = catalog.scan(run_dir)
    assert [e["identity"] for e in entries] == ["run-a", "run-b"]
    n = catalog.rebuild(results, force=True)
    assert n == 2
    back = catalog.read_catalog(catalog.catalog_path(results))
    assert [e["identity"] for e in back] == ["run-a", "run-b"]
    # a rebuild over the same disk state is byte-identical
    with open(catalog.catalog_path(results), "rb") as f:
        first = f.read()
    catalog.rebuild(results, force=True)
    with open(catalog.catalog_path(results), "rb") as f:
        assert f.read() == first


def test_scan_missing_dir_is_empty(tmp_path):
    assert catalog.scan(str(tmp_path / "nope")) == []


# ---------------------------------------------------------------------------
# live path: ObsSession close-time append
# ---------------------------------------------------------------------------

def _session(tmp_path, **kw):
    run_dir = str(tmp_path / "results" / "synthetic")
    cat = catalog.catalog_path(str(tmp_path / "results"))
    info = {"config": {"dataset": "synthetic", "algo": "fedavg",
                       "fault_spec": "drop=0.2"},
            "git_sha": "abc123"}
    s = export.ObsSession(
        jsonl_path=os.path.join(run_dir, "live-run.obs.jsonl"),
        identity="live-run", catalog_path=cat, catalog_info=info,
        **kw)
    return s, cat


def test_session_finish_catalogs_completed(tmp_path):
    s, cat = _session(tmp_path)
    s.record_round({"round": 0, "train_loss": 1.0})
    s.record_round({"round": 1, "train_loss": 0.5,
                    "global_acc": 0.8})
    s.finish()
    entries = catalog.read_catalog(cat)
    assert len(entries) == 1
    e = entries[0]
    assert e["identity"] == "live-run" and e["completed"] is True
    assert e["rounds_recorded"] == 2
    assert e["final_metrics"] == {"train_loss": 0.5,
                                  "global_acc": 0.8}
    assert e["git_sha"] == "abc123"
    assert e["flags"]["fault_spec"] == "drop=0.2"


def test_session_crash_path_catalogs_incomplete(tmp_path):
    s, cat = _session(tmp_path)
    s.record_round({"round": 0, "train_loss": 1.0})
    s.close()  # the runner's finally path — finish() never ran
    (e,) = catalog.read_catalog(cat)
    assert e["completed"] is False and e["rounds_recorded"] == 1


def test_session_close_after_finish_appends_once(tmp_path):
    s, cat = _session(tmp_path)
    s.record_round({"round": 0, "train_loss": 1.0})
    s.finish()
    s.close()  # idempotent: finish already closed
    assert len(catalog.read_catalog(cat, dedupe=False)) == 1


def test_session_without_catalog_path_writes_nothing(tmp_path):
    # --obs_catalog 0: the runner passes catalog_path="" and the
    # session never touches the index
    run_dir = str(tmp_path / "results" / "synthetic")
    cat = catalog.catalog_path(str(tmp_path / "results"))
    s = export.ObsSession(
        jsonl_path=os.path.join(run_dir, "off.obs.jsonl"),
        identity="off")
    s.record_round({"round": 0, "train_loss": 1.0})
    s.finish()
    assert not os.path.exists(cat)


# ---------------------------------------------------------------------------
# the git SHA a catalog entry carries (utils/records.git_sha)
# ---------------------------------------------------------------------------

def test_git_sha_is_head_inside_a_repository_and_empty_outside(
        tmp_path, monkeypatch):
    import subprocess

    from neuroimagedisttraining_tpu.utils.records import git_sha

    # wherever the temporary directory lies, git looks no further up
    monkeypatch.setenv("GIT_CEILING_DIRECTORIES", str(tmp_path))
    outside = tmp_path / "plain"
    outside.mkdir()
    assert git_sha(str(outside)) == ""     # never raises, never guesses
    assert git_sha(str(tmp_path / "missing")) == ""
    repo = tmp_path / "repo"
    repo.mkdir()
    git = ["git", "-c", "user.name=t", "-c", "user.email=t@example.org"]
    for cmd in (["init", "-q"], ["commit", "-q", "--allow-empty", "-m", "x"]):
        subprocess.run(git + cmd, cwd=repo, check=True, capture_output=True)
    head = subprocess.run(["git", "rev-parse", "HEAD"], cwd=repo, check=True,
                          capture_output=True, text=True).stdout.strip()
    assert len(head) == 40 and git_sha(str(repo)) == head
