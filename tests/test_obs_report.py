"""Fleet report (obs/report.py) + fleet CLI surfaces (ls / report /
tail --all).

Covers the byte-determinism contract (two generations over the same
catalog are bit-identical; no timestamps anywhere), the report's
content obligations (every cataloged run renders; INCOMPLETE marker;
wire-cost table from the comm metrics), graceful degradation on
missing artifacts, and the CLI exit codes: ``ls`` (2 on empty, --rebuild migration),
``report`` (2 on empty catalog), ``tail --all`` (catalog-resolved
fan-out, 2 when nothing resolves).
"""
import json
import os

from neuroimagedisttraining_tpu.obs import catalog, report
from neuroimagedisttraining_tpu.obs.__main__ import (
    fleet_ls_cli, fleet_report_cli, resolve_all_streams, tail_all,
)


def _write_jsonl(path, records):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def _seed_fleet(tmp_path, n_runs=2):
    """A results tree with cataloged runs: streams + events + catalog."""
    results = str(tmp_path / "results")
    run_dir = os.path.join(results, "synthetic")
    cat = catalog.catalog_path(results)
    for i in range(n_runs):
        ident = f"run-{i}"
        records = [{"round": r, "train_loss": 1.0 / (r + i + 1),
                    "global_acc": 0.1 * (r + 1),
                    "slo_health": "ok" if r < 2 else "degraded",
                    "comm_bytes_wire": 1024.0, "comm_density": 1.0,
                    "comm_n_params": 1000, "comm_n_devices": 2}
                   for r in range(3)]
        jsonl = os.path.join(run_dir, ident + ".obs.jsonl")
        _write_jsonl(jsonl, records)
        ev_path = os.path.join(run_dir, ident + ".events.jsonl")
        _write_jsonl(ev_path, [{"round": 1, "event_type": "SLO_BREACH",
                                "severity": "warning"}])
        e = catalog.build_entry(
            ident, config={"dataset": "synthetic", "algo": "fedavg"},
            final_metrics={"train_loss": 1.0 / (2 + i + 1)},
            slo_health="degraded", rounds_recorded=3,
            event_counts={"SLO_BREACH": 1},
            artifacts={"obs_jsonl": jsonl, "events_jsonl": ev_path},
            completed=(i == 0))
        catalog.append_entry(cat, e, force=True)
    return results, cat


# ---------------------------------------------------------------------------
# report: byte determinism + content
# ---------------------------------------------------------------------------

def test_report_byte_identical_across_generations(tmp_path):
    results, cat = _seed_fleet(tmp_path)
    p1 = str(tmp_path / "fleet1.html")
    p2 = str(tmp_path / "fleet2.html")
    report.write_report(p1, cat)
    report.write_report(p2, cat)
    with open(p1, "rb") as f1, open(p2, "rb") as f2:
        b1, b2 = f1.read(), f2.read()
    assert b1 == b2 and len(b1) > 0


def test_report_renders_every_run_and_markers(tmp_path):
    results, cat = _seed_fleet(tmp_path)
    out = str(tmp_path / "fleet.html")
    report.write_report(out, cat)
    with open(out) as f:
        html = f.read()
    assert "run-0" in html and "run-1" in html
    assert "INCOMPLETE" in html  # run-1 cataloged completed=False
    assert "wire bytes/round" in html  # the comm wire-cost table
    assert "<polyline" in html  # sparklines rendered
    assert "SLO_BREACH" in html


def test_report_degrades_without_artifacts(tmp_path):
    # a catalog pointing at deleted streams still renders its rows
    cat = str(tmp_path / "runs_index.jsonl")
    e = catalog.build_entry("gone", config={"dataset": "synthetic"},
                            artifacts={"obs_jsonl": "/nope/x.jsonl"})
    catalog.append_entry(cat, e, force=True)
    out = str(tmp_path / "fleet.html")
    report.write_report(out, cat)
    with open(out) as f:
        assert "gone" in f.read()



def test_fmt_is_the_single_float_formatter():
    assert report._fmt(True) == "1" and report._fmt(False) == "0"
    assert report._fmt(3) == "3"
    assert report._fmt(0.123456789) == format(0.123456789, ".6g")
    assert report._fmt("<tag>") == "&lt;tag&gt;"  # escaped


# ---------------------------------------------------------------------------
# CLI: ls / report / tail --all
# ---------------------------------------------------------------------------

def test_fleet_ls_cli_lists_and_empty_exit(tmp_path, capsys):
    results, cat = _seed_fleet(tmp_path)
    lines = []
    assert fleet_ls_cli(results, out=lines.append) == 0
    text = "\n".join(lines)
    assert "run-0" in text and "run-1" in text
    assert "NO" in text  # run-1 is incomplete
    assert fleet_ls_cli(str(tmp_path / "empty")) == 2


def test_fleet_ls_cli_rebuild_migrates(tmp_path):
    # streams on disk, no catalog: --rebuild scans them in
    results = str(tmp_path / "results")
    _write_jsonl(os.path.join(results, "synthetic",
                              "old-run.obs.jsonl"),
                 [{"round": 0, "train_loss": 1.0}])
    assert fleet_ls_cli(results, out=lambda s: None) == 2
    lines = []
    assert fleet_ls_cli(results, rebuild=True,
                        out=lines.append) == 0
    assert any("old-run" in ln for ln in lines)


def test_fleet_ls_cli_json(tmp_path):
    results, cat = _seed_fleet(tmp_path)
    lines = []
    assert fleet_ls_cli(results, as_json=True,
                        out=lines.append) == 0
    entries = json.loads("\n".join(lines))
    assert [e["identity"] for e in entries] == ["run-0", "run-1"]


def test_fleet_report_cli(tmp_path):
    results, cat = _seed_fleet(tmp_path)
    assert fleet_report_cli(results, out=lambda s: None) == 0
    assert os.path.exists(os.path.join(results, "fleet_report.html"))
    assert fleet_report_cli(str(tmp_path / "empty")) == 2


def test_resolve_all_streams_prefers_catalog(tmp_path):
    results, cat = _seed_fleet(tmp_path)
    # an uncataloged stray stream in the results root is not listed:
    # the catalog is authoritative when present
    _write_jsonl(os.path.join(results, "stray.obs.jsonl"),
                 [{"round": 0}])
    paths = resolve_all_streams(results)
    assert len(paths) == 2
    assert all(p.endswith(".obs.jsonl") and "run-" in p
               for p in paths)
    # no catalog: fall back to the on-disk glob
    run_dir = os.path.join(results, "synthetic")
    direct = resolve_all_streams(run_dir)
    assert len(direct) == 2
    # a file target is itself
    assert resolve_all_streams(direct[0]) == [direct[0]]


def test_tail_all_prints_newest_line_per_run(tmp_path):
    results, cat = _seed_fleet(tmp_path)
    lines = []
    assert tail_all(results, out=lines.append) == 2
    assert len(lines) == 2
    for ln in lines:
        assert ln.startswith("run-") and "round 2" in ln
    # events fan-out rides the same catalog artifacts
    ev_lines = []
    assert tail_all(results, suffix=".events.jsonl",
                    out=ev_lines.append) == 2
    assert all("SLO_BREACH" in ln for ln in ev_lines)
    assert tail_all(str(tmp_path / "empty")) == 0  # nothing resolves


def test_every_documented_subcommand_exists_and_the_reverse(capsys):
    """The module docstring is the CLI's ``--help`` text: each ``python -m
    neuroimagedisttraining_tpu.obs <subcommand>`` it shows is one the
    parser takes, and each subcommand is shown (PR 31 removed one)."""
    import re

    import pytest

    from neuroimagedisttraining_tpu.obs import __main__ as cli

    documented = set(re.findall(
        r"python -m neuroimagedisttraining_tpu\.obs (\w+)", cli.__doc__))
    with pytest.raises(SystemExit) as done:
        cli.main(["--help"])
    assert done.value.code == 0
    usage = capsys.readouterr().out
    taken = set(re.search(r"\{([\w,]+)\}", usage).group(1).split(","))
    assert documented == taken == {"analyze", "tail", "slo", "ls", "diff",
                                   "report", "watch", "xtrace"}
