"""Telemetry ANALYSIS layer (obs/analyze, health, compile).

Covers the from-recording-to-diagnosis contract: synthetic round
streams with known-injected anomalies must produce exactly the expected
flags in ``analysis.json`` (straggler round index + phase, memory-leak
key, clean stream silent), the host fault-trace replay must agree
bit-for-bit with the in-jit injector's draws, compile events must
attribute to the dispatching obs span, and the whole pipeline must hold
end-to-end through a real ``--obs`` run with ``--fault_spec
straggle=...``.
"""
import json
import os

import numpy as np
import pytest

from neuroimagedisttraining_tpu.obs import (
    analyze,
    compile as obs_compile,
    export,
    health,
    metrics,
    trace,
)


def _stream(n_rounds=12, round_time=0.1, train_loss=0.5):
    return [{"round": r, "train_loss": train_loss,
             "round_time_s": round_time} for r in range(n_rounds)]


# ---------------------------------------------------------------------------
# analyzer on synthetic streams: exact expected flags
# ---------------------------------------------------------------------------

def test_clean_stream_produces_no_flags():
    recs = _stream(20)
    a = analyze.analyze_records(recs, identity="clean")
    analyze.validate_analysis(a)
    assert a["schema_version"] == analyze.ANALYSIS_SCHEMA_VERSION
    assert a["rounds"] == {"count": 20, "first": 0, "last": 19,
                           "missing": [], "duplicates": []}
    assert a["round_time"]["present"]
    assert a["round_time"]["total_s"] == pytest.approx(2.0)
    assert a["outlier_rounds"] == []
    assert a["stragglers"] == []
    assert a["memory"]["leaks_suspected"] == []
    assert a["flags"] == []


def test_injected_straggler_round_flagged_exactly():
    recs = _stream(20)
    recs[7]["round_time_s"] = 0.4  # 4x the 100 ms baseline
    a = analyze.analyze_records(recs, identity="straggler")
    analyze.validate_analysis(a)
    assert [o["round"] for o in a["outlier_rounds"]] == [7]
    assert a["outlier_rounds"][0]["kind"] == "slow"
    assert [s["round"] for s in a["stragglers"]] == [7]
    assert a["stragglers"][0]["source"] == "round_time"
    assert a["flags"] == ["straggler_round_7"]
    # the rest of the stream stays clean
    assert a["memory"]["leaks_suspected"] == []


def test_fault_trace_stamped_straggler_attributed_to_train_phase():
    recs = _stream(12)
    recs[3]["clients_straggled"] = 2.0
    a = analyze.analyze_records(recs, identity="stamped")
    assert [s["round"] for s in a["stragglers"]] == [3]
    s = a["stragglers"][0]
    assert s["phase"] == "train"
    assert s["source"] == "fault_trace"
    assert s["clients_straggled"] == 2.0
    assert a["faults"]["clients_straggled"] == 2.0


def test_monotone_memory_growth_flags_leak():
    recs = _stream(15)
    for r, rec in enumerate(recs):
        rec["mem_host_rss_bytes"] = 1e8 + r * 1e6  # +1 MB/round
        rec["mem_device_bytes_in_use"] = 5e8  # flat: must NOT flag
    a = analyze.analyze_records(recs, identity="leak")
    analyze.validate_analysis(a)
    assert a["memory"]["leaks_suspected"] == ["host_rss"]
    host = a["memory"]["series"]["host_rss"]
    assert host["leak_suspected"]
    assert host["slope_bytes_per_round"] == pytest.approx(1e6, rel=1e-3)
    assert host["increase_fraction"] == 1.0
    assert not a["memory"]["series"]["device_in_use"]["leak_suspected"]
    assert a["flags"] == ["memory_leak_host_rss"]


def test_noisy_flat_memory_not_flagged():
    rng = np.random.RandomState(0)
    recs = _stream(20)
    for r, rec in enumerate(recs):
        rec["mem_host_rss_bytes"] = 1e8 + rng.randint(-5, 6) * 1e5
    a = analyze.analyze_records(recs, identity="noisy")
    assert a["memory"]["leaks_suspected"] == []


def test_missing_and_duplicate_rounds_reported():
    recs = _stream(6)
    del recs[3]  # round 3 missing
    recs.append({"round": 5, "train_loss": 0.1,
                 "round_time_s": 0.1})  # duplicate 5, keep-last
    a = analyze.analyze_records(recs, identity="gaps")
    assert a["rounds"]["missing"] == [3]
    assert a["rounds"]["duplicates"] == [5]
    assert "missing_rounds_1" in a["flags"]
    # the duplicate kept the LAST record
    assert a["faults"]["rounds_with_faults"] == 0


def test_newer_schema_stream_refused():
    recs = [{"round": 0, "obs_schema": export.OBS_SCHEMA_VERSION + 1}]
    with pytest.raises(ValueError, match="obs_schema"):
        analyze.analyze_records(recs)


def test_validate_analysis_catches_violations():
    a = analyze.analyze_records(_stream(5))
    analyze.validate_analysis(a)
    bad = dict(a)
    del bad["stragglers"]
    bad["rounds"] = "nope"
    with pytest.raises(ValueError, match="stragglers"):
        analyze.validate_analysis(bad)


def test_phase_attribution_from_trace_spans():
    t = trace.Tracer(annotate=False)
    with t.span("build"):
        pass
    for r in range(6):
        with t.step_span("round", r):
            with t.span("sample"):
                pass
            with t.span("dispatch_round"):
                pass
        with t.span("eval"):
            pass
    recs = _stream(6, round_time=0.05)
    a = analyze.analyze_records(recs, trace_doc=t.to_chrome_trace(),
                                identity="phases")
    p = a["phases"]
    assert {"sample", "train_dispatch", "eval", "setup",
            "device_and_wait"} <= set(p)
    assert p["sample"]["count"] == 6
    assert p["train_dispatch"]["count"] == 6
    # container "round" spans are skipped -> no double counting
    assert "other_host" not in p or p["other_host"]["count"] == 0
    assert p["device_and_wait"]["total_s"] <= 0.3


# ---------------------------------------------------------------------------
# export hardening: empty / duplicate / out-of-order streams
# ---------------------------------------------------------------------------

def test_read_jsonl_empty_file(tmp_path):
    p = tmp_path / "empty.jsonl"
    p.write_text("")
    assert export.read_jsonl(str(p)) == []
    blank = tmp_path / "blank.jsonl"
    blank.write_text("\n\n  \n")
    assert export.read_jsonl(str(blank)) == []


def test_merge_host_jsonl_tolerates_empty_stream(tmp_path):
    p0, p1 = str(tmp_path / "h0.jsonl"), str(tmp_path / "h1.jsonl")
    w = export.RoundLogWriter(p0, force=True)
    w.write({"round": 0})
    w.close()
    open(p1, "w").close()
    merged = export.merge_host_jsonl([p0, p1])
    assert [(r["round"], r["host"]) for r in merged] == [(0, 0)]


def test_merge_host_jsonl_dedupes_rounds_keep_last(tmp_path):
    p = str(tmp_path / "h0.jsonl")
    w = export.RoundLogWriter(p, force=True)
    w.write({"round": 0, "train_loss": 1.0})
    w.write({"round": 1, "train_loss": 0.9})
    # a rerun under the same identity appended rounds 0..1 again
    w.write({"round": 0, "train_loss": 0.5})
    w.write({"round": 1, "train_loss": 0.4})
    w.close()
    merged = export.merge_host_jsonl([p])
    assert [(r["round"], r["train_loss"]) for r in merged] == [
        (0, 0.5), (1, 0.4)]
    # dedupe=False preserves the raw stream for duplicate auditing
    assert len(export.merge_host_jsonl([p], dedupe=False)) == 4


def test_merge_host_jsonl_sorts_out_of_order(tmp_path):
    p = str(tmp_path / "h0.jsonl")
    w = export.RoundLogWriter(p, force=True)
    for r in (2, 0, 1):
        w.write({"round": r})
    w.close()
    assert [r["round"] for r in export.merge_host_jsonl([p])] == [0, 1, 2]


def test_dedupe_rounds_drops_keyless_records():
    recs = [{"note": "header"}, {"round": 1}, {"round": 0}]
    assert [r["round"] for r in export.dedupe_rounds(recs)] == [0, 1]


# ---------------------------------------------------------------------------
# health: deterministic replay
# ---------------------------------------------------------------------------

def test_fault_trace_replay_matches_injector():
    """The host-side replay must agree bit-for-bit with the in-jit
    injector's draws — the property the analyzer's attribution rests
    on."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.robust.faults import (
        fault_trace_round,
        make_fault_fn,
        parse_fault_spec,
    )

    spec = parse_fault_spec("drop=0.3,straggle=0.4,nan=0.2,scale=0.1")
    fn = make_fault_fn(spec, seed=7)
    n = 16
    sel = jnp.arange(n, dtype=jnp.int32)
    stacked = {"w": jnp.ones((n, 3))}
    global_params = {"w": jnp.zeros((3,))}
    for r in (0, 3, 11):
        faulted, dropped = fn(stacked, global_params, sel,
                              jnp.asarray(float(r), jnp.float32))
        tr = fault_trace_round(spec, 7, r, np.arange(n))
        np.testing.assert_array_equal(np.asarray(dropped), tr["dropped"])
        # poisoned rows are all-NaN in the injected tree
        nan_rows = np.isnan(np.asarray(faulted["w"])).all(axis=1)
        np.testing.assert_array_equal(nan_rows, tr["poisoned"])


def test_health_ledger_participation_and_fault_attribution():
    config = {"client_num_in_total": 8, "client_num_per_round": 8,
              "seed": 0, "fault_spec": "drop=0.5"}
    recs = _stream(10)
    ledger = health.build_health_ledger(recs, config)
    assert ledger["replay"]["participation"]
    assert ledger["replay"]["faults"]
    assert len(ledger["sites"]) == 8
    # full participation: every site in every round
    for s in ledger["sites"].values():
        assert s["rounds_participated"] == 10
    # drop=0.5 over 10 rounds: replay must find drops somewhere, and a
    # site at >= 50% fault rate is degraded
    total_drops = sum(s["dropped"] for s in ledger["sites"].values())
    assert total_drops > 0
    from neuroimagedisttraining_tpu.robust.faults import (
        fault_trace_round,
        parse_fault_spec,
    )

    spec = parse_fault_spec("drop=0.5")
    expect = np.zeros(8, np.int64)
    for r in range(10):
        expect += fault_trace_round(spec, 0, r, np.arange(8))["dropped"]
    got = np.array([ledger["sites"][str(c)]["dropped"]
                    for c in range(8)])
    np.testing.assert_array_equal(got, expect)
    for c in range(8):
        if expect[c] >= 5:
            assert c in ledger["degraded_sites"]


def test_health_acc_trajectory_flags_regressing_site():
    config = {"client_num_in_total": 4, "client_num_per_round": 4,
              "seed": 0}
    recs = _stream(8)
    for r, rec in enumerate(recs):
        per = [0.8, 0.8, 0.8, 0.8]
        per[2] = 0.9 - 0.1 * r  # site 2 collapses
        rec["acc_per_client"] = per
    ledger = health.build_health_ledger(recs, config)
    assert ledger["degraded_sites"] == [2]
    assert ledger["sites"]["2"]["degraded_reasons"] == ["acc_regressing"]
    assert ledger["sites"]["0"]["degraded"] is False
    assert health.render_health(ledger)  # renders without error


def test_replay_preserves_global_numpy_rng_state():
    """The runner stamps fault counts mid-round-loop; the replay must
    not leave np.random side effects behind (sample_client_indexes
    reseeds the global RNG — replay_client_indexes restores it)."""
    np.random.seed(123)
    expect = np.random.rand(3)
    np.random.seed(123)
    health.replay_client_indexes(5, 8, 2)
    fn = health.make_fault_counts_fn("straggle=0.5", 0, 8, 2)
    fn(5)
    got = np.random.rand(3)
    np.testing.assert_array_equal(got, expect)


def test_replay_retry_nonce_redraws_cohort():
    """A watchdog-retried round's accepted attempt trained the
    re-sampled cohort; the replay must honor the nonce."""
    from neuroimagedisttraining_tpu.algorithms.base import (
        sample_client_indexes,
    )

    base = health.replay_client_indexes(3, 16, 4, retry=0)
    retried = health.replay_client_indexes(3, 16, 4, retry=1)
    np.testing.assert_array_equal(
        retried, sample_client_indexes(3, 16, 4, retry=1))
    assert not np.array_equal(base, retried)
    # the ledger consumes the record's rounds_retried stamp
    config = {"client_num_in_total": 16, "client_num_per_round": 4,
              "seed": 0}
    recs = _stream(1)
    recs[0]["rounds_retried"] = 1.0
    ledger = health.build_health_ledger(recs, config)
    got = sorted(int(c) for c, s in ledger["sites"].items()
                 if s["rounds_participated"])
    assert got == sorted(
        int(i) for i in health.replay_client_indexes(0, 16, 4, retry=1))


def test_partial_participation_replay_counts():
    config = {"client_num_in_total": 8, "client_num_per_round": 2,
              "seed": 0}
    ledger = health.build_health_ledger(_stream(6), config)
    total = sum(s["rounds_participated"]
                for s in ledger["sites"].values())
    assert total == 12  # 6 rounds x 2 selected


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_internal_timer_shim_callers():
    """The deprecated ``utils.profiling.Timer`` shim (DeprecationWarning
    pinned in test_obs.py) has no internal callers left — everything
    times through ``obs.metrics``; this lint keeps it that way."""
    import re

    pkg = os.path.join(REPO, "neuroimagedisttraining_tpu")
    pat = re.compile(r"profiling\s+import\s+Timer|profiling\.Timer\s*\(")
    offenders = []
    for root, _, files in os.walk(pkg):
        for f in files:
            if not f.endswith(".py") or f == "profiling.py":
                continue
            path = os.path.join(root, f)
            if pat.search(open(path).read()):
                offenders.append(path)
    assert not offenders, (
        f"deprecated utils.profiling.Timer used by {offenders}; "
        "use obs.metrics.SectionTimer / MetricsRegistry.timer")


# ---------------------------------------------------------------------------
# compile observability
# ---------------------------------------------------------------------------

def test_compile_watch_records_and_attributes_entry():
    import jax
    import jax.numpy as jnp

    reg = metrics.MetricsRegistry()
    watch = obs_compile.CompileWatch(reg).install()
    t = trace.Tracer(annotate=False)
    trace.set_tracer(t)
    try:
        with trace.span("dispatch_round"):
            jax.jit(lambda x: x * 3 + 1)(jnp.ones((7,)))
    finally:
        trace.set_tracer(None)
        watch.uninstall()
    d = reg.distribution("compile_backend_s")
    assert d.count >= 1
    assert d.labels(entry="dispatch_round").count >= 1
    assert reg.counter("compile_events_total").value >= 1
    s = watch.summarize()
    assert s["compile_total_s"] > 0
    assert reg.gauge("compile_total_s").value == s["compile_total_s"]
    # after uninstall, new compiles record nothing
    before = d.count
    jax.jit(lambda x: x - 5)(jnp.ones((9,)))
    assert reg.distribution("compile_backend_s").count == before


def test_jit_cost_analysis_reports_flops():
    import jax
    import jax.numpy as jnp

    reg = metrics.MetricsRegistry()
    out = obs_compile.jit_cost_analysis(
        jax.jit(lambda x: x @ x), jnp.ones((16, 16)),
        registry=reg, entry="matmul")
    assert out["compile_s"] > 0
    assert out["flops"] and out["flops"] > 0
    assert reg.gauge("compile_aot_s").labels(entry="matmul").value > 0


def test_analyze_folds_compile_metrics():
    m = {
        "compile_backend_s": {
            "type": "distribution",
            "value": {"count": 3, "sum": 1.5},
            "labeled": {"entry=dispatch_round": {"count": 2, "sum": 1.2},
                        "entry=eval": {"count": 1, "sum": 0.3}},
        },
        "compile_cache_cache_hits": {"type": "counter", "value": 4.0},
    }
    a = analyze.analyze_records(_stream(5), metrics=m)
    c = a["compile"]
    assert c["present"] and c["total_s"] == pytest.approx(1.5)
    assert c["by_entry"]["dispatch_round"]["total_s"] == \
        pytest.approx(1.2)
    assert c["cache"]["cache_hits"] == 4.0


# ---------------------------------------------------------------------------
# end-to-end: a real --obs run with an injected straggler, analyzed
# ---------------------------------------------------------------------------

def _argv(tmp_path, **over):
    base = {
        "--model": "small3dcnn", "--dataset": "synthetic",
        "--client_num_in_total": "8", "--batch_size": "8",
        "--epochs": "1", "--comm_round": "4", "--lr": "0.05",
        "--final_finetune": "0",
        "--log_dir": str(tmp_path / "LOG"),
        "--results_dir": str(tmp_path / "results"),
    }
    base.update({k: str(v) for k, v in over.items()})
    argv = []
    for k, v in base.items():
        argv += [k, v]
    return argv


def test_e2e_straggle_run_analyzed(tmp_path):
    """Acceptance: an injected straggler round (--fault_spec
    straggle=...) is flagged with the correct round index and the train
    phase, through the real runner -> JSONL -> analyzer pipeline."""
    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_experiment,
    )
    from neuroimagedisttraining_tpu.robust.faults import (
        fault_trace_round,
        parse_fault_spec,
    )

    out = run_experiment(parse_args(_argv(tmp_path) + [
        "--obs", "1", "--trace_dir", str(tmp_path / "tr"),
        "--fault_spec", "straggle=0.4", "--watchdog", "0",
    ], algo="fedavg"), "fedavg")
    run_dir = os.path.join(str(tmp_path), "results", "synthetic")
    analyses = analyze.analyze_run_dir(run_dir,
                                       trace_dir=str(tmp_path / "tr"))
    assert len(analyses) == 1
    a = analyses[0]
    analyze.validate_analysis(a)
    # the analysis.json artifact exists and round-trips
    ap = os.path.join(run_dir, out["identity"] + ".analysis.json")
    assert os.path.exists(ap)
    analyze.validate_analysis(json.load(open(ap)))
    # expected straggler rounds from the deterministic replay
    spec = parse_fault_spec("straggle=0.4")
    expected = []
    for r in range(4):
        tr = fault_trace_round(spec, 0, r, np.arange(8))
        if tr["straggled"].sum():
            expected.append(r)
    got = [s["round"] for s in a["stragglers"]
           if "fault_trace" in s["source"]]
    assert got == expected and expected  # the spec must actually fire
    for s in a["stragglers"]:
        if "fault_trace" in s["source"]:
            assert s["phase"] == "train"
    # JSONL records carry the schema stamp + replayed counts. This run
    # has no --obs_numerics, so every line needs only schema 1 — the
    # stamp is the LOWEST version the record requires (record_schema),
    # keeping numerics-free streams readable by PR-4-era analyzers
    recs = export.read_jsonl(os.path.join(
        run_dir, out["identity"] + ".obs.jsonl"))
    assert all(r["obs_schema"] == 1 for r in recs)
    assert all(r["obs_schema"] in export.SUPPORTED_OBS_SCHEMAS
               for r in recs)
    assert all("clients_straggled" in r for r in recs
               if r["round"] >= 0)
    # per-site eval vectors reached the stream (health's loss source)
    assert any(isinstance(r.get("acc_per_client"), list) for r in recs)
    # compile metrics reached metrics.json and fold into the analysis
    stat = json.load(open(out["stat_path"] + ".json"))
    om = stat["obs_metrics"]
    assert om["obs_schema_version"]["value"] == \
        export.OBS_SCHEMA_VERSION
    assert om["compile_backend_s"]["value"]["count"] >= 1
    assert a["compile"]["present"]
    assert a["compile"]["by_entry"]
    # phases attributed from the trace
    assert "train_dispatch" in a["phases"]
    assert a["health"]["replay"]["faults"]


def test_e2e_cli_analyze(tmp_path):
    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_experiment,
    )
    from neuroimagedisttraining_tpu.obs.__main__ import main as obs_main

    run_experiment(parse_args(_argv(tmp_path) + ["--obs", "1"],
                              algo="fedavg"), "fedavg")
    run_dir = os.path.join(str(tmp_path), "results", "synthetic")
    assert obs_main(["analyze", run_dir]) == 0
    # empty dir -> exit 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert obs_main(["analyze", str(empty)]) == 2
