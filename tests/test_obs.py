"""Observability subsystem (obs/): tracer, registry, sinks, runner wiring.

Covers the obs contract surface: span nesting + Chrome trace-event
schema, registry counter/gauge/distribution semantics, the bounded
label-cardinality guard, disabled-mode being a true no-op (obs off is
bit-identical to pre-obs behavior; obs knobs never enter run/checkpoint
identity), the per-round JSONL schema including fault_recovery fields,
and the multihost process-0-only export rule.
"""
import json
import os
import warnings

import numpy as np
import pytest

from neuroimagedisttraining_tpu.obs import export, memory, metrics, trace


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_schema(tmp_path):
    t = trace.Tracer(annotate=False)
    with t.span("outer") as so:
        so.add("clients", 8)
        with t.span("inner"):
            pass
        with t.span("inner"):
            pass
    with t.step_span("round", 3):
        pass
    events = t.events
    assert [e["name"] for e in events] == ["inner", "inner", "outer",
                                           "round"]
    for e in events:
        assert e["ph"] == "X"
        assert e["dur"] >= 0 and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    outer = events[2]
    assert outer["args"]["clients"] == 8
    for inner in events[:2]:  # time containment = viewer nesting
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert inner["args"]["depth"] == 1
    assert events[3]["args"]["step"] == 3
    path = t.write(str(tmp_path / "sub" / "trace.json"))
    doc = json.load(open(path))  # Perfetto-loadable: one JSON object
    assert doc["traceEvents"] == events
    assert doc["displayTimeUnit"] == "ms"


def test_null_tracer_is_shared_singleton_noop():
    # zero-cost disabled mode: same object back every time, no state
    s1 = trace.span("anything")
    s2 = trace.span("else")
    assert s1 is s2
    with s1 as sp:
        sp.add("k", 1)  # dropped silently
    assert not trace.tracing_enabled()
    assert trace.get_tracer() is trace.NULL_TRACER


def test_set_tracer_install_and_restore():
    t = trace.Tracer(annotate=False)
    trace.set_tracer(t)
    try:
        assert trace.tracing_enabled()
        with trace.span("via_module"):
            pass
        # (a process's first tracer also holds its import_program event)
        assert t.events[-1]["name"] == "via_module"
    finally:
        trace.set_tracer(None)
    assert not trace.tracing_enabled()


def test_tracer_event_cap_counts_drops(tmp_path):
    t = trace.Tracer(annotate=False, max_events=2)
    for i in range(5):
        with t.span("s"):
            pass
    assert len(t.events) == 2
    doc = json.load(open(t.write(str(tmp_path / "t.json"))))
    assert doc["obs_dropped_events"] == 3


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_gauge_distribution_semantics():
    reg = metrics.MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    assert g.value is None
    g.set(7)
    g.set(3)
    assert g.value == 3.0
    d = reg.distribution("d")
    for v in range(1, 101):
        d.observe(v)
    snap = d.snapshot()["value"]
    assert snap["count"] == 100 and snap["sum"] == 5050.0
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    assert snap["last"] == 100.0
    # reservoir holds the full sample below RESERVOIR_SIZE -> exact-ish
    assert abs(snap["p50"] - 50) <= 1
    assert abs(snap["p99"] - 99) <= 1
    # same-name different-type is an explicit error, not silent aliasing
    with pytest.raises(TypeError):
        reg.counter("g")
    # registry snapshot is JSON-serializable
    json.dumps(reg.snapshot())


def test_distribution_reservoir_bounded():
    d = metrics.Distribution("d", reservoir_size=16)
    for v in range(10_000):
        d.observe(float(v))
    assert len(d._reservoir) == 16
    assert d.count == 10_000
    assert d.quantile(0.5) is not None
    # labeled children inherit the parent's reservoir bound
    child = d.labels(impl="x")
    assert child._reservoir_size == 16
    # reservoir RNG seed is hash-salt-free: two same-name instances fed
    # the same stream report identical quantiles (the determinism the
    # class documents — hash(name) would break under PYTHONHASHSEED)
    d2 = metrics.Distribution("d", reservoir_size=16)
    for v in range(10_000):
        d2.observe(float(v))
    assert d2.quantile(0.5) == d.quantile(0.5)
    assert d2._reservoir == d._reservoir


def test_label_cardinality_guard_raises():
    reg = metrics.MetricsRegistry(max_label_sets=3)
    c = reg.counter("labeled")
    for i in range(3):
        c.labels(impl=str(i)).inc()
    # existing label-sets keep working at the bound
    c.labels(impl="0").inc()
    assert c.labels(impl="0").value == 2.0
    with pytest.raises(metrics.LabelCardinalityError):
        c.labels(impl="3")
    # labeled children land in the snapshot
    snap = reg.snapshot()["labeled"]
    assert snap["labeled"]["impl=0"] == 2.0


def test_registry_timer_elapsed_readable():
    reg = metrics.MetricsRegistry()
    with reg.timer("sec") as h:
        pass
    assert h.elapsed >= 0.0
    assert reg.distribution("sec").count == 1


def test_section_timer_summary_shape():
    t = metrics.SectionTimer()
    with t.section("a"):
        pass
    with t.section("a"):
        pass
    s = t.summary()
    assert s["a"]["count"] == 2
    assert s["a"]["total_s"] >= 0
    assert s["a"]["mean_s"] == pytest.approx(s["a"]["total_s"] / 2)


def test_profiling_timer_shim_deprecated():
    from neuroimagedisttraining_tpu.utils.profiling import Timer

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        t = Timer()
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    with t.section("s"):
        pass
    assert t.summary()["s"]["count"] == 1


# ---------------------------------------------------------------------------
# memory
# ---------------------------------------------------------------------------

def test_memory_sampling_sets_gauges():
    reg = metrics.MetricsRegistry()
    wm = memory.MemoryWatermark(reg, sample_every=2)
    wm.maybe_sample(1)  # off-cadence: no sample
    assert wm.samples == 0
    wm.maybe_sample(2)
    assert wm.samples == 1
    assert reg.gauge("mem_host_rss_bytes").value > 0
    devs = memory.device_memory()
    assert devs and all("bytes_in_use" in d for d in devs)
    assert devs[0]["source"] in ("memory_stats", "live_arrays")


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def test_jsonl_writer_and_merge(tmp_path, monkeypatch):
    p0 = str(tmp_path / "h0.jsonl")
    w = export.RoundLogWriter(p0)
    assert w.exports
    w.write({"round": 0, "train_loss": 1.0})
    w.write({"round": 1, "train_loss": np.float32(0.5)})  # np scalar ok
    w.close()
    recs = export.read_jsonl(p0)
    assert [r["round"] for r in recs] == [0, 1]
    # malformed lines raise with position, never parse silently
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"round": 0}\nnot json\n')
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        export.read_jsonl(str(bad))
    # merge folds per-host streams into one (round, host)-sorted timeline
    p1 = str(tmp_path / "h1.jsonl")
    w1 = export.RoundLogWriter(p1, force=True)
    w1.write({"round": 0, "train_loss": 2.0})
    w1.close()
    merged = export.merge_host_jsonl([p0, p1])
    assert [(r["round"], r["host"]) for r in merged] == [
        (0, 0), (0, 1), (1, 0)]


def test_nonzero_process_never_exports(tmp_path, monkeypatch):
    # the multihost rule: every process records, only process 0 exports
    monkeypatch.setattr(export, "_process_index", lambda: 1)
    p = str(tmp_path / "h1.jsonl")
    w = export.RoundLogWriter(p)
    assert not w.exports
    w.write({"round": 0})
    w.close()
    assert not os.path.exists(p)
    sess = export.ObsSession(jsonl_path=p,
                             trace_dir=str(tmp_path / "tr"),
                             identity="x")
    try:
        sess.record_round({"round": 0, "train_loss": 1.0})
        snap = sess.finish()
    finally:
        sess.close()
    # records flowed into the registry, but no files were exported
    assert snap["rounds_recorded"]["value"] == 1.0
    assert not os.path.exists(p)
    assert not os.path.exists(str(tmp_path / "tr"))


# ---------------------------------------------------------------------------
# runner wiring (e2e)
# ---------------------------------------------------------------------------

def _argv(tmp_path, **over):
    base = {
        "--model": "small3dcnn",
        "--dataset": "synthetic",
        "--client_num_in_total": "4",
        "--batch_size": "8",
        "--epochs": "1",
        "--comm_round": "2",
        "--lr": "0.05",
        "--final_finetune": "0",
        "--log_dir": str(tmp_path / "LOG"),
        "--results_dir": str(tmp_path / "results"),
    }
    base.update({k: str(v) for k, v in over.items()})
    argv = []
    for k, v in base.items():
        argv += [k, v]
    return argv


def test_obs_knobs_never_enter_identity(tmp_path):
    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_identity,
    )

    plain = parse_args(_argv(tmp_path), algo="fedavg")
    obs = parse_args(_argv(tmp_path) + [
        "--obs", "1", "--obs_jsonl", str(tmp_path / "x.jsonl"),
        "--trace_dir", str(tmp_path / "tr"), "--obs_sample_every", "4",
    ], algo="fedavg")
    for ck in (False, True):
        assert run_identity(plain, "fedavg", for_checkpoint=ck) == \
            run_identity(obs, "fedavg", for_checkpoint=ck)


def test_obs_off_bit_identical_and_on_produces_artifacts(tmp_path):
    import jax

    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_experiment,
    )

    out_off = run_experiment(
        parse_args(_argv(tmp_path / "off"), algo="fedavg"), "fedavg")
    out_on = run_experiment(
        parse_args(_argv(tmp_path / "on") + [
            "--obs", "1", "--trace_dir", str(tmp_path / "tr")],
            algo="fedavg"), "fedavg")
    # the model trajectory is untouched by telemetry
    for a, b in zip(
            jax.tree_util.tree_leaves(out_off["state"].global_params),
            jax.tree_util.tree_leaves(out_on["state"].global_params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # history identical up to the obs-only round_time_s stamp
    for h_off, h_on in zip(out_off["history"], out_on["history"]):
        h_on = {k: v for k, v in h_on.items() if k != "round_time_s"}
        assert h_off == h_on
    # artifacts: JSONL with every round, metrics.json merged in stat_info,
    # Perfetto-loadable trace
    jsonl = os.path.join(str(tmp_path / "on"), "results", "synthetic",
                         out_on["identity"] + ".obs.jsonl")
    recs = export.read_jsonl(jsonl)
    assert [r["round"] for r in recs] == [0, 1]
    assert all("train_loss" in r and "round_time_s" in r for r in recs)
    stat = json.load(open(out_on["stat_path"] + ".json"))
    assert "obs_metrics" in stat
    assert stat["obs_metrics"]["rounds_recorded"]["value"] == 2.0
    assert stat["obs_metrics"]["mem_host_rss_bytes"]["value"] > 0
    tr = json.load(open(os.path.join(
        str(tmp_path / "tr"), out_on["identity"] + ".trace.json")))
    names = {e["name"] for e in tr["traceEvents"]}
    assert {"build", "init_state", "sample", "dispatch_round",
            "round", "eval"} <= names


def test_jsonl_fault_recovery_fields_and_fused(tmp_path):
    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_experiment,
    )

    # faulted run: per-round guard counters must reach the JSONL stream
    out = run_experiment(parse_args(_argv(
        tmp_path, **{"--comm_round": "3", "--client_num_in_total": "8"}
    ) + ["--obs", "1", "--fault_spec", "drop=0.3,nan=0.2",
         "--watchdog", "0"], algo="fedavg"), "fedavg")
    jsonl = os.path.join(str(tmp_path), "results", "synthetic",
                         out["identity"] + ".obs.jsonl")
    recs = export.read_jsonl(jsonl)
    rounds = [r["round"] for r in recs]
    assert rounds == sorted(rounds) == [0, 1, 2]
    assert all("clients_dropped" in r and "clients_quarantined" in r
               for r in recs)
    stat = json.load(open(out["stat_path"] + ".json"))
    om = stat["obs_metrics"]
    # RunCounters mirrored its totals into the registry, and they agree
    # with the authoritative stat_info fault_recovery block
    fr = stat["fault_recovery"]
    if fr.get("clients_dropped"):
        assert om["fault_clients_dropped_total"]["value"] == \
            fr["clients_dropped"]
    assert om["fault_recovery_clients_dropped"]["value"] == \
        fr["clients_dropped"]

    # fused path: records arrive at block granularity, same JSONL schema
    out_f = run_experiment(parse_args(_argv(
        tmp_path / "fused", **{"--comm_round": "4"}
    ) + ["--obs", "1", "--fuse_rounds", "2"], algo="fedavg"), "fedavg")
    jsonl_f = os.path.join(str(tmp_path / "fused"), "results", "synthetic",
                           out_f["identity"] + ".obs.jsonl")
    recs_f = export.read_jsonl(jsonl_f)
    assert [r["round"] for r in recs_f] == [0, 1, 2, 3]
    # with obs on, the runner's fused loop stamps round_time_s at flush
    # boundaries (block wall split evenly) like the unfused
    # DeferredRecords(timed=obs) rule — the comm_agg_share stamp needs it
    assert all(r.get("round_time_s", 0) > 0 for r in recs_f)


