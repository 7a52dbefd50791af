"""Communication observability (obs/comm.py, obs/devtrace.py): the
wire-cost model, message/backend byte accounting, the schema-v3 analyzer
comm section, and the live-tail CLI.
"""
import json
import math
import os

import jax
import numpy as np
import pytest

from neuroimagedisttraining_tpu.obs import (
    analyze,
    comm as obs_comm,
    devtrace as obs_devtrace,
    export,
)

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# wire-cost model
# ---------------------------------------------------------------------------

def _toy_params():
    return {
        "Conv_0": {"kernel": np.zeros((3, 3, 8, 16), np.float32),
                   "bias": np.zeros((16,), np.float32)},
        "Dense_0": {"kernel": np.zeros((128, 4), np.float32),
                    "bias": np.zeros((4,), np.float32)},
    }


def _toy_plan(params, density=0.5, seed=0):
    from neuroimagedisttraining_tpu.parallel.collectives import (
        build_sparse_plan,
    )

    rs = np.random.RandomState(seed)
    mask = jax.tree_util.tree_map(
        lambda x: (rs.rand(*x.shape) < density).astype(np.float32)
        if x.ndim > 1 else np.ones(x.shape, np.float32), params)
    return build_sparse_plan(mask), mask


def test_wire_model_bytes_per_impl():
    params = _toy_params()
    plan, _ = _toy_plan(params)
    wm = obs_comm.WireCostModel.from_params(
        params, agg_impl="sparse", plan=plan, n_devices=4)
    m = wm.round_metrics()
    n = sum(int(np.prod(l.shape)) for l in
            jax.tree_util.tree_leaves(params))
    assert m["comm_n_params"] == n
    assert m["comm_bytes_dense"] == 4.0 * n
    assert m["comm_bytes_bucketed"] == m["comm_bytes_dense"]
    assert m["comm_bytes_bf16"] == m["comm_bytes_dense"] / 2
    # int8: 1 byte/param (padded rows) + one f32 scale per row
    assert m["comm_bytes_int8"] < m["comm_bytes_dense"]
    # sparse: live coordinates only — tracks the plan's compressed size
    assert m["comm_bytes_sparse"] == 4.0 * plan.compressed_size
    assert m["comm_bytes_sparse"] < m["comm_bytes_dense"]
    assert m["comm_density"] == pytest.approx(plan.density)
    # active impl's bytes == the per-group attribution's sum
    groups = {k: v for k, v in m.items()
              if k.startswith("comm_bytes_group/")}
    assert set(groups) == {"comm_bytes_group/Conv_0",
                           "comm_bytes_group/Dense_0"}
    assert sum(groups.values()) == pytest.approx(m["comm_bytes_wire"])
    assert m["comm_bytes_wire"] == m["comm_bytes_sparse"]


def test_wire_model_no_plan_omits_sparse():
    wm = obs_comm.WireCostModel.from_params(_toy_params())
    m = wm.round_metrics()
    assert "comm_bytes_sparse" not in m
    assert m["comm_density"] == 1.0
    assert m["comm_bytes_wire"] == m["comm_bytes_dense"]
    with pytest.raises(ValueError, match="agg_impl"):
        obs_comm.WireCostModel.from_params(_toy_params(),
                                           agg_impl="nope")


def test_wire_model_plan_leaf_mismatch_raises():
    plan, _ = _toy_plan(_toy_params())
    with pytest.raises(ValueError, match="different tree"):
        obs_comm.WireCostModel.from_params(
            {"Dense_0": {"kernel": np.zeros((4, 4), np.float32)}},
            plan=plan)


def test_wire_model_bench_model_at_half_density():
    """Acceptance pin: for the bench (flagship 3dcnn) parameter tree at
    0.5 density, the int8 and sparse wires are strictly below dense."""
    from neuroimagedisttraining_tpu.models import (
        create_model,
        init_params,
    )
    from neuroimagedisttraining_tpu.ops.sparsity import kernel_flags
    from neuroimagedisttraining_tpu.parallel.collectives import (
        build_sparse_plan,
    )

    model = create_model("3dcnn", num_classes=1)
    shapes = jax.eval_shape(
        lambda k: init_params(model, k, (121, 145, 121, 1)),
        jax.random.PRNGKey(0))
    flags = kernel_flags(shapes)
    rs = np.random.RandomState(0)
    mask = jax.tree_util.tree_map(
        lambda l, k: (rs.rand(*l.shape) < 0.5).astype(np.float32)
        if k else np.ones(l.shape, np.float32), shapes, flags)
    plan = build_sparse_plan(mask)
    wm = obs_comm.WireCostModel.from_params(
        shapes, agg_impl="sparse", plan=plan, n_devices=8)
    m = wm.round_metrics()
    assert m["comm_bytes_int8"] < m["comm_bytes_dense"]
    assert m["comm_bytes_sparse"] < m["comm_bytes_dense"]
    # a 0.5-density kernel mask shrinks the wire to ~half (+ dense
    # non-kernel leaves)
    assert m["comm_bytes_sparse"] / m["comm_bytes_dense"] < 0.6


def test_message_payload_prediction_exact_dense_and_sparse():
    from neuroimagedisttraining_tpu.comm.message import Message

    params = _toy_params()
    n_leaves = len(jax.tree_util.tree_leaves(params))
    msg = Message("t", 0, 1)
    msg.add_tensor("p", params)
    raw = msg.to_bytes()
    pred = obs_comm.message_payload_nbytes(params)
    assert pred <= len(raw) <= pred + obs_comm.message_overhead_budget(
        n_leaves)
    assert msg.nbytes == len(raw)

    plan, mask = _toy_plan(params)
    msg2 = Message("t", 0, 1)
    msg2.add_masked_tensor("p", params, mask)
    raw2 = msg2.to_bytes()
    pred2 = obs_comm.message_payload_nbytes(params, mask)
    assert pred2 <= len(raw2) <= pred2 + \
        obs_comm.message_overhead_budget(n_leaves)


def test_probe_agg_ms_runs_and_is_bit_inert():
    """The probe times the algorithm's own agg path without touching
    the run's state or RNG: a round after the probe is bit-identical
    to a round without it."""
    from neuroimagedisttraining_tpu.algorithms import FedAvg
    from neuroimagedisttraining_tpu.core.state import HyperParams
    from neuroimagedisttraining_tpu.data import make_synthetic_federated
    from neuroimagedisttraining_tpu.models import create_model

    data = make_synthetic_federated(
        n_clients=4, samples_per_client=8, test_per_client=4,
        sample_shape=(8, 8, 8, 1))
    model = create_model("small3dcnn", num_classes=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=2,
                     batch_size=4)
    algo = FedAvg(model, data, hp, loss_type="bce", frac=1.0, seed=0,
                  track_personal=False)
    state0 = algo.init_state(jax.random.PRNGKey(0))
    ref, _ = algo.run_round(state0, 0)
    ms = obs_comm.probe_agg_ms(algo, iters=2)
    assert ms > 0 and math.isfinite(ms)
    state1 = algo.init_state(jax.random.PRNGKey(0))
    got, _ = algo.run_round(state1, 0)
    for a, b in zip(jax.tree_util.tree_leaves(ref.global_params),
                    jax.tree_util.tree_leaves(got.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    wm = obs_comm.WireCostModel.from_algorithm(algo, state1)
    assert wm.n_params > 0 and wm.agg_impl == "dense"
    # the no-trace fallback's agg-side cost analysis feeds
    # devtrace.share_from_cost_analysis (CPU's backend reports flops)
    cost = obs_comm.probe_agg_cost(algo, state=state1)
    assert cost["compile_s"] > 0
    if cost["flops"] is not None:
        est = obs_devtrace.share_from_cost_analysis(
            cost, {"flops": cost["flops"] * 10})
        assert est["present"] and est["agg_share_est"] == \
            pytest.approx(0.1)


# ---------------------------------------------------------------------------
# schema v3 stamps + ObsSession comm merge
# ---------------------------------------------------------------------------

def test_record_schema_v3():
    assert export.OBS_SCHEMA_VERSION == 4
    assert export.SUPPORTED_OBS_SCHEMAS == (1, 2, 3, 4)
    assert export.record_schema({"round": 0}) == 1
    assert export.record_schema({"round": 0, "num_update_norm": 1.0}) == 2
    assert export.record_schema({"round": 0, "comm_bytes_wire": 4.0}) == 3
    assert export.record_schema(
        {"round": 0, "num_update_norm": 1.0,
         "comm_bytes_wire": 4.0}) == 3
    # v4: the online-SLO stamps promote the line past the comm keys
    assert export.record_schema(
        {"round": 0, "comm_bytes_wire": 4.0, "slo_health": "ok"}) == 4


def test_obs_session_comm_merge(tmp_path):
    path = str(tmp_path / "s.obs.jsonl")
    sess = export.ObsSession(jsonl_path=path, identity="t", comm=True)
    try:
        sess.set_comm_metrics({"comm_bytes_wire": 100.0,
                               "comm_bytes_dense": 100.0,
                               "comm_agg_ms": 2.0})
        sess.record_round({"round": 0, "train_loss": 0.5,
                           "round_time_s": 0.01})
        sess.record_round({"round": -1, "finetune": 1.0})
    finally:
        sess.close()
    recs = export.read_jsonl(path)
    r0 = recs[0]
    assert r0["comm_bytes_wire"] == 100.0
    assert r0["obs_schema"] == 3
    # agg share = probed ms / the line's own wall time
    assert r0["comm_agg_share"] == pytest.approx(0.2)
    # the final (round=-1) record is not a round: no comm stamps
    assert "comm_bytes_wire" not in recs[1]


def test_obs_session_without_comm_adds_zero_keys(tmp_path):
    path = str(tmp_path / "s.obs.jsonl")
    sess = export.ObsSession(jsonl_path=path, identity="t")
    try:
        sess.record_round({"round": 0, "train_loss": 0.5,
                           "round_time_s": 0.01})
    finally:
        sess.close()
    (rec,) = export.read_jsonl(path)
    assert not any(k.startswith("comm_") for k in rec)
    assert rec["obs_schema"] == 1


def test_message_nbytes_hook_and_backend_counters():
    from neuroimagedisttraining_tpu.comm import message as msg_mod
    from neuroimagedisttraining_tpu.comm.local import LocalRouter
    from neuroimagedisttraining_tpu.comm.message import Message

    seen = []
    hook = msg_mod.add_nbytes_hook(lambda t, n: seen.append((t, n)))
    try:
        router = LocalRouter(2)
        m0, m1 = router.manager(0), router.manager(1)
        msg = Message("probe", sender_id=0, receiver_id=1)
        msg.add_tensor("p", {"w": np.arange(16, dtype=np.float32)})
        m0.send_message(msg)
        got = []
        import threading

        class Obs:
            def receive_message(self, t, m):
                got.append(m)
                m1.stop_receive_message()

        m1.add_observer(Obs())
        th = threading.Thread(target=m1.handle_receive_message)
        th.start()
        th.join(timeout=10)
        assert got and got[0].type == "probe"
        n = msg.nbytes
        assert n is not None and n > 16 * 4
        assert seen == [("probe", n)]
        assert m0.counters.snapshot() == {
            "comm_bytes_sent": n, "comm_bytes_received": 0,
            "comm_messages_sent": 1, "comm_messages_received": 0,
            "comm_messages_retried": 0}
        assert m1.counters.bytes_received == n
        assert m1.counters.messages_received == 1
    finally:
        msg_mod.remove_nbytes_hook(hook)
        msg_mod.remove_nbytes_hook(hook)  # idempotent


# ---------------------------------------------------------------------------
# analyzer schema v3 comm section
# ---------------------------------------------------------------------------

def _comm_records(rounds=6):
    recs = []
    for r in range(rounds):
        recs.append({
            "round": r, "train_loss": 0.5, "round_time_s": 0.1,
            "comm_bytes_wire": 500.0, "comm_bytes_dense": 1000.0,
            "comm_bytes_bucketed": 1000.0, "comm_bytes_bf16": 500.0,
            "comm_bytes_int8": 260.0, "comm_bytes_sparse": 520.0,
            "comm_bytes_group/Conv_0": 400.0,
            "comm_bytes_group/Dense_0": 100.0,
            "comm_density": 0.5, "comm_n_params": 250.0,
            "comm_n_devices": 4.0, "comm_agg_ms": 20.0,
            "comm_agg_share": 0.2,
        })
    return recs


def test_analyzer_comm_section():
    a = analyze.analyze_records(_comm_records(),
                                config={"agg_impl": "bf16"})
    analyze.validate_analysis(a)
    assert a["schema_version"] == analyze.ANALYSIS_SCHEMA_VERSION
    cm = a["comm"]
    assert cm["present"] and cm["impl"] == "bf16"
    assert cm["wire_bytes"] == 500.0
    assert cm["groups"] == {"Conv_0": 400.0, "Dense_0": 100.0}
    # what-if sorted ascending by bytes, ratios vs dense
    order = [e["impl"] for e in cm["what_if"]]
    assert order[0] == "int8" and set(order) == {
        "dense", "bucketed", "bf16", "int8", "sparse"}
    assert [e["vs_dense"] for e in cm["what_if"]
            if e["impl"] == "bf16"] == [0.5]
    assert cm["agg_ms"]["median"] == 20.0
    assert cm["agg_share"]["median"] == pytest.approx(0.2)
    # effective GB/s over the probe's full-agg wall (the devtrace's
    # achieved_gbps — collective-time base — is a different metric)
    assert cm["probe_gbps"] == pytest.approx(500.0 / 0.02 / 1e9)
    # share under the 50% line: no aggregation-bound flag
    assert not any(f.startswith("agg_share") for f in a["flags"])


def test_analyzer_comm_absent_for_plain_streams():
    recs = [{"round": r, "train_loss": 0.5, "round_time_s": 0.1}
            for r in range(6)]
    a = analyze.analyze_records(recs)
    analyze.validate_analysis(a)
    assert a["comm"]["present"] is False
    assert a["comm"]["what_if"] == []


def test_analyzer_agg_bound_flag_and_devtrace():
    recs = _comm_records()
    for r in recs:
        r["comm_agg_share"] = 0.6
    devtrace = {"present": True,
                "totals": {"agg_share": 0.7, "collective_s": 0.7,
                           "busy_s": 1.0, "compute_s": 0.3},
                "devices": {"d0": {}}, "achieved_gbps": 1.5,
                "top_collectives": [{"name": "all-reduce.1",
                                     "total_s": 0.7, "count": 10}]}
    a = analyze.analyze_records(recs, devtrace=devtrace)
    # devtrace (measured) share wins the flag over the probed one
    assert "agg_share_70pct" in a["flags"]
    assert a["comm"]["devtrace"]["agg_share"] == 0.7
    report = analyze.render_report(a)
    assert "devtrace" in report and "what-if" in report


def test_v3_document_requires_comm_key():
    doc = {k: t() for k, t in analyze._SCHEMA_KEYS.items()}
    doc.update(schema_version=1, identity="old")
    analyze.validate_analysis(doc)  # v1 documents: no v2/v3 keys
    v2 = dict(doc, schema_version=2, numerics={}, outlier_table=[])
    analyze.validate_analysis(v2)   # v2 documents: no comm key needed
    v3 = dict(v2, schema_version=3)
    with pytest.raises(ValueError, match="comm"):
        analyze.validate_analysis(v3)
    v3["comm"] = {}
    analyze.validate_analysis(v3)
    # v4 documents additionally require the slo section
    v4 = dict(v3, schema_version=4)
    with pytest.raises(ValueError, match="slo"):
        analyze.validate_analysis(v4)
    v4["slo"] = {}
    analyze.validate_analysis(v4)


def test_obs_comm_e2e_fused_and_unfused(tmp_path):
    """--obs_comm through the CLI runner, both loop spellings: every
    round line carries the comm stamps (+ per-round agg share from its
    own round_time_s), the stream is obs-schema v3, and the analyzer's
    comm section reads it back."""
    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_experiment,
    )

    def run(sub, extra):
        argv = [
            "--model", "small3dcnn", "--dataset", "synthetic",
            "--client_num_in_total", "4", "--batch_size", "8",
            "--epochs", "1", "--comm_round", "4", "--lr", "0.05",
            "--frequency_of_the_test", "0", "--final_finetune", "0",
            "--log_dir", str(tmp_path / sub / "LOG"),
            "--results_dir", str(tmp_path / sub / "results"),
            "--obs", "1", "--obs_comm", "1"] + extra
        out = run_experiment(parse_args(argv, algo="fedavg"), "fedavg")
        return export.read_jsonl(os.path.join(
            str(tmp_path / sub), "results", "synthetic",
            out["identity"] + ".obs.jsonl"))

    for sub, extra in (("unfused", []),
                       ("fused", ["--fuse_rounds", "2"])):
        recs = [r for r in run(sub, extra) if r["round"] >= 0]
        assert len(recs) == 4, sub
        for r in recs:
            assert r["obs_schema"] == 3, sub
            assert r["comm_bytes_wire"] > 0 and r["comm_agg_ms"] > 0
            assert 0 <= r["comm_agg_share"] and "comm_density" in r
            assert any(k.startswith("comm_bytes_group/") for k in r)
        a = analyze.analyze_records(recs)
        assert a["comm"]["present"] and a["comm"]["agg_share"]["rounds"] \
            == 4


def test_obs_comm_flag_refusals(tmp_path):
    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.experiments.runner import (
        run_experiment,
    )

    base = ["--model", "small3dcnn", "--dataset", "synthetic",
            "--client_num_in_total", "4", "--comm_round", "1",
            "--log_dir", str(tmp_path / "LOG"),
            "--results_dir", str(tmp_path / "results")]
    with pytest.raises(SystemExit, match="--obs 1"):
        run_experiment(parse_args(base + ["--obs_comm", "1"],
                                  algo="fedavg"), "fedavg")
    with pytest.raises(SystemExit, match="central aggregate"):
        run_experiment(parse_args(
            base + ["--obs", "1", "--obs_comm", "1"], algo="local"),
            "local")


# ---------------------------------------------------------------------------
# devtrace parser
# ---------------------------------------------------------------------------

def _trace_doc():
    return {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 9,
         "args": {"name": "python"}},
        {"ph": "X", "pid": 7, "tid": 0, "ts": 0, "dur": 300.0,
         "name": "all-reduce.42"},
        {"ph": "X", "pid": 7, "tid": 0, "ts": 300, "dur": 100.0,
         "name": "all-gather.3"},
        {"ph": "X", "pid": 7, "tid": 0, "ts": 400, "dur": 600.0,
         "name": "fusion.12"},
        # host-lane event: excluded from device attribution
        {"ph": "X", "pid": 9, "tid": 0, "ts": 0, "dur": 5000.0,
         "name": "HostPython"},
        # incomplete event: ignored
        {"ph": "B", "pid": 7, "tid": 0, "ts": 0, "name": "begin"},
    ]}


def test_devtrace_attribution():
    assert obs_devtrace.is_collective("all-reduce.42")
    assert obs_devtrace.is_collective("ncclAllGather")
    assert not obs_devtrace.is_collective("fusion.12")
    att = obs_devtrace.attribute_trace(_trace_doc())
    (lane,) = att["devices"]
    d = att["devices"][lane]
    assert d["busy_s"] == pytest.approx(1e-3)
    assert d["collective_s"] == pytest.approx(4e-4)
    assert att["totals"]["agg_share"] == pytest.approx(0.4)
    assert att["top_collectives"][0]["name"] == "all-reduce.42"


def test_devtrace_excludes_overlapping_aggregate_rows():
    """Real jax.profiler traces give each device pid 'Steps' / 'XLA
    Modules' annotation rows OVERLAPPING the op rows — counting them
    would inflate busy time and understate the measured agg share."""
    doc = _trace_doc()
    doc["traceEvents"] += [
        {"ph": "M", "name": "thread_name", "pid": 7, "tid": 1,
         "args": {"name": "Steps"}},
        {"ph": "M", "name": "thread_name", "pid": 7, "tid": 2,
         "args": {"name": "XLA Modules"}},
        # whole-step and whole-module rows covering the same 1000 us
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0, "dur": 1000.0,
         "name": "step 0"},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 0, "dur": 1000.0,
         "name": "jit__round"},
    ]
    att = obs_devtrace.attribute_trace(doc)
    # identical to the annotation-free trace: 1 ms busy, 40% collective
    assert att["totals"]["busy_s"] == pytest.approx(1e-3)
    assert att["totals"]["agg_share"] == pytest.approx(0.4)



def test_devtrace_profile_dir_roundtrip(tmp_path):
    import gzip

    prof = tmp_path / "prof" / "plugins" / "profile" / "run1"
    prof.mkdir(parents=True)
    with gzip.open(prof / "host.trace.json.gz", "wt") as f:
        json.dump(_trace_doc(), f)
    summary = obs_devtrace.analyze_profile_dir(
        str(tmp_path / "prof"), modeled_bytes=4e5)
    assert summary["present"] and summary["files"] == 1
    assert summary["totals"]["agg_share"] == pytest.approx(0.4)
    # achieved GB/s: modeled bytes / per-device collective seconds
    assert summary["achieved_gbps"] == pytest.approx(
        4e5 / 4e-4 / 1e9)
    path = obs_devtrace.write_summary(
        summary, str(tmp_path / "out" / "x.devtrace.json"))
    assert json.load(open(path))["present"]
    # an empty dir is the fallback cue, not an error
    empty = obs_devtrace.analyze_profile_dir(str(tmp_path / "nope"))
    assert empty["present"] is False


def test_share_from_cost_analysis_fallback():
    est = obs_devtrace.share_from_cost_analysis(
        {"bytes_accessed": 2e6, "flops": 1e6},
        {"bytes_accessed": 1e7, "flops": 1e9})
    assert est["present"] and est["basis"] == "bytes_accessed"
    assert est["agg_share_est"] == pytest.approx(0.2)
    est2 = obs_devtrace.share_from_cost_analysis(
        {"flops": 1e6}, {"flops": 1e9, "bytes_accessed": None})
    assert est2["basis"] == "flops"
    assert not obs_devtrace.share_from_cost_analysis({}, {})["present"]


# ---------------------------------------------------------------------------
# live tail (satellite)
# ---------------------------------------------------------------------------

def test_tail_stream_and_formatting(tmp_path):
    from neuroimagedisttraining_tpu.obs.__main__ import (
        format_tail_line,
        resolve_stream,
        tail_stream,
    )

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    path = run_dir / "ident.obs.jsonl"
    recs = [
        {"round": 0, "train_loss": 0.5, "round_time_s": 0.1,
         "comm_agg_share": 0.42, "comm_agg_ms": 42.0},
        {"round": 1, "train_loss": 0.4, "round_time_s": 0.1,
         "clients_quarantined": 2.0, "num_drift_s0": float("nan")},
        {"round": 2, "train_loss": 0.3, "round_time_s": 0.1,
         "rounds_retried": 1.0, "round_skipped": 1.0},
        {"round": -1, "personal_acc": 0.9},
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
        f.write("{not json\n")
    assert resolve_stream(str(run_dir)) == str(path)
    assert resolve_stream(str(run_dir), identity="ident") == str(path)
    # a NAMED stream may not exist yet (a just-launched run flushes
    # lazily) — resolution returns the path for follow mode to wait on
    assert resolve_stream(str(run_dir), identity="other") == str(
        run_dir / "other.obs.jsonl")
    assert resolve_stream(str(run_dir / "new.obs.jsonl")) == str(
        run_dir / "new.obs.jsonl")
    assert resolve_stream(str(tmp_path / "missing")) is None
    lines = []
    n = tail_stream(str(path), follow=False, out=lines.append)
    assert n == 4 and len(lines) == 5  # + the malformed-line marker
    assert "round 0" in lines[0] and "agg 42.0% (42.00 ms)" in lines[0]
    assert "GUARD quarantined=2" in lines[1]
    assert "DRIFT nonfinite slots 0" in lines[1]
    assert "WATCHDOG retried=1" in lines[2] and "skipped" in lines[2]
    assert lines[3].startswith("final")
    assert "malformed" in lines[4]
    # a not-yet-created stream in no-follow mode returns without blocking
    assert tail_stream(str(run_dir / "nope.jsonl"), follow=False,
                       out=lines.append) == 0
    # follow mode stops via the stop hook
    assert tail_stream(str(path), poll=0.01, follow=True,
                       out=lambda s: None, stop=lambda: True) == 4


def test_tail_cli(tmp_path, capsys):
    from neuroimagedisttraining_tpu.obs.__main__ import main

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    with open(run_dir / "x.obs.jsonl", "w") as f:
        f.write(json.dumps({"round": 0, "train_loss": 0.5}) + "\n")
    rc = main(["tail", str(run_dir), "--once"])
    out = capsys.readouterr().out
    assert rc == 0 and "round 0" in out
    assert main(["tail", str(tmp_path / "empty"), "--once"]) == 2
