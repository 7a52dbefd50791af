"""Obs smoke: the observability subsystem's end-to-end CI gate.

Runs the scale-8 synthetic dry-run twice — obs off and obs on — and
asserts the obs acceptance contract:

  1. the final global model is BIT-IDENTICAL between the two runs
     (telemetry never touches the training trajectory),
  2. the obs run produced a valid per-round JSONL stream (every round
     present, every line parseable, round indices strictly monotone),
     a metrics.json snapshot merged into stat_info, and a
     Perfetto-loadable trace file,
  3. obs-on marginal per-round wall-clock overhead is ≤ 3% (N-vs-2N
     wall subtraction per config, cancelling compile/setup — the same
     methodology as chaos_smoke's guard probe). The wall gate is
     SKIPPABLE: ``--skip-wall`` drops it explicitly (1-vCPU CI hosts,
     where pre-existing HEAD fails it too), and it auto-skips when the
     probe's own repeat spread (its noise floor) exceeds the budget —
     an unmeasurable gate proves nothing. Deterministic checks are
     never skipped,
  4. the ANALYSIS layer (obs/analyze.py) runs over the smoke's own
     telemetry and emits a schema-valid ``analysis.json`` with full
     round coverage, phase attribution, and compile metrics — so the
     bit-identity and overhead gates above also hold end-to-end through
     the new record enrichment (schema stamp, memory-in-JSONL, compile
     listeners),
  5. the NUMERICS leg (--obs_numerics, obs/numerics.py): the in-jit
     telemetry run is ALSO bit-identical to obs-off, its JSONL carries
     the num_* keys, the analyzer's numerics section reads them, and
     its per-round overhead vs obs-off stays within the same budget,
  6. the COMM leg (--obs_comm, obs/comm.py): the wire-cost telemetry
     run is bit-identical to obs-off, every round line carries the
     comm_bytes_* / comm_agg_* keys (stamped obs-schema v3), the
     analyzer emits a schema-v3 comm section with the what-if table,
     and the same per-round overhead budget holds,
  7. the FLEET leg (obs/catalog.py, obs/diff.py, obs/report.py): the
     obs run self-catalogs into runs_index.jsonl at session close
     (and a rebuilt entry matches the live one), an exact-twin rerun
     passes the comparator's ``obs diff --expect identical`` gate on
     all three planes plus the params plane, and the fleet report is
     byte-identical across two generations,
  8. the STORE leg (--client_store, core/client_store.py): a
     streamed-residency twin of a store-off run diffs ``identical``
     on the trajectory/events planes with ``client_store`` in the
     config plane's inert bucket, and final params bit-match.

    python scripts/obs_smoke.py                     # CI gate
    python scripts/obs_smoke.py --clients 8 --rounds 8
    python scripts/obs_smoke.py --model 3dcnn       # dry-run-sized rounds

Prints ONE JSON line; exits nonzero on any failure.
A CI gate: runs on the CPU platform unless ``JAX_PLATFORMS`` is set (the
chip check is ``chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _build(argv_extra, clients, rounds, tmp, model="small3dcnn",
           epochs=1):
    from neuroimagedisttraining_tpu.experiments import parse_args

    argv = [
        "--model", model, "--dataset", "synthetic",
        "--client_num_in_total", str(clients), "--batch_size", "8",
        "--epochs", str(epochs), "--comm_round", str(rounds),
        "--lr", "0.05",
        "--log_dir", os.path.join(tmp, "LOG"),
        "--results_dir", os.path.join(tmp, "results"),
        "--final_finetune", "0",
    ]
    return parse_args(argv + list(argv_extra), algo="fedavg")


def _check_artifacts(out, tmp, trace_dir, rounds) -> dict:
    """The obs run's JSONL/metrics/trace artifact contract."""
    from neuroimagedisttraining_tpu.obs.export import read_jsonl

    jsonl = os.path.join(tmp, "results", "synthetic",
                         out["identity"] + ".obs.jsonl")
    if not os.path.exists(jsonl):
        raise SystemExit(f"obs run wrote no JSONL stream at {jsonl}")
    recs = read_jsonl(jsonl)  # raises on any malformed line
    idx = [r.get("round") for r in recs]
    if idx != sorted(idx) or len(set(idx)) != len(idx):
        raise SystemExit(f"JSONL round indices not strictly monotone: {idx}")
    if idx != list(range(rounds)):
        raise SystemExit(
            f"JSONL missing rounds: got {idx}, expected 0..{rounds - 1}")
    for r in recs:
        if "train_loss" not in r or "round_time_s" not in r:
            raise SystemExit(f"JSONL record missing timing/loss keys: {r}")
    stat = json.load(open(out["stat_path"] + ".json"))
    if "obs_metrics" not in stat:
        raise SystemExit("stat_info JSON missing the obs_metrics merge")
    if stat["obs_metrics"]["rounds_recorded"]["value"] != rounds:
        raise SystemExit("obs registry recorded a different round count")
    trace_path = os.path.join(trace_dir, out["identity"] + ".trace.json")
    doc = json.load(open(trace_path))
    if not doc.get("traceEvents"):
        raise SystemExit(f"trace file has no events: {trace_path}")
    return {"jsonl_rounds": len(recs),
            "trace_events": len(doc["traceEvents"]),
            "metrics_keys": len(stat["obs_metrics"])}


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--clients", type=int, default=8)
    p.add_argument("--rounds", type=int, default=6)
    p.add_argument("--model", type=str, default="small3dcnn",
                   help="3dcnn sizes rounds closer to the dry-run "
                        "workload (the smoke model's rounds are nearly "
                        "compute-free, which inflates the overhead pct)")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--repeats", type=int, default=4,
                   help="repeat each timed config and keep the MINIMUM "
                        "wall: scheduler/compile noise on a shared host "
                        "only ever ADDS time, so min-of-repeats is the "
                        "robust estimator the 3%% gate needs (a single "
                        "6-round subtraction swings tens of ms/round; "
                        "min-of-4 converges to ~2 ms/round)")
    p.add_argument("--max_overhead_pct", type=float, default=3.0)
    p.add_argument("--skip-wall", dest="skip_wall",
                   action="store_true",
                   help="skip the wall-clock overhead gates (and drop "
                        "to one repeat per config): on 1-vCPU CI hosts "
                        "the N-vs-2N subtraction's noise floor exceeds "
                        "the 3%% budget — pre-existing HEAD fails the "
                        "gate there too — so the wall gate proves "
                        "nothing. The DETERMINISTIC checks "
                        "(bit-identity, artifact/schema contracts, "
                        "analyzer) stay mandatory")
    p.add_argument("--tmp", type=str, default="",
                   help="scratch dir (default: a fresh tempdir)")
    args = p.parse_args(argv)
    if args.skip_wall:
        # one repeat still produces the timing estimates for the JSON
        # line; only the gating (and its repeat cost) is dropped
        args.repeats = 1

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import logging
    import tempfile

    logging.getLogger().setLevel(logging.WARNING)
    tmp = args.tmp or tempfile.mkdtemp(prefix="obs_smoke_")

    from neuroimagedisttraining_tpu.experiments import run_experiment

    trace_dir = os.path.join(tmp, "trace")
    obs_flags = ["--obs", "1", "--trace_dir", trace_dir]

    def timed_wall(extra, sub, n):
        t0 = time.perf_counter()
        out = run_experiment(
            _build(extra + ["--frequency_of_the_test", "0"],
                   args.clients, n, os.path.join(tmp, sub),
                   model=args.model, epochs=args.epochs),
            "fedavg")
        return time.perf_counter() - t0, out

    noise_round_s = [0.0]  # max observed per-round measurement spread

    def per_round(extra, sub):
        """Marginal per-round seconds via N-vs-2N wall subtraction: each
        run pays its own compile (fresh jitted closures per
        FedAlgorithm), the subtraction cancels that fixed cost. Each
        config runs ``--repeats`` times and keeps the MIN wall (noise
        is one-sided); the artifact checks read the last 2N run. The
        repeat SPREAD (max-min, per round) is the probe's own noise
        floor — when it exceeds the overhead budget, the wall gate is
        unmeasurable on this host and auto-skips."""
        w1s = [timed_wall(extra, f"{sub}_n{i}", args.rounds)[0]
               for i in range(args.repeats)]
        w2s, out2 = [], None
        for i in range(args.repeats):
            w, out2 = timed_wall(extra, f"{sub}_2n{i}", 2 * args.rounds)
            w2s.append(w)
        spread = ((max(w1s) - min(w1s)) + (max(w2s) - min(w2s))) \
            / args.rounds
        noise_round_s[0] = max(noise_round_s[0], spread)
        return max(min(w2s) - min(w1s), 1e-9) / args.rounds, out2

    # process-level warmup per config (page cache / BLAS pools), then the
    # measured N and 2N runs (the obs warmup's output feeds the fleet
    # leg's twin diff below)
    timed_wall([], "warm_off", 1)
    _, out_warm = timed_wall(obs_flags, "warm_on", 1)
    off_s, out_off = per_round([], "off")
    on_s, out_on = per_round(obs_flags, "on")
    overhead_pct = 100.0 * (on_s - off_s) / max(off_s, 1e-9)

    def wall_gate_state():
        """Re-evaluated immediately before EACH wall gate: the later
        numerics/comm probes feed noise_round_s too, and a gate must
        see the noise floor measured up to its own probe — freezing
        the decision after off/on would enforce the num/comm gates
        against spread the decision never saw."""
        if args.skip_wall:
            return "skipped_flag"
        if 100.0 * noise_round_s[0] / max(off_s, 1e-9) > \
                args.max_overhead_pct:
            # the subtraction cannot resolve the budget on this host
            # (the 1-vCPU CI case, where pre-existing HEAD fails the
            # gate too): enforcing it would gate on scheduler noise,
            # not obs cost
            return "skipped_noise_floor"
        return "enforced"

    wall_gate = wall_gate_state()

    # 1. bit-identical final model — through the fleet comparator's
    # params plane (obs/diff.py), which names the diverging leaves
    from neuroimagedisttraining_tpu.obs import diff as obs_diff

    pd = obs_diff.params_diff(out_off["state"].global_params,
                              out_on["state"].global_params)
    if not pd["identical"]:
        raise SystemExit(
            f"obs-on run is not bit-identical to obs-off: "
            f"{pd['diverged'][:3]}")

    # 2. artifact contract (on the last 2N obs run)
    on_2n_dir = os.path.join(tmp, f"on_2n{args.repeats - 1}")
    art = _check_artifacts(out_on, on_2n_dir, trace_dir, 2 * args.rounds)

    # 2b. the analysis layer over the smoke's own telemetry: schema-
    # valid analysis.json, every round covered, phases attributed,
    # compile cost recorded
    from neuroimagedisttraining_tpu.obs import analyze as obs_analyze

    run_dir = os.path.join(on_2n_dir, "results", "synthetic")
    analyses = obs_analyze.analyze_run_dir(run_dir, trace_dir=trace_dir)
    if len(analyses) != 1:
        raise SystemExit(
            f"expected one analyzable run under {run_dir}, "
            f"got {len(analyses)}")
    analysis = analyses[0]
    obs_analyze.validate_analysis(analysis)  # raises on schema drift
    if analysis["rounds"]["count"] != 2 * args.rounds or \
            analysis["rounds"]["missing"]:
        raise SystemExit(
            f"analysis round coverage wrong: {analysis['rounds']}")
    if not analysis["round_time"]["present"]:
        raise SystemExit("analysis found no round_time_s series")
    if "train_dispatch" not in analysis["phases"]:
        raise SystemExit(
            f"phase attribution missing train_dispatch: "
            f"{sorted(analysis['phases'])}")
    if not analysis["compile"]["present"]:
        raise SystemExit("compile metrics missing from the analysis")
    art.update({
        "analysis_schema": analysis["schema_version"],
        "analysis_flags": analysis["flags"],
        "compile_total_s": round(analysis["compile"]["total_s"], 3),
    })

    # 3. overhead budget (wall gate; deterministic checks above stay
    # mandatory regardless)
    if wall_gate == "enforced" and overhead_pct > args.max_overhead_pct:
        raise SystemExit(
            f"obs-on per-round overhead {overhead_pct:.2f}% exceeds the "
            f"{args.max_overhead_pct:g}% budget "
            f"(off {off_s * 1e3:.1f} ms, on {on_s * 1e3:.1f} ms)")

    # 4. numerics leg: obs + in-jit numerics telemetry. Bit-identity vs
    # the obs-OFF run (numerics is a pure readout), num_* keys on every
    # JSONL line, analyzer numerics section present, and the same
    # per-round overhead budget measured against obs-off.
    num_s, out_num = per_round(obs_flags + ["--obs_numerics", "1"],
                               "num")
    num_overhead_pct = 100.0 * (num_s - off_s) / max(off_s, 1e-9)
    if not obs_diff.params_diff(
            out_off["state"].global_params,
            out_num["state"].global_params)["identical"]:
        raise SystemExit(
            "obs_numerics run is not bit-identical to obs-off")
    from neuroimagedisttraining_tpu.obs.export import read_jsonl

    num_dir = os.path.join(tmp, f"num_2n{args.repeats - 1}")
    num_jsonl = os.path.join(num_dir, "results", "synthetic",
                             out_num["identity"] + ".obs.jsonl")
    num_recs = read_jsonl(num_jsonl)
    for r in num_recs:
        if "num_update_norm" not in r or \
                not any(k.startswith("num_maxabs/") for k in r):
            raise SystemExit(
                f"numerics JSONL record missing num_* keys: {sorted(r)}")
    num_analyses = obs_analyze.analyze_run_dir(
        os.path.join(num_dir, "results", "synthetic"),
        trace_dir=trace_dir)
    if len(num_analyses) != 1 or \
            not num_analyses[0]["numerics"]["present"]:
        raise SystemExit("analyzer found no numerics section in the "
                         "obs_numerics run")
    wall_gate = wall_gate_state()  # numerics probe fed the noise floor
    if wall_gate == "enforced" and \
            num_overhead_pct > args.max_overhead_pct:
        raise SystemExit(
            f"obs_numerics per-round overhead {num_overhead_pct:.2f}% "
            f"exceeds the {args.max_overhead_pct:g}% budget "
            f"(off {off_s * 1e3:.1f} ms, numerics "
            f"{num_s * 1e3:.1f} ms)")

    # 5. comm leg: obs + wire-cost telemetry. Bit-identity vs obs-off
    # (the model and probe are pure readouts), comm_* keys on every
    # round line with the obs-schema v3 stamp, analyzer comm section
    # present with the what-if table, same overhead budget.
    comm_s, out_comm = per_round(obs_flags + ["--obs_comm", "1"],
                                 "comm")
    comm_overhead_pct = 100.0 * (comm_s - off_s) / max(off_s, 1e-9)
    if not obs_diff.params_diff(
            out_off["state"].global_params,
            out_comm["state"].global_params)["identical"]:
        raise SystemExit(
            "obs_comm run is not bit-identical to obs-off")
    comm_dir = os.path.join(tmp, f"comm_2n{args.repeats - 1}")
    comm_jsonl = os.path.join(comm_dir, "results", "synthetic",
                              out_comm["identity"] + ".obs.jsonl")
    comm_recs = [r for r in read_jsonl(comm_jsonl)
                 if isinstance(r.get("round"), int) and r["round"] >= 0]
    for r in comm_recs:
        if "comm_bytes_wire" not in r or "comm_bytes_dense" not in r \
                or not any(k.startswith("comm_bytes_group/")
                           for k in r) \
                or "comm_agg_share" not in r:
            raise SystemExit(
                f"comm JSONL record missing comm_* keys: {sorted(r)}")
        if r.get("obs_schema") != 3:
            raise SystemExit(
                f"comm record not stamped obs-schema v3: {r['obs_schema']}")
    comm_analyses = obs_analyze.analyze_run_dir(
        os.path.join(comm_dir, "results", "synthetic"),
        trace_dir=trace_dir)
    if len(comm_analyses) != 1 or \
            not comm_analyses[0]["comm"]["present"]:
        raise SystemExit("analyzer found no comm section in the "
                         "obs_comm run")
    if comm_analyses[0]["schema_version"] < 3:
        raise SystemExit(
            f"comm analysis not schema v3: "
            f"{comm_analyses[0]['schema_version']}")
    if not comm_analyses[0]["comm"]["what_if"]:
        raise SystemExit("comm analysis has an empty what-if table")
    wall_gate = wall_gate_state()  # comm probe fed the noise floor
    if wall_gate == "enforced" and \
            comm_overhead_pct > args.max_overhead_pct:
        raise SystemExit(
            f"obs_comm per-round overhead {comm_overhead_pct:.2f}% "
            f"exceeds the {args.max_overhead_pct:g}% budget "
            f"(off {off_s * 1e3:.1f} ms, comm {comm_s * 1e3:.1f} ms)")

    # 7. fleet leg (obs/catalog.py + obs/diff.py + obs/report.py):
    # the obs run self-cataloged at session close; an exact-twin rerun
    # passes the comparator's --expect identical gate; the fleet
    # report is byte-deterministic across two generations.
    from neuroimagedisttraining_tpu.obs import (
        catalog as obs_catalog,
        report as obs_report,
    )

    cat = obs_catalog.catalog_path(os.path.join(on_2n_dir, "results"))
    entries = obs_catalog.read_catalog(cat)
    if len(entries) != 1:
        raise SystemExit(
            f"obs run did not self-catalog: {len(entries)} entries "
            f"at {cat}")
    entry = entries[0]
    if entry["rounds_recorded"] != 2 * args.rounds or \
            not entry["completed"]:
        raise SystemExit(f"catalog entry wrong: {entry}")
    if not os.path.exists(entry["artifacts"].get("obs_jsonl", "")):
        raise SystemExit(
            f"catalog entry's stream path missing: {entry['artifacts']}")
    # scan-vs-live equivalence: a rebuilt entry matches the one the
    # session wrote (modulo the after-the-fact-unknowable git SHA)
    rebuilt = obs_catalog.entry_from_run(run_dir, out_on["identity"],
                                         git_sha=entry["git_sha"])
    for k in ("final_metrics", "rounds_recorded", "completed",
              "flags", "dataset", "slo_health"):
        if rebuilt[k] != entry[k]:
            raise SystemExit(
                f"catalog rebuild diverges from the live entry on "
                f"{k}: {rebuilt[k]!r} != {entry[k]!r}")
    # exact-twin rerun through the comparator's --expect identical
    # gate (1 round each keeps the fleet leg cheap on 1-vCPU CI)
    _, out_twin = timed_wall(obs_flags, "fleet_twin", 1)
    twin_doc = obs_diff.diff_runs(
        obs_diff.load_run(os.path.join(tmp, "warm_on", "results",
                                       "synthetic")),
        obs_diff.load_run(os.path.join(tmp, "fleet_twin", "results",
                                       "synthetic")))
    if obs_diff.expect_exit_code(twin_doc, "identical") != 0:
        raise SystemExit(
            "exact-twin rerun failed obs diff --expect identical\n"
            + obs_diff.render_diff(twin_doc))
    if not obs_diff.params_diff(
            out_warm["state"].global_params,
            out_twin["state"].global_params)["identical"]:
        raise SystemExit("exact-twin rerun's final params diverged")
    # fleet-report byte determinism: two generations over the same
    # catalog are byte-identical (no timestamps, sorted iteration)
    r1 = obs_report.write_report(os.path.join(tmp, "fleet1.html"), cat)
    r2 = obs_report.write_report(os.path.join(tmp, "fleet2.html"), cat)
    with open(r1, "rb") as f1, open(r2, "rb") as f2:
        b1, b2 = f1.read(), f2.read()
    if b1 != b2:
        raise SystemExit("fleet report is not byte-deterministic")

    # 8. store leg (core/client_store.py): a --client_store host twin
    # of a store-off run (same seed, sampled participation) must pass
    # the comparator's identical gate on the trajectory/events planes
    # with client_store classified INERT in the config plane — the
    # streamed-residency bit-identity contract, end-to-end through the
    # runner/obs stack — and the final params must bit-match.
    store_part = ["--frac", "0.5"]  # store refuses full participation
    _, out_soff = timed_wall(obs_flags + store_part, "store_off", 2)
    _, out_son = timed_wall(
        obs_flags + store_part
        + ["--client_store", "host", "--store_hot_clients", "4"],
        "store_on", 2)
    store_doc = obs_diff.diff_runs(
        obs_diff.load_run(os.path.join(tmp, "store_off", "results",
                                       "synthetic")),
        obs_diff.load_run(os.path.join(tmp, "store_on", "results",
                                       "synthetic")))
    if obs_diff.expect_exit_code(store_doc, "identical") != 0:
        raise SystemExit(
            "store-on twin failed obs diff --expect identical\n"
            + obs_diff.render_diff(store_doc))
    cfg_plane = store_doc["planes"]["config"]
    if "client_store" not in cfg_plane["inert"]:
        raise SystemExit(
            "client_store did not land in the config plane's inert "
            f"bucket: {cfg_plane}")
    if not obs_diff.params_diff(
            out_soff["state"].global_params,
            out_son["state"].global_params)["identical"]:
        raise SystemExit("store-on twin's final params diverged")

    result = {
        "obs_ok": True, "clients": args.clients, "rounds": args.rounds,
        "model": args.model,
        "round_s_obs_off": off_s, "round_s_obs_on": on_s,
        "round_s_obs_numerics": num_s, "round_s_obs_comm": comm_s,
        "obs_overhead_pct": round(overhead_pct, 2),
        "numerics_overhead_pct": round(num_overhead_pct, 2),
        "comm_overhead_pct": round(comm_overhead_pct, 2),
        "wall_gate": wall_gate_state(),
        "noise_floor_pct": round(
            100.0 * noise_round_s[0] / max(off_s, 1e-9), 2),
        "comm_wire_mb": round(
            comm_recs[-1]["comm_bytes_wire"] / 1e6, 4),
        "bit_identical": True,
        "catalog_entries": len(entries),
        "twin_diff_identical": True,
        "store_twin_identical": True,
        "report_bytes": len(b1),
        "report_deterministic": True, **art,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
