"""SLO smoke: the online-SLO subsystem's end-to-end CI gate.

Runs the scale-8 synthetic config against a DETERMINISTIC SLO spec
(objectives over fault counters and losses — never wall-clock metrics,
so every verdict is bit-reproducible) and asserts the acceptance
contract of the online SLO engine (obs/slo.py + obs/events.py):

  1. INERTNESS — the obs+slo run's training trajectory is BIT-IDENTICAL
     to the plain obs run (the engine is a pure readout), and its round
     records equal the plain run's modulo the ``slo_*`` stamps and the
     schema bump they imply.
  2. CLEAN TWIN — the fault-free run stays OK on every line, emits ZERO
     breach events, and exits 0 even under ``--slo_enforce``.
  3. SEEDED BREACH — the chaos twin (deterministic ``--fault_spec`` NaN
     injection) trips the expected SLO_BREACH / HEALTH_TRANSITION
     events; two identical runs produce byte-identical events streams;
     ``--slo_enforce`` makes the FAILING run exit nonzero (after
     writing every artifact).
  4. FUSED PARITY — the fused (``--fuse_rounds``) chaos twin passes
     the fleet comparator's full three-plane ``obs diff --expect
     identical`` gate against the unfused run (config splits only on
     the inert fuse_rounds axis).
  5. RESUME — a kill+``--resume`` pair (first half checkpointed, second
     half resumed; the engine deterministically rebuilds its state from
     the JSONL) passes the same ``obs diff --expect identical`` gate
     against the uninterrupted run after the keep-last dedupe — and
     the chaos-vs-clean pair diffs NON-trivially: ``--expect
     different`` holds, the config plane splits on the
     identity-bearing fault_spec, and the event plane names exactly
     the injected breach rounds.
  6. ANALYZER — obs/analyze.py emits a schema-v4 ``slo`` section whose
     breach timeline names the injected rounds and clients (the
     fault-trace join).

    python scripts/slo_smoke.py                 # CI gate
    python scripts/slo_smoke.py --clients 8 --rounds 6

Prints ONE JSON line; exits 0 when the whole contract holds, 1 on any
violation.
A CI gate: runs on the CPU platform unless ``JAX_PLATFORMS`` is set (the
chip check is ``chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

CHAOS_SPEC = "nan=0.4"


def _slo_spec(rounds: int) -> str:
    """Deterministic objectives: the quarantine-rate SLO breaches under
    seeded NaN chaos and never on the clean twin; the loss EWMA is a
    wide always-green guard proving multi-objective evaluation."""
    return (f"rate:clients_quarantined<0.05@w={rounds}"
            ";ewma:train_loss<100@a=0.5")


def _argv(clients, rounds, tmp, sub, extra):
    return [
        "--model", "small3dcnn", "--dataset", "synthetic",
        "--client_num_in_total", str(clients), "--batch_size", "8",
        "--epochs", "1", "--comm_round", str(rounds), "--lr", "0.05",
        "--frequency_of_the_test", "0", "--final_finetune", "0",
        "--log_dir", os.path.join(tmp, sub, "LOG"),
        "--results_dir", os.path.join(tmp, sub, "results"),
    ] + list(extra)


def _run(clients, rounds, tmp, sub, extra):
    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_experiment,
    )

    args = parse_args(_argv(clients, rounds, tmp, sub, extra),
                      algo="fedavg")
    return run_experiment(args, "fedavg")


def _read(path, events=False):
    from neuroimagedisttraining_tpu.obs.export import (
        dedupe_events,
        dedupe_rounds,
        read_jsonl,
    )

    if not os.path.exists(path):
        return []
    recs = read_jsonl(path, allow_partial_tail=events)
    return dedupe_events(recs) if events else dedupe_rounds(recs)


def _event_sig(events):
    """The comparable identity of an event stream (host-field-free)."""
    return [(e["round"], e["event_type"], e.get("objective", ""),
             e.get("message", ""), json.dumps(e.get("detail", {}),
                                              sort_keys=True))
            for e in events]


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--clients", type=int, default=8)
    p.add_argument("--rounds", type=int, default=6,
                   help="total rounds (the resume pair splits it in "
                        "half; >= 4)")
    p.add_argument("--tmp", type=str, default="",
                   help="scratch dir (default: a fresh tempdir)")
    args = p.parse_args(argv)
    if args.rounds < 4:
        raise SystemExit("--rounds must be >= 4 (the resume pair "
                         "needs two halves with >= 2 rounds each)")

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import logging
    import tempfile

    logging.getLogger().setLevel(logging.WARNING)
    tmp = args.tmp or tempfile.mkdtemp(prefix="slo_smoke_")
    spec = _slo_spec(args.rounds)
    slo_flags = ["--obs", "1", "--slo_spec", spec, "--watchdog", "0"]
    chaos = ["--fault_spec", CHAOS_SPEC]

    from neuroimagedisttraining_tpu.obs import diff as obs_diff

    def params_equal(a, b):
        # the params-plane twin comparator (obs/diff.py): bit-level,
        # path-named divergences
        return obs_diff.params_diff(a.global_params,
                                    b.global_params)["identical"]

    def twin_gate(run_dir_a, run_dir_b, label):
        """Route a twin contract through the fleet comparator: the
        full three-plane ``obs diff --expect identical`` gate."""
        doc = obs_diff.diff_runs(obs_diff.load_run(run_dir_a),
                                 obs_diff.load_run(run_dir_b))
        if obs_diff.expect_exit_code(doc, "identical") != 0:
            raise SystemExit(
                f"{label}: obs diff --expect identical failed\n"
                + obs_diff.render_diff(doc))
        return doc

    def streams(sub, out, jsonl_override=""):
        d = os.path.join(tmp, sub, "results", "synthetic")
        base = jsonl_override or os.path.join(
            d, out["identity"] + ".obs.jsonl")
        return (_read(base),
                _read(base[:-len(".obs.jsonl")] + ".events.jsonl",
                      events=True))

    # -- 1. inertness: plain obs vs obs+slo under chaos -----------------
    out_plain = _run(args.clients, args.rounds, tmp, "plain",
                     ["--obs", "1", "--watchdog", "0"] + chaos)
    out_slo = _run(args.clients, args.rounds, tmp, "slo",
                   slo_flags + chaos)
    if not params_equal(out_plain["state"], out_slo["state"]):
        raise SystemExit("slo run is not bit-identical to plain obs")
    recs_plain, _ = streams("plain", out_plain)
    recs_slo, events_slo = streams("slo", out_slo)

    def deterministic(rec, drop_slo):
        # two separate processes can only be compared on the
        # deterministic record content: wall-clock and memory samples
        # differ run to run by nature, and the slo stamps (plus the
        # schema bump they imply) are exactly the delta under test
        return {k: v for k, v in rec.items()
                if k != "round_time_s" and not k.startswith("mem_")
                and k != "obs_schema"
                and not (drop_slo and k.startswith("slo_"))}

    for rp, rs in zip(recs_plain, recs_slo):
        if deterministic(rs, True) != deterministic(rp, False):
            raise SystemExit(
                f"slo stamps changed the record beyond slo_* keys at "
                f"round {rs.get('round')}")
    rounds_rec = [r for r in recs_slo
                  if isinstance(r.get("round"), int) and r["round"] >= 0]
    if not all("slo_health" in r and r["obs_schema"] == 4
               for r in rounds_rec):
        raise SystemExit("slo run lines missing health stamp / v4")

    # -- 3a. seeded breach fired deterministically ----------------------
    etypes = {e["event_type"] for e in events_slo}
    if "SLO_BREACH" not in etypes or "HEALTH_TRANSITION" not in etypes:
        raise SystemExit(
            f"chaos run missed expected events (got {sorted(etypes)})")
    final_health = rounds_rec[-1]["slo_health"]
    if final_health != "failing":
        raise SystemExit(
            f"chaos run ended {final_health!r}, expected 'failing'")
    out_slo2 = _run(args.clients, args.rounds, tmp, "slo2",
                    slo_flags + chaos)
    _, events_slo2 = streams("slo2", out_slo2)
    if _event_sig(events_slo) != _event_sig(events_slo2):
        raise SystemExit("two identical chaos runs emitted different "
                         "event streams")

    # -- 4. fused parity: the full three-plane comparator gate ----------
    # (obs diff --expect identical: config splits only on inert
    # fuse_rounds, trajectories/events/health bit-match)
    out_fused = _run(args.clients, args.rounds, tmp, "fused",
                     slo_flags + chaos + ["--fuse_rounds", "2"])
    fused_doc = twin_gate(
        os.path.join(tmp, "slo", "results", "synthetic"),
        os.path.join(tmp, "fused", "results", "synthetic"),
        "fused parity")
    if "fuse_rounds" not in fused_doc["planes"]["config"]["inert"]:
        raise SystemExit("fused twin's config plane did not report "
                         "the inert fuse_rounds split")
    unfused_health = [(r["round"], r["slo_health"])
                      for r in rounds_rec]

    # -- 2. clean twin stays OK (zero breach events), enforce exits 0 ---
    out_clean = _run(args.clients, args.rounds, tmp, "clean",
                     slo_flags + ["--slo_enforce", "1"])
    recs_clean, events_clean = streams("clean", out_clean)
    bad = [e for e in events_clean
           if e["event_type"] in ("SLO_BREACH", "BUDGET_BURN",
                                  "HEALTH_TRANSITION")]
    if bad:
        raise SystemExit(f"clean twin emitted breach events: {bad}")
    if not all(r.get("slo_health") == "ok" for r in recs_clean
               if isinstance(r.get("round"), int) and r["round"] >= 0):
        raise SystemExit("clean twin left the OK state")

    # -- 2b. chaos vs clean: the comparator's NON-trivial diff ----------
    # (--expect different holds, the config plane splits on the
    # identity-bearing fault_spec, and the event plane names the
    # injected rounds)
    cc_doc = obs_diff.diff_runs(
        obs_diff.load_run(os.path.join(tmp, "slo", "results",
                                       "synthetic")),
        obs_diff.load_run(os.path.join(tmp, "clean", "results",
                                       "synthetic")))
    if obs_diff.expect_exit_code(cc_doc, "different") != 0:
        raise SystemExit("chaos vs clean compared identical")
    if "fault_spec" not in cc_doc["planes"]["config"]["identity"]:
        raise SystemExit("chaos-vs-clean config plane missed the "
                         "identity-bearing fault_spec split")
    chaos_only_rounds = {e["round"]
                         for e in cc_doc["planes"]["events"]["only_a"]
                         if e["event_type"] == "SLO_BREACH"}
    breach_event_rounds = {e["round"] for e in events_slo
                           if e["event_type"] == "SLO_BREACH"}
    if chaos_only_rounds != breach_event_rounds:
        raise SystemExit(
            f"chaos-vs-clean event plane named rounds "
            f"{sorted(chaos_only_rounds)}, expected "
            f"{sorted(breach_event_rounds)}")

    # -- 3b. --slo_enforce: the FAILING chaos run exits nonzero ---------
    enforce_code = 0
    try:
        _run(args.clients, args.rounds, tmp, "enforce",
             slo_flags + chaos + ["--slo_enforce", "1"])
    except SystemExit as e:
        enforce_code = 1 if isinstance(e.code, str) else int(
            e.code or 0)
    if enforce_code == 0:
        raise SystemExit(
            "--slo_enforce did not exit nonzero on the FAILING run")
    # artifacts were still written BEFORE the verdict exit
    enforce_dir = os.path.join(tmp, "enforce", "results", "synthetic")
    if not any(f.endswith(".events.jsonl")
               for f in os.listdir(enforce_dir)):
        raise SystemExit("enforced run wrote no events stream")

    # -- 5. kill + resume reproduces the uninterrupted run --------------
    half = args.rounds // 2
    ckpt = os.path.join(tmp, "resume", "ckpt")
    jsonl_b = os.path.join(tmp, "resume", "stream.obs.jsonl")
    resume_extra = slo_flags + chaos + [
        "--checkpoint_dir", ckpt, "--obs_jsonl", jsonl_b]
    _run(args.clients, half, tmp, "resume", resume_extra)
    out_b = _run(args.clients, args.rounds, tmp, "resume",
                 resume_extra + ["--resume"])
    if not params_equal(out_slo["state"], out_b["state"]):
        raise SystemExit("resumed run's final state differs from the "
                         "uninterrupted run")
    # the full three-plane comparator gate over the streams (the
    # override stream has no stat sidecar, so the config plane
    # abstains; trajectory/events/health must bit-match after the
    # keep-last dedupe)
    resume_doc = twin_gate(
        os.path.join(tmp, "slo", "results", "synthetic"), jsonl_b,
        "kill+resume")
    health_b = [tuple(x) for x in resume_doc["planes"]["health"]["b"]]
    if [tuple(x) for x in resume_doc["planes"]["health"]["a"]] != \
            health_b:
        raise SystemExit(
            f"resumed health trajectory {health_b} != uninterrupted")
    events_b = _read(jsonl_b[:-len(".obs.jsonl")] + ".events.jsonl",
                     events=True)
    if _event_sig(events_b) != _event_sig(events_slo):
        raise SystemExit("resumed event stream (deduped) differs from "
                         "the uninterrupted run's")

    # -- 6. analyzer v4: breach attribution names injected clients ------
    from neuroimagedisttraining_tpu.obs import analyze as obs_analyze

    analyses = obs_analyze.analyze_run_dir(
        os.path.join(tmp, "slo", "results", "synthetic"))
    if len(analyses) != 1:
        raise SystemExit("expected one analyzable slo run")
    a = analyses[0]
    obs_analyze.validate_analysis(a)
    if a["schema_version"] < 4 or not a["slo"]["present"]:
        raise SystemExit("analysis is not schema v4 with a slo section")
    if a["slo"]["health_final"] != "failing":
        raise SystemExit(
            f"analyzer health {a['slo']['health_final']} != failing")
    breaches = [b for b in a["slo"]["breaches"]
                if b["event_type"] == "SLO_BREACH"]
    if not breaches:
        raise SystemExit("analyzer found no SLO_BREACH in the timeline")
    attributed = [b for b in breaches
                  if (b.get("injected") or {}).get("poisoned")]
    if not attributed:
        raise SystemExit("analyzer attributed no breach to the "
                         "injected NaN clients")

    result = {
        "slo_ok": True, "clients": args.clients, "rounds": args.rounds,
        "slo_spec": spec, "fault_spec": CHAOS_SPEC,
        "chaos_final_health": final_health,
        "chaos_events": len(events_slo),
        "clean_events": len(events_clean),
        "enforce_exit": enforce_code,
        "resume_events_match": True, "fused_events_match": True,
        "twin_comparator": "obs_diff",
        "chaos_vs_clean_breach_rounds": sorted(chaos_only_rounds),
        "breach_rounds": sorted({b["round"] for b in breaches}),
        "attributed_clients": sorted({
            c for b in attributed for c in b["injected"]["poisoned"]}),
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
