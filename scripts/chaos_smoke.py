"""Chaos smoke: the fault-tolerance subsystem's end-to-end gate.

Runs the scale-8 synthetic config under the canonical chaos spec —
20% dropout, 10% stragglers, 5% NaN injection — with the in-jit
non-finite guard and the rollback-retry watchdog active, and asserts

  1. the run completes every round (no crash, no hang),
  2. the final global/personal eval losses are finite,
  3. the final state pytree is all-finite,
  4. faults actually fired (the spec is not silently inert).

    python scripts/chaos_smoke.py                       # CI gate
    python scripts/chaos_smoke.py --clients 32 --rounds 4
    python scripts/chaos_smoke.py --bench_guard         # overhead probe
    python scripts/chaos_smoke.py --attack_matrix       # Byzantine gate

``--bench_guard`` instead measures the guard's overhead on the CLEAN
path (guard force-on vs. off, no faults injected — the ≤3% round-time
budget of ISSUE 2's acceptance criteria): per-round wall times over a
short warm run, printed as one JSON line alongside the chaos fields.

``--attack_matrix`` runs the Byzantine scenario matrix: each adversary
kind (100x scaling, sign-flip, colluding cohort) crossed with a robust
aggregation statistic (median / krum) on the in-process round, plus a
real Byzantine SITE process against the sync and buffered federation
under ``--robust_agg median``. Every cell must finish finite with its
faults actually firing; one cell per deployment reruns as a twin and
is gated bit-identical through ``obs/diff.params_diff`` (attacks and
defenses are deterministic, or they are not debuggable).

Prints ONE JSON line; exits nonzero on any assertion failure.
A CI gate: runs on the CPU platform unless ``JAX_PLATFORMS`` is set (the
chip check is ``chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

CHAOS_SPEC = "drop=0.2,straggle=0.1,nan=0.05"


def _build(argv_extra, clients, rounds, tmp, fault_spec="",
           model="small3dcnn", epochs=1):
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
    if fault_spec:
        argv += ["--fault_spec", fault_spec]
    return parse_args(argv + list(argv_extra), algo="fedavg")


def run_chaos(clients: int, rounds: int, tmp: str) -> dict:
    from neuroimagedisttraining_tpu.experiments import run_experiment
    from neuroimagedisttraining_tpu.robust.recovery import tree_finite

    t0 = time.perf_counter()
    out = run_experiment(
        _build([], clients, rounds, tmp, fault_spec=CHAOS_SPEC), "fedavg")
    wall = time.perf_counter() - t0
    hist = [h for h in out["history"] if "train_loss" in h]
    if len(hist) != rounds:
        raise SystemExit(
            f"chaos run recorded {len(hist)} rounds, expected {rounds}")
    final_loss = float(out["final_eval"]["global_loss"])
    if not math.isfinite(final_loss):
        raise SystemExit(f"final global loss not finite: {final_loss}")
    if not all(math.isfinite(float(h["train_loss"])) for h in hist):
        raise SystemExit("non-finite train loss leaked into the history")
    if not tree_finite(out["state"].global_params):
        raise SystemExit("non-finite values in the final global params")
    if not tree_finite(out["state"].personal_params):
        raise SystemExit("non-finite values in the final personal stack")
    dropped = sum(float(h.get("clients_dropped", 0)) for h in hist)
    quarantined = sum(float(h.get("clients_quarantined", 0)) for h in hist)
    if dropped + quarantined == 0:
        raise SystemExit(
            "chaos spec injected nothing — the smoke proved nothing "
            f"(spec {CHAOS_SPEC!r}, {clients} clients x {rounds} rounds)")
    return {
        "chaos_ok": True, "fault_spec": CHAOS_SPEC,
        "clients": clients, "rounds": rounds,
        "final_global_loss": final_loss,
        "clients_dropped_total": dropped,
        "clients_quarantined_total": quarantined,
        "wall_s": round(wall, 2),
    }


#: adversary kinds of the --attack_matrix leg (robust/faults.py specs)
ATTACK_SPECS = {
    "scale100x": "scale=0.3:100x",
    "signflip": "signflip=0.3",
    "collude": "collude=0.3:50x",
}

#: robust statistics each adversary is crossed with
ATTACK_AGGS = ("median", "krum")

#: accuracy-under-attack SLO: the same objective through three
#: estimator kinds (obs/slo.py DSL) — EWMA drift floor, windowed-mean
#: floor, lower-quartile floor. Each attack cell runs it LIVE
#: (``--slo_spec``: every eval-round record is stamped with the
#: engine's verdict as the attacked run executes), then the recorded
#: history replays through a fresh engine offline — the replay must
#: reproduce the live health verdict (the engine is a pure function of
#: the record stream), and the per-estimator breach/no-breach verdict
#: is pinned into the matrix output (the robustness claim as an SLO,
#: not a one-off assert).
ATTACK_SLO = ("ewma:global_acc>0.4@a=0.3;"
              "rate:global_acc>0.4@w=6;"
              "p25:global_acc>0.35@w=6")


def attack_slo_verdicts(name: str, history) -> dict:
    """Replay one attacked run's round records through the SLO engine;
    every estimator must produce a verdict (evaluate at least once),
    and the replay's health must reproduce the verdict the LIVE engine
    stamped on the recorded lines."""
    from neuroimagedisttraining_tpu.obs.slo import (SloEngine,
                                                    parse_slo_spec)

    records = [h for h in history if isinstance(h.get("round"), int)]
    engine = SloEngine(parse_slo_spec(ATTACK_SLO))
    engine.replay(records)
    verdicts = {}
    for obj_name, obj in engine.summary()["objectives"].items():
        if not obj["evaluated"]:
            raise SystemExit(
                f"[{name}] SLO estimator {obj_name} never evaluated — "
                "the attacked history carries no global_acc records")
        verdicts[obj_name] = {
            "breached": bool(obj["violating"]
                             or obj["budget_exhausted"]),
            "violations": obj["violations"],
            "compliance": round(obj["compliance"], 4),
            "value": obj["value"],
        }
    # the live-evaluation contract: the in-run engine stamped its
    # verdict on every eval-round line, and the offline replay agrees
    live = [h for h in records if isinstance(h.get("slo_health"), str)]
    if not live:
        raise SystemExit(
            f"[{name}] no recorded line carries slo_health — the "
            "attack SLO did not run live")
    if live[-1]["slo_health"] != engine.summary()["health"]:
        raise SystemExit(
            f"[{name}] live verdict {live[-1]['slo_health']!r} != "
            f"replay verdict {engine.summary()['health']!r}")
    verdicts["health_live"] = live[-1]["slo_health"]
    return verdicts


def run_attack_matrix(clients: int, rounds: int, tmp: str) -> dict:
    """Adversary x robust_agg x deployment scenario matrix (CI scale)."""
    from neuroimagedisttraining_tpu.experiments import run_experiment
    from neuroimagedisttraining_tpu.obs import diff as obs_diff
    from neuroimagedisttraining_tpu.robust.recovery import tree_finite

    t0 = time.perf_counter()
    cells = {}

    def check(name, out):
        hist = [h for h in out["history"] if "train_loss" in h]
        if not all(math.isfinite(float(h["train_loss"])) for h in hist):
            raise SystemExit(f"[{name}] non-finite train loss")
        if not tree_finite(out["state"].global_params):
            raise SystemExit(f"[{name}] non-finite final global params")
        # the LIVE engine stamps slo_health on the obs JSONL lines
        # (the enriched records), not the in-memory history — read the
        # stream the run wrote
        from neuroimagedisttraining_tpu.obs.export import read_jsonl
        stream = os.path.join(tmp, name, "results", "synthetic",
                              out["identity"] + ".obs.jsonl")
        stamped = read_jsonl(stream, allow_partial_tail=True)
        return {"final_train_loss": float(hist[-1]["train_loss"]),
                "slo": attack_slo_verdicts(name, stamped)}

    # -- in-process: adversary x robust statistic -------------------------
    for adv, spec in ATTACK_SPECS.items():
        for agg in ATTACK_AGGS:
            name = f"{adv}-{agg}"
            out = run_experiment(_build(
                ["--robust_agg", agg, "--watchdog", "0",
                 "--obs", "1", "--slo_spec", ATTACK_SLO],
                clients, rounds, os.path.join(tmp, name),
                fault_spec=spec), "fedavg")
            cells[name] = check(name, out)
    # determinism twin on one cell: identical config, identical bits
    twin_args = ["--robust_agg", "median", "--watchdog", "0"]
    a = run_experiment(_build(twin_args, clients, rounds,
                              os.path.join(tmp, "twin_a"),
                              fault_spec=ATTACK_SPECS["collude"]),
                       "fedavg")
    b = run_experiment(_build(twin_args, clients, rounds,
                              os.path.join(tmp, "twin_b"),
                              fault_spec=ATTACK_SPECS["collude"]),
                       "fedavg")
    pd = obs_diff.params_diff(a["state"].global_params,
                              b["state"].global_params)
    if not pd["identical"]:
        raise SystemExit(
            f"attacked robust run is not deterministic: "
            f"{pd['diverged'][:3]}")

    # -- federation: a real Byzantine site process ------------------------
    def fed_run(name, mode, *extra):
        fed_extra = ["--fed_role", "aggregator", "--fed_mode", mode,
                     "--fed_sites", "3", "--fed_site_faults",
                     "3:byzantine", "--robust_agg", "median",
                     "--frac", "1.0"] + list(extra)
        n = rounds
        if mode == "buffered":
            # enough flushes that the attacker contributes AFTER the
            # norm history is honest-dominated: a forged delta in the
            # very first flush sits against a 2-member median it
            # half-owns and legitimately escapes the screen
            fed_extra += ["--fed_buffer_k", "2"]
            n = max(rounds, 4)
        out = run_experiment(_build(
            fed_extra, clients, n, os.path.join(tmp, name)),
            "fedavg")
        flags = out["fed"].get("byzantine_flags") or {}
        if "3" not in flags:
            raise SystemExit(
                f"[{name}] Byzantine site 3 never flagged by the norm "
                f"screen (flags: {flags})")
        if not tree_finite(out["global_params"]):
            raise SystemExit(f"[{name}] non-finite global params")
        return out

    sync_a = fed_run("fedsync_a", "sync")
    sync_b = fed_run("fedsync_b", "sync")
    pd = obs_diff.params_diff(sync_a["global_params"],
                              sync_b["global_params"])
    if not pd["identical"]:
        raise SystemExit(
            f"attacked fed sync twin diverged: {pd['diverged'][:3]}")
    fed_run("fedbuf", "buffered")
    return {
        "attack_matrix_ok": True, "clients": clients, "rounds": rounds,
        "cells": cells, "aggs": list(ATTACK_AGGS),
        "attack_slo": ATTACK_SLO,
        "fed_modes": ["sync", "buffered"], "bit_identical": True,
        "wall_s": round(time.perf_counter() - t0, 2),
    }


def run_bench_guard(clients: int, rounds: int, tmp: str,
                    model: str = "small3dcnn", epochs: int = 1) -> dict:
    """Clean-path guard overhead: identical runs, guard off vs force-on
    (no faults — the guard's screen/select work is the only delta).
    ``model``/``epochs`` size the per-round compute the overhead is
    relative to (the smoke model's rounds are nearly compute-free, which
    inflates the percentage vs. the real dry-run workload)."""

    from neuroimagedisttraining_tpu.experiments import run_experiment

    def timed_wall(extra, sub, n):
        t0 = time.perf_counter()
        out = run_experiment(
            _build(extra + ["--frequency_of_the_test", "0"],  # round
                   # path only: the guard lives in the round program,
                   # and per-round eval would dominate these tiny rounds
                   clients, n, os.path.join(tmp, sub),
                   model=model, epochs=epochs),
            "fedavg")
        return time.perf_counter() - t0, out

    def per_round(extra, sub):
        """Marginal per-round seconds via an N-vs-2N wall subtraction:
        each run pays its own compile + setup (fresh jitted closures per
        FedAlgorithm, so the compile does NOT cache across runs), and
        the subtraction cancels that shared fixed cost — the CLI runner
        stamps no per-round times at fuse_rounds=1, so run-internal
        timing is not available here."""
        w1, out1 = timed_wall(extra, sub + "_n", rounds)
        w2, out2 = timed_wall(extra, sub + "_2n", 2 * rounds)
        return max(w2 - w1, 1e-9) / rounds, out2

    # warmup pass per config (process-level warmup — page cache, BLAS
    # thread pools — otherwise lands entirely on whichever config runs
    # first and swamps the delta being measured)
    timed_wall(["--guard", "0", "--watchdog", "0"], "warm_off", 1)
    timed_wall(["--guard", "1", "--watchdog", "0"], "warm_on", 1)
    base_ms, out_off = per_round(["--guard", "0", "--watchdog", "0"],
                                 "off")
    guard_ms, out_on = per_round(["--guard", "1", "--watchdog", "0"],
                                 "on")
    # clean-path guard is all selects: the params must be bit-identical
    # — through the fleet comparator's params plane (obs/diff.py),
    # which names the diverging leaves
    from neuroimagedisttraining_tpu.obs import diff as obs_diff

    pd = obs_diff.params_diff(out_off["state"].global_params,
                              out_on["state"].global_params)
    if not pd["identical"]:
        raise SystemExit(
            f"guard-on clean run is not bit-identical to guard-off: "
            f"{pd['diverged'][:3]}")
    return {
        "bench_guard": True, "clients": clients, "rounds": rounds,
        "model": model, "epochs": epochs,
        "round_s_guard_off": base_ms, "round_s_guard_on": guard_ms,
        "guard_overhead_pct": round(
            100.0 * (guard_ms - base_ms) / max(base_ms, 1e-9), 2),
        "bit_identical": True,
    }


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--clients", type=int, default=8)
    p.add_argument("--rounds", type=int, default=6)
    p.add_argument("--bench_guard", action="store_true",
                   help="measure clean-path guard overhead instead of "
                        "running the chaos gate")
    p.add_argument("--attack_matrix", action="store_true",
                   help="run the Byzantine scenario matrix (adversary "
                        "x robust_agg x sync/buffered) instead of the "
                        "chaos gate")
    p.add_argument("--model", type=str, default="small3dcnn",
                   help="bench_guard model (3dcnn sizes the per-round "
                        "compute closer to the dry-run workload)")
    p.add_argument("--epochs", type=int, default=1,
                   help="bench_guard local epochs per round")
    p.add_argument("--tmp", type=str, default="",
                   help="scratch dir (default: a fresh tempdir)")
    args = p.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import logging
    import tempfile

    logging.getLogger().setLevel(logging.WARNING)
    tmp = args.tmp or tempfile.mkdtemp(prefix="chaos_smoke_")
    if args.bench_guard:
        result = run_bench_guard(args.clients, args.rounds, tmp,
                                 model=args.model, epochs=args.epochs)
    elif args.attack_matrix:
        result = run_attack_matrix(args.clients, args.rounds, tmp)
    else:
        result = run_chaos(args.clients, args.rounds, tmp)
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
