"""Serving smoke: the serve/ subsystem's CI gate.

One process, two roles: the serving worker runs in a background thread
(``--serve_role worker --serve_backend tcp``), the training publisher
in the main thread — a real TCP wire between them (the native
transport; falls back to the local loopback shape only where the
native extension cannot build). The gate asserts the contracts the
subsystem stands on:

  1. LIVE PUSH — while the worker absorbs Zipf-skewed open-loop
     traffic against a disk-resident personal-model population, the
     concurrent training run pushes >= 2 checkpoint updates (int8
     delta wire) and the worker adopts and acks every one.
  2. BIT-IDENTITY — the worker's served model after the last push is
     bit-identical to loading that version's checkpoint from disk
     (``obs/diff.py params_diff``): the lossy wire is lossy exactly
     once, at encode, and both ends reconstruct the same bytes.
  3. LIVE SLO — the session evaluates ``p99:serve_latency_ms<50@w=200``
     online: every tick line in the JSONL stream carries slo_health.
     (The VERDICT is not gated — a 1-vCPU CI box serving under
     concurrent training may breach 50ms; that the engine evaluates
     is the contract.)
  4. OBS SURFACE — the JSONL tick lines carry the serving gauges
     (latency/throughput/hit-rate/version/staleness), the drain record
     carries ``serve_drained``, and the run catalog entry records
     ``completed=true`` for the serving stream.
  5. DISTRIBUTED TRACING — a traced session (``--xtrace 1
     --serve_probe_every 4``) merges publisher + worker span lanes
     into one clock-aligned ``federation.trace.json``: every ``adopt``
     span on the worker lane parents to a ``publish`` span on the
     publisher lane (cross-process causality over the real wire), the
     staleness probe stamps ``serve_probe_acc`` on tick lines, and the
     untraced gate run writes NO trace artifacts (tracing off is
     byte-inert).
  6. FAN-OUT — one publisher, two subscribed workers
     (``--serve_workers 2``, loopback): each version is encoded ONCE
     and the frame cloned per subscriber, so both workers adopt
     bit-identical models at the same version; the publisher's
     FleetLedger (worker heartbeats) shows both live and the per-rank
     ack watermarks agree.

    python scripts/serve_smoke.py            # CI gate
    python scripts/serve_smoke.py --requests 128 --rounds 3

Prints ONE JSON line; exits nonzero on any assertion failure.
A CI gate: runs on the CPU platform unless ``JAX_PLATFORMS`` is set (the
chip check is ``chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time

SLO = "p99:serve_latency_ms<50@w=200"

GAUGES = ("serve_requests", "serve_latency_ms", "serve_rps",
          "serve_hit_rate", "serve_model_version",
          "serve_model_staleness_s")


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _argv(args, tmp, sub=""):
    root = os.path.join(tmp, sub) if sub else tmp
    return [
        "--model", "small3dcnn", "--dataset", "synthetic",
        "--client_num_in_total", str(args.clients), "--frac", "0.25",
        "--batch_size", "8", "--epochs", "1",
        "--comm_round", str(args.rounds), "--lr", "0.05",
        "--final_finetune", "0",
        "--log_dir", os.path.join(root, "LOG"),
        "--results_dir", os.path.join(root, "results"),
        "--serve_requests", str(args.requests),
        "--serve_rps", str(args.rps),
        "--serve_batch", "8", "--serve_wire", "int8",
        # a hot set smaller than the population: the Zipf head lives in
        # the LRU, the tail faults to disk — hit_rate < 1 is REAL
        "--serve_store", "disk", "--store_hot_clients", "8",
        "--serve_ckpt_dir", os.path.join(root, "ckpt"),
        "--slo_spec", SLO,
    ]


def _run(argv):
    from neuroimagedisttraining_tpu.experiments import (parse_args,
                                                        run_experiment)
    return run_experiment(parse_args(argv, algo="fedavg"), "fedavg")


def run_serving_gate(args, tmp: str) -> dict:
    from neuroimagedisttraining_tpu.comm.tcp import native_available

    base = _argv(args, tmp)
    tcp = native_available()
    if tcp:
        p0, p1 = _free_ports(2)
        base += ["--serve_backend", "tcp", "--serve_endpoints",
                 f"127.0.0.1:{p0},127.0.0.1:{p1}"]
        worker_box = {}

        def _worker():
            worker_box["res"] = _run(base + ["--serve_role", "worker"])

        wt = threading.Thread(target=_worker, daemon=True)
        wt.start()
        pub = _run(base + ["--serve_role", "publisher"])["serve"]
        wt.join(timeout=180)
        if wt.is_alive() or "res" not in worker_box:
            raise SystemExit("serving worker never drained")
        serve = worker_box["res"]["serve"]
        if pub["acked_version"] < 1:
            raise SystemExit(
                f"publisher saw acks up to v{pub['acked_version']} — "
                "the worker adopted no pushed update")
        pushes = pub["pushes"]
    else:
        # no cc toolchain for the native transport: the loopback shape
        # exercises the same wire codecs over LocalRouter
        serve = _run(base + ["--serve_role", "worker",
                             "--serve_backend", "local"])["serve"]
        pushes = serve["pushes"]
    # contract 1: >= 2 checkpoint updates beyond the full baseline
    # landed while traffic was in flight
    if serve["pushes_adopted"] < 3:
        raise SystemExit(
            f"worker adopted {serve['pushes_adopted']} pushes, need "
            ">= 3 (full baseline + 2 live delta updates)")
    if serve["requests"] != args.requests:
        raise SystemExit(
            f"served {serve['requests']} of {args.requests} requests")
    # contract 2: the runtime's own gate ran and passed (it refuses on
    # divergence; bit_identical=False here means it never compared)
    if not serve["bit_identical"]:
        raise SystemExit("bit-identity gate did not run — no adopted "
                         "push had a visible checkpoint")
    # contracts 3+4: the obs surface
    with open(serve["jsonl"]) as f:
        records = [json.loads(line) for line in f]
    ticks = [r for r in records
             if isinstance(r.get("round"), int) and r["round"] >= 0]
    if not ticks:
        raise SystemExit("no tick records in the serving JSONL")
    missing = [g for g in GAUGES if g not in ticks[-1]]
    if missing:
        raise SystemExit(f"tick records lack serving gauges: {missing}")
    unevaluated = [r for r in ticks if "slo_health" not in r]
    if unevaluated:
        raise SystemExit(
            f"{len(unevaluated)} tick lines lack slo_health — the SLO "
            "engine did not evaluate live")
    if not any(bool(r.get("serve_drained")) for r in records):
        raise SystemExit("no serve_drained record — graceful drain "
                         "left no completion trace")
    cat = os.path.join(tmp, "results", "runs_index.jsonl")
    with open(cat) as f:
        entries = [json.loads(line) for line in f]
    mine = [e for e in entries
            if e["identity"].endswith("-serve") and e["completed"]]
    if not mine:
        raise SystemExit(
            "run catalog has no completed=true entry for the serving "
            f"stream: {[(e['identity'], e['completed']) for e in entries]}")
    # tracing was off: the run dir must hold zero trace artifacts
    from neuroimagedisttraining_tpu.obs import xtrace
    stray = [n for n in sorted(os.listdir(serve["out_dir"]))
             if n.endswith(xtrace.STREAM_SUFFIX)
             or n == xtrace.MERGED_TRACE_NAME]
    if stray:
        raise SystemExit(f"untraced run wrote trace artifacts: {stray}")
    return {
        "transport": "tcp" if tcp else "local",
        "pushes": pushes,
        "pushes_adopted": serve["pushes_adopted"],
        "model_version": serve["model_version"],
        "bit_identical": serve["bit_identical"],
        "requests": serve["requests"],
        "hit_rate": round(serve["hit_rate"], 4),
        "rps": round(serve["rps"], 1),
        "slo_health": serve["slo"]["health_rank"],
        "catalog_completed": True,
    }


def run_tracing_leg(args, tmp: str) -> dict:
    """Contract 5: traced serving session — both lanes in one merged
    trace, adopt spans parent to publish spans across the wire, the
    staleness probe stamps accuracy ticks."""
    from neuroimagedisttraining_tpu.comm.tcp import native_available
    from neuroimagedisttraining_tpu.obs import xtrace

    base = _argv(args, tmp, "xt") + ["--xtrace", "1",
                                     "--serve_probe_every", "4"]
    tcp = native_available()
    if tcp:
        p0, p1 = _free_ports(2)
        base += ["--serve_backend", "tcp", "--serve_endpoints",
                 f"127.0.0.1:{p0},127.0.0.1:{p1}"]
        worker_box = {}

        def _worker():
            worker_box["res"] = _run(base + ["--serve_role", "worker"])

        wt = threading.Thread(target=_worker, daemon=True)
        wt.start()
        _run(base + ["--serve_role", "publisher"])
        wt.join(timeout=180)
        if wt.is_alive() or "res" not in worker_box:
            raise SystemExit("traced serving worker never drained")
        serve = worker_box["res"]["serve"]
    else:
        serve = _run(base + ["--serve_role", "worker",
                             "--serve_backend", "local"])["serve"]
    run_dir = serve["out_dir"]
    # both roles share the run dir here; re-merge once both are done so
    # neither lane is missing (the runtime's own merge may have run
    # before the other role flushed its stream)
    merged = xtrace.merge_run_dir(run_dir)
    if not merged:
        raise SystemExit(f"traced session left no streams in {run_dir}")
    doc = xtrace.load_doc(merged)
    lanes = list((doc.get("xtrace") or {}).get("processes", []))
    if not {"publisher", "serve_worker"} <= set(lanes):
        raise SystemExit(f"merged trace lanes {lanes}, want publisher "
                         "+ serve_worker")
    orphans = xtrace.validate_parentage(doc)
    if orphans:
        raise SystemExit(f"causal tree has orphan spans: {orphans[:5]}")
    idx = xtrace.span_index(doc)
    adopts = 0
    for sid in sorted(idx):
        ev = idx[sid]
        if ev.get("name") != "adopt":
            continue
        parent = str((ev.get("args") or {}).get("parent", ""))
        pev = idx.get(parent)
        if pev is None or pev.get("name") != "publish":
            raise SystemExit(
                f"adopt span {sid} parents to "
                f"{pev and pev.get('name')}, want a publish span")
        adopts += 1
    if not adopts:
        raise SystemExit("traced session recorded no adopt spans")
    with open(serve["jsonl"]) as f:
        records = [json.loads(line) for line in f]
    probes = [r for r in records if "serve_probe_acc" in r]
    if not probes:
        raise SystemExit("--serve_probe_every stamped no "
                         "serve_probe_acc tick")
    lag = [r for r in records if "serve_adopt_lag_ms" in r]
    return {
        "xtrace_transport": "tcp" if tcp else "local",
        "xtrace_lanes": lanes,
        "xtrace_adopts": adopts,
        "probe_ticks": len(probes),
        "adopt_lag_stamped": bool(lag),
    }


def run_fanout_leg(args, tmp: str) -> dict:
    """Contract 6: one publisher, TWO subscribed workers (loopback
    fan-out harness). The publisher encodes each version ONCE and
    clones the frame per subscriber, so both workers adopt
    bit-identical models at the same version; its FleetLedger (fed by
    worker heartbeats) shows both live; ``wait_acked`` paces on the
    slowest subscriber so the per-rank ack watermarks agree."""
    serve = _run(_argv(args, tmp, "fanout") + [
        "--serve_role", "worker", "--serve_backend", "local",
        "--serve_workers", "2", "--obs_heartbeat_every", "0.3",
    ])["serve"]
    workers = serve.get("workers") or []
    if len(workers) != 2:
        raise SystemExit(f"fan-out ran {len(workers)} workers, want 2")
    for w in workers:
        if not w["bit_identical"]:
            raise SystemExit(
                f"fan-out worker {w['rank']} diverged from the "
                f"checkpoint: {w}")
    versions = sorted({w["model_version"] for w in workers})
    if len(versions) != 1 or versions[0] < 1:
        raise SystemExit(
            f"fan-out workers ended at different versions: {workers}")
    acked = serve.get("acked_versions") or {}
    if len(set(acked.values())) != 1 or len(acked) != 2:
        raise SystemExit(
            f"per-rank ack watermarks disagree: {acked}")
    fleet = serve.get("fleet") or {}
    state = {p["peer"]: p["state"] for p in fleet.get("peers", ())}
    if state != {"worker1": "live", "worker2": "live"}:
        raise SystemExit(
            f"publisher ledger missed a fan-out worker: {state}")
    return {
        "fanout_workers": len(workers),
        "fanout_version": versions[0],
        "fanout_bit_identical": True,
        "fanout_acked": sorted(acked.values())[0],
        "fanout_fleet_live": len(state),
    }


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--clients", type=int, default=24)
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--requests", type=int, default=192)
    p.add_argument("--rps", type=float, default=300.0)
    p.add_argument("--tmp", type=str, default="",
                   help="scratch dir (default: a fresh tempdir)")
    args = p.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import logging
    import tempfile

    logging.getLogger().setLevel(logging.WARNING)
    tmp = args.tmp or tempfile.mkdtemp(prefix="serve_smoke_")
    t0 = time.perf_counter()
    result = {"serve_smoke_ok": True, "clients": args.clients,
              "rounds": args.rounds}
    result.update(run_serving_gate(args, tmp))
    result.update(run_tracing_leg(args, tmp))
    result.update(run_fanout_leg(args, tmp))
    result["wall_s"] = round(time.perf_counter() - t0, 2)
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
