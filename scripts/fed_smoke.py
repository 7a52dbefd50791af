"""Federation smoke: the distributed-runtime subsystem's CI gate.

Runs the loopback federation (1 aggregator + 3 sites on a
``LocalRouter``, real wire messages, real handler threads) and
asserts the contracts the subsystem stands on:

  1. SYNC BIT-PARITY — a synchronous federated run produces global
     params bit-identical to the single-process simulation with the
     same argv (compared through ``obs/diff.py params_diff``, which
     names the diverging leaves). This pins that splitting the round
     body across site processes changed NOTHING numerically.
  2. BUFFERED DEGRADATION + REPLAY — with site 3 deliberately
     straggling (asleep longer than the whole run), the buffered-async
     run still completes every flush from the surviving sites, records
     an arrival trace, and replaying that trace reproduces the global
     params bit-for-bit.
  3. DISTRIBUTED TRACING — a traced federation (``--xtrace 1``, over
     the native TCP transport where it builds, the loopback shape
     otherwise) with an injected per-round straggler produces ONE
     clock-aligned ``federation.trace.json`` with span lanes from the
     aggregator AND every site, a closed causal tree (every
     ``site_round`` parents to its round's ``dispatch`` span), and a
     per-round critical-path decomposition whose named straggler
     matches the injected ``--fed_site_faults`` straggle trace.
     Tracing-on vs tracing-off twins stay ``identical`` through the
     ``obs/diff.py`` planes (params + per-stream trajectories +
     events) — tracing off is byte-inert on the wire.
  4. LIVE FLEET TELEMETRY — heartbeats (``--obs_heartbeat_every``)
     are byte-inert (hb-on twin ``identical`` to the plain sync run
     through every diff plane) and the ledger sees every site LIVE;
     a site killed mid-run (``rank:kill:after_s`` fault) turns
     SITE_DOWN with a typed event while the surviving quorum
     finishes every buffered flush, the federation-scope SLO
     (``ewma:fleet_sites_live>=N``) breaches, the ``--obs_prom_port``
     ``/metrics`` endpoint serves parseable fleet gauges MID-RUN, and
     ``obs watch --once`` renders the run dir's fleet frame.

    python scripts/fed_smoke.py              # CI gate
    python scripts/fed_smoke.py --rounds 3 --clients 9

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
import time

STRAGGLER_FAULTS = "3:straggle=1.0:{sleep}"


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _argv(clients, rounds, tmp, sub):
    return [
        "--model", "small3dcnn", "--dataset", "synthetic",
        "--client_num_in_total", str(clients), "--frac", "1.0",
        "--batch_size", "8", "--epochs", "1",
        "--comm_round", str(rounds), "--lr", "0.05",
        "--final_finetune", "0",
        "--log_dir", os.path.join(tmp, sub, "LOG"),
        "--results_dir", os.path.join(tmp, sub, "results"),
    ]


def _run(argv):
    from neuroimagedisttraining_tpu.experiments import (parse_args,
                                                        run_experiment)
    return run_experiment(parse_args(argv, algo="fedavg"), "fedavg")


def _assert_identical(a, b, what):
    from neuroimagedisttraining_tpu.obs import diff as obs_diff

    pd = obs_diff.params_diff(a, b)
    if not pd["identical"]:
        raise SystemExit(
            f"{what} diverged: {len(pd['diverged'])} leaves, first "
            f"{pd['diverged'][:3]}")


def _load_jsonl(path):
    recs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                recs.append(json.loads(line))
            except ValueError:
                break  # partial tail from a killed writer
    return recs


def run_sync_parity(clients: int, rounds: int, sites: int,
                    tmp: str) -> tuple:
    """Contract 1: loopback sync federation == in-process simulation."""
    import jax
    import numpy as np

    base = _argv(clients, rounds, tmp, "sync")
    fed = base + ["--fed_role", "aggregator", "--fed_mode", "sync",
                  "--fed_sites", str(sites), "--fed_backend", "local"]
    out_fed = _run(fed)
    # --mesh_devices 1: the anchor is the UNSHARDED simulation — sites
    # compute on a single device, and a clients-mesh twin (multi-device
    # hosts) reduces in a different order (~1e-7 float drift, not parity)
    out_twin = _run(_argv(clients, rounds, tmp, "twin")
                    + ["--mesh_devices", "1"])
    twin_params = jax.tree_util.tree_map(
        np.asarray, out_twin["state"].global_params)
    _assert_identical(out_fed["global_params"], twin_params,
                      "sync federation vs in-process simulation")
    fed_hist = {h["round"]: h["train_loss"] for h in out_fed["history"]
                if h.get("round", -1) >= 0}
    twin_hist = {h["round"]: h["train_loss"] for h in out_twin["history"]
                 if "train_loss" in h}
    if fed_hist != twin_hist:
        raise SystemExit(
            f"sync round losses diverged: fed={fed_hist} "
            f"twin={twin_hist}")
    statuses = [h.get("fed_status") for h in out_fed["history"]
                if h.get("round", -1) >= 0]
    if statuses != ["completed"] * rounds:
        raise SystemExit(f"sync rounds not all completed: {statuses}")
    if not out_fed["fed"]["federation_jsonl"]:
        raise SystemExit("aggregator produced no folded federation.jsonl")
    # out_fed doubles as the tracing leg's untraced twin
    return {"sync_bit_identical": True, "sync_rounds": rounds}, out_fed


def run_buffered_replay(clients: int, rounds: int, sites: int,
                        tmp: str, straggle_s: float) -> dict:
    """Contract 2: buffered async completes without the straggler and
    the recorded arrival trace replays bit-for-bit."""
    base = _argv(clients, rounds, tmp, "buf")
    buf = base + [
        "--fed_role", "aggregator", "--fed_mode", "buffered",
        "--fed_sites", str(sites), "--fed_buffer_k", str(sites - 1),
        "--fed_backend", "local",
        "--fed_site_faults",
        STRAGGLER_FAULTS.format(sleep=straggle_s),
        "--fed_timeout_s", "60",
    ]
    out_buf = _run(buf)
    flushes = [h for h in out_buf["history"] if h.get("round", -1) >= 0]
    if len(flushes) != rounds:
        raise SystemExit(
            f"buffered run flushed {len(flushes)} times, expected "
            f"{rounds} — the straggler stalled the federation")
    trace_path = out_buf["fed"]["trace_path"]
    with open(trace_path) as f:
        trace = json.load(f)
    members = [tuple(m) for fl in trace["flushes"] for m in fl["members"]]
    if any(site == sites for site, _base in members):
        raise SystemExit(
            f"straggling site {sites} appears in the flush trace "
            f"{members} — the fault never fired")
    if not members:
        raise SystemExit("empty arrival trace — nothing was aggregated")
    replay = _argv(clients, rounds, tmp, "replay") + [
        "--fed_role", "aggregator", "--fed_mode", "buffered",
        "--fed_sites", str(sites), "--fed_buffer_k", str(sites - 1),
        "--fed_backend", "local",
        "--fed_site_faults",
        STRAGGLER_FAULTS.format(sleep=straggle_s),
        "--fed_timeout_s", "60",
        "--fed_replay", trace_path,
    ]
    out_rep = _run(replay)
    if not out_rep["fed"]["replayed"]:
        raise SystemExit("replay run did not take the replay path")
    _assert_identical(out_buf["global_params"], out_rep["global_params"],
                      "buffered run vs its own trace replay")
    hist = out_buf["fed"]["staleness_hist"]
    return {
        "buffered_flushes": len(flushes),
        "replay_bit_identical": True,
        "survivors_only": True,
        "staleness_hist": hist,
    }


def run_tracing_leg(clients: int, rounds: int, sites: int, tmp: str,
                    off_fed: dict, straggle_s: float) -> dict:
    """Contract 3: one merged causal trace, straggler attribution
    matching the injected fault, tracing off byte-inert."""
    import glob
    import threading

    from neuroimagedisttraining_tpu.comm.tcp import native_available
    from neuroimagedisttraining_tpu.obs import analyze as obs_analyze
    from neuroimagedisttraining_tpu.obs import diff as obs_diff
    from neuroimagedisttraining_tpu.obs import xtrace

    # -- leg A: traced federation with an injected per-round straggler
    base = _argv(clients, rounds, tmp, "xt") + [
        "--fed_mode", "sync", "--fed_sites", str(sites),
        "--fed_site_faults", f"{sites}:straggle=1.0:{straggle_s}",
        "--fed_timeout_s", "120",
        "--xtrace", "1",
    ]
    tcp = native_available()
    if tcp:
        ports = _free_ports(sites + 1)
        base += ["--fed_backend", "tcp", "--fed_endpoints",
                 ",".join(f"127.0.0.1:{p}" for p in ports)]
        sites_done = []

        def _site(k):
            _run(base + ["--fed_role", "site",
                         "--fed_site_rank", str(k)])
            sites_done.append(k)

        threads = [threading.Thread(target=_site, args=(k,), daemon=True)
                   for k in range(1, sites + 1)]
        for t in threads:
            t.start()
        out = _run(base + ["--fed_role", "aggregator"])
        for t in threads:
            t.join(timeout=120)
        if len(sites_done) != sites:
            raise SystemExit(
                f"only {len(sites_done)}/{sites} site processes exited")
    else:
        out = _run(base + ["--fed_role", "aggregator",
                           "--fed_backend", "local"])
    run_dir = out["fed"]["out_dir"]
    # TCP runtime merges are partial (each role only sees the streams
    # already on disk when IT exits) — re-merge once every role is done,
    # same as the operator's `obs xtrace <dir>`
    merged = xtrace.merge_run_dir(run_dir)
    if not merged:
        raise SystemExit(f"traced run left no xtrace streams in {run_dir}")
    doc = xtrace.load_doc(merged)
    lanes = list((doc.get("xtrace") or {}).get("processes", []))
    want = ["aggregator"] + [f"site{k}" for k in range(1, sites + 1)]
    if lanes != want:
        raise SystemExit(f"merged trace lanes {lanes}, want {want}")
    orphans = xtrace.validate_parentage(doc)
    if orphans:
        raise SystemExit(f"causal tree has orphan spans: {orphans[:5]}")
    idx = xtrace.span_index(doc)
    for sid in sorted(idx):
        ev = idx[sid]
        if ev.get("name") != "site_round":
            continue
        parent = str((ev.get("args") or {}).get("parent", ""))
        pev = idx.get(parent)
        if pev is None or pev.get("name") != "dispatch":
            raise SystemExit(
                f"site_round {sid} parents to "
                f"{pev and pev.get('name')}, want a dispatch span")
    records = []
    for p in sorted(glob.glob(os.path.join(run_dir, "*.jsonl"))):
        name = os.path.basename(p)
        if name.endswith(".events.jsonl") or name == "federation.jsonl":
            continue
        records.extend(_load_jsonl(p))
    xt = obs_analyze._analyze_xtrace(doc, records)
    if not xt.get("present"):
        raise SystemExit("analyzer saw no merged trace")
    named = [r for r in xt.get("rounds", []) if r.get("straggler")]
    if not named:
        raise SystemExit("no round in the trace named a straggler")
    wrong = [r for r in named if r["straggler"] != f"site{sites}"]
    if wrong:
        raise SystemExit(
            f"critical path missed the injected straggler: {wrong[:2]}")
    if xt.get("straggler_mismatches"):
        raise SystemExit(
            "attribution contradicts the sites' own straggle records: "
            f"{xt['straggler_mismatches']}")

    # -- leg B: tracing-on loopback twin vs the untraced sync run -----
    out_on = _run(_argv(clients, rounds, tmp, "xt_on") + [
        "--fed_role", "aggregator", "--fed_mode", "sync",
        "--fed_sites", str(sites), "--fed_backend", "local",
        "--xtrace", "1",
    ])
    pd = obs_diff.params_diff(off_fed["global_params"],
                              out_on["global_params"])
    if not pd["identical"]:
        raise SystemExit(
            f"tracing is not byte-inert: {len(pd['diverged'])} param "
            f"leaves diverged, first {pd['diverged'][:3]}")
    off_dir = off_fed["fed"]["out_dir"]
    on_dir = out_on["fed"]["out_dir"]
    for name in sorted(os.listdir(off_dir)):
        if name.endswith(xtrace.STREAM_SUFFIX) or \
                name == xtrace.MERGED_TRACE_NAME:
            raise SystemExit(
                f"untraced run wrote a trace artifact: {name}")
        a = _load_jsonl(os.path.join(off_dir, name))
        b_path = os.path.join(on_dir, name)
        if not os.path.exists(b_path):
            raise SystemExit(f"traced twin is missing stream {name}")
        b = _load_jsonl(b_path)
        if name.endswith(".events.jsonl"):
            d = obs_diff.events_diff(a, b)
        elif name.endswith(".jsonl") and name != "federation.jsonl":
            d = obs_diff.trajectory_diff(a, b)
        else:
            continue
        if not d["identical"]:
            raise SystemExit(f"tracing-on twin diverged in {name}: {d}")
    agg_on = _load_jsonl(os.path.join(on_dir, "aggregator.jsonl"))
    if not any("fed_round_ms" in r for r in agg_on):
        raise SystemExit("traced aggregator never stamped fed_round_ms")
    return {
        "xtrace_transport": "tcp" if tcp else "local",
        "xtrace_lanes": len(lanes),
        "xtrace_rounds_attributed": len(named),
        "xtrace_straggler": f"site{sites}",
        "xtrace_inert": True,
    }


def run_live_leg(clients: int, rounds: int, sites: int, tmp: str,
                 off_fed: dict, hb_every: float) -> dict:
    """Contract 4 (live fleet telemetry): heartbeats are byte-inert;
    a site killed mid-run turns SITE_DOWN on the ledger BEFORE the
    round timeout while the surviving quorum finishes every flush; the
    federation-scope SLO (min sites live) breaches; the /metrics
    endpoint serves parseable fleet gauges mid-run; and
    ``obs watch --once`` renders a non-empty frame from the run dir."""
    import threading
    from urllib.request import urlopen

    from neuroimagedisttraining_tpu.obs import diff as obs_diff
    from neuroimagedisttraining_tpu.obs import prom as obs_prom
    from neuroimagedisttraining_tpu.obs.__main__ import watch_cli

    # -- leg A: heartbeat-on loopback twin vs the plain sync run ------
    out_on = _run(_argv(clients, rounds, tmp, "hb_on") + [
        "--fed_role", "aggregator", "--fed_mode", "sync",
        "--fed_sites", str(sites), "--fed_backend", "local",
        "--obs_heartbeat_every", str(hb_every),
    ])
    pd = obs_diff.params_diff(off_fed["global_params"],
                              out_on["global_params"])
    if not pd["identical"]:
        raise SystemExit(
            f"heartbeats are not byte-inert: {len(pd['diverged'])} "
            f"param leaves diverged, first {pd['diverged'][:3]}")
    off_dir = off_fed["fed"]["out_dir"]
    on_dir = out_on["fed"]["out_dir"]
    for name in sorted(os.listdir(off_dir)):
        if not name.endswith(".jsonl") or name == "federation.jsonl":
            continue
        b_path = os.path.join(on_dir, name)
        if not os.path.exists(b_path):
            raise SystemExit(f"heartbeat twin is missing stream {name}")
        a = _load_jsonl(os.path.join(off_dir, name))
        b = _load_jsonl(b_path)
        d = obs_diff.events_diff(a, b) \
            if name.endswith(".events.jsonl") \
            else obs_diff.trajectory_diff(a, b)
        if not d["identical"]:
            raise SystemExit(
                f"heartbeat-on twin diverged in {name}: {d}")
    fleet = (out_on["fed"] or {}).get("fleet") or {}
    live_peers = [p for p in fleet.get("peers", ())
                  if p["state"] == "live" and p["frames"] > 0]
    if len(live_peers) != sites:
        raise SystemExit(
            f"heartbeat run ledger saw {len(live_peers)}/{sites} "
            f"live peers: {fleet}")
    if os.path.exists(os.path.join(off_dir, "fleet.json")):
        raise SystemExit("heartbeat-off run wrote a fleet.json")

    # -- leg B: kill a site mid-run; detect, breach, survive ----------
    # timing: DOWN fires after 6 silent heartbeat intervals (1.2s at
    # 0.2s), while straggling ONE survivor pins the flush cadence (a
    # site has at most one update in flight, so every flush waits on
    # site 1's 0.5s sleep) — the run deterministically outlives the
    # detection threshold with warm jit caches
    hb_kill = min(0.2, hb_every)
    kill_after = 2.0 * hb_kill
    kill_rounds = max(rounds + 3, 5)
    port = _free_ports(1)[0]
    argv = _argv(clients, kill_rounds, tmp, "kill") + [
        "--fed_role", "aggregator", "--fed_mode", "buffered",
        "--fed_sites", str(sites), "--fed_buffer_k", str(sites - 1),
        "--fed_backend", "local",
        "--fed_site_faults",
        f"1:straggle=1.0:0.5;{sites}:kill:{kill_after}",
        "--fed_timeout_s", "120",
        "--obs_heartbeat_every", str(hb_kill),
        "--obs_prom_port", str(port),
        "--slo_spec", f"ewma:fleet_sites_live>={sites}@a=1,min=1",
    ]
    box = {}

    def _agg():
        box["out"] = _run(argv)

    th = threading.Thread(target=_agg, daemon=True)
    th.start()
    # mid-run prom scrape: the endpoint is up for the whole run, so
    # poll until it serves the fleet gauges (run still in flight)
    samples = {}
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and th.is_alive():
        try:
            with urlopen(f"http://127.0.0.1:{port}/metrics",
                         timeout=2.0) as resp:
                samples = obs_prom.parse_prom_text(
                    resp.read().decode("utf-8"))
        except OSError:
            samples = {}
        if "fleet_sites_live" in samples:
            break
        time.sleep(0.1)
    if "fleet_sites_live" not in samples:
        raise SystemExit(
            "prom endpoint never served fleet gauges mid-run "
            f"(last scrape keys: {sorted(samples)[:8]})")
    th.join(timeout=240)
    if "out" not in box:
        raise SystemExit("killed-site run did not finish")
    out_kill = box["out"]
    flushes = [h for h in out_kill["history"]
               if h.get("round", -1) >= 0]
    if len(flushes) != kill_rounds:
        raise SystemExit(
            f"quorum did not survive the kill: {len(flushes)} flushes, "
            f"expected {kill_rounds}")
    # the ledger named the killed site DOWN (the typed event fired
    # during the run — not a post-hoc timeout postmortem)
    events = _load_jsonl(os.path.join(
        out_kill["fed"]["out_dir"], "aggregator.events.jsonl"))
    downs = [e for e in events if e.get("event_type") == "SITE_DOWN"]
    down_peers = sorted({p for e in downs
                         for p in (e.get("detail") or {})["peers"]})
    if f"site{sites}" not in down_peers:
        raise SystemExit(
            f"no SITE_DOWN event named site{sites}: {downs}")
    fleet = (out_kill["fed"] or {}).get("fleet") or {}
    state = {p["peer"]: p["state"] for p in fleet.get("peers", ())}
    if state.get(f"site{sites}") != "down":
        raise SystemExit(
            f"final ledger snapshot missed the kill: {state}")
    # federation-scope SLO: min-sites-live breached once the site died
    slo = (out_kill["fed"] or {}).get("slo") or {}
    breaches = [e for e in events
                if e.get("event_type") == "SLO_BREACH"]
    if slo.get("health") == "ok" or not breaches:
        raise SystemExit(
            "fleet SLO never breached despite the killed site: "
            f"health={slo.get('health')}, breaches={len(breaches)}")
    # obs watch --once renders a non-empty frame from the run dir
    frames = []
    rc = watch_cli(out_kill["fed"]["out_dir"], once=True,
                   out=frames.append)
    if rc != 0 or not frames or f"site{sites}" not in frames[0]:
        raise SystemExit(
            f"obs watch --once failed: rc={rc}, frame={frames[:1]}")
    return {
        "hb_inert": True,
        "hb_live_peers": len(live_peers),
        "kill_flushes": len(flushes),
        "site_down_detected": down_peers,
        "fleet_slo_health": slo.get("health"),
        "fleet_slo_breaches": len(breaches),
        "prom_scrape_keys": len(samples),
        "watch_frame_lines": frames[0].count("\n"),
    }


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--clients", type=int, default=6)
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--sites", type=int, default=3)
    p.add_argument("--straggle_s", type=float, default=30.0,
                   help="straggler sleep; must exceed the whole "
                        "buffered run so the site never reports")
    p.add_argument("--trace_straggle_s", type=float, default=1.5,
                   help="per-round straggle in the traced leg; long "
                        "enough to dominate compile/timing noise, "
                        "short enough that sync rounds still complete")
    p.add_argument("--hb_every", type=float, default=0.5,
                   help="heartbeat interval for the live-telemetry "
                        "leg; DOWN fires at 6x this silence")
    p.add_argument("--tmp", type=str, default="",
                   help="scratch dir (default: a fresh tempdir)")
    args = p.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import logging
    import tempfile

    logging.getLogger().setLevel(logging.WARNING)
    tmp = args.tmp or tempfile.mkdtemp(prefix="fed_smoke_")
    t0 = time.perf_counter()
    result = {"fed_smoke_ok": True, "clients": args.clients,
              "sites": args.sites}
    sync_res, off_fed = run_sync_parity(args.clients, args.rounds,
                                        args.sites, tmp)
    result.update(sync_res)
    result.update(run_buffered_replay(args.clients, args.rounds,
                                      args.sites, tmp, args.straggle_s))
    result.update(run_tracing_leg(args.clients, args.rounds, args.sites,
                                  tmp, off_fed, args.trace_straggle_s))
    result.update(run_live_leg(args.clients, args.rounds, args.sites,
                               tmp, off_fed, args.hb_every))
    result["wall_s"] = round(time.perf_counter() - t0, 2)
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
