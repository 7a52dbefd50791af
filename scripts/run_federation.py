"""Multi-process federation launcher: one aggregator + N site processes.

Forks ``python -m neuroimagedisttraining_tpu.experiments`` once per
role over the native TCP transport, allocating free loopback ports and
wiring ``--fed_endpoints`` for every rank. Everything after ``--`` is
forwarded verbatim to each process (the experiment config: algo,
model, dataset, rounds, fed mode/sites/buffer flags).

    # 3 sites, synchronous rounds (bit-identical to the simulation)
    python scripts/run_federation.py --sites 3 -- \
        --algo fedavg --client_num_in_total 6 --frac 1.0 \
        --fed_mode sync --comm_round 4

    # buffered async, flush at K=2, with a real straggling site
    python scripts/run_federation.py --sites 3 -- \
        --algo fedavg --client_num_in_total 6 \
        --fed_mode buffered --fed_buffer_k 2 \
        --fed_site_faults "3:straggle=1.0:6.0" --comm_round 4

Sites are started FIRST so their listeners are bound before the
aggregator's round-0 dispatch; the aggregator's ``send_with_retry``
backoff covers the residual connect race. The launcher's exit code is
the aggregator's; site processes are terminated if they outlive the
aggregator by ``--site_grace`` seconds (a deliberately-straggling site
may still be asleep in its handler when the federation finishes).

Platform: the processes run on the CPU unless ``JAX_PLATFORMS`` says
otherwise. A TPU chip serves ONE process, and the launcher assigns no
chips: under a TPU platform it refuses a world larger than the host's
chip count, and each process must be pinned to its own chip by the caller
(``TPU_VISIBLE_CHIPS``).

Prints one JSON line describing the launch (ports, pids, exit codes).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time

RUNNER = ["-m", "neuroimagedisttraining_tpu.experiments"]


def free_ports(n: int, host: str = "127.0.0.1"):
    """Bind-to-0 allocation: n distinct free ports, released at once so
    no two ranks are handed the same port."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind((host, 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def local_tpu_chips() -> int:
    """TPU chips this host exposes, counted WITHOUT touching JAX (a
    launcher that initialized the backend would hold the chips its
    children need)."""
    import glob

    visible = os.environ.get("TPU_VISIBLE_CHIPS", "")
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    return len(glob.glob("/dev/accel[0-9]*")) or \
        len(glob.glob("/dev/vfio/[0-9]*"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--sites", type=int, required=True,
                   help="number of site processes (world = sites + 1)")
    p.add_argument("--host", type=str, default="127.0.0.1")
    p.add_argument("--ports", type=str, default="",
                   help="comma-separated ports, rank-ordered "
                        "(aggregator first); default: auto-allocate")
    p.add_argument("--out", type=str, default="",
                   help="shared --fed_out directory (default: every "
                        "process derives the same identity-keyed dir)")
    p.add_argument("--site_grace", type=float, default=30.0,
                   help="seconds to let sites drain after the "
                        "aggregator exits before terminating them")
    p.add_argument("--python", type=str, default=sys.executable)
    p.add_argument("runner_args", nargs=argparse.REMAINDER,
                   help="args after -- go to every runner process")
    args = p.parse_args(argv)

    passthrough = list(args.runner_args)
    if passthrough and passthrough[0] == "--":
        passthrough = passthrough[1:]
    if args.sites < 1:
        p.error("--sites must be >= 1")
    for flag in ("--fed_role", "--fed_site_rank", "--fed_endpoints",
                 "--fed_backend", "--fed_sites"):
        if flag in passthrough:
            p.error(f"{flag} is set by the launcher; remove it from "
                    "the runner args")

    world = args.sites + 1
    if args.ports:
        ports = [int(x) for x in args.ports.split(",") if x.strip()]
        if len(ports) != world:
            p.error(f"--ports needs {world} entries (got {len(ports)})")
    else:
        ports = free_ports(world, args.host)
    endpoints = ",".join(f"{args.host}:{port}" for port in ports)

    common = passthrough + [
        "--fed_backend", "tcp", "--fed_sites", str(args.sites),
        "--fed_endpoints", endpoints,
    ]
    if args.out:
        common += ["--fed_out", args.out]
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    if "tpu" in env["JAX_PLATFORMS"].lower():
        chips = local_tpu_chips()
        if world > chips:
            p.error(f"JAX_PLATFORMS={env['JAX_PLATFORMS']}: {world} "
                    f"processes (1 aggregator + {args.sites} sites) need "
                    f"{world} TPU chips, one each; this host has {chips}")

    procs = {}
    try:
        for rank in range(1, world):
            cmd = [args.python] + RUNNER + common + [
                "--fed_role", "site", "--fed_site_rank", str(rank)]
            procs[rank] = subprocess.Popen(cmd, env=env)
        agg_cmd = [args.python] + RUNNER + common + [
            "--fed_role", "aggregator"]
        agg = subprocess.Popen(agg_cmd, env=env)
        procs[0] = agg
        agg_rc = agg.wait()
        deadline = time.monotonic() + args.site_grace
        site_rcs = {}
        for rank in range(1, world):
            left = max(deadline - time.monotonic(), 0.0)
            try:
                site_rcs[rank] = procs[rank].wait(timeout=left)
            except subprocess.TimeoutExpired:
                procs[rank].terminate()
                try:
                    site_rcs[rank] = procs[rank].wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    procs[rank].kill()
                    site_rcs[rank] = procs[rank].wait()
        print(json.dumps({
            "launcher_ok": agg_rc == 0,
            "world": world, "ports": ports,
            "aggregator_rc": agg_rc,
            "site_rcs": {str(k): v for k, v in sorted(site_rcs.items())},
            "out": args.out or "(identity-derived, see aggregator log)",
        }))
        return agg_rc
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                try:
                    proc.send_signal(signal.SIGTERM)
                except OSError:
                    pass


if __name__ == "__main__":
    sys.exit(main())
