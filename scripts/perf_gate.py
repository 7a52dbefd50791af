"""Perf regression gate: a CI-gateable verdict over the bench trajectory.

Compares a current ``bench.py`` metric value against the durable
``results/bench_history.jsonl`` trajectory (obs/regress.py: median/MAD
noise band) and exits

  0  pass (within the band, or --backfill/--append bookkeeping modes)
  1  significant regression
  2  not enough history to judge (bootstrap; pipelines may soft-pass)

Usage:
    # seed the history once from the committed MULTICHIP_r*.json
    # artifacts (the comm SLO baseline)
    python scripts/perf_gate.py --backfill

    # gate an explicit value
    python scripts/perf_gate.py --value 1.66 \
        --metric salientgrads_rounds_per_sec_abcd_alexnet3d_8clients

    # gate a bench JSON line (file, or - for stdin):
    python bench.py | tail -1 | python scripts/perf_gate.py --from-json -

    # comm SLO gates (seeded from MULTICHIP_r01..r05): lower-is-better
    # and the comm band defaults resolve from the metric name, so the
    # bare value is enough
    python scripts/perf_gate.py --metric scale32_agg_ms --value 1015.3
    python scripts/perf_gate.py --metric scale32_agg_share --value 55.8

    # record the gated value into the history after it passes
    python scripts/perf_gate.py --from-json out.json --append

Prints ONE JSON verdict line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

DEFAULT_HISTORY = os.path.join(REPO_ROOT, "results",
                               "bench_history.jsonl")
DEFAULT_METRIC = "salientgrads_rounds_per_sec_abcd_alexnet3d_8clients"


def main(argv=None) -> int:
    from neuroimagedisttraining_tpu.obs import regress

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--history", default=DEFAULT_HISTORY)
    p.add_argument("--metric", default="",
                   help=f"metric name (default: the --from-json line's, "
                        f"else {DEFAULT_METRIC})")
    p.add_argument("--value", type=float, default=None,
                   help="current metric value to gate")
    p.add_argument("--from-json", default="",
                   help="bench JSON result to gate: a file path, or - "
                        "for stdin (reads the last JSON line)")
    p.add_argument("--rel-threshold", type=float, default=None,
                   help="relative band (default: the metric's entry in "
                        "obs.regress.METRIC_GATE_DEFAULTS, else "
                        f"{regress.DEFAULT_REL_THRESHOLD})")
    p.add_argument("--mad-k", type=float, default=None,
                   help="MAD band multiplier (default: per-metric, else "
                        f"{regress.DEFAULT_MAD_K})")
    p.add_argument("--window", type=int, default=regress.DEFAULT_WINDOW)
    p.add_argument("--lower-is-better", action="store_true",
                   help="metric regresses UPWARD (e.g. ms/aggregation; "
                        "auto for the comm SLO / agg_ms_* metrics)")
    p.add_argument("--backfill", action="store_true",
                   help="seed the history from MULTICHIP_r*.json and "
                        "exit")
    p.add_argument("--append", action="store_true",
                   help="append the gated value to the history when the "
                        "verdict is pass/no-history")
    args = p.parse_args(argv)

    if args.backfill:
        nm = regress.backfill_multichip_files(REPO_ROOT, args.history)
        total = len(regress.read_history(args.history))
        print(json.dumps({"backfilled_multichip": nm,
                          "history_points": total,
                          "history": args.history}))
        return regress.EXIT_OK

    result = None
    if args.from_json:
        text = (sys.stdin.read() if args.from_json == "-"
                else open(args.from_json).read())
        result = regress.last_json_result(text, required=("value",))
        if result is None:
            print(json.dumps({"error": "no bench JSON line found",
                              "from": args.from_json}))
            return regress.EXIT_NO_HISTORY
    value = args.value if args.value is not None else (
        float(result["value"]) if result else None)
    if value is None:
        p.error("need --value, --from-json, or --backfill")
    metric = args.metric or (result or {}).get("metric") or DEFAULT_METRIC

    # fresh clone bootstrap: results/ is gitignored, so the DEFAULT
    # history auto-seeds from the committed MULTICHIP_r*.json artifacts
    # the first time the gate runs (idempotent; explicit --history
    # paths are left alone)
    if not os.path.exists(args.history) and \
            os.path.abspath(args.history) == \
            os.path.abspath(DEFAULT_HISTORY):
        regress.backfill_multichip_files(REPO_ROOT, args.history)

    # per-metric gate defaults (obs/regress.py): the comm SLO metrics
    # are lower-is-better with a pure relative band; explicit flags win
    defaults = regress.metric_gate_defaults(metric)
    rel = (args.rel_threshold if args.rel_threshold is not None
           else defaults.get("rel_threshold",
                             regress.DEFAULT_REL_THRESHOLD))
    mad_k = (args.mad_k if args.mad_k is not None
             else defaults.get("mad_k", regress.DEFAULT_MAD_K))
    higher = (not args.lower_is_better
              and defaults.get("higher_is_better", True))

    sha = regress.git_sha(REPO_ROOT)
    try:
        verdict = regress.gate(
            args.history, metric, value,
            rel_threshold=rel,
            mad_k=mad_k, window=args.window,
            higher_is_better=higher,
            exclude_git_sha=sha)  # never judge a commit against itself
    except ValueError as e:
        # a truncated/corrupted history line must read as "no usable
        # baseline" (exit 2), NEVER as the regression verdict (exit 1)
        print(json.dumps({"error": f"unreadable history: {e}",
                          "metric": metric,
                          "exit_code": regress.EXIT_NO_HISTORY}))
        return regress.EXIT_NO_HISTORY
    if args.append and verdict["exit_code"] != regress.EXIT_REGRESSION:
        dup = any(e.get("value") == value and e.get("git_sha") == sha
                  for e in regress.read_history(args.history, metric))
        if not dup:  # bench.py already appended this run's value
            regress.append_history(
                args.history,
                result or {"metric": metric, "value": value},
                source="perf_gate", repo_root=REPO_ROOT)
        verdict["appended"] = not dup
    print(json.dumps(verdict))
    return int(verdict["exit_code"])


if __name__ == "__main__":
    raise SystemExit(main())
