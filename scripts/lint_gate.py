"""Static-contract lint gate: a CI-gateable verdict over the codebase.

Runs the ``analysis/`` analyzer families — AST trace-purity lint,
jaxpr collective/dtype/donation audit, identity-inertness gate, xfail
hygiene — applies the reviewed suppression baseline
(``results/lint_baseline.json``), and exits

  0  clean (possibly via baseline pins)
  1  findings (or stale baseline / stale xfail-ledger entries)
  2  configuration error (unreadable baseline/ledger, unknown
     analyzer, broken fixture) — a broken gate never reads as clean

Usage:
    # the full gate (what tests/test_lint_gate.py runs in tier-1)
    python scripts/lint_gate.py

    # fast local loop: only modules changed since the merge base
    python scripts/lint_gate.py --changed-only
    python scripts/lint_gate.py --changed-only --base main

    # one analyzer family
    python scripts/lint_gate.py --only astlint
    python scripts/lint_gate.py --only identity,xfail

    # machine-readable verdict (the human report goes to stderr)
    python scripts/lint_gate.py --json -

    # seeded-violation plumbing (tests): lint a copied package tree /
    # an alternate config / a jaxpr fixture (optionally under x64 so
    # latent f64 promotions surface)
    python scripts/lint_gate.py --only astlint --pkg-root /tmp/pkg
    python scripts/lint_gate.py --only identity --config /tmp/config.py
    python scripts/lint_gate.py --only jaxpr \
        --jaxpr-fixture tests/fixtures/jaxpr_fixtures.py::f64_round --x64

    # donation-gate seeded violation: audit a borrowing (un-donated)
    # instance — the baseline's donated_entry_points pins must fire
    python scripts/lint_gate.py --only jaxpr --jaxpr-no-donate

Donation-ledger report (ROADMAP Open item 2's measurement, now a gate:
``results/lint_baseline.json``'s ``donated_entry_points`` pins the
central entry points donated — a regression to un-donated exits 1):
    python scripts/lint_gate.py --only jaxpr --json - | \
        python -c "import json,sys; \
            print(json.load(sys.stdin)['reports']['jaxpr'])"

A CI gate: runs on the CPU platform (8 virtual devices) unless
``JAX_PLATFORMS`` is set.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# the jaxpr audit proves collective parity on the 8-virtual-device test
# mesh; force it (and CPU) BEFORE jax imports, exactly like tests/conftest
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _changed_files(base: str) -> list:
    """Changed repo-relative paths: committed since merge-base(HEAD,
    base) + uncommitted + untracked. A broken git (missing binary,
    corrupt metadata) raises RuntimeError — the CLI maps it to exit 2:
    an empty changed set from a FAILED git read would skip every
    analyzer and read as clean, the exact false all-clear the gate's
    exit-code contract forbids. A missing ``base`` ref alone degrades
    gracefully (uncommitted+untracked still gate)."""
    def run(*args):
        try:
            out = subprocess.run(
                ["git", "-C", REPO_ROOT, *args], capture_output=True,
                text=True, timeout=60)
        except (OSError, subprocess.TimeoutExpired) as e:
            raise RuntimeError(f"git {args[0]} failed: {e}") from e
        if out.returncode != 0:
            return None
        # one path per LINE — .split() would mangle spaced paths
        return [ln for ln in out.stdout.splitlines() if ln.strip()]

    worktree = run("diff", "--name-only", "HEAD")
    untracked = run("ls-files", "--others", "--exclude-standard")
    if worktree is None or untracked is None:
        raise RuntimeError(
            "git cannot read the working tree (broken repo?); "
            "--changed-only has no change set to gate")
    files = set(worktree) | set(untracked)
    mb = run("merge-base", "HEAD", base)
    if mb:  # base ref may legitimately not exist (shallow clone)
        committed = run("diff", "--name-only", mb[0], "HEAD")
        files.update(committed or [])
    return sorted(files)


def main(argv=None) -> int:
    from neuroimagedisttraining_tpu.analysis import gate

    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--only", default="",
                   help="comma-separated analyzer subset "
                        f"({', '.join(gate.ANALYZERS)})")
    p.add_argument("--json", default="",
                   help="write the JSON verdict here (- for stdout; "
                        "the human report then goes to stderr)")
    p.add_argument("--baseline", default=None,
                   help="suppression baseline path (default "
                        "results/lint_baseline.json)")
    p.add_argument("--pkg-root", default=None,
                   help="alternate package root (seeded-violation "
                        "tests lint a copied tree)")
    p.add_argument("--config", default=None,
                   help="alternate config.py for the identity gate")
    p.add_argument("--xfail-ledger", default=None,
                   help="alternate xfail ledger path")
    p.add_argument("--tests-dir", default=None,
                   help="alternate tests/ dir for the xfail check")
    p.add_argument("--jaxpr-fixture", default=None,
                   help="path.py::name — audit this fixture's "
                        "(fn, args) instead of the central algorithms")
    p.add_argument("--x64", action="store_true",
                   help="trace the jaxpr fixture under enable_x64 so "
                        "latent f64 promotions surface")
    p.add_argument("--jaxpr-no-donate", action="store_true",
                   help="audit a borrowing (donate_state=0) instance — "
                        "seeded-violation plumbing proving the "
                        "donated_entry_points gate exits 1 on an "
                        "un-donation regression")
    p.add_argument("--changed-only", action="store_true",
                   help="lint only files changed vs the merge base "
                        "(+ uncommitted/untracked); analyzers whose "
                        "inputs are unchanged are skipped")
    p.add_argument("--base", default="main",
                   help="--changed-only base ref (default main)")
    args = p.parse_args(argv)

    only = [s for s in args.only.split(",") if s] or None
    changed = None
    if args.changed_only:
        try:
            changed = _changed_files(args.base)
        except RuntimeError as e:
            print(json.dumps({"exit_code": 2, "error": str(e)}))
            return 2

    verdict = gate.run_gate(
        only=only,
        pkg_root=args.pkg_root,
        config_path=args.config,
        baseline_path=args.baseline,
        tests_dir=args.tests_dir,
        xfail_ledger=args.xfail_ledger,
        changed_files=changed,
        jaxpr_fixture=args.jaxpr_fixture,
        x64=args.x64,
        jaxpr_donate=not args.jaxpr_no_donate,
    )
    if changed is not None:
        verdict["changed_files"] = changed

    report = verdict.pop("report", "")
    if args.json:
        blob = json.dumps(verdict, indent=1, default=str)
        if args.json == "-":
            print(blob)
            print(report, file=sys.stderr)
        else:
            with open(args.json, "w") as f:
                f.write(blob + "\n")
            print(report)
    else:
        print(report)
    return int(verdict["exit_code"])


if __name__ == "__main__":
    raise SystemExit(main())
