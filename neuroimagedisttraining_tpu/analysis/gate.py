"""Gate orchestration: run the analyzer families, apply the baseline,
produce one verdict (exit codes 0 clean / 1 findings / 2 config error).

Exit codes: 0 clean (possibly via baseline suppressions), 1 findings,
2 configuration error (unreadable baseline/ledger, unknown analyzer,
broken fixture) — a broken gate must never read as an all-clear.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set

from .findings import (
    Finding,
    apply_baseline,
    load_baseline_doc,
    render_report,
)

EXIT_OK = 0
EXIT_FINDINGS = 1
EXIT_CONFIG = 2

ANALYZERS = ("astlint", "identity", "xfail", "jaxpr")

#: top-level package dirs whose edits can change the traced round
#: programs (the --changed-only trigger set for the jaxpr audit);
#: models/ and data/ are traced INTO the round (forward pass, input
#: dtypes), so they trigger too
_JAXPR_TRIGGER_DIRS = ("algorithms", "parallel", "robust", "core",
                       "ops", "models", "data")


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def load_fixture(spec: str):
    """``path/to/file.py::name`` -> the named zero-arg callable, which
    returns ``(fn, args_tuple)`` for the jaxpr auditor. The fixture
    convention keeps seeded-violation tests out of the package tree."""
    if "::" not in spec:
        raise ValueError(f"jaxpr fixture spec {spec!r}: expected "
                         "path.py::callable_name")
    path, name = spec.split("::", 1)
    modspec = importlib.util.spec_from_file_location("_lint_fixture",
                                                     path)
    if modspec is None or modspec.loader is None:
        raise ValueError(f"jaxpr fixture {path!r} not importable")
    mod = importlib.util.module_from_spec(modspec)
    try:
        modspec.loader.exec_module(mod)
    except Exception as e:
        # a broken fixture (SyntaxError, failing import, ...) is a
        # CONFIG error: it must reach the gate's exit-2 path, not
        # crash with a traceback that reads like findings
        raise ValueError(f"jaxpr fixture {path!r} failed to load: "
                         f"{type(e).__name__}: {e}") from e
    fx = getattr(mod, name, None)
    if fx is None:
        raise ValueError(f"jaxpr fixture {path!r} has no {name!r}")
    return fx


def _changed_filter(changed_files: Optional[Iterable[str]],
                    pkg_name: str) -> Optional[Set[str]]:
    """Repo-relative changed paths -> package-relative module set for
    astlint (None = lint everything)."""
    if changed_files is None:
        return None
    out: Set[str] = set()
    prefix = pkg_name + "/"
    for p in changed_files:
        p = p.replace(os.sep, "/")
        if p.startswith(prefix) and p.endswith(".py"):
            out.add(os.path.normpath(p[len(prefix):]))
    return out


def run_gate(
    only: Optional[Sequence[str]] = None,
    pkg_root: Optional[str] = None,
    config_path: Optional[str] = None,
    baseline_path: Optional[str] = None,
    tests_dir: Optional[str] = None,
    xfail_ledger: Optional[str] = None,
    changed_files: Optional[Iterable[str]] = None,
    jaxpr_fixture: Optional[str] = None,
    x64: bool = False,
    jaxpr_algos: Sequence[str] = ("fedavg", "salientgrads"),
    jaxpr_donate: bool = True,
) -> Dict[str, Any]:
    """Run the selected analyzers; returns a verdict dict with
    ``exit_code``, ``findings`` (live), ``suppressed``, ``stale``,
    ``reports`` (per-analyzer detail), and ``report`` (human text)."""
    repo = _repo_root()
    pkg_root = pkg_root or os.path.join(repo,
                                        "neuroimagedisttraining_tpu")
    pkg_name = os.path.basename(os.path.abspath(pkg_root))
    baseline_path = baseline_path if baseline_path is not None else \
        os.path.join(repo, "results", "lint_baseline.json")
    tests_dir = tests_dir or os.path.join(repo, "tests")
    xfail_ledger = xfail_ledger or os.path.join(tests_dir,
                                                "xfail_ledger.json")
    selected = tuple(only) if only else ANALYZERS
    unknown = [a for a in selected if a not in ANALYZERS]

    notes: List[str] = []
    findings: List[Finding] = []
    reports: Dict[str, Any] = {}

    def config_error(msg: str) -> Dict[str, Any]:
        return {"exit_code": EXIT_CONFIG, "error": msg,
                "findings": [], "suppressed": [], "stale": [],
                "reports": reports,
                "report": f"lint_gate: config error: {msg}"}

    if unknown:
        return config_error(f"unknown analyzer(s) {unknown}; "
                            f"choose from {list(ANALYZERS)}")
    try:
        baseline_doc = load_baseline_doc(baseline_path)
    except ValueError as e:
        return config_error(str(e))
    baseline = {str(e["key"]): str(e["justification"])
                for e in baseline_doc.get("entries", ())}
    # the donation GATE's pins ride the same reviewed baseline file:
    # entry points listed under "donated_entry_points" must audit as
    # donated (one parse validates both sections)
    donation_pins: List[str] = list(
        baseline_doc.get("donated_entry_points", ()))

    changed = set(changed_files) if changed_files is not None else None
    if changed is not None and any(
            p.replace(os.sep, "/").startswith(f"{pkg_name}/analysis/")
            or p.replace(os.sep, "/").startswith("scripts/lint_gate")
            for p in changed):
        # editing the analyzers themselves (the documented FLAG_CLASSES
        # workflow, a rule change, the gate) invalidates every skip
        # heuristic: fall back to the full run
        notes.append("changed-only: analyzer sources changed — "
                     "running the full gate")
        changed = None
    ast_changed = _changed_filter(changed, pkg_name)

    if "astlint" in selected:
        if ast_changed is not None and not ast_changed:
            # nothing in the package changed: skip the whole-package
            # parse + traced-set fixpoint (the dominant cost of the
            # fast local loop this mode exists for)
            reports["astlint"] = {"ran": False,
                                  "reason": "no package module changed"}
        else:
            from . import astlint

            try:
                lint = astlint.PackageLint(pkg_root)
            except (ValueError, OSError) as e:
                return config_error(str(e))
            if ast_changed is not None:
                skipped = ast_changed - set(lint.modules)
                ast_changed &= set(lint.modules)
                if skipped:
                    notes.append(
                        f"changed-only: {len(skipped)} changed "
                        "path(s) outside the package ignored")
            findings.extend(lint.lint(changed=ast_changed))
            reports["astlint"] = {
                "modules": len(lint.modules),
                "contract_modules": len(lint.contract_modules()),
                "traced_functions": len(lint.traced),
            }

    if "identity" in selected:
        from . import identity

        cfg_rel = f"{pkg_name}/experiments/config.py"
        run_it = changed is None or config_path is not None or any(
            p.replace(os.sep, "/") == cfg_rel for p in changed)
        if run_it:
            try:
                if config_path is not None:
                    with open(config_path) as f:
                        findings.extend(identity.audit_config_source(
                            f.read(), config_file=config_path))
                else:
                    findings.extend(identity.audit_package(pkg_root))
            except (ValueError, OSError, SyntaxError) as e:
                return config_error(f"identity analyzer: {e}")
            reports["identity"] = {"ran": True}
        else:
            reports["identity"] = {"ran": False,
                                   "reason": "config.py unchanged"}

    if "xfail" in selected:
        from . import astlint

        run_it = changed is None or any(
            p.replace(os.sep, "/").startswith("tests/")
            for p in changed)
        if run_it:
            try:
                findings.extend(astlint.check_xfails(
                    tests_dir, xfail_ledger))
            except (ValueError, OSError) as e:
                return config_error(f"xfail analyzer: {e}")
            reports["xfail"] = {"ran": True}
        else:
            reports["xfail"] = {"ran": False,
                                "reason": "tests/ unchanged"}

    if "jaxpr" in selected:
        from . import jaxpr_audit

        if jaxpr_fixture is not None:
            try:
                fx = load_fixture(jaxpr_fixture)
                fn, args = fx()
                s = jaxpr_audit.summarize(fn, *args, x64=x64)
            except Exception as e:
                # fixture code is caller-supplied: ANY failure in it is
                # a config error (exit 2), never a findings verdict
                return config_error(
                    f"jaxpr fixture {jaxpr_fixture!r}: "
                    f"{type(e).__name__}: {e}")
            label = f"jaxpr-fixture:{jaxpr_fixture.split('::')[-1]}"
            findings.extend(jaxpr_audit.audit_summary(s, label))
            reports["jaxpr"] = {
                "fixture": jaxpr_fixture,
                "collectives": s.collective_multiset(),
                "dtypes": sorted(s.dtypes),
            }
        else:
            run_it = changed is None or any(
                p.replace(os.sep, "/").startswith(
                    tuple(f"{pkg_name}/{d}/"
                          for d in _JAXPR_TRIGGER_DIRS))
                for p in changed)
            if run_it:
                import jax

                if len(jax.devices()) < 2:
                    notes.append(
                        "jaxpr audit off-mesh (single device): "
                        "collective multisets are empty; run under "
                        "the 8-virtual-device test env for the full "
                        "check")
                f, rep = jaxpr_audit.audit_algorithms(
                    jaxpr_algos, donate=jaxpr_donate,
                    donation_pins=donation_pins)
                findings.extend(f)
                reports["jaxpr"] = rep
            else:
                reports["jaxpr"] = {"ran": False,
                                    "reason": "no jit-path dir changed"}

    live, suppressed, stale = apply_baseline(findings, baseline)
    # a partial run (subset of analyzers, changed-only, or a fixture)
    # cannot judge staleness: the suppressed finding may belong to an
    # analyzer that didn't run
    full_run = (set(selected) == set(ANALYZERS) and changed is None
                and jaxpr_fixture is None and config_path is None
                and os.path.abspath(pkg_root) == os.path.abspath(
                    os.path.join(repo, "neuroimagedisttraining_tpu")))
    if not full_run:
        stale = []
    exit_code = EXIT_FINDINGS if (live or stale) else EXIT_OK
    return {
        "exit_code": exit_code,
        "findings": [f.to_dict() for f in live],
        "suppressed": [dict(f.to_dict(),
                            justification=baseline.get(f.key, ""))
                       for f in suppressed],
        "stale": [f.to_dict() for f in stale],
        "reports": reports,
        "notes": notes,
        "report": render_report(live, suppressed, stale, selected,
                                notes),
    }
