"""The present-tense documents name only what the tree holds.

``README.md`` and sections 1-4 of ``PERF.md`` are what a new session plans
from. A ``--flag`` no parser takes, or a repo-relative path that is gone,
sends it after code that was deleted (PR 31 found the README still leading
with the pre-chip harness, its gate scripts and their flags). History, with
its names of what once was, lives in ``CHANGES.md``, ``RESULTS.md`` and the
later sections of ``PERF.md``, which this does not read."""
import glob
import os
import re
import subprocess

import pytest

from neuroimagedisttraining_tpu.analysis.identity import collect_flags

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the reference implementation's own sources, which PERF.md's cell table
#: cites by bare name (BASELINE.md has their repository)
REFERENCE_FILES = {"main_sailentgrads.py", "salient_models.py"}
#: where runs write: a path under these is an example of an output
OUTPUT_DIRS = ("results/", "chiprun_out/", "benchmarks/out/", "LOG/")

_PATH = re.compile(
    r"(?<![\w/.<*-])((?:[\w.-]+/)*[\w.-]+\.(?:py|md|jsonl|json))(?!\w)")
_FLAG = re.compile(r"(?<![\w-])--([a-zA-Z][\w-]*)(\*?)")


def _read(name):
    with open(os.path.join(REPO, name)) as f:
        return f.read()


def _perf_present_tense():
    text = _read("PERF.md")
    return text[:text.index("\n## 5.")]


def _parser_flags():
    """Every ``--flag`` some parser of the repo takes: the program's, the
    scripts', the benchmark's and the subcommands' of ``python -m``."""
    known = set(collect_flags(_read(os.path.join(
        "neuroimagedisttraining_tpu", "experiments", "config.py"))))
    sources = glob.glob(os.path.join(REPO, "scripts", "*.py")) + glob.glob(
        os.path.join(REPO, "neuroimagedisttraining_tpu", "**",
                     "__main__.py"), recursive=True) + [
        os.path.join(REPO, "benchmarks", "run.py")]
    for path in sources:
        with open(path) as f:
            known |= set(re.findall(
                r"""add_argument\(\s*["']--([\w-]+)""", f.read()))
    return known


def _tracked_basenames():
    out = subprocess.run(["git", "ls-files"], cwd=REPO, capture_output=True,
                         text=True)
    names = out.stdout.split() if out.returncode == 0 else []
    if not names:   # the driver's checkout may not be a git repository
        names = [os.path.join(d, f) for d, _, fs in os.walk(REPO) for f in fs]
    return {os.path.basename(n) for n in names}


def _missing_paths(text):
    """Path-like tokens of ``text`` that name nothing: one with a directory
    must exist from the repo's root, the package's or the benchmark's; a
    bare ``.py``/``.md`` name anywhere in the tree; a bare ``.json``/
    ``.jsonl`` only where it is one of the repo's records (capitalised)."""
    roots = ("", "neuroimagedisttraining_tpu", "benchmarks")
    basenames = _tracked_basenames()
    missing = []
    for token in sorted(set(_PATH.findall(text))):
        if token.startswith(".") or token.startswith(OUTPUT_DIRS):
            continue
        if "/" in token:
            found = any(os.path.exists(os.path.join(REPO, root, token))
                        for root in roots)
        elif token.endswith((".json", ".jsonl")) and not token[0].isupper():
            continue    # a run's artefact (summary.json, fleet.json)
        else:
            found = token in basenames or token in REFERENCE_FILES
        if not found:
            missing.append(token)
    return missing


def test_readme_names_no_flag_a_parser_lacks():
    known = _parser_flags()
    unknown = sorted(
        name + star for name, star in set(_FLAG.findall(_read("README.md")))
        if not (any(k.startswith(name) for k in known) if star
                else name in known))
    assert not unknown, unknown


@pytest.mark.parametrize("doc", ["README.md", "PERF.md sections 1-4"])
def test_present_tense_documents_name_no_missing_file(doc):
    text = _read(doc) if doc == "README.md" else _perf_present_tense()
    assert not _missing_paths(text)
