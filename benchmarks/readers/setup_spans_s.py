"""Seconds of set-up by span of the program's tree (``lib/spans.py``): the
union of the spans called ``names`` that end inside set-up, or with ``rest``
the part of ``setup_s`` under no depth-0 span at all: what ran before the
program's import (the interpreter, the benchmark's own imports, ``import
jax``) and between the spans (the program's modules, the backend's start,
the parser). The union and not the sum: compile durations nest.

Into the run's details go ``setup_tree`` (per path such as
``init_state/snip_mask/compile/backend``: how many, their total and their
self seconds; the self seconds add up to the union of the depth-0 spans) and
``setup_gaps`` (the unspanned part by the depth-0 spans on either side, the
first from process start). A name the run did not record: nothing returned,
and listed in ``spans_missing``."""

from benchmarks.lib import spans as sp

START, END = "(process start)", "(first block)"


def _account(ctx, tree, roots, end):
    """``setup_tree`` and ``setup_gaps`` of the details."""
    inside = [s for s in tree if s.end <= end]
    path, own = sp.paths(inside), sp.self_ns(inside)
    table = {}
    for s in inside:
        row = table.setdefault(path[s.id], {"n": 0, "total_s": 0.0,
                                            "self_s": 0.0})
        row["n"] += 1
        row["total_s"] += s.seconds
        row["self_s"] += own[s.id] / 1e9
    # process start on the tracer's clock: setup_s was taken at set-up's end
    at = end - ctx["counters"]["setup_s"] * 1e9
    gaps, name = {}, START
    for r in roots:
        gaps[f"{name} .. {r.name}"] = max(0.0, r.start - at) / 1e9
        at, name = max(at, r.end), r.name
    gaps[f"{name} .. {END}"] = max(0.0, end - at) / 1e9
    ctx["details"]["setup_tree"] = dict(sorted(
        table.items(), key=lambda kv: -kv[1]["total_s"]))
    ctx["details"]["setup_gaps"] = gaps


def read(ctx, names=(), rest=False):
    tree = sp.tree_of(ctx)
    roots = sp.setup_roots(tree)
    if not roots:
        for name in names or ("(set-up)",):
            sp.missing(ctx, name)
        return None
    end = sp.setup_end(tree)
    if "setup_tree" not in ctx["details"]:
        _account(ctx, tree, roots, end)
    if rest:
        return ctx["counters"]["setup_s"] - sp.union_s(roots)
    found = [s for s in tree if s.name in names and s.end <= end]
    absent = set(names) - {s.name for s in found}
    for name in sorted(absent):
        sp.missing(ctx, name)
    return None if absent else sp.union_s(found)
