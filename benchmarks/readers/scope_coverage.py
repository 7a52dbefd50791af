"""The share of a program's device time that no name accounts for: its ops
that are under none of ``scopes`` and belong to no pass, by self time (an
op's interval less the ops nested in it, so a loop is not counted on top of
its body), over the self time of all its ops. By name, not by interval: XLA
may run an op named for one scope inside the loop of another (on one chip
the clients run one at a time through ``lax.map``, and a client's gather can
sink into that loop), and the name is what says whose work it is. Only an
op with no ``op_name`` at all takes the name of the loop or call it runs in.

Into the run's details go the table scope x pass of self time per round
(``-`` = no scope of the list, no pass; an op under two scopes counts for
the first in the list), whose cells add up to the program's device time,
and the ten uncovered ops with most self time."""

from benchmarks.lib.scopes import direction, inherited, under

NONE = "-"
TOP = 10


def read(ctx, program, scopes):
    tr = ctx["trace"]
    cells, uncovered = {}, {}
    for ops in tr.devices.values():
        for op, name in inherited(ops):
            if not op.program.startswith(program):
                continue
            scope = next((s for s in scopes if under(name, s)), NONE)
            way = direction(name) or NONE
            cells[scope, way] = cells.get((scope, way), 0.0) + op.self_ns
            if (scope, way) == (NONE, NONE):
                uncovered[op.label] = uncovered.get(op.label, 0.0) \
                    + op.self_ns
    total = sum(cells.values())
    if not total:
        return None
    per_round = 1e-6 / (len(tr.devices) * tr.rounds)   # ns -> ms per round
    table = {}
    for (scope, way), ns in sorted(cells.items()):
        table.setdefault(scope, {})[way] = ns * per_round
    ranked = sorted(uncovered.items(), key=lambda kv: -kv[1])[:TOP]
    ctx["details"]["scope_coverage"] = {
        "program": program, "total_ms_per_round": total * per_round,
        "self_ms_per_round": table,
        "uncovered_ops_ms_per_round": [[k, ns * per_round]
                                       for k, ns in ranked]}
    return 100.0 * cells.get((NONE, NONE), 0.0) / total

