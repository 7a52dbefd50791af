"""Device time per round of a scope of one program TOGETHER WITH the kernels
that do its work under another name, or with ``layers`` the share of their
roofline that this time is.

XLA's TPU lowering of ``jax.lax.ragged_dot`` is a Mosaic kernel whose
instruction is named ``ragged-dot-...`` and whose ``op_name`` is that name
again: the JAX name stack is dropped, so the grouped products of the expert
layer lie under no scope although nothing else in the program makes such
kernels. ``kernels`` lists instruction-name prefixes; an op counts if it is
under ``scope`` or is such a kernel, and the time is the union of their
intervals. A kernel carries no pass, so the share has no split by pass. The
floor is that of ``scope_roofline``: the rows ``layers`` of the reference's
table (``lib/flops.step_floor`` at the cell's batch) times the steps a chip
runs per round. Nothing matches: nothing returned."""

from benchmarks.lib import scopes
from benchmarks.lib.flops import step_floor


def read(ctx, program, scope, kernels, layers=None):
    tr = ctx["trace"]
    under = scopes.matcher(scope, program=program)
    prefixes = tuple(kernels)

    def kernel(op):
        return op.program.startswith(program) and op.name.startswith(prefixes)

    seconds = tr.where_s(lambda op: under(op) or kernel(op)) / tr.rounds
    if not seconds:
        scopes.note_missing(ctx, scope, program)
        return None
    parts = ctx["details"].setdefault("scope_kernels", {}).setdefault(
        scope, {"kernels": list(kernels),
                "scope_s_per_round": tr.where_s(under) / tr.rounds,
                "kernels_s_per_round": tr.where_s(kernel) / tr.rounds,
                "both_s_per_round": seconds})
    if layers is None:
        return 1e3 * seconds
    steps = ctx["counters"]["steps_per_round_per_chip"]
    _, rows = step_floor(ctx["layers"], ctx["batch"], ctx["itemsize"],
                         ctx["peaks"])
    rows = [r for r in rows if r["layer"] in layers]
    if not rows:
        return None
    floor_s = steps * sum(r["floor_s"] for r in rows)
    parts["floor_s_per_round"] = floor_s
    parts["bounds"] = {f"{r['layer']}.{r['pass']}": r["bound"] for r in rows}
    return 100.0 * floor_s / seconds
