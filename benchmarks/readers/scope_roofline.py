"""A scope's share of its roofline in one program (the round: training
steps), from the rows of the reference's layer table that the scope executes.
``layers`` maps a row's name to the scope its time is measured under
(``{"conv1": "stem/conv", ...}``); rows this model's table does not have are
skipped, so one metric file serves every model with such a scope. The share
is the rows' summed floor (``lib/flops.step_floor``: per row and pass the
larger of operations over peak FLOP/s and bytes over peak bytes/s, at the
cell's batch) times the steps a chip runs per round, over the measured device
time per round under ``scope``. Per pass, and per row and pass, the floor, the
time measured under the row's own scope and their ratio go into the run's
details. XLA fuses across rows (the norm's statistics into the conv, its
backward into the conv's bias gradient) and a fusion is timed under its
root's name, so a single row's ratio can read far over 100 %: only the total
is a metric."""

from benchmarks.lib import scopes
from benchmarks.lib.flops import step_floor

PASSES = {"forward": "fwd", "backward": "bwd"}


def read(ctx, program, scope, layers):
    tr = ctx["trace"]

    def measured(where, way=None):
        return tr.where_s(scopes.matcher(where, way, program)) / tr.rounds

    seconds = measured(scope)
    if not seconds:
        scopes.note_missing(ctx, scope, program)
        return None
    steps = ctx["counters"]["steps_per_round_per_chip"]
    _, rows = step_floor(ctx["layers"], ctx["batch"], ctx["itemsize"],
                         ctx["peaks"])
    table = [{"layer": r["layer"], "pass": PASSES[r["pass"]],
              "scope": layers[r["layer"]], "bound": r["bound"],
              "floor_s_per_round": r["floor_s"] * steps,
              "measured_s_per_round": measured(layers[r["layer"]],
                                               PASSES[r["pass"]])}
             for r in rows if r["layer"] in layers]
    if not table:
        return None
    passes = {way: {"floor_s_per_round": sum(
                        r["floor_s_per_round"] for r in table
                        if r["pass"] == way),
                    "measured_s_per_round": measured(scope, way)}
              for way in PASSES.values()}
    for entry in table + list(passes.values()):
        entry["share_pct"] = 100.0 * entry["floor_s_per_round"] \
            / entry["measured_s_per_round"] \
            if entry["measured_s_per_round"] else None
    floor_s = sum(r["floor_s_per_round"] for r in table)
    ctx["details"][scope.replace("/", "_") + "_roofline"] = {
        "scope": scope, "floor_s_per_round": floor_s,
        "measured_s_per_round": seconds, "passes": passes, "rows": table}
    return 100.0 * floor_s / seconds
