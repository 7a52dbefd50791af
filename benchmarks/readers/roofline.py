"""A scope's share of its roofline: the least time one local step could take
on this chip (``lib/flops.step_floor``: per layer and pass the larger of
operations over peak FLOP/s and bytes over peak bytes/s, at the cell's batch)
over the measured device time of one step under the scope. Which bound holds
for each layer goes into the run's details."""

from benchmarks.lib.flops import step_floor
from benchmarks.lib.reduce_trace import in_scope


def read(ctx, scope):
    tr = ctx["trace"]
    seconds = tr.where_s(lambda op: in_scope(op, scope))
    if not seconds:
        return None
    step_s = seconds / tr.rounds / ctx["counters"]["steps_per_round_per_chip"]
    floor_s, rows = step_floor(ctx["layers"], ctx["batch"], ctx["itemsize"],
                               ctx["peaks"])
    ctx["details"]["roofline"] = {"scope": scope, "step_s": step_s,
                                  "floor_s": floor_s, "layers": rows}
    return 100.0 * floor_s / step_s
