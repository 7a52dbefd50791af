"""Device time per round of the ops under one ``jax.named_scope`` of the
round program. Nothing under the scope in the trace: nothing returned."""

from benchmarks.lib.reduce_trace import in_scope


def read(ctx, scope):
    tr = ctx["trace"]
    seconds = tr.where_s(lambda op: in_scope(op, scope))
    return 1e3 * seconds / tr.rounds if seconds else None
