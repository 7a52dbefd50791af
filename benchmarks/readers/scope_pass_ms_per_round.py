"""Device time per round of the ops of one program (``"jit_round_fn"``: the
harness has ``op_name``s for the round alone) under one ``jax.named_scope``
(``"stem/conv"``: whole path segments, anywhere in the ``op_name``), of one
pass (``"fwd"``: under a ``jvp``; ``"bwd"``: under a ``transpose``), or both:
the union of their intervals. A fused op belongs where the ``op_name`` XLA
gave the fusion (its root's) puts it. Nothing matches: nothing returned, and
a scope that is missing from a round traced with names is noted in the
run's details with the likeliest reason."""

from benchmarks.lib import scopes


def read(ctx, program, scope=None, direction=None):
    tr = ctx["trace"]
    seconds = tr.where_s(scopes.matcher(scope, direction, program))
    if seconds:
        return 1e3 * seconds / tr.rounds
    if scope and not tr.where_s(scopes.matcher(scope, program=program)):
        scopes.note_missing(ctx, scope, program)
    return None
