"""Device time per round of whole programs, chosen by the start of their
XLA module names. The per-program seconds go into the run's details."""


def read(ctx, programs):
    tr = ctx["trace"]
    each = {p: tr.where_s(lambda op, p=p: op.program.startswith(p))
            for p in programs}
    ctx["details"]["program_s_per_round"] = {
        p: s / tr.rounds for p, s in each.items()}
    seconds = tr.where_s(lambda op: op.program.startswith(tuple(programs)))
    return 1e3 * seconds / tr.rounds if seconds else None
