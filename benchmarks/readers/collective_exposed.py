"""The share of the traced window in which a collective ran and no other op
did on that chip: communication that compute does not hide."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * tr.exposed_collective_s() / tr.window_s
