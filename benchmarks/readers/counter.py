"""A number the harness counted during the run (set-up totals of the
program's CompileWatch, bytes after placement, the window's rate), scaled."""


def read(ctx, key, scale=1.0):
    value = ctx["counters"].get(key)
    return None if value is None else value * scale
