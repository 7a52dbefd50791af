"""The device's busy and idle time over the traced rounds: ``busy_ms`` and
``gap_ms`` per round (averaged over the chips), or ``idle_pct``, the idle
share of the window on the chip that idles most."""


def read(ctx, field):
    tr = ctx["trace"]
    if field == "busy_ms":
        return 1e3 * tr.busy_s / tr.rounds
    if field == "gap_ms":
        return 1e3 * (tr.window_s - tr.busy_s) / tr.rounds
    if field == "idle_pct":
        return 100.0 * tr.idle_share()
    raise ValueError(f"device_window: unknown field {field!r}")
