"""A gauge of the program's own metrics registry (``obs/metrics.py``'s
process-wide one), by name: a number the program counted about itself, such
as how evenly the routed slots fall on the held experts. Where the program has
no such registry or gauge, or nothing set it in this run: nothing returned."""


def read(ctx, gauge):
    try:
        from neuroimagedisttraining_tpu.obs import metrics
    except ImportError:
        return None
    entry = metrics.get_registry().snapshot().get(gauge) or {}
    return entry.get("value")
