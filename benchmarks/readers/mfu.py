"""Model FLOP/s utilisation of training: the forward and backward operations
the published architecture needs per sample (``lib/flops.py``, from the
plain reference's layer table) times the samples trained per second in this
run's window, over the chips times the bf16 peak. An end-to-end utilisation:
not a kernel's roofline share, and blind to idle time."""

from benchmarks.lib.flops import train_flops_per_sample


def read(ctx):
    c = ctx["counters"]
    rate = c["rounds_per_s"] * c["samples_per_round"]
    return 100.0 * train_flops_per_sample(ctx["layers"]) * rate / (
        c["chips"] * ctx["peaks"]["bf16_flops"])
