"""Operations and bytes a model's layers need, from shapes alone.

The rows come from a plain reference's ``layers(volume)`` table
(``benchmarks/reference``): the published architecture on the dense volume,
not what the program executes (its phased stem multiplies 216 slots for the
k5 stem's 125 taps; a mask on the weights saves nothing on this chip). The
count is per sample; recomputed operations never count.

* conv, dense: a multiply-add is 2 operations. Forward ``2 * out positions *
  taps * C_in * C_out``; the backward pass costs the same again for the
  weight gradient and again for the input gradient, which the stem does not
  need (its input is data).
* bytes, for the roofline: every layer reads its input and its weights and
  writes its output once going forward, and going backward reads the output
  gradient and what it saved and writes the gradients, each tensor in the
  compute type. That is the traffic of a layer-by-layer execution with
  nothing fused; a fused kernel can move fewer bytes and exceed 100 %.
"""
from __future__ import annotations

from math import prod


def _weights(layer) -> int:
    if layer["kind"] == "conv":
        return layer["taps"] * layer["in"][-1] * layer["out"][-1]
    if layer["kind"] == "dense":
        return layer["in"][0] * layer["out"][0]
    return 0


def forward_flops(layer) -> float:
    if layer["kind"] == "conv":
        return 2.0 * prod(layer["out"][:3]) * _weights(layer)
    if layer["kind"] == "dense":
        return 2.0 * _weights(layer)
    return 0.0


def backward_flops(layer) -> float:
    passes = 1 + bool(layer.get("input_grad"))
    return passes * forward_flops(layer)


def forward_bytes(layer, itemsize: int) -> float:
    return itemsize * (prod(layer["in"]) + _weights(layer)
                       + prod(layer["out"]))


def backward_bytes(layer, itemsize: int) -> float:
    n_in, n_out, n_w = prod(layer["in"]), prod(layer["out"]), _weights(layer)
    if layer["kind"] == "pointwise":
        return itemsize * (n_out + 2 * n_in)   # d_out, saved input -> d_in
    total = n_out + n_in + n_w                 # weight gradient
    if layer.get("input_grad"):
        total += n_out + n_w + n_in            # input gradient
    return itemsize * total


def train_flops_per_sample(layers) -> float:
    """Forward and backward operations of one training sample."""
    return sum(forward_flops(r) + backward_flops(r) for r in layers)


def step_floor(layers, batch: int, itemsize: int, peaks: dict):
    """The least time one training step of ``batch`` samples could take on
    a chip with ``peaks``: per layer and pass, the larger of operations over
    peak FLOP/s and bytes over peak bytes/s. Returns the seconds and, per
    layer, which of the two bounds it (weights are read once a step, not
    once a sample: their share is small and is left in)."""
    total, rows = 0.0, []
    for r in layers:
        for which, flops, nbytes in (
                ("forward", forward_flops(r), forward_bytes(r, itemsize)),
                ("backward", backward_flops(r), backward_bytes(r, itemsize))):
            t_c = batch * flops / peaks["bf16_flops"]
            t_m = batch * nbytes / peaks["hbm_bytes_per_s"]
            total += max(t_c, t_m)
            rows.append({"layer": r["name"], "pass": which,
                         "flops": batch * flops, "bytes": batch * nbytes,
                         "floor_s": max(t_c, t_m),
                         "bound": "compute" if t_c >= t_m else "memory"})
    return total, rows
