"""Plain float32 reference of the zoo's CI-size ``small3dcnn``: the model of
the benchmark's CPU rehearsal (``benchmarks/tests``), which accepts 8^3
volumes. No cell on the chip uses it.

    Conv3d(1, 8, k3, s2, p1) - norm - relu - Conv3d(8, 16, k3, p1) - relu
    - global average pool - Linear(16, 1)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops

WIDTH = 8


def forward(params, x):
    """Logits ``(N,)`` of dense volumes ``x`` ``(N, D, H, W)``."""
    x = x[..., None].astype(jnp.float32)
    s, c, d = params["stem"], params["conv"], params["dense"]
    x = ops.conv3d(x, s["w"], 2, 1) + s["b"]
    x = jax.nn.relu(ops.group_norm(x, s["scale"], s["bias"]))
    x = jax.nn.relu(ops.conv3d(x, c["w"], 1, 1) + c["b"])
    return ops.dense(x.mean(axis=(1, 2, 3)), d["w"], d["b"])[:, 0]


def from_system(tree, dense_stem):
    """The program's ``small3dcnn_s2d`` parameter tree as this file's."""
    stem, g = tree["S2DStemConv_0"], tree["GroupNorm_0"]
    c, d = tree["Conv3d_0"]["Conv_0"], tree["Dense_0"]
    return {"stem": {"w": dense_stem(stem["kernel"]), "b": stem["bias"],
                     "scale": g["scale"], "bias": g["bias"]},
            "conv": {"w": c["kernel"], "b": c["bias"]},
            "dense": {"w": d["kernel"], "b": d["bias"]}}


GRAD_LEAVES = {
    "stem_kernel": (("S2DStemConv_0", "kernel"), ("stem", "w")),
    "last_dense": (("Dense_0", "kernel"), ("dense", "w")),
}


def layers(volume):
    r1, shape = ops.conv_layer("stem", tuple(volume) + (1,), WIDTH, 3, 2, 1,
                               input_grad=False)
    r2, out = ops.conv_layer("conv2", shape, 2 * WIDTH, 3, 1, 1)
    return [r1, ops.pointwise_layer("stem_norm", shape), r2,
            ops.pointwise_layer("pool", out, (1, 1, 1, 2 * WIDTH)),
            ops.dense_layer("dense1", 2 * WIDTH, 1)]
