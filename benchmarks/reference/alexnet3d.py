"""Plain float32 reference of AlexNet3D_Dropout (the reference repo's
``fedml_api/model/cv/salient_models.py:142-191``), for ABCD sex classification.

    Conv3d(1, 64, k5, s2) - norm - relu - MaxPool3d(3, 3)
    Conv3d(64, 128, k3)   - norm - relu - MaxPool3d(3, 3)
    Conv3d(128, 192, k3, p1) - norm - relu
    Conv3d(192, 192, k3, p1) - norm - relu
    Conv3d(192, 128, k3, p1) - norm - relu - MaxPool3d(3, 3)
    flatten (256 at 121x145x121) - Dropout - Linear(256, 64) - relu
    - Dropout - Linear(64, 1)

Departures from the published model, both the zoo's own and documented in
PARITY.md: the norm is GroupNorm (at most 32 groups, eps 1e-6) where the
source has BatchNorm3d, and activations are channels-last, so the flatten
runs over (D, H, W, C). Dropout is the identity here: the comparison runs
both sides in eval mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops

# (features, kernel, stride, pad, pool after the norm and relu)
CONVS = ((64, 5, 2, 0, 3), (128, 3, 1, 0, 3), (192, 3, 1, 1, 0),
         (192, 3, 1, 1, 0), (128, 3, 1, 1, 3))
DENSE = (64, 1)


def forward(params, x):
    """Logits ``(N,)`` of dense volumes ``x`` ``(N, D, H, W)``."""
    x = x[..., None].astype(jnp.float32)
    for (_, _, stride, pad, pool), p in zip(CONVS, params["conv"]):
        x = ops.conv3d(x, p["w"], stride, pad) + p["b"]
        x = jax.nn.relu(ops.group_norm(x, p["scale"], p["bias"]))
        if pool:
            x = ops.max_pool(x, pool, pool)
    x = x.reshape(x.shape[0], -1)
    d0, d1 = params["dense"]
    x = jax.nn.relu(ops.dense(x, d0["w"], d0["b"]))
    return ops.dense(x, d1["w"], d1["b"])[:, 0]


def from_system(tree, dense_stem):
    """The program's ``3dcnn_s2d`` parameter tree, by leaf name, as this
    file's parameters. ``dense_stem`` turns the phased stem kernel into the
    dense k5 one."""
    stem = tree["S2DStemStage_0"]
    conv = [{"w": dense_stem(stem["kernel"]), "b": stem["bias"],
             "scale": stem["scale"], "bias": stem["bias_gn"]}]
    for i in range(len(CONVS) - 1):
        c, g = tree[f"Conv3d_{i}"]["Conv_0"], tree[f"GroupNorm_{i}"]
        conv.append({"w": c["kernel"], "b": c["bias"],
                     "scale": g["scale"], "bias": g["bias"]})
    dense = [{"w": tree[f"Dense_{i}"]["kernel"],
              "b": tree[f"Dense_{i}"]["bias"]} for i in range(len(DENSE))]
    return {"conv": conv, "dense": dense}


# the leaves whose gradients the check compares: the path in the program's
# tree and the path in this file's
GRAD_LEAVES = {
    "stem_kernel": (("S2DStemStage_0", "kernel"), ("conv", 0, "w")),
    "last_dense": (("Dense_1", "kernel"), ("dense", 1, "w")),
}


def layers(volume):
    """The layers by shape, per sample, for the benchmark's operation and
    byte counts (``lib/flops.py``). The stem's input is data, so it has no
    input gradient."""
    out, shape = [], tuple(volume) + (1,)
    for i, (f, k, stride, pad, pool) in enumerate(CONVS, 1):
        row, shape = ops.conv_layer(f"conv{i}", shape, f, k, stride, pad,
                                    input_grad=i > 1)
        out += [row, ops.pointwise_layer(f"norm{i}", shape)]
        if pool:
            pooled = tuple(ops.out_extent(s, pool, pool, 0)
                           for s in shape[:3]) + (f,)
            out.append(ops.pointwise_layer(f"pool{i}", shape, pooled))
            shape = pooled
    width = shape[0] * shape[1] * shape[2] * shape[3]
    for i, f in enumerate(DENSE, 1):
        out.append(ops.dense_layer(f"dense{i}", width, f))
        width = f
    return out
