"""Plain float32 reference of ResNet_l3 (the reference repo's
``fedml_api/model/cv/salient_models.py:84-139``, BasicBlock ``:13-45``).

    Conv3d(1, 64, k3, s2, p3, no bias) - norm - relu - MaxPool3d(3, s2, p1)
    three stages of two BasicBlocks: 64, 128 (s2), 256 (s2) planes
      block: Conv3d(k3, s, p1) - norm - relu - Conv3d(k3, p1) - norm
             (+ Conv3d(k1, s) - norm on the skip where shape changes) - relu
    AvgPool3d(3) - flatten - Linear(., 512) - Linear(512, 1)

Departures from the published model, the zoo's own and documented in
PARITY.md: GroupNorm (at most 32 groups, eps 1e-6) for BatchNorm3d;
channels-last, so the flatten runs over (D, H, W, C); the first Linear takes
its width from the feature map (3072 at 121x145x121) where the source
hard-codes 9216. The model has no dropout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops

STEM = (64, 3, 2, 3)            # features, kernel, stride, pad
STEM_POOL = (3, 2, 1)           # kernel, stride, pad
BLOCKS = ((64, 1), (64, 1), (128, 2), (128, 1), (256, 2), (256, 1))
HEAD_POOL = 3
DENSE = (512, 1)


def _norm(x, p):
    return ops.group_norm(x, p["scale"], p["bias"])


def forward(params, x):
    """Logits ``(N,)`` of dense volumes ``x`` ``(N, D, H, W)``."""
    x = x[..., None].astype(jnp.float32)
    _, _, stride, pad = STEM
    x = jax.nn.relu(_norm(ops.conv3d(x, params["stem"]["w"], stride, pad),
                          params["stem"]))
    x = ops.max_pool(x, *STEM_POOL)
    for (_, stride), p in zip(BLOCKS, params["blocks"]):
        y = jax.nn.relu(_norm(ops.conv3d(x, p["w1"], stride, 1), p["n1"]))
        y = _norm(ops.conv3d(y, p["w2"], 1, 1), p["n2"])
        if "ws" in p:
            x = _norm(ops.conv3d(x, p["ws"], stride, 0), p["ns"])
        x = jax.nn.relu(y + x)
    x = ops.avg_pool(x, HEAD_POOL, HEAD_POOL).reshape(x.shape[0], -1)
    d0, d1 = params["dense"]
    return ops.dense(ops.dense(x, d0["w"], d0["b"]), d1["w"], d1["b"])[:, 0]


def from_system(tree, dense_stem):
    """The program's ``3dresnet_s2d`` parameter tree, by leaf name, as this
    file's parameters. ``dense_stem`` turns the phased stem kernel into the
    dense k3 one."""
    def norm(g):
        return {"scale": g["scale"], "bias": g["bias"]}

    stem = tree["S2DResNetStem_0"]
    blocks = []
    for i in range(len(BLOCKS)):
        b = tree[f"BasicBlock3D_{i}"]
        p = {"w1": b["Conv3d_0"]["Conv_0"]["kernel"],
             "n1": norm(b["GroupNorm_0"]),
             "w2": b["Conv3d_1"]["Conv_0"]["kernel"],
             "n2": norm(b["GroupNorm_1"])}
        if "Conv3d_2" in b:
            p["ws"] = b["Conv3d_2"]["Conv_0"]["kernel"]
            p["ns"] = norm(b["GroupNorm_2"])
        blocks.append(p)
    dense = [{"w": tree[f"Dense_{i}"]["kernel"],
              "b": tree[f"Dense_{i}"]["bias"]} for i in range(len(DENSE))]
    return {"stem": {"w": dense_stem(stem["kernel"]), "scale": stem["scale"],
                     "bias": stem["bias_gn"]},
            "blocks": blocks, "dense": dense}


GRAD_LEAVES = {
    "stem_kernel": (("S2DResNetStem_0", "kernel"), ("stem", "w")),
    "last_dense": (("Dense_1", "kernel"), ("dense", 1, "w")),
}


def layers(volume):
    """The layers by shape, per sample, for the benchmark's operation and
    byte counts (``lib/flops.py``)."""
    f, k, stride, pad = STEM
    row, shape = ops.conv_layer("stem", tuple(volume) + (1,), f, k, stride,
                                pad, input_grad=False)
    pk, ps, pp = STEM_POOL
    pooled = tuple(ops.out_extent(s, pk, ps, pp) for s in shape[:3]) + (f,)
    out = [row, ops.pointwise_layer("stem_norm", shape),
           ops.pointwise_layer("stem_pool", shape, pooled)]
    shape = pooled
    for i, (planes, stride) in enumerate(BLOCKS, 1):
        skip = stride != 1 or shape[-1] != planes
        r1, mid = ops.conv_layer(f"block{i}.conv1", shape, planes, 3, stride, 1)
        r2, mid = ops.conv_layer(f"block{i}.conv2", mid, planes, 3, 1, 1)
        out += [r1, ops.pointwise_layer(f"block{i}.norm1", mid), r2,
                ops.pointwise_layer(f"block{i}.norm2", mid)]
        if skip:
            rs, _ = ops.conv_layer(f"block{i}.skip", shape, planes, 1, stride, 0)
            out += [rs, ops.pointwise_layer(f"block{i}.skip_norm", mid)]
        out.append(ops.pointwise_layer(f"block{i}.add_relu", mid))
        shape = mid
    pooled = tuple(ops.out_extent(s, HEAD_POOL, HEAD_POOL, 0)
                   for s in shape[:3]) + (shape[-1],)
    out.append(ops.pointwise_layer("head_pool", shape, pooled))
    width = pooled[0] * pooled[1] * pooled[2] * pooled[3]
    for i, f in enumerate(DENSE, 1):
        out.append(ops.dense_layer(f"dense{i}", width, f))
        width = f
    return out
