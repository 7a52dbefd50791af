"""3-stage 3D ResNet (ResNet_l3) for ABCD volumes.

Re-design of the reference ``fedml_api/model/cv/salient_models.py:84-139``
(Conv3d stem k3/s2/p3 -> maxpool k3/s2/p1 -> three BasicBlock stages
64/128/256 -> AvgPool3d(3) -> fc -> fc2, returning [logits, features]) with
GroupNorm replacing BatchNorm3d and channels-last layout. The fc input width
is inferred from the flattened feature map instead of the reference's
hard-coded 9216 (which bakes in one specific input size).

BasicBlock/Bottleneck follow the standard torchvision residual recipe the
reference reuses (``salient_models.py:13-81``).

:class:`ResNet3DL3S2D` is the TPU-fast twin over phase-decomposed input —
the r4 measurement found the stem stage (C_in=1 stride-2 conv + GN + relu
+ pool) is 66% of the step at full volume, the same disease the AlexNet3D
path cured with the s2d + pool-first treatment (ops/s2d.py; RESULTS.md
section 1).
"""
from __future__ import annotations

from typing import Sequence

import flax.linen as nn

from .layers import Conv3d, avg_pool3d, flatten, group_norm, max_pool3d


class BasicBlock3D(nn.Module):
    planes: int
    stride: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        y = Conv3d(self.planes, kernel_size=3, strides=self.stride, padding=1,
                   use_bias=False)(x)
        y = group_norm(self.planes)(y)
        y = nn.relu(y)
        y = Conv3d(self.planes, kernel_size=3, strides=1, padding=1,
                   use_bias=False)(y)
        y = group_norm(self.planes)(y)
        if self.stride != 1 or x.shape[-1] != self.planes:
            residual = Conv3d(self.planes, kernel_size=1, strides=self.stride,
                              padding=0, use_bias=False)(x)
            residual = group_norm(self.planes)(residual)
        return nn.relu(y + residual)


class Bottleneck3D(nn.Module):
    planes: int
    stride: int = 1
    expansion: int = 4

    @nn.compact
    def __call__(self, x):
        out_ch = self.planes * self.expansion
        residual = x
        y = Conv3d(self.planes, kernel_size=1, padding=0, use_bias=False)(x)
        y = group_norm(self.planes)(y)
        y = nn.relu(y)
        y = Conv3d(self.planes, kernel_size=3, strides=self.stride, padding=1,
                   use_bias=False)(y)
        y = group_norm(self.planes)(y)
        y = nn.relu(y)
        y = Conv3d(out_ch, kernel_size=1, padding=0, use_bias=False)(y)
        y = group_norm(out_ch)(y)
        if self.stride != 1 or x.shape[-1] != out_ch:
            residual = Conv3d(out_ch, kernel_size=1, strides=self.stride,
                              padding=0, use_bias=False)(x)
            residual = group_norm(out_ch)(residual)
        return nn.relu(y + residual)


class ResNet3DL3(nn.Module):
    """ResNet_l3: 3-stage 3D ResNet returning [logits, penultimate]."""

    num_classes: int = 1
    layers: Sequence[int] = (2, 2, 2)
    block: str = "basic"  # "basic" | "bottleneck"

    @nn.compact
    def __call__(self, x, train: bool = True):
        Block = BasicBlock3D if self.block == "basic" else Bottleneck3D
        x = Conv3d(64, kernel_size=3, strides=2, padding=3, use_bias=False)(x)
        x = group_norm(64)(x)
        x = nn.relu(x)
        x = max_pool3d(x, kernel=3, strides=2, padding=1)
        for stage, (planes, n_blocks) in enumerate(
            zip((64, 128, 256), self.layers)
        ):
            for b in range(n_blocks):
                stride = 2 if (stage > 0 and b == 0) else 1
                x = Block(planes=planes, stride=stride)(x)
        x = avg_pool3d(x, kernel=3, strides=3)
        x = flatten(x)
        x1 = nn.Dense(512)(x)
        logits = nn.Dense(self.num_classes)(x1)
        return [logits, x1]


RESNET_STEM_KERNEL = 3  # salient_models.py:92: Conv3d(1, 64, k3, s2, p3)
RESNET_STEM_PAD = 3


class S2DResNetStem(nn.Module):
    """Fused ResNet stem over phased input: the reference k3/s2/p3 conv
    (``salient_models.py:92``) as a VALID stride-1 (2,2,2,8,F) phased
    conv — 27 of 64 slots carry real taps, kept exact by the
    structural-zero mask — + GroupNorm + relu + the reference's own
    maxpool(3, s2, p1), pool-first. No conv bias (the reference stem is
    ``use_bias=False``). Derivation and param contract:
    :func:`models.alexnet3d.phased_stem_stage`."""

    features: int = 64
    max_groups: int = 32
    pool_first: bool = True
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        from .alexnet3d import phased_stem_stage

        return phased_stem_stage(
            self, x, stem_kernel=RESNET_STEM_KERNEL,
            features=self.features, max_groups=self.max_groups,
            pool=(3, 2, 1), use_bias=False,
            pool_first=self.pool_first, eps=self.eps)


class ResNet3DL3S2D(nn.Module):
    """ResNet_l3 over phase-decomposed input — same function class and
    outputs as :class:`ResNet3DL3`, restated for the MXU.

    Input: ``(B, D', H', 8, W')`` volumes phased for the k3/p3 stem
    (``ops.s2d.phase_decompose(x, kernel=3, pad=3)`` — (64, 76, 8, 64)
    for the canonical 121x145x121 ABCD volume). The stem stage runs as
    the fused pool-first :class:`S2DResNetStem`; everything after it is
    identical to :class:`ResNet3DL3` (module names shift by the stem's
    absorbed GroupNorm).
    """

    num_classes: int = 1
    layers: Sequence[int] = (2, 2, 2)
    block: str = "basic"
    pool_first: bool = True

    @nn.compact
    def __call__(self, x, train: bool = True):
        Block = BasicBlock3D if self.block == "basic" else Bottleneck3D
        x = S2DResNetStem(pool_first=self.pool_first)(x)
        for stage, (planes, n_blocks) in enumerate(
            zip((64, 128, 256), self.layers)
        ):
            for b in range(n_blocks):
                stride = 2 if (stage > 0 and b == 0) else 1
                x = Block(planes=planes, stride=stride)(x)
        x = avg_pool3d(x, kernel=3, strides=3)
        x = flatten(x)
        x1 = nn.Dense(512)(x)
        logits = nn.Dense(self.num_classes)(x1)
        return [logits, x1]


def convert_resnet3d_params(params) -> dict:
    """Map a :class:`ResNet3DL3` param tree to :class:`ResNet3DL3S2D`.

    The stem conv kernel is remapped tap-for-tap (ops.s2d bijection); the
    stem GroupNorm's affine pair moves into the fused stage; every block
    transfers unchanged."""
    from ..ops.s2d import remap_stem_kernel

    out = {"S2DResNetStem_0": {
        "kernel": remap_stem_kernel(
            params["Conv3d_0"]["Conv_0"]["kernel"], RESNET_STEM_KERNEL),
        "scale": params["GroupNorm_0"]["scale"],
        "bias_gn": params["GroupNorm_0"]["bias"],
    }}
    for k, v in params.items():
        if k not in ("Conv3d_0", "GroupNorm_0"):
            out[k] = v
    return out
