"""Shared layers for the 3D/2D model zoo (flax linen, channels-last).

Normalization policy: the reference's 3D nets use BatchNorm3d
(``salient_models.py:146-176``) but its CIFAR ResNet already swaps BN for
GroupNorm(32) as the FL-friendly choice (``resnet.py:91-126`` — no running
stats to desynchronize across clients). We standardize on GroupNorm for every
model (documented deviation for the 3D nets): under vmap-over-clients there is
no per-client mutable running-stat state to carry, and eval needs no
train/eval statistics split. ``norm="batch"`` is intentionally not offered.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax.numpy as jnp

from ..ops.pool_vjp import max_pool3d_of_sum, max_pool3d_windows

Ints3 = Union[int, Tuple[int, int, int]]


def _triple(v: Ints3) -> Tuple[int, int, int]:
    return (v, v, v) if isinstance(v, int) else tuple(v)


def group_norm(channels: int, max_groups: int = 32) -> nn.GroupNorm:
    """GroupNorm with the largest group count <= max_groups dividing channels."""
    g = min(max_groups, channels)
    while channels % g:
        g -= 1
    return nn.GroupNorm(num_groups=g)


class Conv3d(nn.Module):
    """3D conv over (N, D, H, W, C) with torch-style integer padding.

    padding=0 -> VALID (torch default); padding=p -> p voxels each side.
    Output sizes therefore match the torch reference exactly (floor division).
    """

    features: int
    kernel_size: Ints3
    strides: Ints3 = 1
    padding: Ints3 = 0
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        k = _triple(self.kernel_size)
        s = _triple(self.strides)
        p = _triple(self.padding)
        return nn.Conv(
            features=self.features,
            kernel_size=k,
            strides=s,
            padding=[(pi, pi) for pi in p],
            use_bias=self.use_bias,
        )(x)


def max_pool3d(x, kernel: Ints3, strides: Ints3, padding: Ints3 = 0, *,
               summands=None):
    """torch MaxPool3d semantics (floor mode) on (N, D, H, W, C).

    The values are ``nn.max_pool``'s; what the geometry selects is the
    backward (ops/pool_vjp.py), which where it can leaves XLA's
    ``select-and-scatter`` for a kernel with the same first-match gradient:

    * windows that overlap or carry the ``-inf`` ring (``strides <= kernel``
      and not the plain disjoint case): one primitive for every such
      geometry, ResNet_l3's ``(3, 2, 1)`` among them;
    * disjoint windows (``kernel == strides``, no padding) where
      ``summands=(c, bias)`` says that the caller computed ``x`` as
      ``c + bias`` (``bias`` per channel): the backward then needs no copy
      of ``x``; bit-equal to autodiff's;
    * anything else (disjoint windows of a tensor that is no such sum,
      strides past the window) keeps ``lax.reduce_window``'s own VJP."""
    k = _triple(kernel)
    s = _triple(strides)
    p = _triple(padding)
    if (k, p) != (s, (0, 0, 0)):
        if x.ndim == 5 and all(si <= ki and pi < ki
                               for ki, si, pi in zip(k, s, p)):
            return max_pool3d_windows(x, k, s, p)
    elif summands is not None:
        c, bias = summands
        if c.dtype == bias.dtype == x.dtype:
            return max_pool3d_of_sum(x, c, bias, k)
    return nn.max_pool(
        x, window_shape=k, strides=s, padding=[(pi, pi) for pi in p]
    )


def avg_pool3d(x, kernel: Ints3, strides: Ints3 = None, padding: Ints3 = 0):
    k = _triple(kernel)
    s = _triple(strides if strides is not None else kernel)
    p = _triple(padding)
    return nn.avg_pool(
        x, window_shape=k, strides=s, padding=[(pi, pi) for pi in p]
    )


def flatten(x):
    return x.reshape(x.shape[0], -1)


class SyncBatchNorm(nn.Module):
    """Cross-device synchronized BatchNorm.

    TPU-native replacement for the reference's hand-rolled master/slave-pipe
    ``SynchronizedBatchNorm1d/2d/3d`` (``batchnorm_utils.py:150-396``): under
    ``pmap``/``shard_map`` with ``axis_name`` set, flax's BatchNorm psums the
    batch statistics over the mesh axis — XLA's collective IS the sync, no
    callbacks or pipes. Kept for parity/experiments; the zoo's default norm
    remains GroupNorm (see module docstring above) because federated
    personalization makes shared running stats a liability.

    Note: carries mutable ``batch_stats``; models using it must be applied
    with ``mutable=["batch_stats"]`` during training.
    """

    axis_name: Optional[str] = None
    momentum: float = 0.9

    @nn.compact
    def __call__(self, x, train: bool = True):
        return nn.BatchNorm(
            use_running_average=not train,
            momentum=self.momentum,
            axis_name=self.axis_name,
        )(x)


def phased_stem_kernel(mdl: nn.Module, stem_kernel: int, features: int):
    """Create THE masked phased stem kernel param on ``mdl``.

    One source of truth for every phased stem (S2DStemConv, the fused
    phased_stem_stage): a ``kernel`` param of shape ``(r, r, r, 8, F)``
    with mask-aware lecun-normal init — fan_in counts all ``r^3*8``
    slots but only ``stem_kernel^3`` carry taps, so variance is scaled
    by their ratio to match the dense stride-2 stem's (fresh-init
    dynamics parity, not just converted-weights parity). Returns
    ``(w, mask)`` where ``mask`` zeroes the structurally-unused slots
    (see ops/s2d.py — the hypothesis class stays exactly the dense
    stem's)."""
    import jax.numpy as jnp

    from ..ops.s2d import N_PHASES, r_kernel, stem_slot_mask

    r = r_kernel(stem_kernel)
    w = mdl.param(
        "kernel",
        nn.initializers.variance_scaling(
            (r ** 3 * N_PHASES) / float(stem_kernel ** 3),
            "fan_in", "truncated_normal",
            in_axis=(0, 1, 2, 3), batch_axis=()),
        (r,) * 3 + (N_PHASES, features),
    )
    return w, jnp.asarray(stem_slot_mask(stem_kernel), w.dtype)


class S2DStemConv(nn.Module):
    """Masked phased conv replacing a C_in=1 stride-2 stem conv.

    Consumes ``(B, D', H', 8, W')`` phase-decomposed input
    (``ops.s2d.phase_decompose(x, kernel, pad)``) and computes exactly the
    dense ``Conv3d(1->F, kernel, stride=2, padding=pad)`` via a VALID
    stride-1 conv over the phases; structurally-zero remap slots are kept
    zero by a constant mask (see ops/s2d.py — the model class is exactly
    the dense stem's). Params are ``kernel``/``bias`` like an ordinary
    conv, at the remapped shape ``(r, r, r, 8, F)``.
    """

    features: int
    kernel_size: int = 3
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        from jax import lax

        w, mask = phased_stem_kernel(self, self.kernel_size, self.features)
        dn = lax.conv_dimension_numbers(
            x.shape, w.shape, ("NDHCW", "DHWIO", "NDHWC"))
        z = lax.conv_general_dilated(
            x, w * mask, (1, 1, 1), "VALID", dimension_numbers=dn)
        if self.use_bias:
            z = z + self.param("bias", nn.initializers.zeros,
                               (self.features,))
        return z
