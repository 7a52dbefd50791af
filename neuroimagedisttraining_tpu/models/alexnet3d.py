"""AlexNet3D family — the north-star ABCD sex-classification models.

TPU-native re-designs of the reference architectures
(``fedml_api/model/cv/salient_models.py``):
  * AlexNet3D_Dropout          (:142-191) — 5-conv 3D feature stack,
    Dropout/Linear(256->64->num_classes) head
  * AlexNet3D_Deeper_Dropout   (:194-246) — 6-conv, 512->64 head,
    returns [logits, logits]
  * AlexNet3D_Dropout_Regression (:248-297) — regression head,
    returns [pred, features]

Layout is channels-last (N, D, H, W, C) — the TPU-preferred conv layout —
with GroupNorm in place of BatchNorm3d (see models/layers.py docstring).
Spatial arithmetic (VALID convs, floor-mode pools) matches torch exactly, so
on the canonical (121,145,121) volume the flatten width is 256 (resp. 512),
identical to the reference's Linear input sizes.
"""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .layers import (
    Conv3d,
    S2DStemConv,
    avg_pool3d,
    flatten,
    group_norm,
    max_pool3d,
)


class _Features(nn.Module):
    """Shared 5-conv feature stack of AlexNet3D_Dropout."""

    widths: tuple = (64, 128, 192, 192, 128)

    @nn.compact
    def __call__(self, x):
        w1, w2, w3, w4, w5 = self.widths
        x = Conv3d(w1, kernel_size=5, strides=2, padding=0)(x)
        x = group_norm(w1)(x)
        x = nn.relu(x)
        x = max_pool3d(x, kernel=3, strides=3)

        x = Conv3d(w2, kernel_size=3, strides=1, padding=0)(x)
        x = group_norm(w2)(x)
        x = nn.relu(x)
        x = max_pool3d(x, kernel=3, strides=3)

        x = Conv3d(w3, kernel_size=3, padding=1)(x)
        x = group_norm(w3)(x)
        x = nn.relu(x)

        x = Conv3d(w4, kernel_size=3, padding=1)(x)
        x = group_norm(w4)(x)
        x = nn.relu(x)

        x = Conv3d(w5, kernel_size=3, padding=1)(x)
        x = group_norm(w5)(x)
        x = nn.relu(x)
        x = max_pool3d(x, kernel=3, strides=3)
        return x


class S2DStem(S2DStemConv):
    """Phase-decomposed AlexNet stem: the TPU-fast form of
    Conv3d(1->F, k5, s2) — :class:`models.layers.S2DStemConv` at the k5
    spec (125 of 216 slots live)."""

    features: int = 64
    kernel_size: int = 5


def _group_stats(zf, groups, eps):
    """Per-(sample, group) mean and 1/std of a channels-last f32 tensor,
    broadcast back per channel: returns (mu_c, sig_c) shaped
    (B, 1, 1, 1, C). Shared by both S2DStemStage branches so the
    pool_first == textbook equivalence cannot drift."""
    F = zf.shape[-1]
    zg = zf.reshape(zf.shape[:-1] + (groups, F // groups))
    mu = zg.mean(axis=(1, 2, 3, 5))                      # (B, g)
    var = (zg * zg).mean(axis=(1, 2, 3, 5)) - mu * mu
    sig = jnp.sqrt(jnp.maximum(var, 0) + eps)
    mu_c = jnp.repeat(mu, F // groups, axis=-1)[:, None, None, None, :]
    sig_c = jnp.repeat(sig, F // groups, axis=-1)[:, None, None, None, :]
    return mu_c, sig_c


def phased_stem_stage(mdl: nn.Module, x, *, stem_kernel: int, features: int,
                      max_groups: int, pool, use_bias: bool,
                      pool_first: bool, eps: float):
    """THE pool-first fused stem implementation, shared by every phased
    stem stage (AlexNet3D k5 stem, ResNet_l3 k3 stem).

    Computes ``masked phased conv [+ bias] -> GroupNorm -> relu ->
    max_pool3d(*pool)`` with the pool hoisted before the normalize affine:
    max-pool commutes with the monotone per-channel affine+relu — channels
    with negative GroupNorm scale need the window *min*, obtained by
    folding ``sign(scale)`` into the conv kernel so exactly ONE pool runs
    on the conv output and the full-size normalized tensor is never
    materialized (step 20.5 -> 16.2-17.3 ms, RESULTS.md section 1). The GN
    statistics always come from the PRE-pool conv output. ``pool_first=
    False`` computes the textbook order with the same params
    (equivalence testing / fallback).

    Creates params on ``mdl``: ``kernel`` (masked phased conv — SNIP,
    weight decay and the converters see the usual "kernel" leaf),
    optional ``bias``, and ``scale``/``bias_gn`` (the GN affine pair);
    sows ``conv_out`` at the conv's resolution for the FLOPs counter
    (utils/flops.py reads it to cost fused stages correctly).
    """
    from .layers import phased_stem_kernel

    F = features
    g = min(max_groups, F)
    while F % g:
        g -= 1
    w, mask = phased_stem_kernel(mdl, stem_kernel, F)
    b = mdl.param("bias", nn.initializers.zeros, (F,)) if use_bias else None
    gamma = mdl.param("scale", nn.initializers.ones, (F,))
    beta = mdl.param("bias_gn", nn.initializers.zeros, (F,))
    dn_args = ("NDHCW", "DHWIO", "NDHWC")
    pk, ps, pp = pool

    # scopes stem/{conv,norm,pool}: the device trace's names for this
    # stage's three layers in both models (benchmarks/metrics/stem_*.json
    # read them; the list of the round's scopes is in algorithms/base.py)
    if not pool_first:
        with jax.named_scope("stem"):
            with jax.named_scope("conv"):
                dn = lax.conv_dimension_numbers(x.shape, w.shape, dn_args)
                z = lax.conv_general_dilated(
                    x, w * mask, (1, 1, 1), "VALID", dimension_numbers=dn)
                if b is not None:
                    z = z + b
            mdl.sow("intermediates", "conv_out", z)
            with jax.named_scope("norm"):
                # normalize explicitly with this module's own affine params
                zf = z.astype(jnp.float32)
                mu_c, sig_c = _group_stats(zf, g, eps)
                y = (zf - mu_c) / sig_c * gamma + beta
                y = nn.relu(y).astype(z.dtype)
            with jax.named_scope("pool"):
                return max_pool3d(y, kernel=pk, strides=ps, padding=pp)

    with jax.named_scope("stem"):
        sign = jnp.where(gamma >= 0, 1.0, -1.0).astype(w.dtype)
        with jax.named_scope("conv"):
            ws = (w * mask) * sign
            dn = lax.conv_dimension_numbers(x.shape, ws.shape, dn_args)
            zs = lax.conv_general_dilated(
                x, ws, (1, 1, 1), "VALID", dimension_numbers=dn)
            summands = None
            if b is not None:
                conv, shift = zs, b * sign.astype(b.dtype)
                summands, zs = (conv, shift), conv + shift
        mdl.sow("intermediates", "conv_out", zs)
        with jax.named_scope("norm"):
            # group stats of z = zs * sign, in f32
            sf = sign.astype(jnp.float32)
            zf = zs.astype(jnp.float32) * sf
            mu_c, sig_c = _group_stats(zf, g, eps)
        # ONE pool on zs = max over window of z for scale>=0 channels,
        # -min for scale<0 channels (flax pads max-pool with -inf, so a
        # padded pool ring never wins the selection). The pool's geometry
        # picks its backward (ops/pool_vjp.py). Disjoint windows (AlexNet3D)
        # are told that zs is conv + bias: the backward then reads the conv
        # output the conv fusion wrote, not a second copy with the bias
        # added. Overlapping ones (ResNet_l3's (3, 2, 1), no conv bias) are
        # handed zs itself
        with jax.named_scope("pool"):
            m = max_pool3d(zs, kernel=pk, strides=ps, padding=pp,
                           summands=summands)
        with jax.named_scope("norm"):
            sel = m.astype(jnp.float32) * sf
            y = (sel - mu_c) / sig_c * gamma + beta
            return nn.relu(y).astype(zs.dtype)


class S2DStemStage(nn.Module):
    """AlexNet3D fused stem stage (k5/s2 phased conv + GN + relu +
    MaxPool3(3,3)) — see :func:`phased_stem_stage` for the derivation and
    the param contract."""

    features: int = 64
    max_groups: int = 32
    pool_first: bool = True
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        from ..ops.s2d import KERNEL

        return phased_stem_stage(
            self, x, stem_kernel=KERNEL, features=self.features,
            max_groups=self.max_groups, pool=(3, 3, 0), use_bias=True,
            pool_first=self.pool_first, eps=self.eps)


class AlexNet3DS2D(nn.Module):
    """AlexNet3D over phase-decomposed input — same function class and
    output as :class:`AlexNet3D`, restated for the MXU (see ops/s2d.py).

    Input: ``(B, 61, 73, 8, 61)`` phased volumes (for the canonical
    121x145x121 ABCD volume) instead of ``(B, 121, 145, 121, 1)``.
    The first stage (stem conv/GN/relu/pool) runs as the fused pool-first
    :class:`S2DStemStage`; its GroupNorm lives inside the stage, so the
    remaining norms are ``GroupNorm_0..3`` (for convs 2-5).
    """

    num_classes: int = 1
    dropout_rate: float = 0.5
    widths: tuple = (64, 128, 192, 192, 128)
    pool_first: bool = True

    @nn.compact
    def __call__(self, x, train: bool = True):
        w1, w2, w3, w4, w5 = self.widths
        x = S2DStemStage(features=w1, pool_first=self.pool_first)(x)

        x = Conv3d(w2, kernel_size=3, strides=1, padding=0)(x)
        x = group_norm(w2)(x)
        x = nn.relu(x)
        x = max_pool3d(x, kernel=3, strides=3)

        x = Conv3d(w3, kernel_size=3, padding=1)(x)
        x = group_norm(w3)(x)
        x = nn.relu(x)

        x = Conv3d(w4, kernel_size=3, padding=1)(x)
        x = group_norm(w4)(x)
        x = nn.relu(x)

        x = Conv3d(w5, kernel_size=3, padding=1)(x)
        x = group_norm(w5)(x)
        x = nn.relu(x)
        x = max_pool3d(x, kernel=3, strides=3)

        x = flatten(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(64)(x)
        x = nn.relu(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_classes)(x)
        return x


class AlexNet3D(nn.Module):
    """AlexNet3D_Dropout (salient_models.py:142-191).

    For ABCD BCE training use num_classes=1 (the reference trains
    BCEWithLogits on a single logit, ``my_model_trainer.py:191-206``).
    """

    num_classes: int = 1
    dropout_rate: float = 0.5

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = _Features()(x)
        x = flatten(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(64)(x)
        x = nn.relu(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_classes)(x)
        return x


class AlexNet3DDeeper(nn.Module):
    """AlexNet3D_Deeper_Dropout (salient_models.py:194-246); returns [x, x]."""

    num_classes: int = 1
    dropout_rate: float = 0.5

    @nn.compact
    def __call__(self, x, train: bool = True):
        for i, (w, spec) in enumerate(
            [
                (64, dict(kernel_size=5, strides=2, padding=0)),
                (128, dict(kernel_size=3, strides=1, padding=0)),
                (192, dict(kernel_size=3, padding=1)),
                (384, dict(kernel_size=3, padding=1)),
                (256, dict(kernel_size=3, padding=1)),
                (256, dict(kernel_size=3, padding=1)),
            ]
        ):
            x = Conv3d(w, **spec)(x)
            x = group_norm(w)(x)
            x = nn.relu(x)
            if i in (0, 1, 5):
                x = max_pool3d(x, kernel=3, strides=3)
        x = flatten(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(64)(x)
        x = nn.relu(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_classes)(x)
        return [x, x]


class AlexNet3DRegression(nn.Module):
    """AlexNet3D_Dropout_Regression (salient_models.py:248-297).

    Returns [pred, features] like the reference (features = pre-flatten conv
    activations).
    """

    num_outputs: int = 1
    dropout_rate: float = 0.5

    @nn.compact
    def __call__(self, x, train: bool = True):
        feats = _Features()(x)
        x = flatten(feats)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(64)(x)
        x = nn.relu(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_outputs)(x)
        return [x, feats]


class SmallCNN3D(nn.Module):
    """Tiny 3D CNN for CI-scale tests and multi-chip dry-runs.

    Same structural idiom as AlexNet3D (conv/GN/relu/pool -> dense head) but
    works on volumes as small as 8^3, keeping CPU test time negligible. This
    plays the role of the reference's ``--ci 1`` smoke path
    (``sailentgrads_api.py:260-265``).
    """

    num_classes: int = 1
    width: int = 8
    dropout_rate: float = 0.0

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = Conv3d(self.width, kernel_size=3, strides=2, padding=1)(x)
        x = group_norm(self.width)(x)
        x = nn.relu(x)
        x = Conv3d(self.width * 2, kernel_size=3, strides=1, padding=1)(x)
        x = nn.relu(x)
        x = x.mean(axis=(1, 2, 3))  # global average pool
        if self.dropout_rate:
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_classes)(x)
        return x


class SmallCNN3DS2D(nn.Module):
    """SmallCNN3D over phase-decomposed input (k3/s2/p1 stem spec): same
    function class and outputs, the C_in=1 stem conv restated for the MXU
    via :class:`models.layers.S2DStemConv`. Input per sample:
    ``ops.s2d.phased_sample_shape(vol, kernel=3, pad=1)``."""

    num_classes: int = 1
    width: int = 8
    dropout_rate: float = 0.0

    @nn.compact
    def __call__(self, x, train: bool = True):
        from .layers import S2DStemConv

        x = S2DStemConv(self.width, kernel_size=3)(x)
        x = group_norm(self.width)(x)
        x = nn.relu(x)
        x = Conv3d(self.width * 2, kernel_size=3, strides=1, padding=1)(x)
        x = nn.relu(x)
        x = x.mean(axis=(1, 2, 3))
        if self.dropout_rate:
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_classes)(x)
        return x


def convert_smallcnn3d_params(params) -> dict:
    """:class:`SmallCNN3D` param tree -> :class:`SmallCNN3DS2D` (stem
    kernel remapped tap-for-tap, everything else unchanged)."""
    from ..ops.s2d import remap_stem_kernel

    out = dict(params)
    stem = out.pop("Conv3d_0")["Conv_0"]
    out["S2DStemConv_0"] = {
        "kernel": remap_stem_kernel(stem["kernel"], 3),
        "bias": stem["bias"],
    }
    # the second conv keeps its dense-model name via explicit renumber
    out["Conv3d_0"] = out.pop("Conv3d_1")
    return out
