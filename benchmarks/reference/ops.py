"""The layer equations the plain references share: straightforward
``jax.numpy`` and ``lax`` in float32 at the highest matmul precision (on a
TPU a float32 contraction otherwise runs in bfloat16 passes). Channels last,
``(N, D, H, W, C)``. Nothing is imported from the program."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

MAX_GROUPS = 32   # the zoo's GroupNorm: the most groups <= 32 dividing C
EPS = 1e-6
HIGHEST = lax.Precision.HIGHEST


def conv3d(x, w, stride: int, pad: int):
    return lax.conv_general_dilated(
        x, w, (stride,) * 3, [(pad, pad)] * 3,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"), precision=HIGHEST)


def dense(x, w, b):
    return jnp.dot(x, w, precision=HIGHEST) + b


def groups_of(channels: int) -> int:
    g = min(MAX_GROUPS, channels)
    while channels % g:
        g -= 1
    return g


def group_norm(x, scale, bias):
    n, c = x.shape[0], x.shape[-1]
    g = groups_of(c)
    xg = x.reshape(n, -1, g, c // g)
    mean = xg.mean(axis=(1, 3), keepdims=True)
    var = jnp.square(xg - mean).mean(axis=(1, 3), keepdims=True)
    return ((xg - mean) / jnp.sqrt(var + EPS)).reshape(x.shape) * scale + bias


def _pool(x, init, op, k: int, stride: int, pad: int):
    return lax.reduce_window(
        x, init, op, (1, k, k, k, 1), (1,) + (stride,) * 3 + (1,),
        [(0, 0)] + [(pad, pad)] * 3 + [(0, 0)])


def max_pool(x, k: int, stride: int, pad: int = 0):
    """torch MaxPool3d, floor mode: the padding never wins a window."""
    return _pool(x, -jnp.inf, lax.max, k, stride, pad)


def avg_pool(x, k: int, stride: int):
    return _pool(x, 0.0, lax.add, k, stride, 0) / k ** 3


def bce_with_logits(z, y):
    """Mean binary cross-entropy on logits (torch BCEWithLogitsLoss)."""
    y = y.astype(z.dtype)
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def out_extent(size: int, k: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - k) // stride + 1


def conv_layer(name, shape, features, k, stride, pad, input_grad=True):
    """One conv's row of a reference's ``layers`` table, and its output
    shape."""
    out = tuple(out_extent(s, k, stride, pad) for s in shape[:3]) + (features,)
    return {"name": name, "kind": "conv", "in": shape, "out": out,
            "taps": k ** 3, "input_grad": input_grad}, out


def pointwise_layer(name, shape, out=None):
    return {"name": name, "kind": "pointwise", "in": shape,
            "out": out or shape}


def dense_layer(name, width, features):
    return {"name": name, "kind": "dense", "in": (width,), "out": (features,),
            "input_grad": True}
