"""Phase decomposition of a single-channel volume, and its inverse.

The program stores ABCD volumes phase-decomposed for a stride-2 stem
(``neuroimagedisttraining_tpu/ops/s2d.py``): the eight stride-2 subgrids of
the zero-padded volume ride as a next-to-minor axis, ``(D', H', 8, W')``.
The benchmark keeps its own arithmetic for that layout, so that the cohort it
generates and the dense volumes it hands the plain reference do not depend on
the code under test. Tap ``t`` of a kernel-``k`` stride-2 conv reads padded
index ``2o + t`` = subgrid ``t % 2`` at offset ``o + t // 2``.
"""
from __future__ import annotations

import numpy as np

STRIDE = 2


def phase_extent(size: int, kernel: int, pad: int) -> int:
    """Length of one subgrid along an axis of ``size`` voxels."""
    out = (size + 2 * pad - kernel) // STRIDE + 1
    return out + -(-kernel // STRIDE) - 1


def phased_shape(volume, kernel: int, pad: int):
    d, h, w = (phase_extent(s, kernel, pad) for s in volume)
    return (d, h, STRIDE ** 3, w)


def decompose(x, kernel: int, pad: int):
    """``(..., D, H, W)`` -> ``(..., D', H', 8, W')`` on the host. The conv's
    zero padding is folded in on the left, and zeros on the right top every
    subgrid up to its extent."""
    x = np.asarray(x)
    sizes = x.shape[-3:]
    ext = [phase_extent(s, kernel, pad) for s in sizes]
    pads = [(0, 0)] * (x.ndim - 3) + [
        (pad, max(0, STRIDE * e - s - pad)) for e, s in zip(ext, sizes)]
    x = np.pad(x, pads)
    subgrids = [x[..., i::2, j::2, k::2][..., :ext[0], :ext[1], :ext[2]]
                for i in (0, 1) for j in (0, 1) for k in (0, 1)]
    return np.stack(subgrids, axis=-2)


def recompose(p, volume, pad: int):
    """Inverse of :func:`decompose`: ``(..., D', H', 8, W')`` -> the dense
    ``(..., D, H, W)`` volume. What the phased array holds in the padding
    frame is dropped."""
    p = np.asarray(p)
    d, h, _, w = p.shape[-4:]
    lead = p.shape[:-4]
    # (..., D', H', pd, ph, pw, W') -> (..., D', pd, H', ph, W', pw)
    q = p.reshape(lead + (d, h, 2, 2, 2, w))
    n = len(lead)
    q = np.transpose(q, tuple(range(n)) + (n, n + 2, n + 1, n + 3, n + 5,
                                           n + 4))
    frame = q.reshape(lead + (2 * d, 2 * h, 2 * w))
    D, H, W = volume
    return frame[..., pad:pad + D, pad:pad + H, pad:pad + W]


def dense_stem_kernel(w, kernel: int):
    """The phased stem kernel ``(r, r, r, 8, F)`` (or its gradient) as the
    dense ``(k, k, k, 1, F)`` kernel of the stride-2 conv it restates."""
    w = np.asarray(w)
    out = np.zeros((kernel,) * 3 + (1, w.shape[-1]), w.dtype)
    for td in range(kernel):
        for th in range(kernel):
            for tw in range(kernel):
                sub = (td % 2) * 4 + (th % 2) * 2 + (tw % 2)
                out[td, th, tw, 0] = w[td // 2, th // 2, tw // 2, sub]
    return out
