"""Pallas TPU kernels of the ``--agg_kernels pallas`` leg.

The threshold top-k selection, the fused int8 quantize+reduce of the
low-precision wire, and the mask projections of the SalientGrads init
(``fused_mask_apply``, ``fused_score_mask_leaf``): each the Pallas backend
of an XLA spelling it is bit-identical to (``ops/topk_select.py``,
``parallel/collectives.py``).

Layout: an elementwise kernel ravels its leaf and pads it to (rows, 128)
float32 — the VPU lane width; rows are padded to the (8, 128) f32 tile. On
the ``cpu`` backend the kernels run in interpreter mode so the tests
exercise identical code; on a TPU they compile through Mosaic (``pytest -m
tpu`` pins every kernel at the flagship's shapes); any other backend is an
error.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
_BLOCK_ROWS = 512  # 512x128 f32 = 256 KiB/operand: comfortably inside VMEM


def _interpret() -> bool:
    """Emulate only where the tests run. These kernels are written for the
    TPU's memory spaces; on any other accelerator an emulation would pass
    for the kernel while running something else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the pallas kernels target TPU (interpreted on cpu for tests); "
        f"backend {backend!r} has no lowering for them — use the XLA "
        "spelling (--agg_kernels xla)")


def _to_2d(x: jax.Array) -> Tuple[jax.Array, int]:
    """Ravel + zero-pad to a (rows, LANES) f32 panel; rows % SUBLANES == 0."""
    flat = x.ravel()
    n = flat.shape[0]
    per_panel = LANES * SUBLANES
    padded = ((n + per_panel - 1) // per_panel) * per_panel
    flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(-1, LANES), n


def _pick_block_rows(rows: int, budget: int = _BLOCK_ROWS) -> int:
    """Largest block size <= budget dividing ``rows`` (rows % SUBLANES == 0,
    guaranteed by _to_2d, so the loop terminates at SUBLANES or below)."""
    block_rows = min(budget, rows)
    while rows % block_rows:
        block_rows -= SUBLANES if block_rows > SUBLANES else 1
    return max(block_rows, 1)


def _from_2d(panel: jax.Array, n: int, shape, dtype) -> jax.Array:
    return panel.ravel()[:n].reshape(shape).astype(dtype)


# -- threshold top-k selection (the --agg_kernels wire leg) -------------------
#
# ops/topk_select.py owns the algorithm and the tie-break contract; the
# kernel below is its pallas backend: the magnitudes stay VMEM-resident
# across all SEARCH_ITERS count passes of the bit-space binary search —
# ONE read of the row from HBM, vs one sweep per pass for the XLA
# spelling. Both converge to the same unique integer fixed point (the
# largest bit pattern with count >= k), so the backends are bit-identical
# by construction, not by tolerance.

#: per-row element cap for the VMEM-resident search: the row is held in
#: VMEM (double-buffered by the pipeline, 2 x 4 B x n) while the count
#: passes stream over it chunk by chunk. 4 Mi elements = 32 MiB, inside
#: every TPU generation's VMEM; the flagship's whole-model SNIP score
#: row (2.57 M) fits. Larger rows are refused, not rerouted.
THRESHOLD_MAX_N = 1 << 22

#: rows of LANES counted per inner step (512 x 128 f32 = 256 KiB of
#: temporaries, whatever the row length)
_THRESH_CHUNK_ROWS = 512


def _threshold_kernel(k_ref, av_ref, out_ref, *, iters: int,
                      bits_hi: int, chunk_rows: int):
    """Bit-space binary search over ONE row's (rows, LANES) magnitude
    panel: lo converges to the k-th largest magnitude's bit pattern
    (topk_select.exact_threshold, same invariant/fixed point). Counts
    accumulate in f32 — exact, the row holds < 2^24 elements — because
    float reductions are the ones every Mosaic version lowers."""
    k = k_ref[0].astype(jnp.float32)
    n_chunks = av_ref.shape[0] // chunk_rows

    def count_ge(mid):
        def chunk(j, acc):
            start = pl.multiple_of(j * chunk_rows, chunk_rows)
            bits = jax.lax.bitcast_convert_type(
                av_ref[pl.ds(start, chunk_rows), :], jnp.int32)
            hit = jnp.where(bits >= mid, 1.0, 0.0).astype(jnp.float32)
            return acc + jnp.sum(
                hit.reshape(chunk_rows // SUBLANES, SUBLANES, LANES),
                axis=0)

        acc = jax.lax.fori_loop(
            0, n_chunks, chunk, jnp.zeros((SUBLANES, LANES), jnp.float32))
        return jnp.sum(acc)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + ((hi - lo) >> 1)     # hi > lo >= 0: shift == floor-div
        ok = count_ge(mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(
        0, iters, body, (jnp.int32(0), jnp.int32(bits_hi)))
    out_ref[:] = jnp.full(out_ref.shape, lo, jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def threshold_topk(av: jax.Array, k: int) -> jax.Array:
    """Exact k-th largest magnitude per row of a [C, n] nonneg f32
    matrix, VMEM-resident; returns [C, 1] f32 — bit-identical to
    ``topk_select.exact_threshold(av, k)`` (and so to the sort
    spelling) under the tie-break contract. One grid step per row; rows
    are zero-padded to whole count chunks; pad bits (0) never reach a
    count at any positive cut, and a cut can only fall to 0 when the
    true threshold IS 0.0, where counting pads is already harmless."""
    from .topk_select import _BITS_HI, SEARCH_ITERS

    c, n = av.shape
    if n > THRESHOLD_MAX_N:
        raise ValueError(
            f"the pallas threshold kernel keeps each row VMEM-resident "
            f"and takes at most THRESHOLD_MAX_N={THRESHOLD_MAX_N} elements "
            f"per row, got {n}; agg_kernels='xla' computes the identical "
            "threshold at any size")
    rows = -(-n // (LANES * SUBLANES)) * SUBLANES
    chunk_rows = min(_THRESH_CHUNK_ROWS, rows)
    rows = -(-rows // chunk_rows) * chunk_rows
    panels = jnp.pad(av.astype(jnp.float32),
                     ((0, 0), (0, rows * LANES - n))).reshape(
                         c, rows, LANES)

    kernel = functools.partial(_threshold_kernel, iters=SEARCH_ITERS,
                               bits_hi=int(_BITS_HI),
                               chunk_rows=chunk_rows)
    out = pl.pallas_call(
        kernel,
        grid=(c,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # k scalar
            pl.BlockSpec((None, rows, LANES), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((None, SUBLANES, LANES),
                               lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((c, SUBLANES, LANES), jnp.int32),
        # the double-buffered row plus headroom for the count chunks;
        # the default scoped limit (16 MiB on v5e) is below one 4 Mi row
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * rows * LANES * 4 + (8 << 20)),
        interpret=_interpret(),
    )(jnp.asarray([k], jnp.int32), panels)
    return jax.lax.bitcast_convert_type(out[:, 0, :1], jnp.float32)


# -- fused int8 quantize + weighted bucketed reduce ---------------------------
#
# The off-mesh int8 wire (collectives._reduce_mat) is a chain of
# materialized ops per [C, nb, b] bucket tensor: divide -> floor ->
# uniform-compare -> clip -> int8 cast -> dequantize -> tensordot. The
# kernel below fuses the whole quantize/dequantize chain AND the
# weighted client contraction into one pass over the cohort matrix:
# each grid step reads one bucket-aligned chunk of every client's row
# once, stochastic-rounds it with a PRECOMPUTED uniform draw (the same
# rng call and shape as the XLA chain, so the rounding bits are
# identical by construction) and a precomputed per-(client, bucket)
# scale, and contracts the dequantized chunk against the weights with
# ``jnp.dot`` — the SAME dot primitive ``tensordot`` lowers to, and
# per-output-column contractions are independent of how columns are
# chunked, so the kernel's sums are bit-identical to the XLA
# reference's ``tensordot(w, deq)`` (pinned by
# tests/test_pallas_kernels.py). An explicit elementwise accumulate
# spelling was measured to diverge by one ulp instead: XLA:CPU
# contracts ``acc + w*deq`` into an FMA that no barrier/bitcast
# spelling suppresses, while the shared-dot spelling keeps both
# backends inside one primitive. Only the scale's amax reduce stays
# outside the kernel (it must see the whole bucket before the first
# quantized element; max is exact in any association, so it is
# bit-stable and shared by both backends).

#: per-chunk f32 byte budget of the fused kernel (x + u blocks each)
_QR_CHUNK_BYTES = 1 << 21


def _qreduce_kernel(w_ref, x_ref, u_ref, s_ref, out_ref):
    x = x_ref[:]                        # (C, chunk)
    u = u_ref[:]
    scale = s_ref[:]                    # (C, 1) — this chunk's bucket
    y = x / scale
    f = jnp.floor(y)
    q = jnp.clip(f + (u < (y - f)).astype(jnp.float32), -127.0, 127.0)
    out_ref[:] = jnp.dot(w_ref[:], q * scale,   # (8,C)@(C,chunk)
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)


@jax.jit
def fused_quantize_reduce(buckets: jax.Array, weights: jax.Array,
                          uniforms: jax.Array,
                          scales: jax.Array) -> jax.Array:
    """out[j] = sum_c w[c] * dequant(stochastic_int8(buckets[c, j]))
    for a [C, nb, b] bucketed client matrix, quantize chain + weighted
    contraction fused per chunk. ``uniforms`` is the [C, nb, b]
    stochastic-rounding draw and ``scales`` the [C, nb] per-bucket
    max-abs/127 scale — both computed by the caller with the exact
    spelling of the XLA chain, so backend bit-identity needs only this
    kernel's chunk math to match (it does: shared dot primitive, see
    module comment). Returns [nb, b] f32.

    Any bucket size runs: buckets are zero-padded here to the 1024-element
    (SUBLANES x LANES) panel the chunks tile — a zero quantizes to zero
    under every draw, and the scales come from the caller's unpadded
    buckets — and the pad columns are sliced off the result."""
    c, nb, b = buckets.shape
    per_panel = LANES * SUBLANES
    b_pad = -(-b // per_panel) * per_panel
    n = nb * b_pad
    pad = ((0, 0), (0, 0), (0, b_pad - b))
    x = jnp.pad(buckets.astype(jnp.float32), pad).reshape(c, n)
    u = jnp.pad(uniforms.astype(jnp.float32), pad).reshape(c, n)
    budget = max(per_panel,
                 (_QR_CHUNK_BYTES // (max(c, 1) * 4)) // per_panel
                 * per_panel)
    chunk = min(b_pad, budget)
    while b_pad % chunk:                # b_pad % per_panel == 0, so this
        chunk -= per_panel              # terminates at per_panel
    # the weights ride the MXU's minimum 8-row tile (rows 1..7 zero);
    # scales are laid out [nb, C, 1] so each chunk's (C, 1) block spans
    # the array's last two dimensions whole, which the TPU tiling accepts
    w8 = jnp.zeros((SUBLANES, c), jnp.float32).at[0].set(
        weights.astype(jnp.float32))
    s3 = scales.astype(jnp.float32).T[..., None]

    block = pl.BlockSpec((c, chunk), lambda ci: (0, ci),
                         memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _qreduce_kernel,
        grid=(n // chunk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),      # (8, C) weights
            block, block,
            pl.BlockSpec((None, c, 1),
                         lambda ci: (ci * chunk // b_pad, 0, 0),
                         memory_space=pltpu.VMEM),      # bucket scale
        ],
        out_specs=pl.BlockSpec((SUBLANES, chunk), lambda ci: (0, ci),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, n), jnp.float32),
        interpret=_interpret(),
    )(w8, x, u, s3)
    return out[0].reshape(nb, b_pad)[:, :b]


# -- fused SNIP mask ops (SalientGrads selection path) ------------------------

def _mask_apply_kernel(p_ref, m_ref, out_ref):
    out_ref[:] = p_ref[:] * m_ref[:]


@jax.jit
def fused_mask_apply_leaf(p: jax.Array, m: jax.Array) -> jax.Array:
    """One-pass ``p * m`` mask projection for one leaf (the SalientGrads
    post-aggregate re-mask) — bit-identical to the jnp spelling (one
    f32 multiply either way; masks are binary)."""
    shape, dtype = p.shape, p.dtype
    p2, n = _to_2d(p.astype(jnp.float32))
    m2, _ = _to_2d(m.astype(jnp.float32))
    rows = p2.shape[0]
    block_rows = _pick_block_rows(rows)
    vmem_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _mask_apply_kernel,
        grid=(rows // block_rows,),
        in_specs=[vmem_spec, vmem_spec],
        out_specs=vmem_spec,
        out_shape=jax.ShapeDtypeStruct(p2.shape, jnp.float32),
        interpret=_interpret(),
    )(p2, m2)
    return _from_2d(out, n, shape, dtype)


def fused_mask_apply(tree: Any, mask: Any) -> Any:
    """Pytree-level fused mask projection (drop-in for
    ``tree_map(lambda p, m: p * m, tree, mask)``)."""
    return jax.tree_util.tree_map(fused_mask_apply_leaf, tree, mask)


def _score_mask_kernel(nt_ref, s_ref, out_ref):
    norm = nt_ref[0]
    thr = nt_ref[1]
    out_ref[:] = (s_ref[:] / norm >= thr).astype(jnp.float32)


@jax.jit
def fused_score_mask_leaf(s: jax.Array, norm: jax.Array,
                          thr: jax.Array) -> jax.Array:
    """One-pass magnitude-score mask build for one leaf:
    ``(s / norm >= thr) -> {0, 1}`` fused (normalize + compare + cast),
    bit-identical to the jnp spelling in ``sparsity.mask_from_scores``.
    Zero-pad is harmless: pad lanes are sliced away before the
    compare's result leaves the kernel wrapper."""
    shape, dtype = s.shape, s.dtype
    s2, n = _to_2d(s.astype(jnp.float32))
    rows = s2.shape[0]
    block_rows = _pick_block_rows(rows)
    vmem_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    nt = jnp.stack([jnp.asarray(norm, jnp.float32).reshape(()),
                    jnp.asarray(thr, jnp.float32).reshape(())])
    out = pl.pallas_call(
        _score_mask_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem_spec],
        out_specs=vmem_spec,
        out_shape=jax.ShapeDtypeStruct(s2.shape, jnp.float32),
        interpret=_interpret(),
    )(nt, s2)
    return _from_2d(out, n, shape, dtype)
