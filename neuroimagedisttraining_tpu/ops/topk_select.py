"""Threshold-refinement top-k selection — the wire's shared selection core.

``jax.lax.top_k`` is SORT-bound in row length at ANY density, which is
why ``--agg_topk_sample`` existed at all. But the selection never
needed the sorted ORDER — only the k-th largest magnitude, used as a
threshold. This module computes that threshold exactly in O(n) passes
with no data-dependent memory traffic, by refining a cut over the f32
bit space:

* nonnegative IEEE-754 floats compare exactly like their bit patterns
  viewed as integers, so "the k-th largest |x|" is "the largest bit
  pattern ``b`` with ``count(bits >= b) >= k``";
* 31 monotone count-above-cut passes binary-search that ``b`` over the
  finite-magnitude bit range — equivalently, a binary search of the
  cumulative magnitude histogram, whose first 8 steps walk the exponent
  byte (the coarse |x| histogram cut) and the remaining 23 refine the
  mantissa;
* the selection itself is then ONE masked compare (``|x| >= thr``) —
  a single pass, no sort, no scatter.

Tie-break contract (pinned by tests/test_pallas_kernels.py and
tests/test_fed_wire.py):

* **In-graph selection** (``collectives.topk_sparsify``, every kernel
  backend) keeps every coordinate whose magnitude is ``>=`` the exact
  k-th largest — coordinates tying the threshold are ALL kept (>= k
  survive; a measure-zero event on continuous deltas). This is exactly
  the legacy sort spelling ``av >= lax.top_k(av, k)[0][..., -1:]``, so
  threshold and sort selection pick IDENTICAL coordinate sets and the
  backends are bit-interchangeable.
* **Host wire encode** (``fed/wire._topk_leaf``) must ship EXACTLY k
  pairs: every coordinate with ``|x| >`` threshold, then ties at the
  threshold by ascending flat index — byte-identical to the historical
  stable ``np.argsort(-|x|)[:k]`` spelling. :func:`host_topk_indices`
  is that rule via ``np.argpartition`` (O(n) expected, no full sort).
* Non-finite magnitudes are OUTSIDE the contract: the guard
  (robust/guard.py) quarantines non-finite client rows before any
  selection runs, and both spellings degrade the same way (a NaN
  threshold selects nothing — every ``>=`` compare is False).

Backends (the ``--agg_kernels`` surface, threaded from
``algorithms/base.py`` down to :func:`select_threshold`):

* ``"xla"`` (default) — pure-XLA bit-space search, the bit-exact
  reference. Replaces the sort with NO trajectory change (same
  coordinate sets, same floats).
* ``"pallas"`` — the fused Pallas kernel (ops/pallas_kernels.py): the
  magnitudes stay VMEM-resident across all 31 count passes, one HBM
  read total. Bit-identical to ``"xla"`` by construction (both converge
  to the same unique integer fixed point); a row too large for VMEM
  (``pallas_kernels.THRESHOLD_MAX_N``) is refused with the reason —
  a ``pallas`` request never runs the XLA search in silence.
* ``"sort"`` — the legacy ``lax.top_k`` spelling, kept as the internal
  reference for parity tests and bench baselines (not a flag choice).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the ``--agg_kernels`` flag surface (analysis/identity.py classifies it
#: inert: backends are bit-identical by the tie-break contract)
KERNEL_BACKENDS = ("xla", "pallas")

#: internal backend spellings accepted by :func:`select_threshold`
#: ("sort" = the legacy lax.top_k reference, tests/bench only)
_ALL_BACKENDS = KERNEL_BACKENDS + ("sort",)

#: one past the +inf bit pattern: the exclusive upper bound of the
#: bit-space search (every finite-or-inf magnitude lies below it)
_BITS_HI = np.int32(0x7F800001)

#: ceil(log2(_BITS_HI)) — halvings until the search interval is one wide
SEARCH_ITERS = 31


def check_kernels(kernels: str) -> str:
    """Validate a kernel-backend name (flag surface + 'sort')."""
    if kernels not in _ALL_BACKENDS:
        raise ValueError(
            f"agg_kernels {kernels!r} not in {_ALL_BACKENDS}")
    return kernels


def _count_ge(bits: jax.Array, cut: jax.Array) -> jax.Array:
    """count(bits >= cut) per row — the monotone search oracle."""
    return jnp.sum((bits >= cut).astype(jnp.int32), axis=-1,
                   keepdims=True)


@functools.partial(jax.jit, static_argnames=("k",))
def exact_threshold(av: jax.Array, k: int) -> jax.Array:
    """Exact k-th largest magnitude per row, no sort: binary-search the
    f32 bit space with :data:`SEARCH_ITERS` count passes.

    ``av`` is ``[..., n]`` nonnegative f32 (magnitudes); returns
    ``[..., 1]`` f32 — the same float ``lax.top_k(av, k)[0][..., -1:]``
    produces, so ``av >= thr`` selects the identical coordinate set
    (the tie-break contract above). Invariant: ``lo`` always satisfies
    ``count >= k`` (true at ``lo=0`` since ``k <= n``), ``hi`` never
    does; the loop is stationary once the interval is one wide, so a
    fixed :data:`SEARCH_ITERS` trip count is exact, trace-friendly,
    and backend-independent (the fixed point is a unique integer —
    any correct search order lands on it)."""
    bits = jax.lax.bitcast_convert_type(av.astype(jnp.float32),
                                        jnp.int32)
    lead = av.shape[:-1] + (1,)
    lo = jnp.zeros(lead, jnp.int32)
    hi = jnp.full(lead, _BITS_HI, jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ok = _count_ge(bits, mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, SEARCH_ITERS, body, (lo, hi))
    return jax.lax.bitcast_convert_type(lo, jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "sample"))
def sampled_threshold(av: jax.Array, k: int, sample: int) -> jax.Array:
    """The ``--agg_topk_sample`` strided threshold estimator (Deep
    Gradient Compression hierarchical sampling, Lin et al. 2018),
    hoisted verbatim out of ``collectives.topk_sparsify`` so both the
    in-graph selection and its calibration test share one spelling:
    deterministic fixed-stride ~``sample``-element subsample, exact
    top-k on the candidates, k scaled by the stride. The shipped count
    is only approximately k — drift the error-feedback residual absorbs
    by construction (tests pin the calibration band against
    :func:`exact_threshold`)."""
    n = av.shape[-1]
    stride = max(1, n // int(sample))
    cand = av[..., ::stride]
    ks = min(cand.shape[-1], max(1, int(round(k / stride))))
    return jax.lax.top_k(cand, ks)[0][..., -1:]


def select_threshold(av: jax.Array, k: int, *, kernels: str = "xla",
                     sample: int = 0) -> jax.Array:
    """Per-row selection threshold for ``av >= thr`` top-k masking,
    routed by kernel backend. ``sample > 0`` uses the strided estimator
    on every backend (the subsample's top_k is tiny — already
    sort-affordable; exact backends make it an optimization, not a
    necessity)."""
    check_kernels(kernels)
    n = av.shape[-1]
    if sample and n > sample:
        return sampled_threshold(av, k, sample)
    if kernels == "sort":
        return jax.lax.top_k(av, k)[0][..., -1:]
    if kernels == "pallas":
        from . import pallas_kernels as pk

        return pk.threshold_topk(av, k)  # refuses rows VMEM cannot hold
    return exact_threshold(av, k)


def host_topk_indices(mag: np.ndarray, k: int) -> np.ndarray:
    """Exactly-k flat indices of the largest magnitudes, host-side,
    under the wire tie-break contract: all ``mag > T`` plus ties at
    ``T`` by ascending index, returned ascending int32 — byte-identical
    to ``np.sort(np.argsort(-mag, kind='stable')[:k])`` without the
    full sort (``np.argpartition`` is O(n) expected). NaNs order last,
    exactly like the stable-argsort spelling (np.sort semantics)."""
    mag = np.asarray(mag).ravel()
    n = mag.size
    k = int(k)
    if k >= n:
        return np.arange(n, dtype=np.int32)
    part = np.argpartition(-mag, k - 1)[:k]
    vals = mag[part]
    if np.isnan(vals).any():
        # >= k non-finites in play: fall back to the reference spelling
        # (outside the contract; correctness over speed)
        order = np.argsort(-mag, kind="stable")[:k]
        return np.sort(order).astype(np.int32)
    thr = vals.min()
    above = np.flatnonzero(mag > thr)
    ties = np.flatnonzero(mag == thr)
    idx = np.concatenate([above, ties[: k - above.size]])
    return np.sort(idx).astype(np.int32)
