"""Spatial (volume) parallelism — this framework's sequence/context-parallel slot.

The reference has no sequence axis (3D CNNs over fixed 121x145x121 volumes;
SURVEY.md §5.7): the analogous long-context scaling axis here is the *conv
grid of a single volume*. When one volume (or the activations of a deep 3D
net on it) exceeds per-core HBM, we shard the depth axis of the volume across
a ``space`` mesh axis, the way ring attention shards the sequence axis.

Two complementary paths:

1. **GSPMD path** (production default): annotate the batch with
   ``PartitionSpec(None, "space")`` (depth axis sharded) and jit the normal
   forward/train step over the mesh. XLA's SPMD partitioner inserts the halo
   exchanges for every conv/pool automatically and overlaps them with
   compute. Use :func:`shard_spatial` + any jitted function.

2. **Explicit halo-exchange path**: :func:`halo_exchange` /
   :func:`sharded_conv3d` implement the ring-communication pattern by hand
   with ``lax.ppermute`` under ``shard_map`` — the direct analogue of ring
   attention's neighbor exchange, for cases where manual scheduling beats
   GSPMD (custom fused kernels, pallas) and as an executable spec that the
   GSPMD path is tested against.

The reference's closest artifact is the host-RAM-bound full-cohort load
(``ABCD/data_loader.py:105-136``) — it has no answer to a volume that does
not fit one device; this module is that answer.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SPACE_AXIS = "space"


# ---------------------------------------------------------------------------
# GSPMD path
# ---------------------------------------------------------------------------

def spatial_spec(batch_ndim: int = 5, axis_name: str = SPACE_AXIS) -> P:
    """PartitionSpec sharding the depth axis of an (N, D, H, W, C) batch."""
    return P(*([None, axis_name] + [None] * (batch_ndim - 2)))


def shard_spatial(x: jax.Array, mesh: Mesh, axis_name: str = SPACE_AXIS):
    """Place a volume batch on the mesh with the depth axis sharded.

    jax requires the depth extent to divide the ``space`` axis size; for
    volumes that don't (the canonical ABCD 121x145x121 has no power-of-two
    factors), zero-pad the depth first with :func:`pad_depth_to` — neutral
    for brain-masked MRI data whose background is already zero
    (``Preprocess_ABCD.ipynb`` mean-mask step).
    """
    n = mesh.shape[axis_name]
    if x.shape[1] % n:
        raise ValueError(
            f"depth {x.shape[1]} not divisible by space axis {n}; "
            "pad with parallel.spatial.pad_depth_to(x, n) first"
        )
    return jax.device_put(x, NamedSharding(mesh, spatial_spec(x.ndim, axis_name)))


def pad_depth_to(x, multiple: int, depth_axis: int = 1):
    """Zero-pad the depth axis up to the next multiple (background padding).

    Note conv arithmetic sees the padded extent, so model init must use the
    padded shape too — flax infers Dense fan-in at init, nothing else changes.
    Host numpy arrays stay on host (padding a full cohort must not stage it
    onto one device before sharding).
    """
    import numpy as np

    d = x.shape[depth_axis]
    pad = (-d) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[depth_axis] = (0, pad)
    xp = jnp if isinstance(x, jax.Array) else np
    return xp.pad(x, widths)


def make_spatial_forward(
    apply_fn: Callable[..., Any],
    mesh: Mesh,
    axis_name: str = SPACE_AXIS,
):
    """Jit the eval-mode forward with params replicated and ``x``
    depth-sharded over ``axis_name``. XLA GSPMD inserts conv halo exchanges.

    Returns ``fwd(params, x) -> logits`` (train=False, no dropout rng);
    ``apply_fn`` must follow the model-zoo signature
    ``apply_fn(params, x, train, rng)``. For a sharded *training* step just
    jit your own step with the same shardings — see
    tests/test_spatial.py::test_hybrid_clients_space_grad_step.
    """
    repl = NamedSharding(mesh, P())

    @functools.partial(
        jax.jit,
        in_shardings=(repl, NamedSharding(mesh, spatial_spec(5, axis_name))),
        static_argnums=(),
    )
    def fwd(params, x):
        return apply_fn(params, x, train=False, rng=None)

    return fwd


# ---------------------------------------------------------------------------
# Explicit halo-exchange path (ring-attention-style neighbor comms)
# ---------------------------------------------------------------------------

def halo_exchange(
    x: jax.Array,
    halo: int,
    axis_name: str = SPACE_AXIS,
    *,
    depth_axis: int = 1,
) -> jax.Array:
    """Exchange ``halo`` planes with ring neighbors along a sharded depth axis.

    Must be called inside ``shard_map``/``pmap`` with ``axis_name`` bound.
    ``x`` is this shard's local block; returns the block extended by ``halo``
    planes on each side. Boundary shards (first/last) receive zeros — i.e.
    non-periodic zero-padding semantics, matching a conv with integer padding.

    This is the framework's ring-communication primitive: two ``ppermute``
    shifts (one per direction) over the ICI ring, exactly the neighbor
    exchange at the heart of ring attention.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)

    def dslice(start, size):
        return lax.slice_in_dim(x, start, start + size, axis=depth_axis)

    d_local = x.shape[depth_axis]
    if halo > d_local:
        raise ValueError(f"halo {halo} exceeds local depth {d_local}")

    # send my top `halo` planes to the next shard (they become its lower halo)
    top = dslice(d_local - halo, halo)
    lo_halo = lax.ppermute(top, axis_name, [(i, (i + 1) % n) for i in range(n)])
    # send my bottom `halo` planes to the previous shard (its upper halo)
    bot = dslice(0, halo)
    hi_halo = lax.ppermute(bot, axis_name, [(i, (i - 1) % n) for i in range(n)])

    zeros = jnp.zeros_like(lo_halo)
    lo_halo = jnp.where(idx == 0, zeros, lo_halo)
    hi_halo = jnp.where(idx == n - 1, zeros, hi_halo)
    return jnp.concatenate([lo_halo, x, hi_halo], axis=depth_axis)


def sharded_conv3d(
    x: jax.Array,
    kernel: jax.Array,
    bias: Optional[jax.Array] = None,
    axis_name: str = SPACE_AXIS,
) -> jax.Array:
    """Depth-sharded stride-1 'same' 3D conv via explicit halo exchange.

    Inside ``shard_map``: ``x`` is the local (N, D_local, H, W, Cin) block of
    a depth-sharded batch; ``kernel`` is the replicated (kd, kh, kw, Cin,
    Cout) filter with odd kd. Produces the local block of the conv with
    torch-style padding ``p = k//2`` on every spatial dim (so global output
    shape == global input shape).
    """
    kd, kh, kw = kernel.shape[:3]
    if kd % 2 != 1:
        raise ValueError("explicit path requires odd depth kernel")
    x = halo_exchange(x, kd // 2, axis_name)
    out = lax.conv_general_dilated(
        x,
        kernel,
        window_strides=(1, 1, 1),
        padding=[(0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2)],
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
    )
    if bias is not None:
        out = out + bias
    return out


def make_sharded_conv3d(mesh: Mesh, axis_name: str = SPACE_AXIS):
    """shard_map-wrapped :func:`sharded_conv3d` over ``mesh``.

    Returns ``f(x, kernel, bias) -> y`` where ``x``/``y`` are global arrays
    depth-sharded over ``axis_name`` and the filter/bias are replicated.
    """
    spec_x = spatial_spec(5, axis_name)

    def local(x, kernel, bias):
        return sharded_conv3d(x, kernel, bias, axis_name)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(spec_x, P(), P()),
        out_specs=spec_x,
        check_vma=False,
    )


def pad_federated_depth(data: Any, multiple: int) -> Any:
    """Zero-pad every volume array of a FederatedData so its depth (axis 2
    of the [C, n, D, H, W, ...] layout) divides the ``space`` mesh axis.

    Background padding is neutral for brain-masked MRI (the cohort's
    background is already zero, ``Preprocess_ABCD.ipynb`` mean-mask step);
    model init must use the padded sample shape (flax infers Dense fan-in
    at init), which falls out naturally when the data is padded before the
    algorithm is constructed."""
    if multiple <= 1:
        return data

    def pad(x):
        if x is None:
            return None
        return pad_depth_to(x, multiple, depth_axis=2)

    return data.replace(
        x_train=pad(data.x_train), x_test=pad(data.x_test),
        x_val=pad(data.x_val))


# ---------------------------------------------------------------------------
# Hybrid client x space training-step sharding
# ---------------------------------------------------------------------------

def hybrid_batch_spec(axis_name: str = SPACE_AXIS) -> P:
    """Spec for a federated volume batch (clients, n, D, H, W, C): client
    axis over ``clients``, depth over ``space`` — FL data parallelism and
    volume parallelism composed on one mesh."""
    return P("clients", None, axis_name)


def shard_hybrid(x: jax.Array, mesh: Mesh, axis_name: str = SPACE_AXIS):
    """Place a (clients, n, D, H, W, C) federated batch with the client axis
    over ``clients`` and volume depth over ``space``."""
    return jax.device_put(x, NamedSharding(mesh, hybrid_batch_spec(axis_name)))
