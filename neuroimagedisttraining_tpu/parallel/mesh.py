"""Device mesh + sharding helpers.

This is the TPU-native replacement for the reference's distributed substrate
(``fedml_core/distributed/``: MPI send/recv daemon threads with pickled
state_dicts, ``mpi/com_manager.py:13-98``): instead of explicit peer sends,
per-client values carry a leading client axis laid out over a ``clients`` mesh
axis, and aggregation/gossip lower to XLA collectives over ICI. Multi-host
(DCN) uses the same mesh spanning all processes after
``jax.distributed.initialize`` — see ``parallel/multihost.py``.

Mesh axes:
  * ``clients`` — the federated axis: one (or more) simulated site/hospital
    client per device.
  * ``space``   — optional spatial axis for sharding a single 3D volume's
    conv grid across devices (this framework's sequence/context-parallel
    analogue; see SURVEY.md §5.7 — consumer lands in parallel/spatial.py).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    n_client_devices: Optional[int] = None,
    n_space: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (clients[, space]) mesh over the available devices."""
    devices = list(devices if devices is not None else jax.devices())
    if n_client_devices is None:
        n_client_devices = len(devices) // n_space
    n_total = n_client_devices * n_space
    if n_total > len(devices):
        raise ValueError(
            f"mesh needs {n_total} devices, have {len(devices)}"
        )
    arr = np.array(devices[:n_total])
    if n_space == 1:
        return Mesh(arr.reshape(n_client_devices), ("clients",))
    return Mesh(arr.reshape(n_client_devices, n_space), ("clients", "space"))


def fit_client_devices(n_clients: int, available: int) -> int:
    """Largest device count <= available that divides ``n_clients`` (the
    clients mesh axis must divide the client count). Shared by the runner
    and the benchmark's harness so device-fitting policy lives in one
    place."""
    n = min(max(1, available), max(1, n_clients))
    while n_clients % n:
        n -= 1
    return n


def mesh_of(tree: Any) -> Optional[Mesh]:
    """The live :class:`Mesh` behind any ``NamedSharding`` leaf of
    ``tree`` (None when the pytree is unsharded / single-device). Lets the
    aggregation collectives (``parallel/collectives.py``) discover the
    ``clients`` mesh the data was placed on without threading a mesh
    handle through every algorithm constructor."""
    for leaf in jax.tree_util.tree_leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        mesh = getattr(sharding, "mesh", None)
        if isinstance(mesh, Mesh) and mesh.axis_names:
            return mesh
    return None


def shard_over_clients(tree: Any, mesh: Mesh) -> Any:
    """Place a pytree whose leaves have a leading client axis onto the mesh,
    sharded over ``clients``."""
    sharding = NamedSharding(mesh, P("clients"))
    return jax.device_put(tree, sharding)


def shard_federated_hybrid(tree: Any, mesh: Mesh) -> Any:
    """Place a FederatedData pytree on a (clients[, space]) mesh: the client
    axis over ``clients`` and — when the mesh has a ``space`` axis — each
    volume's depth (leaf axis 2 of the [C, n, D, H, W, ...] arrays) over
    ``space``. Labels/counts ([C, n] / [C]) shard over clients only."""
    has_space = "space" in mesh.axis_names

    def put(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        if has_space and x.ndim >= 3:
            spec = P("clients", None, "space")
        else:
            spec = P("clients")
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, tree)


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Replicate a pytree (e.g. global model params) across the mesh."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def client_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("clients"))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
