"""Multi-host (pod / multi-slice) support: DCN init + global client arrays.

SURVEY §7.9: the reference's only inter-process substrate is the orphaned
MPI/gRPC message layer; scaling there means one SLURM process on one GPU.
Here multi-host is the same SPMD program on more chips:

  1. every process calls :func:`initialize_distributed` (on TPU pods JAX
     auto-detects coordinator/process ids from the TPU environment);
  2. :func:`make_multihost_mesh` lays the ``clients`` axis over ALL global
     devices — contiguous per process, so one federated client's local
     training never straddles DCN, and the per-round weighted-mean
     aggregation is the only cross-host collective;
  3. each process loads only its own clients' shards
     (:func:`local_client_indices`) and assembles the global client-sharded
     arrays with :func:`make_global_client_array` — no host ever
     materializes the full cohort (the reference loads everything into one
     host's RAM, ``ABCD/data_loader.py:105-136``).

Single-process runs degrade to the plain ``make_mesh`` path, so everything
here is exercised by the CPU test mesh too.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Callable, Optional, TypeVar

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

_T = TypeVar("_T")


def _with_retries(what: str, fn: Callable[[], _T],
                  max_retries: int = 2,
                  backoff_s: float = 5.0) -> _T:
    """Bounded retries for STARTUP host-sync points (the jax.distributed
    coordinator handshake, where every process retries in lockstep until
    the coordinator appears): transient runtime/IO errors retry with
    linear backoff, the final failure propagates. Mid-run collectives
    are NEVER retried per-process (see host_client_counts) — that would
    break the SPMD collective-matching invariant."""
    retries = max(0, int(max_retries))
    delay = float(backoff_s)
    for attempt in range(retries + 1):
        try:
            return fn()
        except (RuntimeError, OSError, TimeoutError) as e:
            if attempt >= retries:
                raise
            logger.warning(
                "%s failed (%s: %s); retry %d/%d in %.1fs", what,
                type(e).__name__, e, attempt + 1, retries,
                delay * (attempt + 1))
            time.sleep(delay * (attempt + 1))
    raise RuntimeError(f"unreachable: {what} retry loop")  # pragma: no cover


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    timeout_s: Optional[float] = None,
    max_retries: Optional[int] = None,
) -> bool:
    """Idempotent ``jax.distributed.initialize`` wrapper.

    On TPU pods all three arguments are auto-detected from the runtime
    environment; pass them explicitly for CPU/GPU clusters. Returns True if
    a multi-process runtime is active after the call.

    ``timeout_s`` bounds the coordinator handshake, and transient init
    failures retry under ``max_retries`` bounded retries
    with linear backoff — a slow coordinator degrades to a few logged
    retries instead of hanging the whole SLURM allocation.

    MUST run before anything initializes the XLA backend (even
    ``jax.devices()``/``jax.process_count()`` counts) — which is also why
    this function itself touches no backend state before calling
    ``jax.distributed.initialize``.
    """
    explicit = not (coordinator_address is None and num_processes is None)

    class _Permanent(Exception):
        """Non-transient init outcome — bypasses the retry loop."""

    def _init_once() -> None:
        kw = {}
        if explicit:
            kw = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
        if timeout_s:
            # ceil, floor 1: int() truncation would turn a sub-second
            # --multihost_timeout_s into an instant zero-second
            # handshake timeout
            kw["initialization_timeout"] = max(
                1, int(-(-float(timeout_s) // 1)))
        try:
            jax.distributed.initialize(**kw)
        except (RuntimeError, OSError, TimeoutError) as e:
            msg = str(e)
            if isinstance(e, RuntimeError) and (
                    ("already" in msg and "initialize" in msg) or
                    ("before" in msg and "XLA backend" in msg) or
                    "only be called once" in msg):
                raise _Permanent() from e  # retrying cannot change these
            # transient failure (connect timeout, coordinator refused —
            # any of the retryable error types): jax assigns
            # global_state.client BEFORE the connect, so without a
            # shutdown the re-attempt would die with 'initialize should
            # only be called once' instead of retrying the handshake
            try:
                jax.distributed.shutdown()
            except Exception:  # never-connected client; nothing to undo
                logger.debug("post-failure distributed shutdown noop",
                             exc_info=True)
            raise

    try:
        try:
            _with_retries(
                "jax.distributed.initialize", _init_once,
                # auto-detect mode never retries (a missing cluster env
                # is not transient); None = the default budget
                max_retries=(max_retries if max_retries is not None
                             else 2) if explicit else 0)
        except _Permanent as p:
            raise p.__cause__  # classified below exactly as before
    except RuntimeError as e:
        msg = str(e)
        if "already" in msg and "initialize" in msg:
            pass  # repeated call — fine, keep the existing runtime
        elif "before" in msg and "XLA backend" in msg:
            # too late: something already touched the backend. Silently
            # degrading here would mean every pod host training alone.
            raise RuntimeError(
                "initialize_distributed() was called after the XLA backend "
                "was initialized — call it first (before jax.devices(), "
                "device_put, jit, ...). The CLI does this when --multihost "
                "is set.") from e
        elif explicit:
            raise
        else:
            # auto-detect found no cluster environment: single-process run
            logger.info("single-process run (distributed init skipped: %s)",
                        e)
            return False
    except ValueError as e:
        if explicit:
            raise
        logger.info("single-process run (distributed init skipped: %s)", e)
        return False
    return jax.process_count() > 1


def make_multihost_mesh(n_space: int = 1,
                        num_clients: Optional[int] = None,
                        max_client_devices: Optional[int] = None) -> Mesh:
    """(clients[, space]) mesh over every device of every process.

    Device order keeps each process's devices contiguous along ``clients``
    (jax.devices() global order), so client shards are process-local and
    ICI carries all per-client work; only the aggregation collective
    crosses DCN. ``space`` subdivides each client's devices for volume
    sharding (parallel/spatial.py) and must divide the per-process device
    count so halo exchanges stay on ICI (enforced).

    ``num_clients``/``max_client_devices`` shrink the clients axis (like
    the single-host runner path) until it divides ``num_clients`` and
    splits evenly across processes — e.g. the canonical 8-client workload
    on a 32-chip pod gets an 8-row clients axis, not a crash.
    """
    if n_space > 1 and jax.local_device_count() % n_space:
        raise ValueError(
            f"{n_space=} must divide the per-process device count "
            f"{jax.local_device_count()} so a client's space shards (and "
            "their halo exchanges) stay on one host's ICI")
    devices = jax.devices()
    n_proc = jax.process_count()
    rows = len(devices) // n_space
    if max_client_devices:
        rows = min(rows, max_client_devices)
    if num_clients is not None:
        rows = min(rows, num_clients)
        # rows must divide num_clients and split evenly over processes
        while rows > 1 and (num_clients % rows or rows % n_proc):
            rows -= 1
        if num_clients % rows or rows % n_proc:
            raise ValueError(
                f"cannot lay {num_clients} clients over {n_proc} processes")
    else:
        # even without a client count, rows must split evenly over
        # processes or the balanced device selection below under-fills
        rows -= rows % n_proc
        if rows < n_proc:
            raise ValueError(
                f"clients axis of {rows} rows cannot span {n_proc} "
                "processes; raise max_client_devices")
    # take an equal number of devices from every process, so a shrunk
    # clients axis still spreads across all hosts (a global-order prefix
    # would put every row on the first hosts and starve the rest)
    per_proc = (rows // n_proc) * n_space
    chosen = []
    for p in range(n_proc):
        pdevs = [d for d in devices if d.process_index == p]
        chosen.extend(pdevs[:per_proc])
    arr = np.array(chosen).reshape(rows, n_space)
    if n_space == 1:
        return Mesh(arr.reshape(-1), ("clients",))
    return Mesh(arr, ("clients", "space"))


def local_client_indices(num_clients: int, mesh: Mesh) -> np.ndarray:
    """Client ids whose data THIS process must load.

    Clients are block-distributed over the ``clients`` mesh axis; a
    process owns the clients that land on its addressable devices.
    """
    axis = list(mesh.axis_names).index("clients")
    mesh_devices = np.moveaxis(mesh.devices, axis, 0).reshape(
        mesh.shape["clients"], -1)
    n_rows = mesh_devices.shape[0]
    if num_clients % n_rows:
        raise ValueError(
            f"{num_clients=} must be a multiple of the clients mesh "
            f"extent {n_rows}")
    per_row = num_clients // n_rows
    pid = jax.process_index()
    mine = [r for r in range(n_rows)
            if mesh_devices[r, 0].process_index == pid]
    return np.concatenate([
        np.arange(r * per_row, (r + 1) * per_row) for r in mine
    ]) if mine else np.zeros((0,), np.int64)


def make_global_client_array(local_rows: np.ndarray, global_shape: tuple,
                             mesh: Mesh) -> jax.Array:
    """Assemble a global client-sharded array from this process's rows.

    ``local_rows`` must hold exactly the rows of
    :func:`local_client_indices` in order; the result is a global
    ``jax.Array`` sharded ``P("clients")`` whose addressable shards came
    only from local memory.
    """
    sharding = NamedSharding(mesh, P("clients"))
    return jax.make_array_from_process_local_data(
        sharding, np.ascontiguousarray(local_rows), global_shape)


def host_client_counts(n) -> np.ndarray:
    """Per-client sample counts as a host ndarray, safe for multi-host
    global arrays.

    ``n_train`` is client-sharded on a multi-host mesh, so a plain
    ``np.asarray`` raises (non-addressable shards). Every process then
    needs the SAME answer — derived hyperparameters like
    ``steps_per_epoch`` and the epoch fast-path flag feed jitted program
    construction, and divergent values would desync the SPMD programs —
    so the local shards are allgathered (clients are contiguous per
    process, ``local_client_indices``)."""
    try:
        return np.asarray(n)
    except RuntimeError:
        pass
    from jax.experimental import multihost_utils

    shards = sorted(n.addressable_shards,
                    key=lambda s: (s.index[0].start or 0))
    local = np.concatenate([np.asarray(s.data).ravel() for s in shards])
    # NOTE deliberately NOT retried: a mid-run collective must execute in
    # lockstep across processes — one host re-issuing its allgather while
    # peers (which succeeded) have moved on would hang against no
    # counterpart or pair with a LATER collective and garble data. The
    # bounded-retry policy (_with_retries) applies only to the startup
    # handshake (initialize_distributed), where every process is retrying
    # until the coordinator appears; mid-run sync points are protected by
    # the init-time timeout instead (a failure here fails fast).
    gathered = multihost_utils.process_allgather(local)
    return np.asarray(gathered).ravel()


def shard_federated_data_global(local_data: Any, num_clients: int,
                                mesh: Mesh) -> Any:
    """Lift a process-local FederatedData (holding only this process's
    clients, in ``local_client_indices`` order) to the global sharded
    pytree every process passes to the same jitted round.

    On a (clients, space) mesh the volume arrays ([C, n, D, ...]) are
    additionally depth-sharded over ``space`` (context parallelism) — the
    same placement as the single-host ``shard_federated_hybrid``."""
    has_space = "space" in mesh.axis_names

    def lift(x):
        x = np.asarray(x)
        if has_space and x.ndim >= 3:
            spec = P("clients", None, "space")
        else:
            spec = P("clients")
        sharding = NamedSharding(mesh, spec)
        return jax.make_array_from_process_local_data(
            sharding, np.ascontiguousarray(x),
            (num_clients,) + x.shape[1:])

    return jax.tree_util.tree_map(lift, local_data)
