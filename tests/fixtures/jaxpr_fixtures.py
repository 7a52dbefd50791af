"""Seeded-violation fixtures for the jaxpr auditor (loaded by
``scripts/lint_gate.py --jaxpr-fixture path.py::name``).

Each fixture is a zero-arg callable returning ``(fn, args)``; the gate
traces ``fn(*args)`` with ``jax.make_jaxpr`` (under ``enable_x64`` when
``--x64`` is passed) and applies the hot-path contracts. These model
the regressions the auditor exists to catch *before* they reach pod
hardware: a latent f64 promotion, a host callback on the round path,
and a branch-dependent collective (the SPMD deadlock hazard).
"""
import numpy as np


def f64_round():
    """A round-body fragment with a latent f64 promotion: an np.float64
    weight scalar. With x64 off jax silently demotes it — the exact
    reason the auditor traces fixtures under enable_x64."""
    import jax.numpy as jnp

    w = np.float64(0.5)  # strongly-typed f64 scalar: promotes under x64

    def fn(x):
        return (x * w).sum() / jnp.asarray(x.shape[0], jnp.float32)

    return fn, (np.ones((8, 4), np.float32),)


def callback_round():
    """A round body that smuggles a host callback onto the hot path."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return jnp.sum(y)

    return fn, (np.ones((4,), np.float32),)


def branch_collective():
    """A ``lax.cond`` whose branches issue DIFFERENT collectives — on
    real multi-host SPMD a data-dependent branch like this deadlocks
    (processes disagree on whether to enter the psum)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("clients",))

    def inner(x):
        return jax.lax.cond(
            jnp.sum(x) > 0,
            lambda v: jax.lax.psum(v, "clients"),
            lambda v: v * 2.0,
            x)

    fn = shard_map(inner, mesh=mesh, in_specs=P("clients"),
                   out_specs=P("clients"), check_vma=False)
    return fn, (np.ones((len(devs), 3), np.float32),)


def clean_round():
    """Whitelist-clean control: f32 math, no callbacks, no branches."""
    import jax.numpy as jnp

    def fn(x, w):
        return jnp.sum(x * w[:, None]) / jnp.maximum(jnp.sum(w), 1.0)

    return fn, (np.ones((8, 4), np.float32),
                np.ones((8,), np.float32))
