"""The traffic generator: a federated cohort made on the device from a seed.

A copy of ``bench.py:_device_synth_data`` (sound; its key was hard-wired),
driven by ``--seed``: per site ``train_per_site`` training and
``test_per_site`` test volumes, standard-normal voxels with a mean shift of
+-0.75 planted by the label, stored phase-decomposed in bfloat16 as the
program's ``--layout s2d`` loader stores them. One jitted program builds all
of it, so the add never holds a second cohort-sized buffer, and with a
``sharding`` every chip generates its own sites.

Random phased volumes are the same workload as phased real ones: the
program's arithmetic does not depend on the voxel values. What they hold in
the conv's padding frame is not zero, as a real volume's would be; the
correctness check re-derives its two volumes through :mod:`phase` for that
reason.
"""
from __future__ import annotations

import numpy as np

from . import phase

COHORT_KEYS = {"n_sites", "train_per_site", "test_per_site",
               "train_min_per_site"}
SIGNAL = 0.75


def site_counts(cohort: dict, seed: int) -> np.ndarray:
    """Valid training volumes per site: ``train_per_site`` each, or, with
    ``train_min_per_site``, drawn from ``[min, train_per_site]`` by the
    seed (uneven acquisition sites)."""
    n, top = cohort["n_sites"], cohort["train_per_site"]
    low = cohort.get("train_min_per_site")
    if low is None:
        return np.full((n,), top, np.int32)
    return np.random.default_rng(seed).integers(
        low, top + 1, n).astype(np.int32)


def make_cohort(cohort: dict, volume, stem: dict, seed: int, sharding=None):
    """The cohort as the program's ``FederatedData``, resident on the
    device(s) ``sharding`` names (default: JAX's default device)."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.data.types import FederatedData

    unknown = set(cohort) - COHORT_KEYS
    if unknown:
        raise ValueError(f"unknown cohort parameter(s): {sorted(unknown)}")
    sites = cohort["n_sites"]
    n_train, n_test = cohort["train_per_site"], cohort["test_per_site"]
    sample = phase.phased_shape(volume, stem["kernel"], stem["pad"])

    def planted(k_x, k_y, rows):
        y = jax.random.bernoulli(k_y, 0.5, (sites, rows)).astype(jnp.int32)
        x = jax.random.normal(k_x, (sites, rows) + sample, jnp.bfloat16)
        shift = y[(...,) + (None,) * len(sample)].astype(x.dtype)
        return x + SIGNAL * (shift * 2 - 1), y

    def build(key):
        k_x, k_y, k_tx, k_ty = jax.random.split(key, 4)
        return planted(k_x, k_y, n_train) + planted(k_tx, k_ty, n_test)

    x, y, xt, yt = jax.jit(build, out_shardings=sharding)(
        jax.random.PRNGKey(seed))
    counts = jnp.asarray(site_counts(cohort, seed))
    tests = jnp.full((sites,), n_test, jnp.int32)
    if sharding is not None:
        counts, tests = jax.device_put((counts, tests), sharding)
    return FederatedData(x_train=x, y_train=y, n_train=counts,
                         x_test=xt, y_test=yt, n_test=tests, class_num=2)
