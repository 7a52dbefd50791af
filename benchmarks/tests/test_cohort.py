"""The traffic generator at a toy size on the CPU: shapes, the seed, the
planted signal, uneven sites, and generation onto a mesh."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from benchmarks.lib import cohort

SITES = {"n_sites": 4, "train_per_site": 6, "test_per_site": 2}
STEM = {"kernel": 5, "pad": 0}
VOLUME = (13, 15, 11)


def test_shapes_seed_and_signal():
    a = cohort.make_cohort(SITES, VOLUME, STEM, seed=7)
    assert a.x_train.shape == (4, 6, 7, 8, 8, 6)      # (D', H', 8, W')
    assert a.x_test.shape == (4, 2, 7, 8, 8, 6)
    assert a.x_train.dtype == jax.numpy.bfloat16 and a.class_num == 2
    assert np.asarray(a.n_train).tolist() == [6] * 4
    assert np.asarray(a.n_test).tolist() == [2] * 4
    b = cohort.make_cohort(SITES, VOLUME, STEM, seed=7)
    c = cohort.make_cohort(SITES, VOLUME, STEM, seed=8)
    assert (np.asarray(a.x_train) == np.asarray(b.x_train)).all()
    assert (np.asarray(a.x_train) != np.asarray(c.x_train)).any()
    # the label shifts a volume's mean by +-0.75
    x = np.asarray(a.x_train, np.float32).reshape(24, -1).mean(axis=1)
    y = np.asarray(a.y_train).reshape(24)
    assert 0 < y.sum() < 24
    assert np.allclose(x, cohort.SIGNAL * (2 * y - 1), atol=0.15)


def test_uneven_sites_and_unknown_parameters():
    uneven = {**SITES, "train_min_per_site": 2}
    counts = cohort.site_counts(uneven, seed=3)
    assert counts.min() >= 2 and counts.max() <= 6 and len(set(counts)) > 1
    assert (counts == cohort.site_counts(uneven, seed=3)).all()
    data = cohort.make_cohort(uneven, VOLUME, STEM, seed=3)
    assert data.x_train.shape[1] == 6
    assert np.asarray(data.n_train).tolist() == counts.tolist()
    with pytest.raises(ValueError, match="unknown cohort parameter"):
        cohort.make_cohort({**SITES, "burst": 2}, VOLUME, STEM, seed=0)


def test_generated_on_the_mesh_it_is_given():
    mesh = Mesh(np.array(jax.devices()[:4]), ("clients",))
    sharding = NamedSharding(mesh, PartitionSpec("clients"))
    data = cohort.make_cohort(SITES, VOLUME, STEM, seed=7, sharding=sharding)
    for leaf in (data.x_train, data.y_train, data.n_train, data.x_test):
        assert len({s.device for s in leaf.addressable_shards}) == 4
    assert data.x_train.addressable_shards[0].data.shape[0] == 1
    one = cohort.make_cohort(SITES, VOLUME, STEM, seed=7)
    assert (np.asarray(one.y_train) == np.asarray(data.y_train)).all()
