"""Every cell's round program compiled at its real size for a v5e that is
described, not attached (``on-chip-measurement`` guide, section 2.3): what the
chip's compiler refuses here costs no chip time. A size check, never a time.

    python -m pytest benchmarks/tests/test_chip_compile.py -q -m slow

The topology is described inside a fixture of this one file and nowhere at
import time: only one process may load the TPU's library.
"""
import json

import numpy as np
import pytest
from conftest import MANIFEST

from benchmarks.lib import harness, manifest, phase, reduce_trace

HBM_GIB = 15.75     # what a v5e chip offers a program (PR 21)
CELLS = [w["name"] for w in json.load(open(MANIFEST))["workloads"]]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def abstract_cell(cell, topo):
    """The cell's algorithm over shapes placed on the described chips, and
    the arguments of its round program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from neuroimagedisttraining_tpu.algorithms.salientgrads import (
        SalientGradsState)
    from neuroimagedisttraining_tpu.data.types import FederatedData
    from neuroimagedisttraining_tpu.experiments import parse_args, runner
    from neuroimagedisttraining_tpu.models import init_params

    argv = harness.program_flags(cell, 0)
    if cell.chips == 1:
        # what --client_chunk 0 resolves to on a chip that reports a memory
        # limit; the CPU here reports none
        argv += ["--client_chunk", "1"]
        by_site = whole = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices[:cell.chips]), ("clients",))
        by_site = NamedSharding(mesh, PartitionSpec("clients"))
        whole = NamedSharding(mesh, PartitionSpec())
    args = parse_args(argv)
    c, n, m = (cell.cohort[k] for k in ("n_sites", "train_per_site",
                                  "test_per_site"))
    sample = phase.phased_shape(cell.config["volume"],
                                cell.config["stem"]["kernel"],
                                cell.config["stem"]["pad"])

    def shape(dims, dtype, sharding):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    data = FederatedData(
        x_train=shape((c, n) + sample, jnp.bfloat16, by_site),
        y_train=shape((c, n), jnp.int32, by_site),
        n_train=np.full((c,), n, np.int32),
        x_test=shape((c, m) + sample, jnp.bfloat16, by_site),
        y_test=shape((c, m), jnp.int32, by_site),
        n_test=np.full((c,), m, np.int32), class_num=2)
    algo, _ = runner.build_algorithm(args, args.algo, data=data)
    params = jax.eval_shape(lambda: init_params(
        algo.model, jax.random.PRNGKey(0), algo.init_sample_shape))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = SalientGradsState(
        global_params=jax.tree_util.tree_map(
            lambda a: shape(a.shape, a.dtype, whole), params),
        mask=jax.tree_util.tree_map(
            lambda a: shape(a.shape, a.dtype, whole), params),
        personal_params=jax.tree_util.tree_map(
            lambda a: shape((c,) + a.shape, a.dtype, by_site), params),
        rng=shape(key.shape, key.dtype, whole))
    round_args = (state, shape((algo.clients_per_round,), jnp.int32, whole),
                  shape((), jnp.float32, whole), data.x_train, data.y_train,
                  shape((c,), jnp.int32, by_site))
    test_gib = c * m * np.prod(sample) * 2 / cell.chips / 2 ** 30
    return algo, round_args, test_gib


@pytest.mark.slow
@pytest.mark.parametrize("name", CELLS)
def test_round_program_fits_the_chip(topo, name):
    cell = manifest.load_cell(MANIFEST, name)
    algo, round_args, test_gib = abstract_cell(cell, topo)
    compiled = algo._round_jit.lower(*round_args).compile()
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    print(f"{name}: round program {gib:.2f} GiB + {test_gib:.2f} GiB of test "
          f"volumes (logical) per chip, compiled for a described v5e")
    assert gib + test_gib < HBM_GIB
    hlo = compiled.as_text()
    scopes = set(reduce_trace.hlo_op_names(hlo).values())
    for scope in ("local_train", "aggregate"):
        assert any(f"/{scope}/" in f"/{s}/" for s in scopes), scope
    if cell.chips > 1:
        assert " all-reduce(" in hlo or " all-reduce-start(" in hlo
