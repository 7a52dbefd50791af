"""The clients-mesh round trains each chip's sites under ``shard_map``
(ISSUE 29).

On a ``clients`` mesh whose devices divide the selected clients, the local
training of ``_train_selected_weighted`` runs inside ``jax.shard_map`` over
``clients`` (``base.py:_train_clients``): each chip lowers the training of
its own sites as one device's program, so the stem pool's Pallas backward
(``ops/pool_vjp.py``) engages there as it does on one chip. Everything around
it stays GSPMD's. Pinned here, on the 8 virtual CPU devices at 8^3:

* the mesh round holds a manual region, every instruction of ``local_train``
  lies inside it, and the aggregate's all-reduce lies outside;
* inside it a chip that reports a memory limit maps its sites one at a time
  (``runner._auto_client_chunk``'s rule for one device holding several
  clients), the CPU keeps them vmapped;
* either form computes the parent's round (the one vmapped program that
  GSPMD partitions, which stays reachable as ``_vmap_clients``);
* a ``space`` axis of 2 and a client count the devices do not divide compile
  that parent's program;
* a round on one device lowers to the parent's text;
* lowered for the TPU, the AlexNet3D round on a mesh holds the kernel;
* lowered for the TPU on one device (ISSUE 32), the ResNet_l3 round holds
  the overlapping-window kernel under ``stem/pool`` and no
  ``select_and_scatter``, the AlexNet3D round is ISSUE 32's parent's but for
  source locations, and ``pool_bwd_lowerings`` counted both choices.
"""
import base64
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.algorithms import FedAvg, SalientGrads
from neuroimagedisttraining_tpu.algorithms import base
from neuroimagedisttraining_tpu.algorithms.base import FedAlgorithm
from neuroimagedisttraining_tpu.core.state import HyperParams
from neuroimagedisttraining_tpu.data import make_synthetic_federated
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.ops.s2d import phased_sample_shape
from neuroimagedisttraining_tpu.parallel import make_mesh
from neuroimagedisttraining_tpu.parallel.mesh import shard_federated_hybrid

ALGOS = {"salientgrads": SalientGrads, "fedavg": FedAvg}
MANUAL = "sdy.manual_computation"


def build(algo_name, clients=8, frac=1.0, devices=4, space=1,
          model="small3dcnn", sample_shape=(8, 8, 8, 1), client_chunk=None):
    data = make_synthetic_federated(
        n_clients=clients, samples_per_client=8, test_per_client=4,
        sample_shape=sample_shape)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=2,
                     batch_size=4)
    algo = ALGOS[algo_name](
        create_model(model, num_classes=1), data, hp, loss_type="bce",
        frac=frac, seed=3, client_chunk=client_chunk)
    if devices * space > 1:
        algo.data = shard_federated_hybrid(
            algo.data, make_mesh(devices, space))
    return algo


def lowered_round(algo, state=None, platform=None):
    if state is None:
        state = algo.init_state(jax.random.PRNGKey(3))
    d = algo.data
    sel = jnp.arange(algo.clients_per_round, dtype=jnp.int32)
    traced = algo._round_jit.trace(
        state, sel, jnp.asarray(0, jnp.float32), d.x_train, d.y_train,
        d.n_train)
    return traced.lower(lowering_platforms=platform and (platform,))


def the_parents(monkeypatch):
    """``_train_clients`` as the parent spelled it at both call sites: the
    one vmapped program, on a mesh or off it."""
    monkeypatch.setattr(
        FedAlgorithm, "_train_clients",
        lambda self, client_update, n_clients: self._vmap_clients(
            client_update, in_axes=(0, 0, 0, 0, 0, 0, 0, None, 0)))


def compiled_text(lowered):
    """The compiled module's text with the names of THIS trace: the compile
    cache's key leaves ``op_name`` out unless told otherwise."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update(flag, before)


@pytest.mark.parametrize("memory_limit,mapped", [(0, False), (2 ** 34, True)],
                         ids=["cpu_reports_no_limit", "a_chip_reports_one"])
@pytest.mark.parametrize("algo_name", sorted(ALGOS))
def test_mesh_round_trains_inside_a_manual_region_and_reduces_outside(
        algo_name, memory_limit, mapped, monkeypatch):
    monkeypatch.setattr(base, "_device_memory_limit", lambda: memory_limit)
    lowered = lowered_round(build(algo_name))
    assert lowered.as_text().count(MANUAL) == 1
    hlo = compiled_text(lowered)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    trained = {n for n in names if "/local_train/" in n + "/"}
    inside = {n for n in trained if "/local_train/shard_map/" in n}
    # what is left outside is the region's own boundary and the keys' slice
    assert len(inside) > 100 and len(trained - inside) <= 2, trained - inside
    # the chip's two sites: one after the other, or side by side
    assert any("/shard_map/while/body/" in n for n in inside) == mapped
    reduces = [re.findall(r'op_name="([^"]*)"', line)[0]
               for line in hlo.splitlines()
               if re.search(r" all-reduce(-start)?\(", line)]
    assert any("/aggregate/" in n for n in reduces), reduces
    assert not any("/local_train/" in n for n in reduces), reduces


@pytest.mark.parametrize("client_chunk", [None, 1],
                         ids=["sites_vmapped", "one_site_at_a_time"])
@pytest.mark.parametrize("algo_name", sorted(ALGOS))
def test_mesh_round_computes_the_parents_round(algo_name, client_chunk,
                                               monkeypatch):
    """New global model, personal stack and loss of one round against the
    parent's GSPMD round on the same mesh and state, for both forms the
    chip's two sites take inside the shard. Not a bit pin, though on this
    host the four cases agree to the bit: a chip's program convolves its own
    sites' batches where the parent's convolves a slice of eight clients',
    and nothing holds XLA to one summation order for the weight gradients.
    The limit is float32 rounding of two SGD steps at lr 0.05 on parameters
    of order 1: 1e-6 absolute, a thousandth of what the round moves them."""
    algo = build(algo_name, client_chunk=client_chunk)
    state = algo.init_state(jax.random.PRNGKey(3))
    ours = lowered_round(algo, state)
    assert MANUAL in ours.as_text()
    got = ours.compile()
    args = (state, jnp.arange(8, dtype=jnp.int32),
            jnp.asarray(0, jnp.float32), algo.data.x_train,
            algo.data.y_train, algo.data.n_train)
    new_state, loss = got(*args)[:2]
    the_parents(monkeypatch)
    lowered = lowered_round(build(algo_name), state)
    assert MANUAL not in lowered.as_text()
    want_state, want_loss = lowered.compile()(*args)[:2]
    for name in ("global_params", "personal_params"):
        for leaf, want in zip(
                jax.tree_util.tree_leaves(getattr(new_state, name)),
                jax.tree_util.tree_leaves(getattr(want_state, name))):
            np.testing.assert_allclose(leaf, want, rtol=0, atol=1e-6)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    moved = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(new_state.global_params),
        jax.tree_util.tree_leaves(state.global_params)))
    assert moved > 1e-3     # the round trained: the tolerance means something


@pytest.mark.parametrize("case", [
    dict(frac=0.75),            # 6 of 8 clients on 4 devices
    dict(devices=2, space=2),   # each volume's depth over a second axis
], ids=["count_not_divisible", "space_axis_of_2"])
@pytest.mark.parametrize("algo_name", sorted(ALGOS))
def test_what_the_devices_do_not_divide_keeps_the_parents_program(
        algo_name, case, monkeypatch):
    lowered = lowered_round(build(algo_name, **case))
    text = lowered.as_text()
    assert MANUAL not in text
    lowered.compile()
    the_parents(monkeypatch)
    assert lowered_round(build(algo_name, **case)).as_text() == text


def sha256_without_locations(lowered):
    text = re.sub(r"loc\([^)]*\)", "", lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("frac", [0.5, 1.0], ids=["frac05", "frac1"])
@pytest.mark.parametrize("algo_name", sorted(ALGOS))
def test_one_device_round_lowers_to_the_parents_text(algo_name, frac,
                                                     monkeypatch):
    ours = sha256_without_locations(
        lowered_round(build(algo_name, frac=frac, devices=1)))
    the_parents(monkeypatch)
    assert ours == sha256_without_locations(
        lowered_round(build(algo_name, frac=frac, devices=1)))


# about the smallest volume AlexNet3D's pools admit (tests/test_round_scopes.py)
ALEXNET = dict(model="3dcnn_s2d", sample_shape=phased_sample_shape(
    (69, 69, 69), 5, 0))


@pytest.mark.parametrize("frac,backward", [
    (1.0, "tpu_custom_call"), (0.75, "select_and_scatter")],
    ids=["sites_divide", "sites_do_not_divide"])
def test_alexnet_round_on_a_mesh_lowered_for_the_tpu(frac, backward):
    """The stem pool's backward in the whole round, lowered for the TPU from
    here: the kernel inside the manual region, XLA's op where GSPMD
    partitions the vmapped clients."""
    algo = build("fedavg", clients=4, frac=frac, devices=2, **ALEXNET)
    shapes = jax.eval_shape(algo.init_state, jax.random.PRNGKey(3))
    text = lowered_round(algo, shapes, platform="tpu").as_text()
    assert (MANUAL in text) == (backward == "tpu_custom_call")
    # the trunk's two overlapping pools keep XLA's op in either round
    assert "select_and_scatter" in text
    assert ("tpu_custom_call" in text) == (backward == "tpu_custom_call")


RESNET = dict(model="3dresnet_s2d", sample_shape=phased_sample_shape(
    (33, 33, 33), 3, 3))
MOSAIC_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')
# sha256 of the AlexNet3D round below at ISSUE 32's parent (c663281)
PARENTS_ALEXNET_ROUND = (
    "935aa0758ae0d43206f2b082adb4d0f34d4896d9e006554ee328fd306b998f99")


def without_source_locations(text):
    """A lowered module's text less its ``loc(...)`` and with every Mosaic
    kernel, serialised in its custom call with the source lines of every
    frame that led to it, printed without them."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def kernel(match):
        context = ir.Context()
        tpu.register_dialect(context)
        context.allow_unregistered_dialects = True  # 'stable_mosaic'
        context.load_all_available_dialects()
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)
    return MOSAIC_BODY.sub(kernel, re.sub(r"loc\([^)]*\)", "", text))


def names_of(text, ref):
    """Every name in the chain of locations that ``ref`` (``#loc12``) of a
    module printed with debug info stands for."""
    defs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    seen, todo, names = set(), [ref], []
    while todo:
        ref = todo.pop()
        if ref not in seen and ref in defs:
            seen.add(ref)
            names += re.findall(r'"([^"]*)"', defs[ref])
            todo += re.findall(r"#loc\d+", defs[ref])
    return names


def one_chip_round_for_the_tpu(**model):
    # a chip that reports a memory limit maps its clients one at a time
    algo = build("salientgrads", clients=4, frac=0.5, devices=1,
                 client_chunk=1, **model)
    shapes = jax.eval_shape(algo.init_state, jax.random.PRNGKey(3))
    return lowered_round(algo, shapes, platform="tpu")


def test_one_chip_rounds_lowered_for_the_tpu_hold_both_pool_kernels():
    """``resnet3d_abcd.protocol``'s and ``alexnet3d_abcd.train``'s rounds at
    CI size (33^3, 69^3), as one chip lowers them."""
    before = obs_metrics.set_registry(None)
    try:
        resnet = one_chip_round_for_the_tpu(**RESNET).as_text(
            debug_info=True)
        alexnet = one_chip_round_for_the_tpu(**ALEXNET).as_text()
        counted = obs_metrics.get_registry().snapshot()[
            "pool_bwd_lowerings"]["labeled"]
    finally:
        obs_metrics.set_registry(before)
    # ResNet_l3: its one pool is the stem's, (3, 2, 1) on the conv output
    assert "select_and_scatter" not in resnet
    assert len(re.findall(r"custom_call @tpu_custom_call", resnet)) == 1
    # the kernel sits in a function of its own (jitted: traced once for all
    # the programs of a process), called from under stem/pool in the backward
    call, = re.findall(r"call @pool_backward\(.*loc\((#loc\d+)\)", resnet)
    named = names_of(resnet, call)
    assert any("transpose(jvp(" in n and "stem/pool" in n for n in named), named
    kernel_fn = resnet[resnet.index("func.func private @pool_backward("):]
    assert "tpu_custom_call" in kernel_fn[:kernel_fn.index("return")]
    # AlexNet3D: PR 26's kernel on the stem, XLA's op on the trunk's pools
    assert alexnet.count("tpu_custom_call") == 1
    assert "select_and_scatter" in alexnet
    assert hashlib.sha256(without_source_locations(alexnet).encode()
                          ).hexdigest() == PARENTS_ALEXNET_ROUND
    assert counted == {"geometry=3_2_1,spelling=kernel": 1.0,
                       "geometry=3_3_0,spelling=kernel": 1.0}


@pytest.mark.parametrize("algo_name", sorted(ALGOS))
def test_the_driver_lowers_the_mesh_round_once(algo_name):
    """``init_state`` leaves a fresh key (FedAvg: the whole model) uncommitted
    on one device and the round returns everything on the mesh: without
    ``place_state`` rounds 0 and 1 are two programs, each lowered, compiled
    or loaded, and holding the kernel's lowering twice on the chip."""
    algo = build(algo_name)
    state = algo.init_state(jax.random.PRNGKey(3))
    assert not state.rng.committed
    state, history = algo.run(3, eval_every=0, state=state, finalize=False)
    assert len(history) == 3 and state.rng.committed
    assert algo._round_jit._cache_size() == 1
    off_mesh = build(algo_name, devices=1)
    fresh = off_mesh.init_state(jax.random.PRNGKey(3))
    assert off_mesh.place_state(fresh) is fresh
