"""The round program's ``jax.named_scope`` labels, as the benchmark reads them.

``benchmarks/metrics/*.json`` find the round's phases, the stem's layers and
the pass BY NAME in the ``op_name`` metadata of the compiled round
(``benchmarks/lib/scopes.py``; the list of scopes is the comment above
``named_scope("local_train")`` in ``algorithms/base.py``). A scope that is
renamed, dropped or no longer reaches the compiled program empties a metric
in silence on the chip; here it fails on the CPU, in seconds, at 8^3.

The persistent compile cache's key leaves HLO metadata out
(``jax_compilation_cache_include_metadata_in_key``, default off), so an
executable cached before a scope was edited comes back with the OLD names.
These tests read names out of compiled programs, so they put the metadata
into the key while they run; a traced benchmark run after such an edit needs
an emptied cache directory instead.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import scopes
from neuroimagedisttraining_tpu.algorithms import FedAvg, SalientGrads
from neuroimagedisttraining_tpu.core.state import HyperParams
from neuroimagedisttraining_tpu.core.trainer import make_client_update
from neuroimagedisttraining_tpu.data import make_synthetic_federated
from neuroimagedisttraining_tpu.models import (
    create_model,
    init_params,
    make_apply_fn,
)
from neuroimagedisttraining_tpu.ops.s2d import phased_sample_shape

ALGOS = {"salientgrads": SalientGrads, "fedavg": FedAvg}
ROUND_SCOPES = ("batch_gather", "optimizer", "personal_update",
                "local_train", "aggregate")


@contextlib.contextmanager
def metadata_in_cache_key():
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)


@pytest.fixture(autouse=True)
def fresh_names():
    with metadata_in_cache_key():
        yield


def op_names(text):
    """Every ``op_name`` of a compiled module's text or of a lowered
    module's text with debug info (there a ``loc("...")``)."""
    return set(re.findall(r'op_name="([^"]*)"', text)) or set(
        re.findall(r'loc\("([^"]*)"', text))


def count(names, scope=None, direction=None):
    return sum((scope is None or scopes.under(n, scope))
               and (direction is None or scopes.direction(n) == direction)
               for n in names)


def compiled_round_names(algo_name, frac, client_chunk=None, **algo_kw):
    """The ``op_name``s of the compiled tiny round (4 sites, 8^3)."""
    data = make_synthetic_federated(
        n_clients=4, samples_per_client=8, test_per_client=4,
        sample_shape=(8, 8, 8, 1))
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=2,
                     batch_size=4)
    algo = ALGOS[algo_name](
        create_model("small3dcnn", num_classes=1), data, hp,
        loss_type="bce", frac=frac, seed=3, client_chunk=client_chunk,
        **algo_kw)
    state = algo.init_state(jax.random.PRNGKey(3))
    extra = (data.x_test, data.y_test, data.n_test) \
        if getattr(algo, "eval_cache", False) else ()
    sel = jnp.arange(algo.clients_per_round, dtype=jnp.int32)
    compiled = algo._round_jit.lower(
        state, sel, jnp.asarray(0, jnp.float32), data.x_train, data.y_train,
        data.n_train, *extra).compile()
    return op_names(compiled.as_text())


@pytest.mark.parametrize("client_chunk", [None, 1],
                         ids=["vmapped", "client_chunk1"])
@pytest.mark.parametrize("algo_name", sorted(ALGOS))
def test_compiled_round_holds_every_round_scope(algo_name, client_chunk):
    names = compiled_round_names(algo_name, 0.5, client_chunk)
    for scope in ROUND_SCOPES + ("cohort_gather",):
        assert count(names, scope) > 0, (scope, "no instruction under it")
    assert count(names, direction="fwd") > 0
    assert count(names, direction="bwd") > 0
    # the step's scopes sit inside local_train, and a gather is not a pass
    assert count(names, "batch_gather", "fwd") == 0
    assert count(names, "batch_gather", "bwd") == 0


@pytest.mark.parametrize("algo_name", sorted(ALGOS))
def test_full_participation_has_no_cohort_gather(algo_name):
    names = compiled_round_names(algo_name, 1.0, None)
    assert count(names, "cohort_gather") == 0
    for scope in ROUND_SCOPES:
        assert count(names, scope) > 0, scope


# (dense volume, stem kernel, stem pad): about the smallest volumes the two
# models' pools admit (AlexNet3D: 69^3; ResNet_l3: as tests/test_s2d.py)
STEM_VOLUMES = {"3dcnn_s2d": ((69, 69, 69), 5, 0),
                "3dresnet_s2d": ((29, 33, 29), 3, 3)}


def lowered_step_names(model_name, pool_first=True, platform="cpu"):
    """The ``op_name``s of a full model's training step lowered for
    ``platform``. Lowering with debug info is enough (no full-size compile):
    the names are set at trace time."""
    model = create_model(model_name, num_classes=1, pool_first=pool_first)
    shape = (2,) + phased_sample_shape(*STEM_VOLUMES[model_name])
    params = init_params(model, jax.random.PRNGKey(0), shape[1:])
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                     batch_size=2)
    update = make_client_update(make_apply_fn(model), "bce", hp)
    lowered = jax.jit(update).trace(
        params, params, params, jax.random.PRNGKey(1),
        jnp.zeros(shape, jnp.float32), jnp.zeros(shape[:1], jnp.int32),
        jnp.asarray(2, jnp.int32), jnp.asarray(0, jnp.float32), params
    ).lower(lowering_platforms=(platform,))
    return op_names(lowered.as_text(debug_info=True))


@pytest.mark.parametrize("pool_first", [True, False],
                         ids=["pool_first", "textbook_order"])
@pytest.mark.parametrize("model_name", sorted(STEM_VOLUMES))
def test_lowered_step_holds_stem_scopes_in_both_passes(model_name,
                                                       pool_first):
    names = lowered_step_names(model_name, pool_first)
    for layer in ("conv", "norm", "pool"):
        for direction in ("fwd", "bwd"):
            assert count(names, f"stem/{layer}", direction) > 0, (
                layer, direction)
    for scope in ("batch_gather", "optimizer"):
        assert count(names, scope) > 0, scope


@pytest.mark.parametrize("platform,backward", [
    ("cpu", "select_and_scatter_add"), ("tpu", "pallas_call")])
def test_alexnet_pool_keeps_its_names_whatever_its_backward(platform,
                                                            backward):
    """``stem_pool_{fwd,bwd}_ms_per_round`` read ``stem/pool`` under
    ``jvp(...)`` and ``transpose(jvp(...))``. The pool's backward is a
    ``custom_vjp`` over one primitive with a lowering per target
    (ops/pool_vjp.py): each must leave its ops under those names."""
    names = lowered_step_names("3dcnn_s2d", platform=platform)
    pool = {d: {n.rsplit("/", 1)[-1] for n in names
                if scopes.under(n, "stem/pool") and scopes.direction(n) == d}
            for d in ("fwd", "bwd")}
    assert "reduce_window_max" in pool["fwd"], pool
    assert backward in pool["bwd"], pool


DECODER_SCOPES = ("embed", "attention", "attention/full", "attention/window",
                  "router", "experts", "shared_expert", "dense_mlp",
                  "lm_head")


def test_folding_round_of_the_decoder_holds_its_scopes():
    """The tiny decoder's compiled round (the body that folds each client
    into the sum): the round's scopes, the decoder's in both passes, and
    every scope the new cell's metric files ask for by name."""
    import glob
    import json
    import os

    from neuroimagedisttraining_tpu.data.tokens import make_token_shards
    from neuroimagedisttraining_tpu.models import decoder

    share = decoder.Share(layers=5, expert_shards=4, tensor_shards=2)
    data = make_token_shards(0, n_clients=4, vocab=32, sequence_length=32,
                             train_per_client=2)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=2,
                     batch_size=1)
    algo = FedAvg(decoder.decoder("laguna_tiny", share), data, hp,
                  loss_type="token_ce", frac=0.5, seed=3, client_chunk=1,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(3))
    compiled = algo._round_jit.lower(
        state, jnp.arange(2, dtype=jnp.int32), jnp.asarray(0, jnp.float32),
        data.x_train, data.y_train, data.n_train).compile()
    names = op_names(compiled.as_text())
    for scope in ("cohort_gather", "local_train", "batch_gather",
                  "optimizer", "aggregate"):
        assert count(names, scope) > 0, (scope, "no instruction under it")
    assert count(names, "personal_update") == 0
    for scope in DECODER_SCOPES:
        assert count(names, scope, "fwd") > 0, (scope, "forward")
        assert count(names, scope, "bwd") > 0, (scope, "backward")
    # the scores and the band sit inside attention, the CE beside the head
    assert count(names, "full") == count(names, "attention/full")
    assert count(names, "window") == count(names, "attention/window")
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "metrics", "*.json")
    asked = set()
    for path in glob.glob(metrics):
        with open(path) as f:
            args = json.load(f).get("args", {})
        if args.get("scope", "").split("/")[0] in DECODER_SCOPES:
            asked.add(args["scope"])
            layers = args.get("layers", {})
            if isinstance(layers, dict):    # row -> the scope it is timed under
                asked.update(layers.values())
    # a selecting layer's scopes and cross attention's are other models'
    # (their own tests hold them)
    others = set(SELECTING_SCOPES) | {"attention/cross"}
    assert asked == {"attention", "attention/full", "attention/window",
                     "router", "experts", "lm_head", "dense_mlp",
                     "shared_expert", "embed"} | others
    for scope in asked - others:
        assert count(names, scope) > 0, scope


SELECTING_SCOPES = ("attention/indexer", "attention/select",
                    "attention/selected")


def test_folding_round_of_the_selecting_decoder_holds_its_scopes():
    """The tiny selecting decoder's compiled round at a length over
    ``topk``: the indexer and the choice of the keys in the forward pass and
    its recomputation (nothing of them is differentiated), the attention
    over the selection in both passes, and none of the scopes of layers
    this model has none of."""
    from neuroimagedisttraining_tpu.data.tokens import make_token_shards
    from neuroimagedisttraining_tpu.models import decoder

    share = decoder.Share(4, 4, 2, 0, 4)
    data = make_token_shards(0, n_clients=4, vocab=16, sequence_length=32,
                             train_per_client=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                     batch_size=1)
    algo = FedAvg(decoder.decoder("keye_tiny", share), data, hp,
                  loss_type="token_ce", frac=0.5, seed=3, client_chunk=1,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(3))
    compiled = algo._round_jit.lower(
        state, jnp.arange(2, dtype=jnp.int32), jnp.asarray(0, jnp.float32),
        data.x_train, data.y_train, data.n_train).compile()
    names = op_names(compiled.as_text())
    for scope in ("local_train", "aggregate", "embed", "attention", "router",
                  "experts", "lm_head") + SELECTING_SCOPES:
        assert count(names, scope) > 0, scope
    for scope in ("attention", "attention/selected", "router", "experts"):
        assert count(names, scope, "fwd") > 0, (scope, "forward")
        assert count(names, scope, "bwd") > 0, (scope, "backward")
    for scope in ("attention/full", "attention/window", "shared_expert",
                  "dense_mlp"):
        assert count(names, scope) == 0, scope
    assert count(names, "indexer") == count(names, "attention/indexer")
    assert count(names, "select") == count(names, "attention/select")


def test_folding_round_of_the_short_conv_decoder_holds_its_scopes():
    """The tiny decoder with conv layers, compiled round: ``short_conv``
    around the whole operator in both passes (the scope
    ``short_conv_ms_per_round`` and ``short_conv_roofline`` ask for by
    name), the one attention layer's ``attention/full``, the tied head's
    product under ``lm_head``, and none of the scopes of layers this model
    has none of."""
    import json
    import os

    from neuroimagedisttraining_tpu.data.tokens import make_token_shards
    from neuroimagedisttraining_tpu.models import decoder

    share = decoder.Share(6, 4, 2)
    data = make_token_shards(0, n_clients=4, vocab=32, sequence_length=32,
                             train_per_client=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                     batch_size=1)
    algo = FedAvg(decoder.decoder("lfm2_tiny", share), data, hp,
                  loss_type="token_ce", frac=0.5, seed=3, client_chunk=1,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(3))
    compiled = algo._round_jit.lower(
        state, jnp.arange(2, dtype=jnp.int32), jnp.asarray(0, jnp.float32),
        data.x_train, data.y_train, data.n_train).compile()
    names = op_names(compiled.as_text())
    for scope in ("local_train", "aggregate"):
        assert count(names, scope) > 0, scope
    for scope in ("embed", "short_conv", "attention", "attention/full",
                  "router", "experts", "dense_mlp", "lm_head"):
        assert count(names, scope, "fwd") > 0, (scope, "forward")
        assert count(names, scope, "bwd") > 0, (scope, "backward")
    # the head's product is the embedding's transpose: still under lm_head
    assert any(n.rsplit("/", 1)[-1] == "dot_general" for n in names
               if scopes.under(n, "lm_head"))
    for scope in ("attention/window", "attention/indexer", "shared_expert"):
        assert count(names, scope) == 0, scope
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "metrics")
    for name in ("short_conv_ms_per_round", "short_conv_roofline"):
        with open(os.path.join(metrics, name + ".json")) as f:
            args = json.load(f)["args"]
        assert args["scope"] == "short_conv"
        assert set(args.get("layers", {}).values()) <= {"short_conv"}


SSM_SCOPES = ("ssm", "ssm/conv", "ssm/scan", "ssm/norm")


def test_folding_round_of_the_hybrid_decoder_holds_its_scopes():
    """The tiny decoder with a state-space mixer beside attention in every
    block, compiled round: ``ssm`` around the whole mixer with ``conv``,
    ``scan`` and ``norm`` inside it in both passes (the scopes the four
    ``ssm_*`` metric files ask for by name), the attention's
    ``attention/full`` beside it, the dense MLP of a share's columns, and
    none of the scopes of layers this model has none of."""
    import json
    import os

    from neuroimagedisttraining_tpu.data.tokens import make_token_shards
    from neuroimagedisttraining_tpu.models import decoder

    share = decoder.Share(4, 1, 2, 0, 4, 2, 2)
    data = make_token_shards(0, n_clients=4, vocab=16, sequence_length=32,
                             train_per_client=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                     batch_size=1)
    algo = FedAvg(decoder.decoder("falcon_h1_tiny", share), data, hp,
                  loss_type="token_ce", frac=0.5, seed=3, client_chunk=1,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(3))
    compiled = algo._round_jit.lower(
        state, jnp.arange(2, dtype=jnp.int32), jnp.asarray(0, jnp.float32),
        data.x_train, data.y_train, data.n_train).compile()
    names = op_names(compiled.as_text())
    for scope in ("local_train", "aggregate"):
        assert count(names, scope) > 0, scope
    for scope in ("embed", "attention", "attention/full", "dense_mlp",
                  "lm_head") + SSM_SCOPES:
        assert count(names, scope, "fwd") > 0, (scope, "forward")
        assert count(names, scope, "bwd") > 0, (scope, "backward")
    # the three inner scopes sit inside the mixer's, and the carry's loop
    # over the chunks inside the scan's
    for inner in ("conv", "scan", "norm"):
        assert count(names, inner) == count(names, "ssm/" + inner) > 0
    assert any("while" in n for n in names if scopes.under(n, "ssm/scan"))
    for scope in ("attention/window", "attention/indexer", "short_conv",
                  "router", "experts", "shared_expert"):
        assert count(names, scope) == 0, scope
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "metrics")
    asked = set()
    for name in ("ssm_ms_per_round", "ssm_scan_ms_per_round", "ssm_roofline",
                 "ssm_scan_roofline"):
        with open(os.path.join(metrics, name + ".json")) as f:
            args = json.load(f)["args"]
        asked |= {args["scope"]} | set(args.get("layers", {}).values())
    assert asked == set(SSM_SCOPES)
    # and the table of scopes names them in its one place
    import inspect

    from neuroimagedisttraining_tpu.algorithms import base
    assert "ssm (a state-space mixer)" in inspect.getsource(base)


LINEAR_SCOPES = ("linear_attention", "linear_attention/conv",
                 "linear_attention/scan", "linear_attention/norm")


@pytest.fixture
def delta_rule_kernels_interpreted():
    """ops/delta_rule.py's TPU lowering rule, its kernels interpreted, in
    the CPU's place."""
    from neuroimagedisttraining_tpu.ops import delta_rule, lowering

    with lowering.interpreted(delta_rule._forward_p, delta_rule._backward_p):
        yield


@pytest.mark.parametrize("spelling", ["xla", "kernel"])
def test_folding_round_of_the_linear_attention_decoder_holds_its_scopes(
        spelling, request):
    """The tiny decoder with Gated DeltaNet layers among a full one,
    compiled round: ``linear_attention`` around the whole operator with
    ``conv``, ``scan`` and ``norm`` inside it in both passes (the scopes the
    ``linear_*`` metric files ask for by name), the carry's loop over the
    chunks inside the scan's, the full layer's ``attention/full``, the
    router, the held experts and the shared expert beside them, and none of
    the scopes of layers this model has none of. The delta rule is
    ops/delta_rule.py's two primitives: at the tiny widths XLA's scan; with
    the linear heads 128 wide and chunks of 64 its kernels, here
    interpreted (the TPU's rule in the CPU's place), whose jitted functions
    stand under ``linear_attention/scan`` in both passes (the interpreter
    inlines the ``pallas_call``: tests/test_chip_kernels.py reads it there
    out of a compile for the chip), each pass counted in
    ``linear_scan_lowerings``."""
    import json
    import os

    from neuroimagedisttraining_tpu.data.tokens import make_token_shards
    from neuroimagedisttraining_tpu.models import decoder
    from neuroimagedisttraining_tpu.obs import metrics as obs_metrics

    share = decoder.Share(4, 4, 2, 0, 4)
    cfg, seq = decoder.held_config("qwen3_next_tiny", share), 32
    if spelling == "kernel":
        request.getfixturevalue("delta_rule_kernels_interpreted")
        cfg, seq = dict(cfg, linear_key_head_dim=128,
                        linear_value_head_dim=128, linear_chunk_size=64), 128
    data = make_token_shards(0, n_clients=4, vocab=16, sequence_length=seq,
                             train_per_client=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                     batch_size=1)
    algo = FedAvg(decoder.Decoder(decoder._freeze(cfg)), data, hp,
                  loss_type="token_ce", frac=0.5, seed=3, client_chunk=1,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(3))
    before = obs_metrics.set_registry(None)
    try:
        compiled = algo._round_jit.lower(
            state, jnp.arange(2, dtype=jnp.int32),
            jnp.asarray(0, jnp.float32), data.x_train, data.y_train,
            data.n_train).compile()
        counted = obs_metrics.get_registry().snapshot()[
            "linear_scan_lowerings"]["labeled"]
    finally:
        obs_metrics.set_registry(before)
    assert counted == {f"pass={p},spelling={spelling}": 1.0
                       for p in ("forward", "backward")}
    names = op_names(compiled.as_text())
    for scope in ("local_train", "aggregate"):
        assert count(names, scope) > 0, scope
    for scope in ("embed", "attention", "attention/full", "router",
                  "experts", "shared_expert", "lm_head") + LINEAR_SCOPES:
        assert count(names, scope, "fwd") > 0, (scope, "forward")
        assert count(names, scope, "bwd") > 0, (scope, "backward")
    # the three inner scopes sit inside the operator's, and the loops over
    # the chunks inside the scan's
    for inner in ("conv", "scan", "norm"):
        assert count(names, inner) == count(names,
                                            "linear_attention/" + inner) > 0
    assert any("while" in n for n in names
               if scopes.under(n, "linear_attention/scan"))
    for direction, kernel in (("fwd", "jit(delta_rule_forward)"),
                              ("bwd", "jit(delta_rule_backward)")):
        under = [n for n in names if scopes.under(n, "linear_attention/scan")
                 and scopes.direction(n) == direction]
        assert any(kernel in n.split("/") for n in under) == (
            spelling == "kernel"), (direction, sorted(under)[:5])
    for scope in ("attention/window", "attention/indexer", "short_conv",
                  "ssm", "dense_mlp"):
        assert count(names, scope) == 0, scope
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "metrics")
    asked = set()
    for name in ("linear_attention_ms_per_round", "linear_scan_ms_per_round",
                 "linear_scan_roofline"):
        with open(os.path.join(metrics, name + ".json")) as f:
            args = json.load(f)["args"]
        asked |= {args["scope"]} | set(args.get("layers", {}).values())
    assert asked == {"linear_attention", "linear_attention/scan"}
    # and the table of scopes names them in its one place
    import inspect

    from neuroimagedisttraining_tpu.algorithms import base
    assert "linear_attention (a Gated DeltaNet layer)" in inspect.getsource(
        base)


def test_folding_round_of_the_sambay_decoder_holds_its_scopes():
    """The tiny SambaY decoder (Mamba-1 mixers, differential attention in
    window, full and cross form, gated memory units), compiled round:
    ``mamba`` around the whole mixer with ``scan`` inside it, ``gmu``,
    ``attention`` with ``window``, ``full`` and ``cross`` inside it, in both
    passes (the scopes the new metric files ask for by name, and the
    accepted attention metrics read in this model too), the dense MLP, and
    none of the scopes of layers this model has none of; the gauge the
    ``mamba_chunk_carry`` file names is the one the program sets."""
    import json
    import os

    from neuroimagedisttraining_tpu.data.tokens import make_token_shards
    from neuroimagedisttraining_tpu.models import decoder
    from neuroimagedisttraining_tpu.obs import mamba_carry

    share = decoder.Share(layers=10, tensor_shards=2, vocab_shards=4,
                          mlp_shards=8, first_layer=8)
    data = make_token_shards(0, n_clients=4, vocab=16, sequence_length=32,
                             train_per_client=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                     batch_size=1)
    algo = FedAvg(decoder.decoder("phi4_flash_tiny", share), data, hp,
                  loss_type="token_ce", frac=0.5, seed=3, client_chunk=1,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(3))
    compiled = algo._round_jit.lower(
        state, jnp.arange(2, dtype=jnp.int32), jnp.asarray(0, jnp.float32),
        data.x_train, data.y_train, data.n_train).compile()
    names = op_names(compiled.as_text())
    for scope in ("local_train", "aggregate"):
        assert count(names, scope) > 0, scope
    new = ("mamba", "mamba/scan", "gmu", "attention/cross")
    for scope in ("embed", "attention", "attention/window", "attention/full",
                  "dense_mlp", "lm_head") + new:
        assert count(names, scope, "fwd") > 0, (scope, "forward")
        assert count(names, scope, "bwd") > 0, (scope, "backward")
    assert count(names, "scan") == count(names, "mamba/scan")
    assert any("while" in n for n in names if scopes.under(n, "mamba/scan"))
    for scope in ("ssm", "linear_attention", "short_conv", "router",
                  "experts", "shared_expert", "attention/indexer"):
        assert count(names, scope) == 0, scope
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "metrics")
    asked = set()
    for name in ("mamba_ms_per_round", "mamba_scan_ms_per_round",
                 "mamba_scan_roofline", "gmu_ms_per_round",
                 "attention_cross_ms_per_round"):
        with open(os.path.join(metrics, name + ".json")) as f:
            args = json.load(f)["args"]
        asked |= {args["scope"]} | set(args.get("layers", {}).values())
    assert asked == set(new)
    with open(os.path.join(metrics, "mamba_chunk_carry.json")) as f:
        assert json.load(f)["args"]["gauge"] == mamba_carry.GAUGE
    # and the table of scopes names them in its one place
    import inspect

    from neuroimagedisttraining_tpu.algorithms import base
    source = inspect.getsource(base)
    assert "mamba (a Mamba-1 mixer) with scan" in source
    assert "gmu (a gated memory unit)" in source


@pytest.mark.parametrize("widest,spelling,widths", [
    (48, "slabs", [48, 16]), (None, "take", [64])])
def test_the_embeddings_backward_keeps_its_scope_whatever_its_spelling(
        widest, spelling, widths, monkeypatch):
    """``embed_ms_per_round`` reads the scope ``embed`` in both passes. The
    token lookup has a backward of its own wherever the table is wider than
    XLA's scatter takes whole (ops/embedding.py: column slabs behind a
    ``custom_vjp``; here the tiny hybrid decoder's 64 columns against a
    widest slab of 48): in the folding round's lowered program the slabs'
    scatter-adds and their join still stand under ``embed``, going
    backward, no scatter there is wider than its slab, and the trace counts
    one forward and one backward under the spelling taken. With the rule as
    it stands 64 columns are one slab, the lookup is ``jnp.take`` and the
    one scatter is its own gradient's."""
    from neuroimagedisttraining_tpu.data.tokens import make_token_shards
    from neuroimagedisttraining_tpu.models import decoder
    from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
    from neuroimagedisttraining_tpu.ops import embedding

    if widest:
        monkeypatch.setattr(embedding, "_WIDEST", widest)
    share = decoder.Share(4, 1, 2, 0, 4, 2, 2)
    data = make_token_shards(0, n_clients=4, vocab=16, sequence_length=32,
                             train_per_client=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                     batch_size=1)
    algo = FedAvg(decoder.decoder("falcon_h1_tiny", share), data, hp,
                  loss_type="token_ce", frac=0.5, seed=3, client_chunk=1,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(3))
    before = obs_metrics.set_registry(None)
    try:
        lowered = algo._round_jit.trace(
            state, jnp.arange(2, dtype=jnp.int32),
            jnp.asarray(0, jnp.float32), data.x_train, data.y_train,
            data.n_train).lower()
        counted = obs_metrics.get_registry().snapshot()[
            "embed_lowerings"]["labeled"]
    finally:
        obs_metrics.set_registry(before)
    assert counted == {
        f"pass={p},spelling={spelling}": 1.0
        for p in (("forward", "backward") if widest else ("forward",))}
    text = lowered.as_text(debug_info=True)
    # the only scatters of rows in this model are the embedding's gradient
    # (the loss's picks one number a token): one a slab, as wide as its slab
    rows = [int(m.group(1)) for _, kind in scatters(text)
            for m in [re.fullmatch(r"tensor<1x32x(\d+)xf32>", kind)]
            if m and int(m.group(1)) > 1]
    assert rows == widths, scatters(text)
    # they stand in ``jnp.take``'s own jitted transpose, called under the
    # scope: the backward's name stack survives the ``custom_vjp``
    names = op_names(text)
    for direction in ("fwd", "bwd"):
        assert count(names, "embed", direction) > 0, direction
    backward = {n.split("embed/")[-1] for n in names
                if scopes.under(n, "embed") and scopes.direction(n) == "bwd"}
    assert "jit(_take)" in backward
    assert {"slice", "concatenate"} <= backward if widest else not (
        {"slice", "concatenate"} & backward), backward


@pytest.mark.parametrize("platform,spelling,products", [
    ("cpu", "xla", ("dot_general", "dot_general")),
    ("tpu", "kernel", ("jit(attention_forward)", "jit(attention_backward)"))])
def test_selected_attention_keeps_its_names_whatever_its_lowering(
        platform, spelling, products):
    """``attention_selected_ms_per_round`` and ``selected_attention_roofline``
    read ``attention/selected`` in both passes. The masked product is a
    ``custom_vjp`` over two primitives with a lowering per target
    (ops/masked_attention.py): in the folding round of a selecting decoder
    whose heads and sequence the kernels take (128 wide, 512 tokens), each
    must leave its products under that name, forward and backward, and
    count itself in ``attention_lowerings``. The kernels are one jitted
    function a pass, shared by the layers: the lowered text has the call
    under the scope, and the compiler puts the callee's ``pallas_call``
    behind it (tests/test_chip_kernels.py reads that out of a compile)."""
    from neuroimagedisttraining_tpu.data.tokens import make_token_shards
    from neuroimagedisttraining_tpu.models import decoder
    from neuroimagedisttraining_tpu.obs import metrics as obs_metrics

    cfg = decoder.held_config("keye_tiny", decoder.Share(2, 4, 2, 0, 4))
    cfg = dict(cfg, head_dim=128, rope_scaling=dict(
        cfg["rope_scaling"], mrope_section=[16, 24, 24]))
    data = make_token_shards(0, n_clients=4, vocab=16, sequence_length=512,
                             train_per_client=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                     batch_size=1)
    algo = FedAvg(decoder.Decoder(decoder._freeze(cfg)), data, hp,
                  loss_type="token_ce", frac=0.5, seed=3, client_chunk=1,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(3))
    before = obs_metrics.set_registry(None)
    try:
        lowered = algo._round_jit.trace(
            state, jnp.arange(2, dtype=jnp.int32),
            jnp.asarray(0, jnp.float32), data.x_train, data.y_train,
            data.n_train).lower(lowering_platforms=(platform,))
        counted = obs_metrics.get_registry().snapshot()[
            "attention_lowerings"]["labeled"]
    finally:
        obs_metrics.set_registry(before)
    assert counted == {f"kind=selected,pass={p},spelling={spelling}": 1.0
                       for p in ("forward", "backward")}
    names = op_names(lowered.as_text(debug_info=True))
    for direction, product in zip(("fwd", "bwd"), products):
        under = {n.rsplit("/", 1)[-1] for n in names
                 if scopes.under(n, "attention/selected")
                 and scopes.direction(n) == direction}
        assert product in under, (direction, under)
    for scope in ("attention/indexer", "attention/select"):
        assert count(names, scope) > 0, scope
        assert count(names, scope, "bwd") == 0, scope


@pytest.mark.parametrize("platform,spelling,products", [
    ("cpu", "xla", ("dot_general", "dot_general")),
    ("tpu", "kernel", ("jit(attention_forward)", "jit(attention_backward)"))])
def test_ruled_attention_keeps_its_names_whatever_its_lowering(
        platform, spelling, products):
    """``attention_full_ms_per_round``, ``attention_window_ms_per_round`` and
    ``attention_roofline`` read ``attention/full`` and ``attention/window``
    in both passes. Both layer kinds go through ops/masked_attention.py's
    two primitives under a rule of the positions: in the folding round of a
    small Laguna share whose heads and sequence the kernels take (128 wide;
    1024 tokens, a window of 256), each lowering must leave its products
    under those names, forward and backward, and count itself per kind and
    pass. On the TPU the block's remat keeps the kernels' output and
    log-sum-exp by name, so the whole round holds one forward call a layer
    (two full, three sliding) and no second one going backward."""
    from neuroimagedisttraining_tpu.data.tokens import make_token_shards
    from neuroimagedisttraining_tpu.models import decoder
    from neuroimagedisttraining_tpu.obs import metrics as obs_metrics

    cfg = decoder.held_config("laguna_tiny", decoder.Share(5, 4, 2))
    cfg = dict(cfg, head_dim=128, sliding_window=256)
    data = make_token_shards(0, n_clients=4, vocab=32, sequence_length=1024,
                             train_per_client=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                     batch_size=1)
    algo = FedAvg(decoder.Decoder(decoder._freeze(cfg)), data, hp,
                  loss_type="token_ce", frac=0.5, seed=3, client_chunk=1,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(3))
    before = obs_metrics.set_registry(None)
    try:
        lowered = algo._round_jit.trace(
            state, jnp.arange(2, dtype=jnp.int32),
            jnp.asarray(0, jnp.float32), data.x_train, data.y_train,
            data.n_train).lower(lowering_platforms=(platform,))
        counted = obs_metrics.get_registry().snapshot()[
            "attention_lowerings"]["labeled"]
    finally:
        obs_metrics.set_registry(before)
    assert counted == {f"kind={k},pass={p},spelling={spelling}": 1.0
                       for k in ("full", "window")
                       for p in ("forward", "backward")}
    text = lowered.as_text(debug_info=True)
    names = op_names(text)
    for scope in ("attention/full", "attention/window"):
        for direction, product in zip(("fwd", "bwd"), products):
            under = {n.rsplit("/", 1)[-1] for n in names
                     if scopes.under(n, scope)
                     and scopes.direction(n) == direction}
            assert product in under, (scope, direction, under)
    for kernels in ("attention_forward", "attention_backward"):
        calls = len(re.findall(rf"call @{kernels}(_\d+)?\(", text))
        assert calls == (5 if spelling == "kernel" else 0), kernels


def scatters(text):
    """``(op_name, update operand's type)`` of every scatter of a lowered
    module's text with debug info."""
    named = dict(re.findall(r'(#loc\d+) = loc\("([^"]*)"', text))
    return [(named.get(loc, ""), types.split(", ")[-1])
            for types, loc in re.findall(
                r'"stablehlo\.scatter"\(.*?\}\) : \(([^)]*)\) -> [^\n]*?'
                r'loc\((#loc\d+)\)', text, flags=re.S)]


@pytest.mark.parametrize("platform,hidden,spelling,products", [
    ("cpu", 128, "xla", ("ragged_dot_general", "ragged_dot_general")),
    ("tpu", 128, "kernel", ("jit(experts_forward)", "jit(experts_backward)")),
    ("cpu", 32, "xla", ("ragged_dot_general", "ragged_dot_general")),
    ("tpu", 32, "xla", ("ragged_dot_general", "ragged_dot_general"))])
def test_the_expert_layer_scatters_no_rows_whatever_its_lowering(
        platform, hidden, spelling, products, monkeypatch):
    """``experts_ms_per_round`` and ``experts_roofline`` read the scope
    ``experts`` in both passes. The held experts' chunk is laid out in
    tile-aligned groups and dispatched and combined by each slot's row
    (models/decoder.routed_part) wherever the row tile fits the chunk (here
    a tile of 128, so that 1024 tokens will do): in the folding round of a
    small Laguna share the lowered program holds no scatter of rows of
    ``hidden`` numbers
    under that scope (what is left there places integers: where each slot
    stands in the sorted order), and the grouped SwiGLU is
    ops/grouped_mlp.py's two primitives with a lowering per target: at
    lane-aligned widths and 1024 tokens (a chunk of 4096 slots, row tiles of
    128) the kernels on a TPU, ``ragged_dot`` everywhere else; each must
    leave its products under the scope, forward and backward, and count
    itself per product and pass."""
    from neuroimagedisttraining_tpu.data.tokens import make_token_shards
    from neuroimagedisttraining_tpu.models import decoder
    from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
    from neuroimagedisttraining_tpu.ops import grouped_mlp

    monkeypatch.setattr(grouped_mlp, "_ROW_TILE", 128)
    cfg = decoder.held_config("laguna_tiny", decoder.Share(5, 4, 2))
    cfg = dict(cfg, hidden_size=hidden, moe_intermediate_size=hidden)
    data = make_token_shards(0, n_clients=4, vocab=32, sequence_length=1024,
                             train_per_client=1)
    hp = HyperParams(lr=0.05, local_epochs=1, steps_per_epoch=1,
                     batch_size=1)
    algo = FedAvg(decoder.Decoder(decoder._freeze(cfg)), data, hp,
                  loss_type="token_ce", frac=0.5, seed=3, client_chunk=1,
                  track_personal=False)
    state = algo.init_state(jax.random.PRNGKey(3))
    before = obs_metrics.set_registry(None)
    try:
        lowered = algo._round_jit.trace(
            state, jnp.arange(2, dtype=jnp.int32),
            jnp.asarray(0, jnp.float32), data.x_train, data.y_train,
            data.n_train).lower(lowering_platforms=(platform,))
        counted = obs_metrics.get_registry().snapshot()[
            "expert_lowerings"]["labeled"]
    finally:
        obs_metrics.set_registry(before)
    assert counted == {
        f"pass={p},product={product},spelling={spelling}": 1.0
        for p in ("forward", "backward")
        for product in grouped_mlp.PRODUCTS[p]}
    text = lowered.as_text(debug_info=True)
    found = scatters(text)
    assert len(found) >= 2      # the embedding's gradient, the slots' places
    under = [(name, kind) for name, kind in found
             if scopes.under(name, "experts")]
    assert under and all(
        re.fullmatch(r"tensor<\d+xi32>", kind) for _, kind in under), under
    names = op_names(text)
    for direction, product in zip(("fwd", "bwd"), products):
        here = {n.rsplit("/", 1)[-1] for n in names
                if scopes.under(n, "experts")
                and scopes.direction(n) == direction}
        assert product in here, (direction, here)
    for kernels in ("experts_forward", "experts_backward"):
        calls = len(re.findall(rf"call @{kernels}(_\d+)?\(", text))
        assert (calls > 0) == (spelling == "kernel"), (kernels, calls)
