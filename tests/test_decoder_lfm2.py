"""The decoder with gated short convolutions in most of attention's places, a
sigmoid router that chooses by its scores plus a selection bias, and a tied
head (models/decoder.py, ``lfm2_tiny``: the published pattern's first kinds,
2 dense layers, 4 KV heads with 8 query heads of 8, 16 experts top-4, kernel
3) held to its plain reference (benchmarks/reference/lfm2_moe.py) on seeded
weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_moe as ref
from neuroimagedisttraining_tpu.core.losses import PER_EXAMPLE_LOSSES
from neuroimagedisttraining_tpu.models import (
    create_model, decoder, init_params, make_apply_fn)
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.obs.expert_load import (
    record_expert_load, stacked_stats)

TINY = "lfm2_tiny"
SHARE = decoder.Share(layers=6, expert_shards=4, tensor_shards=2)
SEQ = 32


def _scaled(params, scale=8.0):
    """The matrices scaled up so that the gates, attention and the router
    are far from their trivial values; the vectors (norms' weights, the
    selection bias) moved by a tenth."""
    return jax.tree_util.tree_map(
        lambda a: a * scale if a.ndim > 1 else a
        + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape),
        params)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(1.0, float(np.max(np.abs(want))))
    assert float(np.max(np.abs(got - want))) <= tol * scale


def _model_and_batch(seed=1):
    cfg = decoder.held_config(TINY, SHARE)
    model = decoder.decoder(TINY, SHARE)
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (2, SEQ), 0,
                                cfg["vocab_size"])
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], axis=1)
    params = _scaled(model.init(jax.random.PRNGKey(0), tokens)["params"])
    return cfg, model, params, tokens, targets


def _sparse(cfg, first=0, held=None):
    return decoder.SparseMLP(
        cfg["published"]["num_experts"], cfg["num_experts_per_tok"], True, 1,
        first, cfg["published"]["num_experts"] if held is None else held,
        cfg["moe_intermediate_size"], 0, score="sigmoid")


def test_whole_model_logits_loss_and_the_gradient_of_every_leaf():
    cfg, model, params, tokens, targets = _model_and_batch()
    apply_fn = make_apply_fn(model)
    loss = PER_EXAMPLE_LOSSES["token_ce"]

    def system(p):
        logits = apply_fn(p, tokens, train=True, rng=jax.random.PRNGKey(0))
        return jnp.mean(loss(logits, targets)), logits

    def plain(p):
        out = [ref.loss_and_logits(p, tokens[b], targets[b], cfg,
                                   cfg["first_expert"]) for b in range(2)]
        return (out[0][0] + out[1][0]) / 2, (
            jnp.stack([o[1][0] for o in out]),
            jnp.stack([jnp.stack(o[1][1]) for o in out], axis=1))

    (s_loss, s_logits), s_grad = jax.value_and_grad(system, has_aux=True)(
        params)
    (r_loss, (r_logits, r_route)), r_grad = jax.value_and_grad(
        plain, has_aux=True)(ref.from_system(params))
    _close(s_logits, r_logits)
    _close(s_loss, r_loss)
    # the routing is the reference's, expert for expert
    _, sown = apply_fn(params, tokens, train=False, rng=None,
                       mutable=[decoder.EXPERT_STATS])
    stats = stacked_stats(sown)
    np.testing.assert_array_equal(
        np.sort(stats["top_experts"], -1),
        np.sort(np.asarray(r_route).reshape(4, 2 * SEQ, 4), -1))
    s_leaves = jax.tree_util.tree_leaves_with_path(ref.from_system(s_grad))
    r_leaves = jax.tree_util.tree_leaves(r_grad)
    # embedding and final norm; 2 dense conv layers of 8 leaves; a sparse
    # attention layer of 13, 3 sparse conv layers of 10
    assert len(s_leaves) == len(r_leaves) == 2 + 2 * 8 + 13 + 3 * 10
    kinds = set()
    for (path, got), want in zip(s_leaves, r_leaves):
        name = jax.tree_util.keystr(path)
        kinds.add(name.rsplit("['", 1)[-1].rstrip("']"))
        if "expert_bias" in name:   # no gradient reaches it: exactly zero
            assert not np.any(got) and not np.any(want), name
            continue
        err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-12)
        assert err < 1e-4, (name, err)
        assert np.linalg.norm(want) > 0, name
    assert {"embed", "final_norm", "in_proj", "conv", "out_proj", "q_norm",
            "k_norm", "q_proj", "router", "expert_bias", "up_proj"} <= kinds


def test_conv_operator_is_causal():
    """A change to token ``t`` moves no output before ``t``, and moves the
    outputs at ``t``, ``t + 1`` and ``t + 2`` (three taps) and none after."""
    layer = decoder.ShortConv(16, 3)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, SEQ, 24))
    p = _scaled(layer.init(jax.random.PRNGKey(3), x)["params"], 20.0)
    out = layer.apply({"params": p}, x)
    t = 11
    moved = np.any(np.asarray(
        layer.apply({"params": p}, x.at[0, t].add(1.0)) != out)[0], axis=-1)
    np.testing.assert_array_equal(
        moved, (np.arange(SEQ) >= t) & (np.arange(SEQ) <= t + 2))
    _close(out[0], ref.short_conv(p, x[0]))


@pytest.mark.parametrize("tap", [0, 1, 2])
def test_conv_taps_are_in_the_stated_order(tap):
    """Tap ``j`` alone weighs the token ``2 - j`` back: ``c[t] = w[:, 0]
    u[t-2] + w[:, 1] u[t-1] + w[:, 2] u[t]``, zeros before the sequence."""
    u = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, 8))
    w = jnp.zeros((8, 3)).at[:, tap].set(jnp.arange(1.0, 9.0))
    back = 2 - tap
    want = jnp.arange(1.0, 9.0) * jnp.pad(
        u, [(0, 0), (back, 0), (0, 0)])[:, :SEQ]
    np.testing.assert_array_equal(decoder.short_conv(u, w), want)
    # the grouped convolution PyTorch's Conv1d is, left-padded by 2
    conv = jax.lax.conv_general_dilated(
        u, w.T[:, None, :], (1,), [(2, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=8)
    _close(decoder.short_conv(u, w), conv, tol=1e-6)


@pytest.mark.parametrize("kind", ["conv", "attention", "sparse"])
def test_four_shares_add_up_to_the_uncut_layer(kind):
    """The parts that the four shares of a layer compute add up to what the
    uncut reference gives for the whole layer: a conv layer's (the same
    channels of B, C, x, of the taps and of ``out_proj``'s rows), an
    attention layer's (a KV head with its query heads), a sparse layer's
    (every share routes over all 16 experts with the whole bias)."""
    cfg = decoder.held_config(TINY)
    hidden = cfg["hidden_size"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, hidden))
    total = 0.0
    if kind == "conv":
        whole = decoder.ShortConv(hidden, cfg["conv_L_cache"])
        p = _scaled(whole.init(jax.random.PRNGKey(6), x)["params"])
        want = jnp.stack([ref.short_conv(p, x[b]) for b in range(2)])
        c = hidden // 4
        assert decoder.held_config(TINY, decoder.Share(tensor_shards=4))[
            "conv_channels"] == c
        for s in range(4):
            mine = np.concatenate([np.arange(s * c, (s + 1) * c) + i * hidden
                                   for i in range(3)])
            share = {"in_proj": p["in_proj"][:, mine],
                     "conv": p["conv"][s * c:(s + 1) * c],
                     "out_proj": p["out_proj"][s * c:(s + 1) * c]}
            total = total + decoder.ShortConv(c, 3).apply({"params": share},
                                                          x)
    elif kind == "attention":
        plan = decoder.layer_plan(cfg, 2)
        d = plan["head_dim"]

        def attention(q_heads, kv_heads):
            return decoder.Attention(
                plan["kind"], q_heads, kv_heads, d, 0,
                decoder._freeze(plan["rope"]), False, True, plan["eps"])

        p = _scaled(attention(8, 4).init(jax.random.PRNGKey(7), x)["params"])
        assert set(p) == {"q_proj", "k_proj", "v_proj", "o_proj", "q_norm",
                          "k_norm"}
        want = jnp.stack([ref.attention(p, x[b], cfg) for b in range(2)])
        for s in range(4):      # KV head s with its two query heads
            q = slice(2 * s * d, 2 * (s + 1) * d)
            kv = slice(s * d, (s + 1) * d)
            share = {**p, "q_proj": p["q_proj"][:, q],
                     "o_proj": p["o_proj"][q], "k_proj": p["k_proj"][:, kv],
                     "v_proj": p["v_proj"][:, kv]}
            total = total + attention(2, 1).apply({"params": share}, x)
    else:
        p = _scaled(_sparse(cfg).init(jax.random.PRNGKey(8), x)["params"])
        assert set(p) == {"router", "expert_bias", "experts"}
        flat = x.reshape(-1, hidden)
        want = ref.sparse_mlp(p, flat, cfg)[0].reshape(x.shape)
        for s in range(4):
            held = {**p, "experts": {k: w[4 * s:4 * s + 4]
                                     for k, w in p["experts"].items()}}
            part = _sparse(cfg, 4 * s, 4).apply({"params": held}, x)
            _close(part.reshape(flat.shape),
                   ref.sparse_mlp(held, flat, cfg, first_expert=4 * s)[0])
            total = total + part
    _close(total, want)


def test_bias_changes_the_choice_and_not_the_weights():
    """The four experts are the top-4 of ``s + b``; their weights are the
    sigmoid's over ``(sum + 1e-6)``, without ``b``; ``b`` gets a gradient of
    exactly zero; the layer sows what the scores alone would choose, and
    under a bfloat16 compute copy the bias is still the float32 leaf."""
    cfg = decoder.held_config(TINY)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, SEQ, cfg["hidden_size"]))
    layer = _sparse(cfg)
    p = _scaled(layer.init(jax.random.PRNGKey(10), x)["params"])
    _, sown = layer.apply({"params": p}, x, mutable=[decoder.EXPERT_STATS])
    stats = sown[decoder.EXPERT_STATS]
    chosen, plain = stats["top_experts"][0], stats["unbiased_experts"][0]
    scores = jax.nn.sigmoid(x[0] @ p["router"])
    np.testing.assert_array_equal(
        chosen, jax.lax.top_k(scores + p["expert_bias"], 4)[1])
    np.testing.assert_array_equal(plain, jax.lax.top_k(scores, 4)[1])
    swapped = np.any(np.sort(chosen, -1) != np.sort(plain, -1), axis=-1)
    assert 0.1 < swapped.mean() < 1.0
    top_p, top_e = ref.route(p, x[0], cfg)
    np.testing.assert_array_equal(top_e, chosen)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    _close(top_p, picked / (picked.sum(-1, keepdims=True) + 1e-6), tol=1e-7)
    assert float(jnp.max(jnp.abs(top_p.sum(-1) - 1.0))) < 2e-6
    np.testing.assert_array_equal(ref.route(p, x[0], cfg, bias=False)[1],
                                  plain)
    # the compute copy leaves the bias float32: with every score 0.5 and
    # biases 2^-12 apart (all 1.0 in bfloat16, where the lowest ids would
    # win the tie) the choice is the four highest
    from neuroimagedisttraining_tpu.obs.expert_load import stacked_stats

    _, model, params, tokens, _ = _model_and_batch()
    n = cfg["published"]["num_experts"]
    fine_bias = 1.0 + jnp.arange(n, dtype=jnp.float32) * 2.0 ** -12
    assert np.all(np.asarray(fine_bias.astype(jnp.bfloat16)) == 1)
    fine = jax.tree_util.tree_map_with_path(
        lambda path, a: fine_bias if path[-1].key == "expert_bias"
        else jnp.zeros_like(a) if path[-1].key == "router" else a, params)
    _, sown = make_apply_fn(model, jnp.bfloat16)(
        fine, tokens, train=False, rng=None, mutable=[decoder.EXPERT_STATS])
    chosen = np.sort(stacked_stats(sown)["top_experts"], -1)
    assert chosen.shape[0] == 4
    np.testing.assert_array_equal(
        chosen, np.broadcast_to(np.arange(n - 4, n), chosen.shape))
    grad = jax.grad(lambda q: jnp.sum(layer.apply({"params": q}, x) ** 2))(p)
    assert not np.any(grad["expert_bias"])
    assert np.linalg.norm(grad["router"]) > 0


def test_tied_head_is_one_leaf_whose_gradient_holds_both_uses():
    """The same configuration untied has a leaf ``lm_head``; with the
    embedding's transpose in it, its gradient and the embedding's are the
    two uses apart, and the tied leaf's gradient is their sum."""
    cfg, model, params, tokens, targets = _model_and_batch()
    assert "lm_head" not in params and cfg["tie_word_embeddings"]
    loss = PER_EXAMPLE_LOSSES["token_ce"]
    untied = decoder.Decoder(decoder._freeze(
        {**cfg, "tie_word_embeddings": False}))

    def mean_loss(m, p):
        return jnp.mean(loss(m.apply({"params": p}, tokens), targets))

    apart = {**params, "lm_head": params["embed"].T}
    _close(untied.apply({"params": apart}, tokens),
           model.apply({"params": params}, tokens), tol=1e-6)
    whole = jax.grad(lambda p: mean_loss(model, p))(params)["embed"]
    grads = jax.grad(lambda p: mean_loss(untied, p))(apart)
    assert np.linalg.norm(grads["lm_head"]) > 0
    assert np.linalg.norm(grads["embed"]) > 0
    both = grads["embed"] + grads["lm_head"].T
    assert np.linalg.norm(whole - both) / np.linalg.norm(whole) < 1e-5
    assert np.linalg.norm(whole - grads["embed"]) / np.linalg.norm(whole) \
        > 0.1


def test_a_round_of_two_equal_sites_leaves_the_bias_bit_for_bit():
    """Through the program's CLI modules and ``FedAlgorithm.run``: ``--model
    lfm2_tiny`` with the cell's flags trains in the folding round, the loss
    falls, every other leaf kind moves and each ``expert_bias`` is, bit for
    bit, what ``init_state`` drew."""
    from neuroimagedisttraining_tpu.experiments import parse_args, runner

    args = parse_args([
        "--algo", "fedavg", "--model", TINY, "--lm_layers", "6",
        "--lm_expert_shards", "4", "--lm_tensor_shards", "2", "--dataset",
        "token_shards", "--track_personal", "0", "--momentum", "0",
        "--batch_size", "1", "--epochs", "1", "--lr", "0.2", "--lr_decay",
        "0.998", "--grad_clip", "10", "--client_num_in_total", "2", "--frac",
        "1.0", "--frequency_of_the_test", "0", "--seed", "5"])
    algo, _ = runner.build_algorithm(args, "fedavg")
    assert algo.clients_per_round == 2
    state = algo.init_state(jax.random.PRNGKey(5))
    before = jax.device_get(state.global_params)
    state, history = algo.run(4, eval_every=0, state=state, finalize=False)
    after = jax.device_get(state.global_params)
    losses = [float(rec["train_loss"]) for rec in history]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    moved = {jax.tree_util.keystr(path): bool(np.any(a != b))
             for (path, a), b in zip(
                 jax.tree_util.tree_leaves_with_path(after),
                 jax.tree_util.tree_leaves(before))}
    biases = [name for name in moved if "expert_bias" in name]
    assert len(biases) == 4
    assert not any(moved[name] for name in biases)
    assert all(flag for name, flag in moved.items() if name not in biases)


@pytest.mark.parametrize("name,layer,want", [
    (TINY, 0, {"kind": "conv", "sparse": False, "heads": 8, "head_dim": 8,
               "eps": 1e-5, "qk_norm": True, "gate": False, "scale": 1,
               "score": "sigmoid"}),
    (TINY, 2, {"kind": "full_attention", "sparse": True, "heads": 8,
               "head_dim": 8, "eps": 1e-5}),
    ("lfm2_8b_a1b", 21, {"kind": "full_attention", "sparse": True,
                         "heads": 32, "head_dim": 64, "eps": 1e-5,
                         "rope": {"rope_theta": 1000000,
                                  "rope_type": "default"}}),
    ("laguna_s", 1, {"kind": "sliding_attention", "sparse": True,
                     "heads": 72, "head_dim": 128, "eps": 1e-6, "gate": True,
                     "qk_norm": False, "scale": 2.5, "score": "softmax"}),
    ("laguna_s", 0, {"kind": "full_attention", "sparse": False, "heads": 48}),
    ("keye_vl2", 7, {"kind": "selected_attention", "sparse": True,
                     "heads": 32, "head_dim": 128, "eps": 1e-6, "gate": False,
                     "qk_norm": True, "scale": 1, "score": "softmax"}),
])
def test_layer_plan_reads_what_a_layer_is_from_the_keys(name, layer, want):
    """Kinds, dense layers, eps, head width, scale and the router's rule from
    LFM2's keys (``layer_types`` with ``conv``, ``num_dense_layers``,
    ``norm_eps``, no ``head_dim``, ``routed_scaling_factor``,
    ``scoring_func``), and Laguna's and Keye's as before."""
    plan = decoder.layer_plan(decoder.held_config(name), layer)
    assert {k: plan[k] for k in want} == want
    # a share's heads are fewer; the head's width is not
    if "conv_L_cache" in decoder.CONFIGS[name]:
        cut = decoder.held_config(name, decoder.Share(tensor_shards=4))
        assert decoder.layer_plan(cut, layer)["head_dim"] == want["head_dim"]
        assert decoder.layer_plan(cut, layer)["heads"] == want["heads"] // 4


def test_router_rule_is_read_from_the_configs_key_not_the_models_name():
    cfg = decoder.held_config(TINY, SHARE)
    assert decoder.layer_plan({**cfg, "model_type": "another"}, 2)[
        "score"] == "sigmoid"
    # the rule is the key ``scoring_func``, not what ``use_expert_bias``
    # lets one guess: without the key a softmax, and then without a bias
    bare = {k: v for k, v in cfg.items()
            if k not in ("scoring_func", "use_expert_bias")}
    assert decoder.layer_plan(bare, 2)["score"] == "softmax"
    shapes = jax.eval_shape(lambda: decoder.Decoder(decoder._freeze(
        bare)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert "expert_bias" not in shapes["layers_2"]["mlp"]
    # the pairings no branch routes are refused by the keys' names
    for odd in ({**cfg, "use_expert_bias": False},
                {**bare, "use_expert_bias": True},
                {**cfg, "scoring_func": "tanh"}):
        with pytest.raises(ValueError, match="scoring_func .* with "
                                             "use_expert_bias"):
            decoder.layer_plan(odd, 2)
    untied = jax.eval_shape(lambda: decoder.Decoder(decoder._freeze(
        {**cfg, "tie_word_embeddings": False})).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert untied["lm_head"].shape == (64, 32)


@pytest.mark.parametrize("share,match", [
    (decoder.Share(5, 4, 2), "5 layers cut a period of layer_types"),
    (decoder.Share(2, 4, 2), "cut a period of layer_types"),
    (decoder.Share(6, 4, 8), "num_key_value_heads 4 does not divide"),
    (decoder.Share(6, 3, 2), "num_experts 16 does not divide"),
])
def test_held_config_refuses_with_the_keys_name(share, match):
    with pytest.raises(ValueError, match=match):
        decoder.held_config(TINY, share)


def test_held_config_refuses_channels_that_do_not_divide(monkeypatch):
    """A conv layer's channels go as the heads do: ``hidden_size`` must
    divide over ``tensor_shards`` where the model has conv layers."""
    odd = dict(decoder.CONFIGS[TINY], hidden_size=66)
    monkeypatch.setitem(decoder.CONFIGS, "odd", odd)
    with pytest.raises(ValueError, match="hidden_size 66 does not divide"):
        decoder.held_config("odd", decoder.Share(6, 4, 4))
    assert decoder.held_config("odd", decoder.Share(6, 4, 2))[
        "conv_channels"] == 33


def test_share_cuts_counts_never_widths_and_builds_the_stated_model():
    cfg = decoder.held_config("lfm2_8b_a1b", decoder.Share(6, 4, 4))
    pub = decoder.CONFIGS["lfm2_8b_a1b"]
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                  "num_experts_per_tok", "conv_L_cache", "rope_theta",
                  "norm_eps", "num_dense_layers"):
        assert cfg[width] == pub[width]
    assert {k: cfg[k] for k in cfg["published"]} == {
        "num_hidden_layers": 6, "num_experts": 8, "num_attention_heads": 8,
        "num_key_value_heads": 2, "vocab_size": 16384, "conv_channels": 512}
    assert cfg["published"]["conv_channels"] == 2048
    assert cfg["layer_types"] == ["conv", "conv", "full_attention", "conv",
                                  "conv", "conv"]
    assert decoder.least_layers(pub) == 6
    assert decoder.least_layers(decoder.CONFIGS["laguna_s"]) == 5
    assert decoder.held_config("lfm2_8b_a1b", decoder.Share(
        6, 4, 4, index=3))["first_expert"] == 24
    # the whole list: 18 conv and 6 attention layers
    kinds = pub["layer_types"]
    assert (len(kinds), kinds.count("conv")) == (24, 18)
    assert [i for i, k in enumerate(kinds) if k != "conv"] == [
        2, 6, 10, 14, 18, 21]
    model = create_model("lfm2_8b_a1b", num_classes=16384, layers=6,
                         expert_shards=4, tensor_shards=4)
    shapes = jax.eval_shape(lambda: init_params(
        model, jax.random.PRNGKey(0), (16,), jnp.int32))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) \
        == 497_846_016
    conv = shapes["layers_3"]["conv"]
    assert {k: v.shape for k, v in conv.items()} == {
        "in_proj": (2048, 1536), "conv": (512, 3), "out_proj": (512, 2048)}
    assert shapes["layers_2"]["mlp"]["expert_bias"].shape == (32,)
    assert shapes["layers_2"]["mlp"]["router"].shape == (2048, 32)
    assert shapes["layers_2"]["attention"]["q_norm"].shape == (64,)
    assert "lm_head" not in shapes and model.tpu_compiler_options == {}


@pytest.mark.parametrize("name,share,swaps", [
    (TINY, SHARE, True),
    ("laguna_tiny", decoder.Share(5, 4, 2), False)])
def test_expert_bias_swap_share_gauge(name, share, swaps):
    """The program's own one forward sets ``expert_bias_swap_share`` beside
    the expert-load gauges: the share of (token, sparse layer) pairs whose
    experts the bias changed; no such gauge for a model without one."""
    from neuroimagedisttraining_tpu.algorithms import FedAvg
    from neuroimagedisttraining_tpu.core.trainer import HyperParams
    from neuroimagedisttraining_tpu.data.tokens import make_token_shards

    cfg = decoder.held_config(name, share)
    data = make_token_shards(0, n_clients=2, vocab=cfg["vocab_size"],
                             sequence_length=SEQ, train_per_client=1)
    algo = FedAvg(decoder.decoder(name, share), data,
                  HyperParams(lr=0.1, local_epochs=1, steps_per_epoch=1,
                              batch_size=1),
                  loss_type="token_ce", seed=0, client_chunk=1,
                  track_personal=False)
    params = _scaled(algo.init_state(jax.random.PRNGKey(0)).global_params)
    registry = obs_metrics.MetricsRegistry()
    got = record_expert_load(algo, params, registry)
    assert set(got) == set(registry.snapshot()) == {
        "expert_load_max_over_mean", "held_slot_share"} | (
            {"expert_bias_swap_share"} if swaps else set())
    if swaps:
        assert registry.snapshot()["expert_bias_swap_share"]["value"] \
            == got["expert_bias_swap_share"]
        assert 0.1 < got["expert_bias_swap_share"] <= 1.0
        # by hand, from what the layers sowed
        _, sown = algo.apply_fn(params, data.x_train[0, :1], train=False,
                                rng=None, mutable=[decoder.EXPERT_STATS])
        layers = [sown[decoder.EXPERT_STATS][f"layers_{i}"]["mlp"]
                  for i in range(2, 6)]
        want = np.mean([np.any(
            np.sort(m["top_experts"][0], -1)
            != np.sort(m["unbiased_experts"][0], -1), axis=-1)
            for m in layers])
        assert got["expert_bias_swap_share"] == pytest.approx(want)
