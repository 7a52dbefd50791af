"""The decoder family (models/decoder.py) held to its plain reference
(benchmarks/reference/laguna_s.py) on seeded weights at the tiny preset: 5
layers with the published pattern, 16 experts top-4 with 4 held, 2 KV heads,
window 8, sequences of 32. And what the language-model job forced elsewhere:
integer inputs, the per-token loss, a round that folds each client into the
sum."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna_s as ref
from neuroimagedisttraining_tpu.core.losses import (
    PER_EXAMPLE_LOSSES, predictions, softmax_ce_per_example)
from neuroimagedisttraining_tpu.models import (
    create_model, decoder, init_params, make_apply_fn)

TINY = "laguna_tiny"
SHARE = decoder.Share(layers=5, expert_shards=4, tensor_shards=2)
SEQ = 32


def _seeded(model, tokens, seed=0, scale=8.0):
    """Seeded weights, the matrices scaled up so that attention, gates and
    the router are far from their trivial values."""
    params = model.init(jax.random.PRNGKey(seed), tokens)["params"]
    return jax.tree_util.tree_map(
        lambda a: a * scale if a.ndim > 1 else a
        + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape),
        params)


def _tokens(cfg, rows=2, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ), 0,
                              cfg["vocab_size"])


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(1.0, float(np.max(np.abs(want))))
    assert float(np.max(np.abs(got - want))) <= tol * scale


def test_whole_model_logits_loss_and_every_gradient():
    cfg = decoder.held_config(TINY, SHARE)
    model = decoder.decoder(TINY, SHARE)
    tokens = _tokens(cfg)
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], axis=1)
    params = _seeded(model, tokens)
    apply_fn = make_apply_fn(model)
    loss = PER_EXAMPLE_LOSSES["token_ce"]

    def system(p):
        logits = apply_fn(p, tokens, train=True, rng=jax.random.PRNGKey(0))
        return jnp.mean(loss(logits, targets)), logits

    def plain(p):
        out = [ref.loss_and_logits(p, tokens[b], targets[b], cfg,
                                   cfg["first_expert"]) for b in range(2)]
        return (out[0][0] + out[1][0]) / 2, jnp.stack(
            [o[1][0] for o in out])

    (s_loss, s_logits), s_grad = jax.value_and_grad(system, has_aux=True)(
        params)
    (r_loss, r_logits), r_grad = jax.value_and_grad(plain, has_aux=True)(
        ref.from_system(params))
    _close(s_logits, r_logits)
    _close(s_loss, r_loss)
    s_leaves = jax.tree_util.tree_leaves_with_path(ref.from_system(s_grad))
    r_leaves = jax.tree_util.tree_leaves(r_grad)
    assert len(s_leaves) == len(r_leaves) == 5 * 7 + 3 + 4 * 7 + 3
    for (path, got), want in zip(s_leaves, r_leaves):
        err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-12)
        assert err < 1e-4, (jax.tree_util.keystr(path), err)
        assert np.linalg.norm(want) > 0, jax.tree_util.keystr(path)


@pytest.mark.parametrize("kind,heads", [("full_attention", 4),
                                        ("sliding_attention", 6)])
def test_attention_layer_and_its_head_shares_add_up(kind, heads):
    """One attention layer against the reference, whole; and the two head
    shares' partial outputs (each KV head with its group of query heads, the
    output projection's rows of those heads) add up to the whole."""
    cfg = decoder.held_config(TINY)
    d, hidden = cfg["head_dim"], cfg["hidden_size"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, hidden))

    def layer(q_heads, kv_heads):
        return decoder.Attention(
            kind, q_heads, kv_heads, d, cfg["sliding_window"],
            decoder._freeze(cfg["rope_parameters"][kind]))

    whole = layer(heads, 2)
    p = jax.tree_util.tree_map(
        lambda a: a * 8.0, whole.init(jax.random.PRNGKey(3), x)["params"])
    out = whole.apply({"params": p}, x)
    for b in range(2):
        want = ref.attention(p, x[b], cfg, kind)
        _close(out[b], want)
        # the reference's own loop over blocks of queries, recomputed or not
        _close(ref.attention(p, x[b], cfg, kind, q_block=8), want, tol=1e-6)
        _close(ref.attention(p, x[b], cfg, kind, q_block=8, remat=True),
               want, tol=1e-6)
        _close(ref.attention(p, x[b], cfg, kind, q_block=12), want, tol=1e-6)
    group = heads // 2
    parts = []
    for s in range(2):      # KV head s and its query heads
        q = slice(s * group * d, (s + 1) * group * d)
        kv = slice(s * d, (s + 1) * d)
        share = {"q_proj": p["q_proj"][:, q], "k_proj": p["k_proj"][:, kv],
                 "v_proj": p["v_proj"][:, kv],
                 "gate_proj": p["gate_proj"][:, s * group:(s + 1) * group],
                 "o_proj": p["o_proj"][q]}
        parts.append(layer(group, 1).apply({"params": share}, x))
    _close(parts[0] + parts[1], out)


def _qkv(seq, seed=4):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k1, (2, seq, 2, 3, 16)),
            jax.random.normal(k2, (2, seq, 2, 16)),
            jax.random.normal(k3, (2, seq, 2, 16)))


def _masked_full_product(q, k, v, window):
    s = q.shape[1]
    scores = jnp.einsum("bqngd,bknd->bngqk", q, k) / math.sqrt(q.shape[-1])
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i) & (i - j < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bngqk,bknd->bqngd", probs, v)


@pytest.mark.parametrize("seq,window", [(32, 8), (30, 8), (32, 5), (9, 8),
                                        (8, 8), (6, 8), (32, 64)])
def test_band_equals_masked_full_product(seq, window):
    """The banded computation against the masked S x S product: window < S
    (block-aligned or not), and window >= S, where it is the full layer."""
    q, k, v = _qkv(seq)
    _close(decoder.window_attention(q, k, v, window),
           _masked_full_product(q, k, v, window))
    if window >= seq:
        _close(decoder.window_attention(q, k, v, window),
               decoder.full_attention(q, k, v))


@pytest.mark.parametrize("block", [8, 12, 64])
def test_full_attention_in_blocks_of_queries(block):
    q, k, v = _qkv(32, seed=5)
    _close(decoder._causal_blocks(q, k, v, block=block),
           _masked_full_product(q, k, v, 32))


def test_yarn_frequencies_and_half_rotary_split_by_hand():
    """Published full-attention rope: 64 of 128 features turn; of their 32
    frequencies those that turn 32 times or more inside the original 8192
    positions are kept, those that turn once or less are divided by 128,
    a linear ramp between; cos and sin carry the attention factor."""
    rope = decoder.CONFIGS["laguna_s"]["rope_parameters"]["full_attention"]
    cos, sin, rot = decoder.rope_tables(rope, 128, 4)
    assert rot == 64 and cos.shape == (4, 32)
    factor = rope["attention_factor"]
    assert abs(factor - (0.1 * math.log(128) + 1)) < 1e-12
    base = 500000.0
    # the correction range, by hand: dim * ln(L / (2 pi r)) / (2 ln base)
    low = math.floor(64 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(64 * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (9, 18)
    want = []
    for i in range(32):
        f = base ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f * (1 - ramp) + f / 128 * ramp)
    angle = np.arcsin(sin[1] / factor)      # position 1: every angle <= 1
    np.testing.assert_allclose(angle, want, rtol=1e-5)
    np.testing.assert_allclose(cos[1] / factor, np.cos(want), rtol=1e-6)
    assert want[9] == base ** (-18 / 64) and want[18] == base ** (
        -36 / 64) / 128
    ref_freq, ref_rot, ref_scale = ref.rope_inv_freq(rope, 128)
    np.testing.assert_allclose(ref_freq, want, rtol=1e-12)
    assert (ref_rot, ref_scale) == (64, factor)
    # the split: features 0..31 pair with 32..63, features 64.. pass through
    x = jnp.arange(2 * 3 * 1 * 128, dtype=jnp.float32).reshape(2, 3, 1, 128)
    y = decoder._rotate(x, cos[:3], sin[:3], rot)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    np.testing.assert_allclose(
        y[1, 2, 0, :32], x[1, 2, 0, :32] * cos[2] - x[1, 2, 0, 32:64] * sin[2],
        rtol=1e-6)
    np.testing.assert_allclose(
        y[1, 2, 0, 32:64],
        x[1, 2, 0, 32:64] * cos[2] + x[1, 2, 0, :32] * sin[2], rtol=1e-6)
    # sliding layers: plain RoPE over the whole head, no scale
    plain = decoder.CONFIGS["laguna_s"]["rope_parameters"]["sliding_attention"]
    _, sin, rot = decoder.rope_tables(plain, 128, 2)
    assert rot == 128
    np.testing.assert_allclose(np.arcsin(sin[1][:3]),
                               [1.0, 10000 ** (-2 / 128),
                                10000 ** (-4 / 128)], rtol=1e-5)


def _sparse(cfg, first, held=4, usual_load=2):
    """A sparse MLP of the tiny preset whose chunk is half the worst case,
    so that the held slots pass in two chunks."""
    return decoder.SparseMLP(
        cfg["published"]["num_experts"], cfg["num_experts_per_tok"],
        cfg["norm_topk_prob"], cfg["moe_routed_scaling_factor"], first, held,
        cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"],
        usual_load)


def _sparse_params(cfg, x, seed=6):
    whole = _sparse(cfg, 0, held=cfg["published"]["num_experts"])
    return jax.tree_util.tree_map(
        lambda a: a * 8.0, whole.init(jax.random.PRNGKey(seed), x)["params"])


def _expert_slice(p, first, held=4):
    return {**p, "experts": {k: w[first:first + held]
                             for k, w in p["experts"].items()}}


def test_expert_shares_add_up_to_the_uncut_layer():
    """The four expert shares' routed parts plus the shared expert counted
    once equal the uncut reference's layer; each share equals the
    reference given the same share."""
    cfg = decoder.held_config(TINY, decoder.Share(expert_shards=4))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, cfg["hidden_size"]))
    p = _sparse_params(cfg, x)
    flat = x.reshape(-1, x.shape[-1])
    uncut, _ = ref.sparse_mlp(p, flat, cfg, first_expert=0)
    shared = ref.swiglu(p["shared_expert"], flat)
    total = -3 * shared         # the shared expert comes with every share
    for s in range(4):
        part = _sparse(cfg, 4 * s).apply(
            {"params": _expert_slice(p, 4 * s)}, x).reshape(flat.shape)
        want, _ = ref.sparse_mlp(_expert_slice(p, 4 * s), flat, cfg,
                                 first_expert=4 * s)
        _close(part, want)
        total = total + part
    _close(total, uncut)
    assert float(jnp.max(jnp.abs(uncut - shared))) > 0.1


def test_routing_edges_nothing_dropped_nothing_held_and_ties():
    cfg = decoder.held_config(TINY, decoder.Share(expert_shards=4))
    hidden, n, k = cfg["hidden_size"], 16, cfg["num_experts_per_tok"]
    # positive inputs, so a router column of +c scores c * sum(x) > 0
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, hidden))) \
        + 0.1
    flat = x.reshape(-1, hidden)
    p = _sparse_params(cfg, x)

    def router(scores):     # column e = scores[e] * ones
        return jnp.asarray(np.outer(np.full(hidden, 1.0 / hidden, np.float32),
                                    np.asarray(scores, np.float32)))

    # (1) every token to the four held experts 4..7: all 64 * 4 slots land
    # here (the buffer's worst case) and none is dropped
    scores = np.full(n, -1.0)
    scores[4:8] = [4.0, 3.0, 2.0, 1.0]
    p1 = {**p, "router": router(scores)}
    layer = _sparse(cfg, 4)
    out, sown = layer.apply({"params": _expert_slice(p1, 4)}, x,
                            mutable=[decoder.EXPERT_STATS])
    counts = sown[decoder.EXPERT_STATS]["held_counts"][0]
    np.testing.assert_array_equal(counts, [2 * SEQ] * 4)
    want, _ = ref.sparse_mlp(_expert_slice(p1, 4), flat, cfg, first_expert=4)
    _close(out.reshape(flat.shape), want)
    # ... and it is the whole layer: the other shares hold nothing routed
    uncut, _ = ref.sparse_mlp(p1, flat, cfg, first_expert=0)
    _close(out.reshape(flat.shape), uncut)
    # one expert takes one slot of every token: its group is every token
    scores = np.linspace(-1.0, -2.0, n)
    scores[5] = 4.0
    p1 = {**p, "router": router(scores)}
    out, sown = layer.apply({"params": _expert_slice(p1, 4)}, x,
                            mutable=[decoder.EXPERT_STATS])
    np.testing.assert_array_equal(
        sown[decoder.EXPERT_STATS]["held_counts"][0], [0, 2 * SEQ, 0, 0])
    want, _ = ref.sparse_mlp(_expert_slice(p1, 4), flat, cfg, first_expert=4)
    _close(out.reshape(flat.shape), want)

    # (2) no token to a held expert: the shared expert's output alone
    scores = np.full(n, -1.0)
    scores[8:12] = [4.0, 3.0, 2.0, 1.0]
    p2 = {**p, "router": router(scores)}
    out, sown = layer.apply({"params": _expert_slice(p2, 4)}, x,
                            mutable=[decoder.EXPERT_STATS])
    np.testing.assert_array_equal(
        sown[decoder.EXPERT_STATS]["held_counts"][0], [0] * 4)
    _close(out.reshape(flat.shape), ref.swiglu(p["shared_expert"], flat))
    grads = jax.grad(lambda q: jnp.sum(layer.apply({"params": q}, x)))(
        _expert_slice(p2, 4))
    assert all(float(jnp.max(jnp.abs(g))) == 0.0
               for g in jax.tree_util.tree_leaves(grads["experts"]))

    # (3) ties: all logits equal; both sides take the lowest ids, 0..3
    p3 = {**p, "router": jnp.zeros_like(p["router"])}
    for first, held_slots in ((0, 2 * SEQ), (4, 0)):
        out, sown = _sparse(cfg, first).apply(
            {"params": _expert_slice(p3, first)}, x,
            mutable=[decoder.EXPERT_STATS])
        np.testing.assert_array_equal(
            sown[decoder.EXPERT_STATS]["held_counts"][0], [held_slots] * 4)
        np.testing.assert_array_equal(
            sown[decoder.EXPERT_STATS]["top_experts"][0],
            np.tile(np.arange(k), (2 * SEQ, 1)))
        want, top = ref.sparse_mlp(_expert_slice(p3, first), flat, cfg,
                                   first_expert=first)
        _close(out.reshape(flat.shape), want)
        np.testing.assert_array_equal(top, np.tile(np.arange(k),
                                                   (2 * SEQ, 1)))


def test_no_capacity_factor_anywhere():
    import inspect

    source = inspect.getsource(decoder).lower()
    assert "capacity_factor" not in source and "drop_tokens" not in source
    # chunks of four times the expected load, as many as hold the worst
    # case: every token's slots on held experts (65,536 at the cell's size)
    layer = decoder.SparseMLP(256, 10, True, 2.5, 0, 8, 1024, 1024)
    assert layer.buffer_rows(8192) == (10240, 7)
    assert _sparse(decoder.held_config(TINY), 0).buffer_rows(64) == (128, 2)
    assert decoder.SparseMLP(16, 4, True, 2.5, 0, 4, 16, 16).buffer_rows(
        64) == (256, 1)


@pytest.mark.parametrize("mlp", ["dense", "sparse"])
@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_block_against_reference(kind, mlp):
    """One decoder block of each pairing of attention and MLP kind."""
    cfg = decoder.held_config(TINY, SHARE)
    cfg.update(layer_types=[kind], mlp_layer_types=[mlp],
               num_hidden_layers=1,
               num_attention_heads_per_layer=[
                   2 if kind == "full_attention" else 3])
    block = decoder.Block(decoder._freeze(cfg), 0)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, cfg["hidden_size"]))
    p = jax.tree_util.tree_map(
        lambda a: a * 8.0 if a.ndim > 1 else a,
        block.init(jax.random.PRNGKey(10), x)["params"])

    def plain(p, x):
        eps = cfg["rms_norm_eps"]
        h = x + ref.attention(p["attention"],
                              ref.rms_norm(x, p["attn_norm"], eps), cfg, kind)
        g = ref.rms_norm(h, p["mlp_norm"], eps)
        if mlp == "dense":
            return h + ref.swiglu(p["mlp"], g)
        return h + ref.sparse_mlp(p["mlp"], g, cfg, cfg["first_expert"])[0]

    out = block.apply({"params": p}, x)
    for b in range(2):
        _close(out[b], plain(p, x[b]))
    s_grad = jax.grad(lambda q: jnp.sum(jnp.sin(block.apply({"params": q},
                                                            x))))(p)
    r_grad = jax.grad(lambda q: sum(jnp.sum(jnp.sin(plain(q, x[b])))
                                    for b in range(2)))(p)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(s_grad),
                                 jax.tree_util.tree_leaves(r_grad)):
        err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-12)
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def test_share_cuts_counts_never_widths():
    cfg = decoder.held_config("laguna_s", decoder.Share(5, 32, 8))
    pub = decoder.CONFIGS["laguna_s"]
    for width in ("hidden_size", "head_dim", "intermediate_size",
                  "moe_intermediate_size", "shared_expert_intermediate_size",
                  "num_experts_per_tok", "sliding_window"):
        assert cfg[width] == pub[width]
    assert cfg["published"]["num_experts"] == 256 and cfg["num_experts"] == 8
    assert cfg["num_attention_heads_per_layer"] == [6, 9, 9, 9, 6]
    assert cfg["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (cfg["num_key_value_heads"], cfg["vocab_size"]) == (1, 12544)
    assert decoder.held_config("laguna_s", decoder.Share(
        5, 32, 8, index=9))["first_expert"] == 72
    with pytest.raises(ValueError, match="does not divide"):
        decoder.held_config("laguna_s", decoder.Share(5, 7, 8))
    model = create_model("laguna_s", num_classes=12544, layers=5,
                         expert_shards=32, tensor_shards=8)
    shapes = jax.eval_shape(lambda: init_params(
        model, jax.random.PRNGKey(0), (16,), jnp.int32))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) \
        == 567_957_504
    with pytest.raises(ValueError, match="vocabulary rows"):
        create_model("laguna_s", num_classes=100352, layers=5,
                     expert_shards=32, tensor_shards=8)


def test_apply_fn_leaves_integer_inputs_alone():
    cfg = decoder.held_config(TINY, SHARE)
    model = decoder.decoder(TINY, SHARE)
    tokens = _tokens(cfg)
    params = init_params(model, jax.random.PRNGKey(0), (SEQ,), jnp.int32)
    seen = {}

    class Spy:
        def apply(self, variables, x, **kw):
            seen["x"], seen["w"] = x.dtype, variables["params"]["embed"].dtype
            return model.apply(variables, x, **kw)

    out = make_apply_fn(Spy(), compute_dtype=jnp.bfloat16)(
        params, tokens, train=False, rng=None)
    assert seen == {"x": jnp.int32, "w": jnp.bfloat16}
    assert out.dtype == jnp.float32 and out.shape == (2, SEQ, 32)
    out, sown = make_apply_fn(model)(params, tokens, train=False, rng=None,
                                     mutable=[decoder.EXPERT_STATS])
    assert len(jax.tree_util.tree_leaves(sown)) == 8


@pytest.mark.parametrize("name,shape,dtype", [
    ("lenet5", (28, 28, 1), None), ("small3dcnn", (8, 8, 8, 1), None),
    ("small3dcnn", (8, 8, 8, 1), "bfloat16"), ("cnn_cifar10", (32, 32, 3),
                                               "bfloat16")])
def test_cnn_outputs_bit_equal_to_the_parents_apply(name, shape, dtype):
    """``make_apply_fn`` as the parent commit wrote it (inputs cast whole),
    against today's (floating inputs cast): the CNNs' outputs and parameters
    bit for bit, train and eval."""
    model = create_model(name, num_classes=2)
    dt = None if dtype is None else jnp.dtype(dtype)

    def parents(params, x, train, rng):
        cast = lambda t, to: jax.tree_util.tree_map(  # noqa: E731
            lambda a: a.astype(to)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, t)
        if dt is not None:
            params, x = cast(params, dt), x.astype(dt)
        out = model.apply({"params": params}, x, train=train,
                          **({"rngs": {"dropout": rng}} if train else {}))
        return cast(out, jnp.float32) if dt is not None else out

    x = jax.random.normal(jax.random.PRNGKey(1), (3,) + shape)
    params = init_params(model, jax.random.PRNGKey(0), shape)
    old = jax.jit(lambda r: model.init(
        {"params": r, "dropout": r}, jnp.zeros((1,) + shape, jnp.float32),
        train=False)["params"])(jax.random.PRNGKey(0))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(old)):
        np.testing.assert_array_equal(a, b)
    apply_fn = make_apply_fn(model, compute_dtype=dt)
    for train in (False, True):
        got = apply_fn(params, x, train=train, rng=jax.random.PRNGKey(2))
        want = parents(params, x, train, jax.random.PRNGKey(2))
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(a, b)


def test_token_ce_against_softmax_ce_on_flattened_tokens():
    logits = 3 * jax.random.normal(jax.random.PRNGKey(0), (3, 16, 11))
    labels = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, 11)
    loss = PER_EXAMPLE_LOSSES["token_ce"]
    want = softmax_ce_per_example(logits.reshape(-1, 11),
                                  labels.reshape(-1)).reshape(3, 16)
    np.testing.assert_allclose(loss(logits, labels), want.mean(axis=1),
                               rtol=1e-6)
    # a label below 0 has no target: the mean is over the others
    ignored = labels.at[:, -1].set(-1).at[1, 3].set(-1)
    w = np.asarray(ignored >= 0, np.float32)
    np.testing.assert_allclose(
        loss(logits, ignored), (np.asarray(want) * w).sum(1) / w.sum(1),
        rtol=1e-6)
    assert loss(logits.astype(jnp.bfloat16), ignored).dtype == jnp.float32
    # the reference's loss is the same number, sequence by sequence
    for b in range(3):
        np.testing.assert_allclose(
            loss(logits, ignored)[b],
            ref.token_cross_entropy(logits[b], ignored[b]), rtol=1e-6)
    assert predictions(logits, "token_ce").shape == (3, 16)
    np.testing.assert_array_equal(predictions(logits, "token_ce"),
                                  jnp.argmax(logits, -1))


@pytest.mark.parametrize("usual_load", [1, 2, 4],
                         ids=["four_chunks", "two_chunks", "one_chunk"])
def test_rows_past_the_held_slots_may_hold_anything(monkeypatch, usual_load):
    """On a TPU the grouped product visits only the row tiles inside a group:
    rows past the groups come back undefined, going forward and (the input's
    gradient) going backward. Emulated here with NaN in those rows, the
    layer's output and every gradient stay finite and stay the reference's,
    however many chunks the slots pass in (my chip run, PR 28: a mask
    applied after the routing weight let 0 x NaN through into the router's
    gradient; now no row of a chunk lies outside its groups)."""
    real = jax.lax.ragged_dot

    def rows_in_groups(x, sizes):
        return (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return jnp.where(rows_in_groups(lhs, sizes), real(lhs, rhs, sizes),
                         jnp.nan)

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(saved, g):
        lhs, rhs, sizes = saved
        inside = rows_in_groups(lhs, sizes)
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(jnp.where(inside, g, 0))     # never read outside
        return jnp.where(inside, d_lhs, jnp.nan), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    cfg = decoder.held_config(TINY, decoder.Share(expert_shards=4))
    x = jax.random.normal(jax.random.PRNGKey(11), (2, SEQ,
                                                   cfg["hidden_size"]))
    p = _expert_slice(_sparse_params(cfg, x), 4)
    layer = _sparse(cfg, 4, usual_load=usual_load)
    assert layer.buffer_rows(2 * SEQ) == (64 * usual_load, 4 // usual_load)
    flat = x.reshape(-1, x.shape[-1])

    def system(p, x):
        return jnp.sum(jnp.sin(layer.apply({"params": p}, x)))

    def plain(p, x):
        return jnp.sum(jnp.sin(ref.sparse_mlp(
            p, x.reshape(flat.shape), cfg, first_expert=4)[0]))

    assert bool(jnp.isfinite(layer.apply({"params": p}, x)).all())
    got = jax.grad(system, argnums=(0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", real)
    want = jax.grad(plain, argnums=(0, 1))(p, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert bool(jnp.isfinite(a).all()), jax.tree_util.keystr(path)
        err = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def test_any_chunking_gives_the_same_routed_sum():
    """``routed_part`` in one chunk of the worst case, in two and in four,
    for counts of held slots that end in the first chunk, in a later one and
    in none."""
    cfg = decoder.held_config(TINY, decoder.Share(expert_shards=4))
    assert _sparse(cfg, 4).buffer_rows(2 * SEQ) == (128, 2)
    x = jax.random.normal(jax.random.PRNGKey(12), (2 * SEQ,
                                                   cfg["hidden_size"]))
    w = {k: jax.random.normal(jax.random.PRNGKey(i), s)
         for i, (k, s) in enumerate({"gate_proj": (4, 32, 16),
                                     "up_proj": (4, 32, 16),
                                     "down_proj": (4, 16, 32)}.items())}
    for held_slots in (0, 5, 128, 200, 256):
        local = np.full(2 * SEQ * 4, 4, np.int32)
        local[np.random.default_rng(held_slots).permutation(local.size)[
            :held_slots]] = np.arange(held_slots) % 4
        sizes = jnp.asarray(np.bincount(local, minlength=5)[:4], jnp.int32)
        order = jnp.argsort(jnp.asarray(local), stable=True)
        weight = jax.random.uniform(jax.random.PRNGKey(13), (local.size,))
        whole = decoder.routed_part(256, 1, x, w, order, weight, sizes, 4)
        for rows, chunks in ((128, 2), (64, 4), (96, 3)):
            _close(decoder.routed_part(rows, chunks, x, w, order, weight,
                                       sizes, 4), whole, tol=1e-6)
        assert (held_slots == 0) == (float(jnp.max(jnp.abs(whole))) == 0.0)
        # against the plain sum over the held slots
        want = np.zeros(x.shape, np.float32)
        for slot in np.flatnonzero(local < 4):
            e, row = local[slot], np.asarray(x[slot // 4], np.float32)
            h = row @ np.asarray(w["gate_proj"][e])
            h = h / (1 + np.exp(-h)) * (row @ np.asarray(w["up_proj"][e]))
            want[slot // 4] += float(weight[slot]) * (
                h @ np.asarray(w["down_proj"][e]))
        _close(whole, want, tol=1e-5)
