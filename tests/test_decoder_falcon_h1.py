"""The decoder whose every block feeds one normed input to two mixers side by
side, attention and a Mamba-2 state-space mixer, with muP multipliers read
from the config (models/decoder.py, ``falcon_h1_tiny``: 4 query / 2 KV heads
of 16, 4 state-space heads of 16 in 2 groups, state 16, chunks of 8, conv 4,
every multiplier different from 1) held to its plain reference
(benchmarks/reference/falcon_h1.py, which runs the mixer as the token-by-token
recurrence) on seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import falcon_h1 as ref
from neuroimagedisttraining_tpu.core.losses import PER_EXAMPLE_LOSSES
from neuroimagedisttraining_tpu.models import (
    create_model, decoder, init_params, make_apply_fn)
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.obs.expert_load import record_expert_load

TINY = "falcon_h1_tiny"
# 4 layers; heads 2 ways, the vocabulary 4, the mixer 2, the MLP's columns 2
SHARE = decoder.Share(layers=4, tensor_shards=2, vocab_shards=4,
                      ssm_shards=2, mlp_shards=2)
SEQ = 40        # five chunks of 8


def _scaled(params, scale=4.0):
    """The matrices scaled up so that attention and the gates are far from
    their trivial values; the vectors moved by a tenth (the mixer's small
    leaves are drawn as Mamba-2 draws them and stay)."""
    def one(path, a):
        if path[-1].key in ("A_log", "dt_bias", "D", "conv", "conv_bias"):
            return a
        if a.ndim > 1:
            return a * scale
        return a + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size),
                                           a.shape)
    return jax.tree_util.tree_map_with_path(one, params)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(1.0, float(np.max(np.abs(want))))
    assert float(np.max(np.abs(got - want))) <= tol * scale


def _model_and_batch(seed=1, share=SHARE):
    cfg = decoder.held_config(TINY, share)
    model = decoder.decoder(TINY, share)
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (2, SEQ), 0,
                                cfg["vocab_size"])
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], axis=1)
    params = _scaled(model.init(jax.random.PRNGKey(0), tokens)["params"])
    return cfg, model, params, tokens, targets


def _mixer(cfg):
    plan = decoder.layer_plan(cfg, 0)["multipliers"]
    return decoder.StateSpace(
        cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
        cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_chunk_size"],
        cfg["rms_norm_eps"], plan["ssm_in"], plan["ssm"])


def _mixer_params(cfg, x, key=6):
    return _scaled(_mixer(cfg).init(jax.random.PRNGKey(key), x)["params"])


def test_whole_model_logits_loss_and_the_gradient_of_every_leaf():
    cfg, model, params, tokens, targets = _model_and_batch()
    apply_fn = make_apply_fn(model)
    loss = PER_EXAMPLE_LOSSES["token_ce"]

    def system(p):
        logits = apply_fn(p, tokens, train=True, rng=jax.random.PRNGKey(0))
        return jnp.mean(loss(logits, targets)), logits

    def plain(p):
        out = [ref.loss_and_logits(p, tokens[b], targets[b], cfg)
               for b in range(2)]
        return (out[0][0] + out[1][0]) / 2, jnp.stack([o[1] for o in out])

    (s_loss, s_logits), s_grad = jax.value_and_grad(system, has_aux=True)(
        params)
    (r_loss, r_logits), r_grad = jax.value_and_grad(plain, has_aux=True)(
        ref.from_system(params))
    assert float(jnp.max(jnp.abs(r_logits))) > 0.1
    _close(s_logits, r_logits)
    _close(s_loss, r_loss)
    s_leaves = jax.tree_util.tree_leaves_with_path(ref.from_system(s_grad))
    r_leaves = jax.tree_util.tree_leaves(r_grad)
    # embedding, head and final norm; 4 layers of 2 norms, 4 attention
    # leaves, 8 of the mixer, 3 of the MLP
    assert len(s_leaves) == len(r_leaves) == 3 + 4 * 17
    kinds = set()
    for (path, got), want in zip(s_leaves, r_leaves):
        name = jax.tree_util.keystr(path)
        kinds.add(name.rsplit("['", 1)[-1].rstrip("']"))
        err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-12)
        assert err < 2e-4, (name, err)
        assert np.linalg.norm(want) > 0, name
    assert {"embed", "lm_head", "final_norm", "attn_norm", "mlp_norm",
            "q_proj", "k_proj", "v_proj", "o_proj", "in_proj", "conv",
            "conv_bias", "A_log", "dt_bias", "D", "norm", "out_proj",
            "gate_proj", "up_proj", "down_proj"} == kinds
    # every leaf the reference's round check names is a leaf of this tree
    for path in ref.GRAD_LEAVES.values():
        leaf = ref.from_system(params)
        for key in path:
            leaf = leaf[key]
        assert leaf.size > 0


def _scan_operands(seq=SEQ, heads=4, groups=2, width=8, state=6):
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(keys[0], (1, seq, heads, width))
    # Mamba-2's draw: dt log-uniform in [1e-3, 1e-1], A uniform in [1, 16]
    dt = jnp.exp(jax.random.uniform(keys[1], (1, seq, heads),
                                    minval=np.log(1e-3), maxval=np.log(1e-1)))
    a = -jnp.exp(jax.random.uniform(keys[2], (heads,), minval=0.0,
                                    maxval=np.log(16.0)))
    b = jax.random.normal(keys[3], (1, seq, groups, state))
    c = jax.random.normal(keys[4], (1, seq, groups, state))
    return x, dt, a, b, c, jnp.linspace(0.5, 1.5, heads)


@pytest.mark.parametrize("chunk", [4, 8, SEQ, 16],
                         ids=["chunk4", "chunk8", "whole_sequence",
                              "chunk_that_does_not_divide"])
def test_chunked_scan_is_the_token_by_token_recurrence(chunk):
    """Forward and the gradient of every operand, at chunk sizes 4, 8, the
    whole sequence and one the sequence does not divide by, on a draw whose
    states cross the chunks (what a chunk keeps of its entering state: over
    0.1 in the mean)."""
    x, dt, a, b, c, d = _scan_operands()

    def chunked(x, dt, a, b, c, d):
        return decoder.ssm_scan(x, dt, a, b, c, d, chunk)[0]

    def plain(x, dt, a, b, c, d):
        return ref.recurrence(x[0], dt[0], a, b[0], c[0], d)[None]

    want = plain(x, dt, a, b, c, d)
    _close(chunked(x, dt, a, b, c, d), want, tol=1e-5)
    keep = decoder.ssm_scan(x, dt, a, b, c, d, chunk)[1]
    assert keep.shape == (1, -(-SEQ // chunk), 4)
    if chunk <= 8:
        assert float(keep.mean()) > 0.1
    weights = jax.random.normal(jax.random.PRNGKey(12), want.shape)
    got_g = jax.grad(lambda *t: jnp.sum(chunked(*t) * weights),
                     argnums=range(6))(x, dt, a, b, c, d)
    want_g = jax.grad(lambda *t: jnp.sum(plain(*t) * weights),
                      argnums=range(6))(x, dt, a, b, c, d)
    for name, got, want in zip("x dt a b c d".split(), got_g, want_g):
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err < 2e-5, (name, err)


def test_the_carry_between_chunks_matters_on_this_draw():
    """With every chunk started from a zero state (the control's fault) the
    scan is far from the recurrence, and is the reference's with its state
    zeroed at every chunk's first token."""
    x, dt, a, b, c, d = _scan_operands()
    want = ref.recurrence(x[0], dt[0], a, b[0], c[0], d)
    local = ref.recurrence(x[0], dt[0], a, b[0], c[0], d, reset_every=8)
    assert float(jnp.max(jnp.abs(local - want))) > 0.1
    # the first chunk has nothing to carry
    _close(local[:8], want[:8], tol=1e-6)
    own = jnp.ones((1, 5, 2, 2, 3, 4))
    keep = jnp.full((1, 5, 2, 2), 0.5)
    entering = decoder.carried_states(own, keep)
    np.testing.assert_allclose(
        np.asarray(entering[0, :, 0, 0, 0, 0]),
        [0.0, 1.0, 1.5, 1.75, 1.875])


def test_mixer_is_causal_and_is_the_references():
    """A change to token ``t`` moves no output before ``t``; the mixer whole
    is the reference's on the same weights."""
    cfg = decoder.held_config(TINY)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, SEQ, cfg["hidden_size"]))
    layer, p = _mixer(cfg), _mixer_params(cfg, x)
    out = layer.apply({"params": p}, x)
    t = 19
    moved = np.any(np.asarray(
        layer.apply({"params": p}, x.at[0, t].add(1.0)) != out)[0], axis=-1)
    assert not moved[:t].any() and moved[t:t + 4].all()
    _close(out[0], ref.ssm(p, x[0], cfg))


@pytest.mark.parametrize("tap", [0, 1, 2, 3])
def test_conv_taps_are_in_the_stated_order_with_their_bias(tap):
    """Tap ``j`` alone weighs the token ``3 - j`` back: ``c[t] = w[:, 0]
    u[t-3] + w[:, 1] u[t-2] + w[:, 2] u[t-1] + w[:, 3] u[t] + bias``, zeros
    before the sequence; the mixer reads its taps and its bias so."""
    u = jax.random.normal(jax.random.PRNGKey(4), (SEQ, 8))
    w = jnp.zeros((8, 4)).at[:, tap].set(jnp.arange(1.0, 9.0))
    bias = jnp.linspace(-1.0, 1.0, 8)
    back = 3 - tap
    want = jnp.arange(1.0, 9.0) * jnp.pad(u, [(back, 0), (0, 0)])[:SEQ] + bias
    _close(ref.causal_conv(u, w, bias), want, tol=1e-6)
    _close(decoder.short_conv(u[None], w)[0] + bias, want, tol=1e-6)
    # in the mixer: with one tap alone the conv's output at t is the input
    # at t - back; the reference reads the same leaves the same way
    cfg = decoder.held_config(TINY)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, SEQ, cfg["hidden_size"]))
    p = _mixer_params(cfg, x)
    one = {**p, "conv": jnp.zeros_like(p["conv"]).at[:, tap].set(1.5)}
    _close(_mixer(cfg).apply({"params": one}, x)[0], ref.ssm(one, x[0], cfg))
    other = {**p, "conv": jnp.zeros_like(p["conv"]).at[:, (tap + 1) % 4]
             .set(1.5)}
    assert float(jnp.max(jnp.abs(
        _mixer(cfg).apply({"params": one}, x)
        - _mixer(cfg).apply({"params": other}, x)))) > 1e-3
    shifted = {**one, "conv_bias": one["conv_bias"] + 0.25}
    assert float(jnp.max(jnp.abs(
        _mixer(cfg).apply({"params": one}, x)
        - _mixer(cfg).apply({"params": shifted}, x)))) > 1e-3


MULTIPLIERS = [
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier"] + [("ssm_multipliers", i) for i in range(5)] + [
        ("mlp_multipliers", i) for i in range(2)]


@pytest.mark.parametrize("which", MULTIPLIERS, ids=[
    m if isinstance(m, str) else f"{m[0]}_{m[1]}" for m in MULTIPLIERS])
def test_each_multiplier_moves_the_output_when_changed_alone(which):
    """Each of the twelve muP multipliers and the MLP's two is read from the
    config as a key: changed alone it moves the logits, as it moves the
    reference's; absent it is 1."""
    cfg, model, params, tokens, _ = _model_and_batch()
    base = model.apply({"params": params}, tokens)
    changed = dict(cfg)
    if isinstance(which, str):
        changed[which] = cfg[which] * 1.5
        absent = {k: v for k, v in cfg.items() if k != which}
        one = {**cfg, which: 1}
    else:
        key, i = which
        changed[key] = [v * (1.5 if j == i else 1)
                        for j, v in enumerate(cfg[key])]
        absent = {k: v for k, v in cfg.items() if k != key}
        one = {**cfg, key: [1] * len(cfg[key])}
    got = decoder.Decoder(decoder._freeze(changed)).apply(
        {"params": params}, tokens)
    assert float(jnp.max(jnp.abs(got - base))) > 1e-4
    _close(got[0], ref.forward(ref.from_system(params), tokens[0], changed))
    np.testing.assert_array_equal(
        decoder.Decoder(decoder._freeze(absent)).apply(
            {"params": params}, tokens),
        decoder.Decoder(decoder._freeze(one)).apply(
            {"params": params}, tokens))


@pytest.mark.parametrize("kind", ["attention", "ssm", "mlp", "block"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """The parts that the shares of a layer compute add up to what the uncut
    reference gives for the whole layer: attention's four (a KV head with
    its query heads), the mixer's two (whole groups: the scan and the gated
    norm over a group are local), the MLP's eight (columns of ``gate_proj``
    and ``up_proj``, rows of ``down_proj``), and the block with both mixers
    counted once each."""
    cfg = decoder.held_config(TINY)
    hidden, d = cfg["hidden_size"], cfg["head_dim"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, hidden))
    plan = decoder.layer_plan(cfg, 0)
    mult = plan["multipliers"]

    def attention(q_heads, kv_heads):
        return decoder.Attention(
            "full_attention", q_heads, kv_heads, d, 0,
            decoder._freeze(plan["rope"]), False, False, plan["eps"], (),
            mult["key"])

    def attention_shares(p, parts, q_heads, kv_heads):
        g = q_heads // kv_heads
        for s in range(parts):      # KV head(s) s with their query heads
            q = slice(s * g * d * kv_heads // parts,
                      (s + 1) * g * d * kv_heads // parts)
            kv = slice(s * d * kv_heads // parts,
                       (s + 1) * d * kv_heads // parts)
            yield {"q_proj": p["q_proj"][:, q], "o_proj": p["o_proj"][q],
                   "k_proj": p["k_proj"][:, kv], "v_proj": p["v_proj"][:, kv]}

    def ssm_shares(p):
        ch, gs, h = 64, 32, 4       # channels, B's (and C's) columns, heads
        for s in range(2):          # group s with its two heads, whole
            c = slice(s * 32, (s + 1) * 32)
            cols = np.concatenate([
                np.arange(32) + s * 32,                 # z
                ch + np.arange(32) + s * 32,            # x
                2 * ch + np.arange(16) + s * 16,        # B
                2 * ch + gs + np.arange(16) + s * 16,   # C
                2 * ch + 2 * gs + np.arange(2) + s * 2])    # dt
            mixed = np.concatenate([np.arange(32) + s * 32,
                                    ch + np.arange(16) + s * 16,
                                    ch + gs + np.arange(16) + s * 16])
            yield {"in_proj": p["in_proj"][:, cols], "conv": p["conv"][mixed],
                   "conv_bias": p["conv_bias"][mixed],
                   "dt_bias": p["dt_bias"][2 * s:2 * s + 2],
                   "A_log": p["A_log"][2 * s:2 * s + 2],
                   "D": p["D"][2 * s:2 * s + 2], "norm": p["norm"][c],
                   "out_proj": p["out_proj"][c]}

    def mlp_shares(p, parts=8):
        w = p["gate_proj"].shape[1] // parts
        for s in range(parts):
            yield {"gate_proj": p["gate_proj"][:, s * w:(s + 1) * w],
                   "up_proj": p["up_proj"][:, s * w:(s + 1) * w],
                   "down_proj": p["down_proj"][s * w:(s + 1) * w]}

    total = 0.0
    if kind == "attention":
        # eight query heads on four KV heads, as the published model's 20
        # on 4 go four ways
        p = _scaled(attention(8, 4).init(jax.random.PRNGKey(7), x)["params"])
        want = jnp.stack([ref.attention(p, x[b], {
            **cfg, "attention_in_multiplier": 1}) for b in range(2)])
        for share in attention_shares(p, 4, 8, 4):
            total = total + attention(2, 1).apply({"params": share}, x)
    elif kind == "ssm":
        p = _mixer_params(cfg, x)
        want = jnp.stack([ref.ssm(p, x[b], cfg) for b in range(2)])
        held = decoder.held_config(TINY, decoder.Share(ssm_shards=2))
        assert (held["mamba_n_heads"], held["mamba_n_groups"]) == (2, 1)
        for share in ssm_shares(p):
            part = _mixer(held).apply({"params": share}, x)
            _close(part[0], ref.ssm(share, x[0], held))
            total = total + part
    elif kind == "mlp":
        whole = decoder.SwiGLU(cfg["intermediate_size"], mult["mlp"])
        p = _scaled(whole.init(jax.random.PRNGKey(8), x)["params"])
        want = jnp.stack([ref.mlp(p, x[b], cfg) for b in range(2)])
        held = decoder.held_config(TINY, decoder.Share(mlp_shards=8))
        assert held["mlp_columns"] == 128
        for share in mlp_shares(p):
            total = total + decoder.SwiGLU(128, mult["mlp"]).apply(
                {"params": share}, x)
    else:
        # one whole block; two shares of it, each with one KV head, one
        # group and half the MLP's columns: the residual stream entered
        # both, so it is counted once
        whole = decoder.Block(decoder._freeze(cfg), 0)
        p = _scaled(whole.init(jax.random.PRNGKey(9), x)["params"])
        want = jnp.stack([ref.block(p, x[b], cfg) for b in range(2)])
        _close(whole.apply({"params": p}, x), want)
        held = decoder.held_config(TINY, decoder.Share(
            tensor_shards=2, ssm_shards=2, mlp_shards=2))
        part = decoder.Block(decoder._freeze(held), 0)
        eps = cfg["rms_norm_eps"]
        mixed = x
        for attn, ssm in zip(attention_shares(p["attention"], 2, 4, 2),
                             ssm_shares(p["ssm"])):
            share = {**p, "attention": attn, "ssm": ssm,
                     "mlp": jax.tree_util.tree_map(jnp.zeros_like, next(
                         mlp_shares(p["mlp"], 2)))}
            # a share's block without its MLP: x + its mixers' parts
            mixed = mixed + part.apply({"params": share}, x) - x
        h = decoder.rms_norm(mixed, p["mlp_norm"], eps)
        total = mixed
        for share in mlp_shares(p["mlp"], 2):
            total = total + decoder.SwiGLU(512, mult["mlp"]).apply(
                {"params": share}, h)
    _close(total, want)


def test_small_ssm_leaves_stay_float32_beside_a_bfloat16_compute_copy():
    """``A_log``, ``dt_bias`` and ``D`` are named by the model and the apply
    closure's cast leaves them float32: with a bfloat16 copy of everything
    else, ``A_log`` values that bfloat16 would tie still give different
    outputs."""
    _, model, params, tokens, _ = _model_and_batch()
    assert {"A_log", "dt_bias", "D"} <= set(model.float32_leaves)
    apply_fn = make_apply_fn(model, jnp.bfloat16)
    a_log = params["layers_0"]["ssm"]["A_log"]
    nudged = jax.tree_util.tree_map_with_path(
        lambda path, a: a * (1 + 2.0 ** -11) if path[-1].key == "A_log"
        else a, params)
    assert np.all(np.asarray(a_log.astype(jnp.bfloat16)) == np.asarray(
        nudged["layers_0"]["ssm"]["A_log"].astype(jnp.bfloat16)))
    seen = {}
    from neuroimagedisttraining_tpu import models as zoo

    real_apply = model.apply

    def spy(variables, *args, **kwargs):
        seen.update({jax.tree_util.keystr(p): a.dtype for p, a in
                     jax.tree_util.tree_leaves_with_path(variables["params"])})
        return real_apply(variables, *args, **kwargs)

    object.__setattr__(model, "apply", spy)
    try:
        out = zoo.make_apply_fn(model, jnp.bfloat16)(
            params, tokens, train=False, rng=None)
    finally:
        object.__delattr__(model, "apply")
    kept = {k: v for k, v in seen.items()
            if k.rsplit("['", 1)[-1].rstrip("']") in ("A_log", "dt_bias",
                                                      "D")}
    assert len(kept) == 12 and set(kept.values()) == {jnp.dtype("float32")}
    assert {v for k, v in seen.items() if k not in kept} == {
        jnp.dtype("bfloat16")}
    assert out.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(
        out - apply_fn(nudged, tokens, train=False, rng=None)))) > 0


@pytest.mark.parametrize("name,layer,want", [
    (TINY, 3, {"kind": "full_attention", "sparse": False, "ssm": True,
               "heads": 4, "head_dim": 16, "eps": 1e-5, "gate": False,
               "qk_norm": False,
               "rope": {"rope_theta": 100, "rope_type": "default"}}),
    ("falcon_h1_34b", 71, {"kind": "full_attention", "sparse": False,
                           "ssm": True, "heads": 20, "head_dim": 128,
                           "rope": {"rope_theta": 100000000000,
                                    "rope_type": "default"}}),
    ("laguna_s", 1, {"kind": "sliding_attention", "sparse": True,
                     "ssm": False, "heads": 72, "head_dim": 128,
                     "gate": True, "scale": 2.5, "score": "softmax"}),
    ("keye_vl2", 7, {"kind": "selected_attention", "sparse": True,
                     "ssm": False, "heads": 32, "qk_norm": True}),
    ("lfm2_8b_a1b", 0, {"kind": "conv", "sparse": False, "ssm": False,
                        "head_dim": 64, "eps": 1e-5, "score": "sigmoid"}),
    ("lfm2_8b_a1b", 2, {"kind": "full_attention", "sparse": True,
                        "ssm": False}),
])
def test_layer_plan_reads_the_block_form_from_the_keys(name, layer, want):
    """A config with ``mamba_d_ssm`` and no ``layer_types``: every layer is
    attention AND a state-space mixer side by side, its MLP dense (no
    ``num_experts``); Laguna's, Keye's and LFM2's keys read as before, and
    their multipliers are all 1."""
    cfg = decoder.held_config(name)
    plan = decoder.layer_plan(cfg, layer)
    assert {k: plan[k] for k in want} == want
    flat = [v for value in plan["multipliers"].values()
            for v in (value if isinstance(value, tuple) else (value,))]
    assert len(flat) == 14
    if plan["ssm"]:
        pub = decoder.CONFIGS[name]
        assert plan["multipliers"]["key"] == pub["key_multiplier"]
        assert plan["multipliers"]["ssm"] == tuple(pub["ssm_multipliers"])
        assert plan["multipliers"]["mlp"] == tuple(pub["mlp_multipliers"])
        assert all(v != 1 for v in flat) or name != TINY
        # what a layer is comes from the keys, never from a model's name
        assert decoder.layer_plan({**cfg, "model_type": "another"},
                                  layer) == plan
    else:
        assert all(v == 1 for v in flat)


@pytest.mark.parametrize("name,share,match", [
    (TINY, decoder.Share(ssm_shards=3), "mamba_n_groups 2 does not divide "
                                        "over 3 chips"),
    (TINY, decoder.Share(ssm_shards=4), "mamba_n_groups 2 does not divide"),
    ("odd_heads", decoder.Share(ssm_shards=2), "mamba_n_heads 5 does not "
                                               "divide over 2 chips"),
    # 0 is "as the heads": four ways divide the KV heads' two no more
    (TINY, decoder.Share(tensor_shards=4), "num_key_value_heads 2 does not"),
    (TINY, decoder.Share(mlp_shards=16), "intermediate_size 1024 does not "
                                         "divide over 16 chips in whole "
                                         "tiles of 128"),
    (TINY, decoder.Share(mlp_shards=3), "intermediate_size 1024 does not"),
    ("laguna_tiny", decoder.Share(ssm_shards=2), "ssm_shards 2 on a config "
                                                 "without mamba_n_groups"),
    ("lfm2_tiny", decoder.Share(6, 4, 2, mlp_shards=2),
     "mlp_shards 2 on a config with num_experts"),
    (TINY, decoder.Share(layers=7), "7 layers of 6"),
])
def test_held_config_refuses_with_the_keys_name(name, share, match,
                                                monkeypatch):
    monkeypatch.setitem(decoder.CONFIGS, "odd_heads",
                        dict(decoder.CONFIGS[TINY], mamba_n_heads=5))
    with pytest.raises(ValueError, match=match):
        decoder.held_config(name, share)


def test_share_cuts_counts_never_widths_and_builds_the_stated_model():
    share = decoder.Share(4, 1, 4, 0, 8, 2, 8)
    cfg = decoder.held_config("falcon_h1_34b", share)
    pub = decoder.CONFIGS["falcon_h1_34b"]
    for width in ("hidden_size", "intermediate_size", "head_dim",
                  "mamba_d_ssm", "mamba_d_head", "mamba_d_state",
                  "mamba_d_conv", "mamba_chunk_size", "rope_theta",
                  "rms_norm_eps", "key_multiplier", "ssm_multipliers",
                  "mlp_multipliers"):
        assert cfg[width] == pub[width]
    assert {k: cfg[k] for k in cfg["published"]} == {
        "num_hidden_layers": 4, "num_attention_heads": 5,
        "num_key_value_heads": 1, "vocab_size": 32640, "mamba_n_heads": 16,
        "mamba_n_groups": 1, "mlp_columns": 2688}
    assert cfg["published"] == {
        "num_hidden_layers": 72, "num_attention_heads": 20,
        "num_key_value_heads": 4, "vocab_size": 261120, "mamba_n_heads": 32,
        "mamba_n_groups": 2, "mlp_columns": 21504}
    assert "first_expert" not in cfg and "num_experts" not in cfg
    # without the two new shards a config holds what it held: the mixer goes
    # as the heads do, the MLP whole
    plain = decoder.held_config(TINY, decoder.Share(tensor_shards=2))
    assert plain["mamba_n_groups"] == 1 and "mlp_columns" not in plain
    assert "mlp_columns" not in decoder.held_config("laguna_tiny")[
        "published"]
    model = create_model("falcon_h1_34b", num_classes=32640, layers=4,
                         tensor_shards=4, vocab_shards=8, ssm_shards=2,
                         mlp_shards=8)
    shapes = jax.eval_shape(lambda: init_params(
        model, jax.random.PRNGKey(0), (16,), jnp.int32))
    # ISSUE 39's table, leaf for leaf
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) \
        == 667_589_824
    layer = shapes["layers_3"]
    assert {k: v.shape for k, v in layer["ssm"].items()} == {
        "in_proj": (5120, 4624), "conv": (2560, 4), "conv_bias": (2560,),
        "dt_bias": (16,), "A_log": (16,), "D": (16,), "norm": (2048,),
        "out_proj": (2048, 5120)}
    assert {k: v.shape for k, v in layer["attention"].items()} == {
        "q_proj": (5120, 640), "k_proj": (5120, 128), "v_proj": (5120, 128),
        "o_proj": (640, 5120)}
    assert {k: v.shape for k, v in layer["mlp"].items()} == {
        "gate_proj": (5120, 2688), "up_proj": (5120, 2688),
        "down_proj": (2688, 5120)}
    assert shapes["embed"].shape == (32640, 5120)
    assert shapes["lm_head"].shape == (5120, 32640)
    assert model.tpu_compiler_options == {}
    rows = {r["name"]: r["params"] for r in ref.layers(cfg, 8192)}
    assert sum(rows.values()) == 667_589_824
    assert rows["ssm_proj"] + rows["ssm_conv"] + rows["ssm_scan"] \
        + rows["ssm_norm"] == 136_702_144
    assert rows["attention_proj"] == 31_457_280
    assert rows["dense_mlp"] == 165_150_720
    assert rows["embed"] + rows["lm_head"] == 334_233_600
    assert rows["norms"] == 46_080


def test_real_width_gradient_lowers_with_the_embeddings_own_backward():
    """One layer of the cell's share at its real widths, 256 tokens, nothing
    allocated (shapes only): the gradient program lowers, the ``embed``
    leaf's gradient has the held table's shape ``[32640, 5120]`` and the
    master's dtype, and at 5120 columns the lookup's backward is its own:
    two scatter-adds, 4096 and 1024 columns wide, none of 5120."""
    import re

    model = create_model("falcon_h1_34b", num_classes=32640, layers=1,
                         tensor_shards=4, vocab_shards=8, ssm_shards=2,
                         mlp_shards=8)
    params = jax.eval_shape(lambda: init_params(
        model, jax.random.PRNGKey(0), (16,), jnp.int32))
    assert params["embed"].shape == (32640, 5120)
    apply_fn = make_apply_fn(model, jnp.bfloat16)
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32)

    def loss(p, x, y):
        logits = apply_fn(p, x, train=True, rng=jax.random.PRNGKey(0))
        return jnp.mean(PER_EXAMPLE_LOSSES["token_ce"](logits, y))

    before = obs_metrics.set_registry(None)
    try:
        traced = jax.jit(jax.grad(loss)).trace(params, tokens, tokens)
        counted = obs_metrics.get_registry().snapshot()[
            "embed_lowerings"]["labeled"]
    finally:
        obs_metrics.set_registry(before)
    grads = traced.out_info
    assert grads["embed"].shape == (32640, 5120)
    assert grads["embed"].dtype == jnp.float32
    assert counted == {"pass=forward,spelling=slabs": 1.0,
                       "pass=backward,spelling=slabs": 1.0}
    text = traced.lower().as_text()
    wide = sorted(int(w) for w in re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<32640x(\d+)xbf16>', text,
        flags=re.S))
    assert wide == [1024, 4096], wide


def test_small_leaves_are_drawn_as_mamba2_draws_them():
    """``A`` in [1, 16], ``dt`` in [1e-3, 1e-1] through the inverse softplus,
    ``D`` ones, taps and bias in +-1/2: states cross chunk boundaries on a
    seeded model, and the gauge the program's one forward sets says so."""
    from neuroimagedisttraining_tpu.algorithms import FedAvg
    from neuroimagedisttraining_tpu.core.trainer import HyperParams
    from neuroimagedisttraining_tpu.data.tokens import make_token_shards

    cfg = decoder.held_config(TINY, SHARE)
    data = make_token_shards(0, n_clients=2, vocab=cfg["vocab_size"],
                             sequence_length=SEQ, train_per_client=1)
    algo = FedAvg(decoder.decoder(TINY, SHARE), data,
                  HyperParams(lr=0.1, local_epochs=1, steps_per_epoch=1,
                              batch_size=1),
                  loss_type="token_ce", seed=0, client_chunk=1,
                  track_personal=False)
    params = algo.init_state(jax.random.PRNGKey(0)).global_params
    for i in range(4):
        p = params[f"layers_{i}"]["ssm"]
        a, dt = np.exp(p["A_log"]), np.log1p(np.exp(p["dt_bias"]))
        assert np.all((a >= 1) & (a <= 16)) and np.all(p["D"] == 1)
        assert np.all((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001))
        assert np.all(np.abs(p["conv"]) <= 0.5) and np.std(p["conv"]) > 0.2
        assert np.all(np.abs(p["conv_bias"]) <= 0.5)
    registry = obs_metrics.MetricsRegistry()
    got = record_expert_load(algo, params, registry)
    assert set(got) == set(registry.snapshot()) == {"ssm_chunk_carry",
                                                    "ssm_dt_mean"}
    assert 0.1 < got["ssm_chunk_carry"] < 1.0
    assert 1e-3 < got["ssm_dt_mean"] < 0.2
    # by hand, from what the layers sowed
    _, sown = algo.apply_fn(params, data.x_train[0, :1], train=False,
                            rng=None, mutable=[decoder.EXPERT_STATS])
    kept = [sown[decoder.EXPERT_STATS][f"layers_{i}"]["ssm"][
        "ssm_chunk_keep"][0] for i in range(4)]
    assert kept[0].shape == (1, SEQ // 8, 2)
    assert got["ssm_chunk_carry"] == pytest.approx(float(np.mean(kept)))


def test_a_round_through_the_cli_trains_every_leaf():
    """Through the program's CLI modules and ``FedAlgorithm.run``: ``--model
    falcon_h1_tiny`` with the cell's flags (the two new ones among them)
    trains in the folding round, the loss falls and every leaf moves."""
    from neuroimagedisttraining_tpu.experiments import parse_args, runner
    from neuroimagedisttraining_tpu.experiments.config import run_identity

    argv = [
        "--algo", "fedavg", "--model", TINY, "--lm_layers", "4",
        "--lm_tensor_shards", "2", "--lm_ssm_shards", "2",
        "--lm_mlp_shards", "2", "--lm_vocab_shards", "4", "--dataset",
        "token_shards", "--track_personal", "0", "--momentum", "0",
        "--batch_size", "1", "--epochs", "1", "--lr", "0.5", "--lr_decay",
        "0.998", "--grad_clip", "10", "--client_num_in_total", "2", "--frac",
        "1.0", "--frequency_of_the_test", "0", "--seed", "5"]
    args = parse_args(argv)
    assert "lm4e1t2-v4-s2-m2" in run_identity(args).replace("_", "-")
    algo, _ = runner.build_algorithm(args, "fedavg")
    assert algo.clients_per_round == 2 and algo.data.class_num == 16
    state = algo.init_state(jax.random.PRNGKey(5))
    before = jax.device_get(state.global_params)
    assert before["layers_0"]["mlp"]["up_proj"].shape == (64, 512)
    assert before["layers_0"]["ssm"]["in_proj"].shape == (64, 2 * 32 + 2 * 16
                                                          + 2)
    state, history = algo.run(4, eval_every=0, state=state, finalize=False)
    after = jax.device_get(state.global_params)
    losses = [float(rec["train_loss"]) for rec in history]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    moved = {jax.tree_util.keystr(path): bool(np.any(a != b))
             for (path, a), b in zip(
                 jax.tree_util.tree_leaves_with_path(after),
                 jax.tree_util.tree_leaves(before))}
    assert len(moved) == 3 + 4 * 17 and all(moved.values()), moved
    # the flags are known to the identity table
    from neuroimagedisttraining_tpu.analysis.identity import FLAG_CLASSES
    assert FLAG_CLASSES["lm_ssm_shards"][0] == "identity"
    assert FLAG_CLASSES["lm_mlp_shards"][0] == "identity"
