"""The SambaY decoder-hybrid-decoder (models/decoder.py, ``phi4_flash_tiny``:
24 layers by the published index rule, so that layers 8-17 are the published
stage's pattern; 8 query and 4 KV heads of 8, differential in pairs; Mamba-1
mixers of 1024 channels with state 8 in chunks of 8; GMUs reading the
memory's channels) held to its plain reference
(benchmarks/reference/phi4_flash.py, which runs the mixer as the
token-by-token recurrence and attention with explicit masks) on seeded
weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4_flash as ref
from neuroimagedisttraining_tpu.core.losses import PER_EXAMPLE_LOSSES
from neuroimagedisttraining_tpu.models import decoder, make_apply_fn
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.obs.expert_load import record_expert_load

TINY = "phi4_flash_tiny"
# layers 8-17 (Mamba, window, Mamba, window, the memory's Mamba, full, GMU,
# cross, GMU, cross); heads 2 ways, the vocabulary 4, the MLP and GMU 8
SHARE = decoder.Share(layers=10, tensor_shards=2, vocab_shards=4,
                      mlp_shards=8, first_layer=8)
SEQ = 40        # five chunks of 8


def _scaled(params, scale=4.0):
    """The matrices scaled up so that attention and the gates are far from
    their trivial values; the norms' vectors moved by a tenth (the mixer's
    small leaves are drawn so that states outlive their chunks, and stay)."""
    def one(path, a):
        if path[-1].key in ("A_log", "dt_bias", "D", "conv", "conv_bias") \
                or path[-1].key.startswith("lambda"):
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


def test_layer_kinds_follow_the_published_index_rule():
    whole = decoder.held_config("phi4_mini_flash")
    kinds = whole["layer_types"]
    assert kinds[:16] == ["mamba", "sliding_attention"] * 8
    assert kinds[16:18] == ["mamba_memory", "full_attention"]
    assert kinds[18:] == ["gmu", "cross_attention"] * 7
    held = decoder.held_config("phi4_mini_flash", decoder.Share(
        layers=10, tensor_shards=2, vocab_shards=8, mlp_shards=8,
        first_layer=12))
    assert held["layer_types"] == kinds[12:22]
    assert (held["first_layer"], held["num_attention_heads"],
            held["num_key_value_heads"], held["mlp_columns"],
            held["gmu_channels"], held["vocab_size"]) == (
                12, 20, 10, 1280, 640, 25008)
    plan = decoder.layer_plan(held, 5)
    assert (plan["kind"], plan["published_layer"], plan["head_dim"],
            plan["rope"], plan["layer_norm"], plan["differential"]) == (
                "full_attention", 17, 64, None, True, True)
    # the tiny preset's stage is the published stage's pattern
    assert decoder.held_config(TINY, SHARE)["layer_types"] == kinds[12:22]
    # a leading stage needs every kind: up to the first cross layer
    assert decoder.least_layers(whole) == 20


@pytest.mark.parametrize("share,match", [
    (decoder.Share(layers=6, first_layer=8), "leave out the kind"),
    (decoder.Share(layers=15), "cut a period"),
    (decoder.Share(layers=4, first_layer=22), "4 layers of 24 from layer 22"),
    (decoder.Share(tensor_shards=4), "heads go in pairs"),
    (decoder.Share(mlp_shards=16), "in whole tiles of 128"),
])
def test_held_config_refuses_a_stage_or_share_the_model_cannot_take(
        share, match):
    with pytest.raises(ValueError, match=match):
        decoder.held_config(TINY, share)


def test_held_config_refuses_gmu_channels_that_do_not_tile(monkeypatch):
    """An MLP that divides in whole tiles of 128 beside a GMU whose channels
    do not."""
    monkeypatch.setitem(decoder.CONFIGS, "odd_gmu",
                        {**decoder.CONFIGS[TINY], "mamba_expand": 12})
    decoder.held_config("odd_gmu", decoder.Share(mlp_shards=2))
    with pytest.raises(ValueError, match="GMU's 768 channels do not divide"):
        decoder.held_config("odd_gmu", decoder.Share(mlp_shards=8))


def test_whole_model_logits_loss_and_the_gradient_of_every_leaf():
    """Every leaf's gradient, among them the full layer's K and V
    projections (fed by the two cross layers that read them) and the
    memory's Mamba ``W_in`` (fed through the two GMUs)."""
    cfg, model, params, tokens, targets = _model_and_batch()
    apply_fn = make_apply_fn(model)
    loss = PER_EXAMPLE_LOSSES["token_ce"]

    def system(p):
        logits = apply_fn(p, tokens, train=True, rng=jax.random.PRNGKey(0))
        return jnp.mean(loss(logits, targets)), logits

    def plain(p):
        out = [ref.loss_and_logits(p, tokens[b], targets[b], cfg)
               for b in range(2)]
        logits = jnp.stack([ref.forward(p, tokens[b], cfg)[0]
                            for b in range(2)])
        return (out[0][0] + out[1][0]) / 2, logits

    (s_loss, s_logits), s_grad = jax.value_and_grad(system, has_aux=True)(
        params)
    (r_loss, r_logits), r_grad = jax.value_and_grad(plain, has_aux=True)(
        ref.from_system(params))
    assert float(jnp.max(jnp.abs(r_logits))) > 0.1
    _close(s_logits, r_logits)
    _close(s_loss, r_loss)
    s_leaves = jax.tree_util.tree_leaves_with_path(ref.from_system(s_grad))
    r_leaves = jax.tree_util.tree_leaves(r_grad)
    # embedding and final norm's two; per layer 4 norm leaves, 3 of the
    # MLP; 9 of a Mamba mixer, 9 of an attention layer of its own, 7 of a
    # cross layer, 2 of a GMU
    assert len(s_leaves) == len(r_leaves) == 3 + 10 * 7 + 3 * 9 + 3 * 9 \
        + 2 * 7 + 2 * 2
    kinds = set()
    for (path, got), want in zip(s_leaves, r_leaves):
        name = jax.tree_util.keystr(path)
        kinds.add(name.rsplit("['", 1)[-1].rstrip("']"))
        err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-12)
        assert err < 2e-4, (name, err)
        assert np.linalg.norm(want) > 0, name
    assert {"in_proj", "conv", "conv_bias", "x_proj", "dt_proj", "dt_bias",
            "A_log", "D", "out_proj", "q_proj", "k_proj", "v_proj",
            "o_proj", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
            "subln"} <= kinds
    # what reaches the full layer's K projection and the memory's W_in
    # comes from its readers alone: the layers after them read nothing else
    for leaf in (s_grad["layers_5"]["attention"]["k_proj"],
                 s_grad["layers_4"]["mamba"]["in_proj"]):
        assert np.linalg.norm(leaf) > 0
    for path in ref.GRAD_LEAVES.values():
        leaf = ref.from_system(params)
        for key in path:
            leaf = leaf[key]
        assert leaf.size > 0


def _scan_operands(seq=SEQ, channels=24, state=6):
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    u = jax.random.normal(keys[0], (1, seq, channels))
    delta = jnp.exp(jax.random.uniform(keys[1], (1, seq, channels),
                                       minval=np.log(1e-3),
                                       maxval=np.log(1e-1)))
    a = -jnp.broadcast_to(jnp.arange(1, state + 1, dtype=jnp.float32),
                          (channels, state))
    b = jax.random.normal(keys[2], (1, seq, state))
    c = jax.random.normal(keys[3], (1, seq, state))
    return u, delta, a, b, c, jnp.linspace(0.5, 1.5, channels)


@pytest.mark.parametrize("chunk", [8, 16], ids=["chunk_divides",
                                                "chunk_does_not_divide"])
def test_selective_scan_is_the_token_by_token_recurrence(chunk):
    """Forward and the gradient of every operand, on a draw whose states
    cross the chunks (what a chunk keeps of its entering state: over 0.1 in
    the mean)."""
    u, delta, a, b, c, d = _scan_operands()

    def chunked(u, delta, a, b, c, d):
        return decoder.selective_scan(u, delta, a, b, c, d, chunk)[0]

    def plain(u, delta, a, b, c, d):
        return ref.recurrence(u[0], delta[0], a, b[0], c[0], d)[None]

    _close(chunked(u, delta, a, b, c, d), plain(u, delta, a, b, c, d))
    keep = decoder.selective_scan(u, delta, a, b, c, d, chunk)[1]
    assert keep.shape == (1, -(-SEQ // chunk))
    assert float(jnp.mean(keep)) > 0.1
    w = jax.random.normal(jax.random.PRNGKey(3), u.shape)
    ops = (u, delta, a, b, c, d)
    got = jax.grad(lambda *t: jnp.sum(chunked(*t) * w), range(6))(*ops)
    want = jax.grad(lambda *t: jnp.sum(plain(*t) * w), range(6))(*ops)
    for g, r in zip(got, want):
        assert np.linalg.norm(g - r) <= 1e-4 * np.linalg.norm(r)


@pytest.mark.parametrize("kind,window", [("window", 8), ("full", 0),
                                         ("cross", 0)])
def test_padded_differential_product_is_the_unpadded_one(kind, window):
    """Each map is one product with q and k zero-padded from d to 2d lanes
    and q scaled by sqrt(2): the unpadded maps at scale d^-1/2 under the
    mask, ``A1 v - lam A2 v``."""
    b, s_len, pairs, group, d = 1, 24, 2, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (b, s_len, pairs, group, 2, d)) * 2
    k = jax.random.normal(keys[1], (b, s_len, pairs, 2, d)) * 2
    v = jax.random.normal(keys[2], (b, s_len, pairs, 2 * d))
    lam = 0.37
    got = decoder.differential_maps(q, k, v, lam, window, kind)
    i, j = jnp.arange(s_len)[:, None], jnp.arange(s_len)[None, :]
    seen = (j <= i) & ((j > i - window) if window else True)

    def softmax_map(m):
        scores = jnp.einsum("bqpgd,bkpd->bpgqk", q[..., m, :],
                            k[..., m, :]) / np.sqrt(d)
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)

    want = jnp.einsum("bpgqk,bkpe->bqpge",
                      softmax_map(0) - lam * softmax_map(1), v)
    _close(got, want, 1e-5)


def _attention_shares(p, parts, q_heads, kv_heads, d):
    """The attention layer's shares by whole pairs: q and o by query
    heads, k and v by KV heads; the lambda vectors and the norm whole."""
    qn, kn = q_heads // parts * d, kv_heads // parts * d
    for s in range(parts):
        share = {k: p[k] for k in p if k.startswith("lambda")
                 or k == "subln"}
        share["q_proj"] = p["q_proj"][:, s * qn:(s + 1) * qn]
        share["o_proj"] = p["o_proj"][s * qn:(s + 1) * qn]
        for name in ("k_proj", "v_proj"):
            if name in p:
                share[name] = p[name][:, s * kn:(s + 1) * kn]
        yield share


@pytest.mark.parametrize("part", ["window", "cross", "mlp", "gmu"])
def test_the_shares_add_up_to_the_uncut_layer(part):
    """The 2 attention shares (whole differential pairs), the 8 MLP shares
    and the 8 GMU shares (each reading its channels of the memory, which
    the whole Mamba mixer writes once) add up to the uncut reference's
    layer."""
    cfg = decoder.held_config(TINY)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64))
    total = 0.0
    if part in ("window", "cross"):
        kind = {"window": "sliding_attention", "cross": "cross_attention"}[
            part]
        shared_kv = tuple(jax.random.normal(jax.random.PRNGKey(k),
                                            (2, 24, 4, 8)) for k in (6, 7))
        whole = decoder.DiffAttention(kind, 8, 4, 8, 8, 13, 1e-5)
        p = _scaled(whole.init(jax.random.PRNGKey(7), x, shared_kv)[
            "params"])
        want = jnp.stack([ref.attention(
            p, x[b], cfg, part, 13,
            None if part == "window" else tuple(t[b] for t in shared_kv))[0]
            for b in range(2)])
        _close(whole.apply({"params": p}, x, shared_kv)[0], want)
        for s, share in enumerate(_attention_shares(p, 2, 8, 4, 8)):
            kv = tuple(t[:, :, 2 * s:2 * s + 2] for t in shared_kv)
            total = total + decoder.DiffAttention(
                kind, 4, 2, 8, 8, 13, 1e-5).apply({"params": share}, x, kv)[0]
    elif part == "mlp":
        whole = decoder.SwiGLU(1024)
        p = _scaled(whole.init(jax.random.PRNGKey(8), x)["params"])
        want = jnp.stack([ref.mlp(p, x[b]) for b in range(2)])
        held = decoder.held_config(TINY, decoder.Share(mlp_shards=8))
        assert held["mlp_columns"] == 128
        for c in range(8):
            cols = slice(c * 128, (c + 1) * 128)
            share = {"gate_proj": p["gate_proj"][:, cols],
                     "up_proj": p["up_proj"][:, cols],
                     "down_proj": p["down_proj"][cols]}
            total = total + decoder.SwiGLU(128).apply({"params": share}, x)
    else:
        memory = jax.random.normal(jax.random.PRNGKey(9), (2, 24, 1024))
        whole = decoder.GatedMemory(1024, 0)
        p = _scaled(whole.init(jax.random.PRNGKey(10), x, memory)["params"])
        want = jnp.stack([ref.gmu(p, x[b], memory[b], cfg)
                          for b in range(2)])
        for c in range(8):
            held = decoder.held_config(TINY, decoder.Share(mlp_shards=8,
                                                           index=c))
            assert (held["gmu_channels"], held["first_gmu_channel"]) == (
                128, 128 * c)
            cols = slice(c * 128, (c + 1) * 128)
            share = {"in_proj": p["in_proj"][:, cols],
                     "out_proj": p["out_proj"][cols]}
            part_out = decoder.GatedMemory(128, 128 * c).apply(
                {"params": share}, x, memory)
            _close(part_out[0], ref.gmu(share, x[0], memory[0], {
                **held, "first_gmu_channel": 128 * c}))
            total = total + part_out
    _close(total, want, 1e-5)


def _leaves_drawn_to_outlive_a_chunk_and_the_gauge(algo, params):
    """``A_log`` S4D-real's, ``delta`` log-uniform in [1e-3, 1e-1], ``D``
    ones, taps within +-1/2, the lambda vectors small; the gauge the runner
    sets after ``init_state`` from one forward."""
    data = algo.data
    p = params["layers_0"]["mamba"]
    assert np.allclose(np.exp(p["A_log"]), np.arange(1, 9)[None, :])
    dt = np.log1p(np.exp(p["dt_bias"]))
    assert np.all((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001))
    assert np.all(p["D"] == 1) and np.all(np.abs(p["conv"]) <= 0.5)
    lam = params["layers_1"]["attention"]["lambda_q1"]
    assert lam.dtype == jnp.float32 and 0.02 < np.std(lam) < 0.3
    registry = obs_metrics.MetricsRegistry()
    got = record_expert_load(algo, params, registry)
    assert set(got) == set(registry.snapshot()) == {"mamba_chunk_carry"}
    assert 0.1 < got["mamba_chunk_carry"] < 1.0
    _, sown = algo.apply_fn(params, data.x_train[0, :1], train=False,
                            rng=None, mutable=[decoder.EXPERT_STATS])
    kept = [sown[decoder.EXPERT_STATS][f"layers_{i}"]["mamba"][
        "mamba_chunk_keep"][0] for i in (0, 2, 4)]
    assert kept[0].shape == (1, 16)
    assert got["mamba_chunk_carry"] == pytest.approx(float(np.mean(kept)))
    assert {"lambda_q1", "A_log", "dt_bias", "D"} <= set(
        decoder.Decoder.float32_leaves)


def test_a_round_through_the_cli_trains_every_leaf():
    """Through the program's CLI modules and ``FedAlgorithm.run``: ``--model
    phi4_flash_tiny`` with a stage that starts at layer 8 trains in the
    folding round, the loss falls and every leaf moves; before it, the
    mixers' leaves are drawn so that states outlive a chunk and the gauge
    reads the carry."""
    from neuroimagedisttraining_tpu.analysis.identity import FLAG_CLASSES
    from neuroimagedisttraining_tpu.experiments import parse_args, runner
    from neuroimagedisttraining_tpu.experiments.config import run_identity

    argv = [
        "--algo", "fedavg", "--model", TINY, "--lm_first_layer", "8",
        "--lm_layers", "10", "--lm_tensor_shards", "2", "--lm_mlp_shards",
        "8", "--lm_vocab_shards", "4", "--dataset", "token_shards",
        "--track_personal", "0", "--momentum", "0", "--batch_size", "1",
        "--epochs", "1", "--lr", "0.05", "--lr_decay", "0.998",
        "--grad_clip", "10", "--client_num_in_total", "2", "--frac", "1.0",
        "--frequency_of_the_test", "0", "--seed", "5"]
    args = parse_args(argv)
    assert "lm10e1t2-v4-m8-f8" in run_identity(args).replace("_", "-")
    assert FLAG_CLASSES["lm_first_layer"][0] == "identity"
    algo, _ = runner.build_algorithm(args, "fedavg")
    assert algo.clients_per_round == 2 and algo.data.class_num == 16
    state = algo.init_state(jax.random.PRNGKey(5))
    _leaves_drawn_to_outlive_a_chunk_and_the_gauge(algo, state.global_params)
    before = jax.device_get(state.global_params)
    assert before["layers_6"]["gmu"]["in_proj"].shape == (64, 128)
    assert "k_proj" not in before["layers_7"]["attention"]
    state, history = algo.run(4, eval_every=0, state=state, finalize=False)
    after = jax.device_get(state.global_params)
    losses = [float(rec["train_loss"]) for rec in history]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    moved = {jax.tree_util.keystr(path): bool(np.any(a != b))
             for (path, a), b in zip(
                 jax.tree_util.tree_leaves_with_path(after),
                 jax.tree_util.tree_leaves(before))}
    assert len(moved) == 145 and all(moved.values()), [
        k for k, v in moved.items() if not v]
