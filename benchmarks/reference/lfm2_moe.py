"""Plain float32 reference of ``LFM2-8B-A1B``
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json,
``model_type`` ``lfm2_moe``): forward, per-token cross-entropy and gradients
in straightforward ``jax.numpy``, every product at
``jax.default_matmul_precision("highest")``. Nothing is imported from the
program; no kernel, no cache, no batching (one sequence at a time). What it
shares with the other decoder references (the norm, the SwiGLU, the rotary
embedding, the loss, a counted row) it takes from ``laguna_s.py``.

``cfg`` is the configuration's file
(``benchmarks/configs/lfm2_8b_a1b_fed.json``) with the counts a chip holds
laid over it, or any dictionary with the same
keys. Counts and widths that the weights state (heads and their width, conv
channels and taps, experts held, vocabulary rows, router outputs) are read
from the weights' shapes, so one function serves the uncut model and any
chip's share of it; ``first_expert`` says which of the router's experts the
held ones are.

The equations are transformers' ``modeling_lfm2_moe.py``'s, written from
memory (no network here): each stands under ``ASSUMED`` and in the
configuration's file. ``h`` is ``[T, 2048]``; no ``Linear`` has a bias; keys
of ``config.json`` in brackets::

    block:  h = h + operator(rms_norm(h));  h = h + feed_forward(rms_norm(h))
      rms_norm(x) = x / sqrt(mean(x^2) + 1e-5) * w [norm_eps]; after the last
      block one more (``embedding_norm``), then the head        [assumed 1]
    operator ``conv`` [layer_types], the gated short convolution:
      [B, C, x] = split(h W_in, 3), in that order, W_in [2048, 3 x 2048]
      u = B * x
      c[t] = w[:, 0] u[t-2] + w[:, 1] u[t-1] + w[:, 2] u[t], u zero before
        the sequence: a depthwise Conv1d of kernel 3 [conv_L_cache], groups =
        channels, left padding 2, cut to T; no bias [conv_bias]
      y = (C * c) W_out, W_out [2048, 2048]; no nonlinearity    [assumed 2]
    operator ``full_attention``: q, k, v = h W_q, h W_k, h W_v, 32 / 8 / 8
      heads [num_attention_heads, num_key_value_heads] of 2048 / 32 = 64 (no
      head_dim key); q and k each through an RMSNorm over a head's 64
      features with a learned weight, eps 1e-5, BEFORE the rotary embedding
      (``q_layernorm``, ``k_layernorm``); RoPE over the whole head,
      rotate-half pairs (i, i + 32), f_i = 1e6 ** (-2 i / 64) [rope_theta];
      causal softmax at scale 64 ** -0.5, a KV head shared by 4 query heads;
      W_o; no gate, no window                                    [assumed 3]
    feed_forward of layers 0, 1 [num_dense_layers]: W_2 (silu(W_1 h) * W_3 h),
      width 7168 [intermediate_size]
    feed_forward of the others: s = sigmoid(h W_r) over all 32 experts
      [num_experts], float32; the 4 experts [num_experts_per_tok] are the
      top-4 of s + b, b the ``expert_bias`` [use_expert_bias], float32 [32],
      which enters the choice only; their weights are s at the chosen experts
      (WITHOUT b) over (their sum + 1e-6) [norm_topk_prob], times 1
      [routed_scaling_factor]; the weighted sum of the chosen experts'
      SwiGLUs of width 1792 [moe_intermediate_size]; no shared expert
                                                                 [assumed 4]
    logits = rms_norm(h_last) W_embed^T: embedding and head tied  [assumed 5]

``ASSUMED`` (the catalog's row is silent): (1) the block's order and the
final norm; (2) the conv operator's split order, its taps' order and that it
has no nonlinearity; (3) the QK-norm before the rotary, stated as the key
``qk_norm`` beside the published ones; (4) sigmoid scores (stated as the
key ``scoring_func``, the name DeepSeek-V3's config gives the rule), the
bias in the choice only, the ``1e-6``; (5) the tied head (``Lfm2MoeConfig``'s default
and every LFM2 release's ``tie_embedding: true``), stated as the key
``tie_word_embeddings``.

Departures from the published description:

* **the selection bias is not maintained.** In pre-training ``b`` follows a
  load-balancing update outside the gradient whose rule and rate no public
  config holds. It is left out: ``b`` is drawn from the seed (normal, 0.02,
  as every matrix), the token loss gives it no gradient, and it stays as
  initialised through a round.
* a share's partial results, as in ``laguna_s.py``: the held experts' part of
  the routed sum, the held heads' part of the attention output, the held
  channels' part of the conv operator's output (the same channels of ``B``,
  ``C``, ``x``, of the taps and of ``W_out``'s rows), logits and loss over
  the held vocabulary rows. Sums over all shares give the uncut layer
  (``tests/test_decoder_lfm2.py``).
* the loss ignores targets below 0 (the last position has no next token).
* attention is the masked full product in blocks of ``q_block`` queries
  against every key; with ``remat=True`` a block's scores, a dense MLP's and
  each held expert's activations (every held expert is evaluated densely on
  every token) are computed again going backward.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .laguna_s import _row as _counted_row
from .laguna_s import (_highest, apply_rope, rms_norm, swiglu,
                       token_cross_entropy)

Q_BLOCK = 1024
ASSUMED = ("block_order", "conv_operator", "qk_norm", "sigmoid_router_bias",
           "tied_head")


# -- the forward pass --------------------------------------------------------

@_highest
def short_conv(p, x, mix=True):
    """This share's part of the conv operator's output ``[S, hidden]``.
    ``mix=False`` keeps the current token's tap alone (a control: the
    convolution left out)."""
    gate_in, gate_out, u = jnp.split(x @ p["in_proj"], 3, axis=-1)
    u = gate_in * u
    taps = p["conv"].shape[1]
    mixed = p["conv"][:, taps - 1] * u
    for back in range(1, taps if mix else 1):    # the token ``back`` before
        before = jnp.concatenate([jnp.zeros_like(u[:back]), u[:-back]])
        mixed = mixed + p["conv"][:, taps - 1 - back] * before
    return (gate_out * mixed) @ p["out_proj"]


@_highest
def attention(p, x, cfg, q_block=Q_BLOCK, remat=False):
    """This share's part of the attention output ``[S, hidden]``."""
    s_len, d = x.shape[0], p["q_norm"].shape[0]
    rope = {"rope_theta": cfg["rope_theta"], "rope_type": "default"}
    eps = cfg["norm_eps"]
    q = apply_rope(rms_norm((x @ p["q_proj"]).reshape(s_len, -1, d),
                            p["q_norm"], eps), rope, d)
    k = apply_rope(rms_norm((x @ p["k_proj"]).reshape(s_len, -1, d),
                            p["k_norm"], eps), rope, d)
    v = (x @ p["v_proj"]).reshape(s_len, -1, d)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    k_pos = jnp.arange(s_len)[None, :]

    def block(q_blk, start):
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k) / math.sqrt(d)
        seen = k_pos <= start + jnp.arange(q_blk.shape[0])[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    if remat:
        block = jax.checkpoint(block)
    if s_len % q_block == 0 and s_len > q_block:
        # one loop over the blocks (a traced start, the same body): the
        # compiler sees one block, not S / q_block copies of it
        out = jax.lax.map(
            lambda blk: block(*blk),
            (q.reshape((-1, q_block) + q.shape[1:]),
             jnp.arange(0, s_len, q_block))).reshape(q.shape)
    else:
        out = jnp.concatenate([block(q[i:i + q_block], jnp.asarray(i))
                               for i in range(0, s_len, q_block)], axis=0)
    return out.reshape(s_len, -1) @ p["o_proj"]


@_highest
def route(p, x, cfg, bias=True):
    """``(weights [S, k], experts [S, k])``: the routing of every token over
    all of the router's experts. ``bias=False`` leaves ``expert_bias`` out
    of the choice (a control)."""
    scores = jax.nn.sigmoid(x @ p["router"])
    chosen_by = scores + p["expert_bias"] if bias and cfg["use_expert_bias"] \
        else scores
    _, top_e = jax.lax.top_k(chosen_by, cfg["num_experts_per_tok"])
    top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-6)
    return top_p * cfg["routed_scaling_factor"], top_e


@_highest
def sparse_mlp(p, x, cfg, first_expert=0, remat=False, bias=True):
    """The held experts' part of the routed sum, each held expert evaluated
    densely on every token and weighted by its routing weight (zero where
    the token was not routed to it); the second value is the routing
    ``[S, k]``."""
    top_p, top_e = route(p, x, cfg, bias)

    def expert(weights, x, share):
        return swiglu(weights, x) * share[:, None]

    if remat:
        expert = jax.checkpoint(expert)

    def add(out, held_expert):      # one loop over the held experts
        weights, e = held_expert
        share = jnp.sum(jnp.where(top_e == first_expert + e, top_p, 0.0), -1)
        return out + expert(weights, x, share), None

    held = p["experts"]["gate_proj"].shape[0]
    out, _ = jax.lax.scan(add, jnp.zeros_like(x),
                          (p["experts"], jnp.arange(held)))
    return out, top_e


@_highest
def forward(params, tokens, cfg, first_expert=0, remat=False, mix=True,
            bias=True):
    """``(logits [S, V_held], routing)`` of one sequence: each sparse
    layer's routing ``[S, k]``."""
    x = params["embed"][tokens]
    eps, routing = cfg["norm_eps"], []
    dense = jax.checkpoint(swiglu) if remat else swiglu
    for i, (layer, kind) in enumerate(zip(params["layers"],
                                          cfg["layer_types"])):
        h = rms_norm(x, layer["attn_norm"], eps)
        if kind == "conv":
            x = x + short_conv(layer["conv"], h, mix)
        else:
            x = x + attention(layer["attention"], h, cfg, remat=remat)
        h = rms_norm(x, layer["mlp_norm"], eps)
        if i < cfg["num_dense_layers"]:
            x = x + dense(layer["mlp"], h)
        else:
            y, top_e = sparse_mlp(layer["mlp"], h, cfg, first_expert, remat,
                                  bias)
            x = x + y
            routing.append(top_e)
    x = rms_norm(x, params["final_norm"], eps)
    return x @ params["embed"].T, routing


def loss_and_logits(params, tokens, targets, cfg, first_expert=0,
                    remat=False):
    logits, routing = forward(params, tokens, cfg, first_expert, remat)
    return token_cross_entropy(logits, targets), (logits, routing)


def sgd_step(params, tokens, targets, cfg, lr, clip, first_expert=0,
             remat=False):
    """One step of plain SGD on one sequence, as ``laguna_s.sgd_step``: the
    gradient of the token cross-entropy, scaled down to the norm ``clip``
    where it is longer (times ``clip / (norm + 1e-6)``, at most 1), times
    ``lr`` off the parameters. Returns ``(parameters, loss, logits,
    routing)``, the last three at the parameters it was given."""
    (loss, aux), grads = jax.value_and_grad(loss_and_logits, has_aux=True)(
        params, tokens, targets, cfg, first_expert, remat)
    norm = jnp.sqrt(sum(jnp.sum(g * g)
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = lr * jnp.minimum(1.0, clip / (norm + 1e-6))
    return (jax.tree_util.tree_map(lambda p, g: p - scale * g, params,
                                   grads), loss) + aux


# -- the program's tree ------------------------------------------------------

def from_system(tree):
    """The program's parameter tree (``models/decoder.py``; or a gradient of
    that shape) as this file's: the blocks ``layers_<i>`` become a list, the
    leaves keep their names; no ``lm_head`` (the embedding is the head)."""
    n = sum(1 for key in tree if key.startswith("layers_"))
    return {"embed": tree["embed"], "final_norm": tree["final_norm"],
            "layers": [tree[f"layers_{i}"] for i in range(n)]}


# one trained leaf of each kind, as (path in this file's tree) per name; the
# layer indices are those of the six-layer cut (0, 1 dense; 2 attention;
# 0, 1, 3, 4, 5 conv; 2-5 sparse). And every sparse layer's selection bias,
# which no step may move
GRAD_LEAVES = {
    "conv_in_proj": ("layers", 3, "conv", "in_proj"),
    "conv_taps": ("layers", 3, "conv", "conv"),
    "conv_out_proj": ("layers", 3, "conv", "out_proj"),
    "q_proj": ("layers", 2, "attention", "q_proj"),
    "q_layernorm": ("layers", 2, "attention", "q_norm"),
    "dense_up": ("layers", 1, "mlp", "up_proj"),
    "router_first": ("layers", 2, "mlp", "router"),
    "expert_up_last": ("layers", -1, "mlp", "experts", "up_proj"),
    "embed": ("embed",),
}


def bias_leaves(cfg: dict) -> dict:
    """``expert_bias_layer<i>`` -> path, for every sparse layer of ``cfg``."""
    return {f"expert_bias_layer{i}": ("layers", i, "mlp", "expert_bias")
            for i in range(cfg["num_dense_layers"], cfg["num_hidden_layers"])}


# -- operations and elements, from shapes alone ------------------------------

def layers(cfg: dict, seq: int) -> list:
    """The cut model's rows per scope of the program, for one sequence of
    ``seq`` tokens: ``embed``; the conv operators as ``conv_proj`` (the two
    projections) and ``conv_mix`` (the two gates and the three taps: seven
    operations a channel and token, and by bytes the three streams read and
    the gated output written); ``attention_proj`` (with the QK-norm's
    weights) and ``attention_full`` (the causal pairs); ``router``,
    ``experts`` at the expected load (each token's ``num_experts_per_tok``
    slots fall on a held expert with probability held / published),
    ``dense_mlp``, ``lm_head`` (the tied head's product: the embedding's
    rows read again and their gradient written again, no parameter of its
    own) and the norms' weights. ``params`` add up to the model."""
    h = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // cfg["published"]["num_attention_heads"]
    vocab, held = cfg["vocab_size"], cfg["num_experts"]
    published = cfg["published"]["num_experts"]
    k, n = cfg["num_experts_per_tok"], cfg["num_hidden_layers"]
    ch, taps = cfg["conv_channels"], cfg["conv_L_cache"]
    width = cfg["moe_intermediate_size"]
    expert, dense = 3 * h * width, 3 * h * cfg["intermediate_size"]
    n_conv = sum(1 for kind in cfg["layer_types"] if kind == "conv")
    n_full, n_dense = n - n_conv, min(cfg["num_dense_layers"], n)
    n_sparse = n - n_dense
    conv_proj = h * 3 * ch + ch * h
    proj = h * (2 * heads * d + 2 * kv * d) + 2 * d      # and the QK-norm
    pairs = seq * (seq + 1) // 2
    routed_slots = seq * k * held / published            # expected, a layer
    head = _counted_row("lm_head", "lm_head", vocab * h,
                        2.0 * seq * h * vocab, seq * (h + 2 * vocab))
    head["params"] = 0      # the embedding's, counted there
    return [
        _counted_row("embed", "embed", vocab * h, 0, 2 * seq * h),
        _counted_row("conv_proj", "short_conv", n_conv * conv_proj,
                     2.0 * seq * n_conv * conv_proj,
                     n_conv * seq * (2 * h + 4 * ch)),
        _counted_row("conv_mix", "short_conv", n_conv * ch * taps,
                     n_conv * seq * ch * (2.0 * taps + 1),
                     n_conv * seq * 4 * ch),
        _counted_row("attention_proj", "attention", n_full * proj,
                     2.0 * seq * n_full * proj, n_full * 4 * seq * h),
        _counted_row("attention_full", "attention/full", 0,
                     n_full * 2.0 * 2 * pairs * d * heads,
                     n_full * (seq * d * (2 * heads + 2 * kv)
                               + 2 * pairs * heads)),
        _counted_row("router", "router", n_sparse * (h * published
                                                     + published),
                     2.0 * seq * n_sparse * h * published,
                     n_sparse * seq * (h + published)),
        _counted_row("experts", "experts", n_sparse * held * expert,
                     2.0 * routed_slots * n_sparse * expert,
                     n_sparse * (routed_slots * (2 * h + 3 * width)
                                 + seq * h)),
        _counted_row("dense_mlp", "dense_mlp", n_dense * dense,
                     2.0 * seq * n_dense * dense,
                     n_dense * seq * (2 * h + 3 * cfg["intermediate_size"])),
        head,
        _counted_row("norms", "-", (2 * n + 1) * h, 0,
                     (2 * n + 1) * 2 * seq * h),
    ]
