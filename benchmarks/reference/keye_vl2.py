"""Plain float32 reference of the language model of ``Keye-VL-2.0-30B-A3B``
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
``model_type`` ``KeyeVL2``): forward, per-token cross-entropy and gradients in
straightforward ``jax.numpy``, every product at
``jax.default_matmul_precision("highest")``. Nothing is imported from the
program; no kernel, no cache, no batching (one sequence at a time). What it
shares with the other decoder reference (the norm, the SwiGLU, the loss, the
tree's shape, a counted row) it takes from ``laguna_s.py``.

``cfg`` is the configuration's file (``benchmarks/configs/keye_vl2_fed.json``)
with the counts a chip holds laid over it, or any dictionary with the same
keys. Counts that the weights state (heads, experts held, vocabulary rows,
router outputs) are read from the weights' shapes, so one function serves the
uncut model and any chip's share of it; ``first_expert`` says which of the
router's experts the held ones are. **The vision tower is not here**: the
catalog's row holds the language model's settings only, no width of the
tower; the three position streams it would feed are built in full
(``positions [3, S]``) and a text job feeds them equal.

One layer, input ``x [S, 2048]``, eps 1e-6 (keys of ``config.json`` in
brackets; ``assumed`` marks what it is silent on)::

    h = rms_norm(x);  q, k, v = h W_q, h W_k, h W_v    no bias [attention_bias]
      heads of 128 [head_dim], one KV head for each group of query heads;
      q and k each through an RMSNorm over their 128 features with a learned
      weight, before the rotary embedding                           [assumed 1]
    rotary: 64 pairs (i, i + 64), f_i = 1e7 ** (-2 i / 128) [rope_theta]; pair
      i turns by positions[s(i), t] * f_i with stream s(i) = 0 for i < 16, 1
      for 16 <= i < 40, 2 beyond [rope_scaling.mrope_section 16, 24, 24],
      the sections contiguous                                       [assumed 2]
    indexer [sa_config]: qI = h W_qI [S, 16, 64], kI = LayerNorm(h W_kI)
      [S, 64] (weight and bias), a = h W_a [S, 16]; qI and kI rotated on the
      temporal stream over their 32 pairs, f_i = 1e7 ** (-2 i / 64); in float32
        I[t, s] = (16 * 64) ** -0.5 * sum_j a[t, j] * relu(qI[t, j] . kI[s])
      S_t = the min(2048, t + 1) largest of {I[t, s] : s <= t} [topk], ties
      to the lower s (``lax.top_k``'s order)                        [assumed 3]
    o[t, head] = sum over s in S_t of softmax over S_t (q . k / sqrt(128)) v[s]
    y = x + concat(o) W_o
    h2 = rms_norm(y);  p = softmax(h2 W_r) over 128 [num_experts], float32; the
      8 largest [num_experts_per_tok], renormalised to sum 1 [norm_topk_prob],
      no scale, no shared expert;  z = y + sum over the chosen and held experts
      of p_e * (silu(h2 W_gate) * (h2 W_up)) W_down [moe_intermediate_size 768,
      hidden_act silu]; every layer sparse [mlp_only_layers [],
      decoder_sparse_step 1]
    logits = rms_norm(z_last) W_head, embedding and head untied

``ASSUMED`` (the config is silent): (1) the QK-norm, Qwen3-MoE's, whose keys
the config uses; (2) contiguous sections of the frequency pairs; (3) the
indexer's equations: DeepSeek-V3.2-Exp's lightning indexer, which the
catalog's ``described_as`` names; its FP8 cast and Hadamard transform are
inference quantisation and are left out; ``q_chunk_size`` and
``kv_chunk_size`` 512 are read as the tiling the scores are computed in and
change no result.

Departures from the published description:

* **the indexer is not trained.** It reads ``stop_gradient(h)`` and the
  selection is a hard choice, so the token loss gives ``W_qI``, ``W_kI``,
  ``W_a`` and the LayerNorm no gradient (as in DeepSeek-V3.2's sparse stage).
  DSA trains them by a separate alignment loss (KL to the main attention's
  distribution); this job leaves that loss out and the indexer stays as
  initialised.
* a share's partial results, as in ``laguna_s.py``: the held experts' part of
  the routed sum, the held heads' part of the attention output, logits and
  loss over the held vocabulary rows; the indexer is held whole by every
  chip (its score sums over its 16 heads before the top-k). Sums over all
  shares give the uncut layer (``tests/test_decoder_selected.py``).
* the loss ignores targets below 0 (the last position has no next token).
* attention is the masked full product in blocks of ``q_block`` queries
  against every key, the mask scattered from an exact ``lax.top_k``; with
  ``remat=True`` a block's scores (not its selection) and each held expert,
  which is evaluated densely on every token, are computed again going
  backward.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .laguna_s import _row as _counted_row
from .laguna_s import (_highest, from_system, rms_norm,  # noqa: F401
                       swiglu, token_cross_entropy)

Q_BLOCK = 1024
ASSUMED = ("qk_norm", "mrope_sections_contiguous", "indexer_equations")


# -- the forward pass --------------------------------------------------------

def text_positions(length: int):
    """``[3, length]``: the three streams of a text sequence, all 0, 1, ..."""
    return jnp.broadcast_to(jnp.arange(length, dtype=jnp.float32),
                            (3, length))


def rotary_angles(theta: float, width: int, positions, sections):
    """``[S, width / 2]``: pair ``i`` of ``width / 2`` turns by its section's
    stream of ``positions [streams, S]`` times ``theta ** (-2 i / width)``."""
    stream = [s for s, n in enumerate(sections) for _ in range(n)]
    assert len(stream) == width // 2, (sections, width)
    return jnp.stack([positions[stream[i]] * float(theta) ** (-2 * i / width)
                      for i in range(width // 2)], axis=-1)


def rotate(x, angles):
    """``x [S, heads, width]`` with the pairs ``(i, i + width / 2)`` turned
    by ``angles [S, width / 2]``."""
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w + b


@_highest
def indexer(p, x, cfg, positions):
    """``(qI [S, J, e], kI [S, e], a [S, J])``, rotated."""
    sa, s_len = cfg["sa_config"], x.shape[0]
    e = sa["indexer_head_dim"]
    angles = rotary_angles(cfg["rope_theta"], e, positions[:1], [e // 2])
    q = rotate((x @ p["q_proj"]).reshape(s_len, -1, e), angles)
    k = layer_norm(x @ p["k_proj"], p["k_norm_scale"], p["k_norm_bias"],
                   cfg["rms_norm_eps"])
    return q, rotate(k[:, None, :], angles)[:, 0], x @ p["weights_proj"]


@_highest
def attention(p, x, cfg, positions=None, q_block=Q_BLOCK, remat=False,
              select=True):
    """This share's part of the attention output ``[S, hidden]`` and the
    selection ``[S, S]`` (query, key). ``select=False`` puts full causal
    attention in the selection's place (a control: the mechanism left out)."""
    s_len, d = x.shape[0], cfg["head_dim"]
    eps, topk = cfg["rms_norm_eps"], min(cfg["sa_config"]["topk"], s_len)
    positions = text_positions(s_len) if positions is None else positions
    angles = rotary_angles(cfg["rope_theta"], d, positions,
                           cfg["rope_scaling"]["mrope_section"])
    q = rotate(rms_norm((x @ p["q_proj"]).reshape(s_len, -1, d),
                        p["q_norm"], eps), angles)
    k = rotate(rms_norm((x @ p["k_proj"]).reshape(s_len, -1, d),
                        p["k_norm"], eps), angles)
    v = (x @ p["v_proj"]).reshape(s_len, -1, d)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    q_idx, k_idx, a_idx = indexer(p["indexer"], jax.lax.stop_gradient(x),
                                  cfg, positions)
    heads, e = q_idx.shape[1:]
    k_pos = jnp.arange(s_len)[None, :]

    def chosen(start, q_idx, a_idx):
        """``[Q, S]``: the keys each query of the block attends."""
        rows = q_idx.shape[0]
        causal = k_pos <= start + jnp.arange(rows)[:, None]
        if not select:
            return causal
        scores = jnp.einsum("qje,se->qjs", q_idx, k_idx)
        scores = jnp.sum(a_idx[:, :, None] * jax.nn.relu(scores), axis=1) \
            / math.sqrt(heads * e)
        _, top = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
        return jnp.zeros((rows, s_len), bool).at[
            jnp.arange(rows)[:, None], top].set(True) & causal

    def attend(q_blk, keep):
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    if remat:
        attend = jax.checkpoint(attend)

    def block(q_blk, start, q_idx, a_idx):
        keep = chosen(start, q_idx, a_idx)
        return attend(q_blk, keep), keep

    if s_len % q_block == 0 and s_len > q_block:
        # one loop over the blocks (a traced start, the same body): the
        # compiler sees one block, not S / q_block copies of it
        def blocks(a):
            return a.reshape((-1, q_block) + a.shape[1:])

        out, keep = jax.lax.map(
            lambda blk: block(*blk),
            (blocks(q), jnp.arange(0, s_len, q_block), blocks(q_idx),
             blocks(a_idx)))
        out, keep = out.reshape(q.shape), keep.reshape(s_len, s_len)
    else:
        parts = [block(q[i:i + q_block], jnp.asarray(i), q_idx[i:i + q_block],
                       a_idx[i:i + q_block])
                 for i in range(0, s_len, q_block)]
        out = jnp.concatenate([o for o, _ in parts], axis=0)
        keep = jnp.concatenate([m for _, m in parts], axis=0)
    return out.reshape(s_len, -1) @ p["o_proj"], keep


@_highest
def route(router, x, cfg):
    """``(weights [S, k], experts [S, k])``: the routing of every token over
    all of the router's experts."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_e


@_highest
def sparse_mlp(p, x, cfg, first_expert=0, remat=False):
    """The held experts' part of the routed sum, each held expert evaluated
    densely on every token and weighted by its routing weight (zero where
    the token was not routed to it); the second value is the routing
    ``[S, k]``."""
    top_p, top_e = route(p["router"], x, cfg)

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
def forward(params, tokens, cfg, first_expert=0, remat=False, positions=None,
            select=True):
    """``(logits [S, V_held], routing, selection)`` of one sequence: each
    layer's routing ``[S, k]`` and selection ``[S, S]``."""
    x = params["embed"][tokens]
    eps, routing, selection = cfg["rms_norm_eps"], [], []
    for layer in params["layers"]:
        y, keep = attention(layer["attention"],
                            rms_norm(x, layer["attn_norm"], eps), cfg,
                            positions, remat=remat, select=select)
        x = x + y
        y, top_e = sparse_mlp(layer["mlp"], rms_norm(x, layer["mlp_norm"],
                                                     eps),
                              cfg, first_expert, remat)
        x = x + y
        routing.append(top_e)
        selection.append(keep)
    x = rms_norm(x, params["final_norm"], eps)
    return x @ params["lm_head"], routing, selection


def loss_and_logits(params, tokens, targets, cfg, first_expert=0,
                    remat=False, positions=None):
    logits, routing, selection = forward(params, tokens, cfg, first_expert,
                                         remat, positions)
    return token_cross_entropy(logits, targets), (logits, routing, selection)


def sgd_step(params, tokens, targets, cfg, lr, clip, first_expert=0,
             remat=False):
    """One step of plain SGD on one sequence, as ``laguna_s.sgd_step``: the
    gradient of the token cross-entropy, scaled down to the norm ``clip``
    where it is longer (times ``clip / (norm + 1e-6)``, at most 1), times
    ``lr`` off the parameters. Returns ``(parameters, loss, logits, routing,
    selection)``, the last four at the parameters it was given."""
    (loss, aux), grads = jax.value_and_grad(loss_and_logits, has_aux=True)(
        params, tokens, targets, cfg, first_expert, remat)
    norm = jnp.sqrt(sum(jnp.sum(g * g)
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = lr * jnp.minimum(1.0, clip / (norm + 1e-6))
    return (jax.tree_util.tree_map(lambda p, g: p - scale * g, params,
                                   grads), loss) + aux


# one trained leaf of each kind, as (path in this file's tree) per name; and
# the last layer's indexer, which no step may move
GRAD_LEAVES = {
    "expert_up_last": ("layers", -1, "mlp", "experts", "up_proj"),
    "router_layer1": ("layers", 1, "mlp", "router"),
    "q_proj": ("layers", 1, "attention", "q_proj"),
    "o_proj": ("layers", -1, "attention", "o_proj"),
    "q_norm": ("layers", 1, "attention", "q_norm"),
    "lm_head": ("lm_head",),
    "embed": ("embed",),
}
INDEXER_LEAVES = {
    "indexer_" + name: ("layers", -1, "attention", "indexer", name)
    for name in ("q_proj", "k_proj", "weights_proj")}


# -- operations and elements, from shapes alone ------------------------------

def _row(name, scope, params, flops, acts, trained=True):
    """``laguna_s._row``'s counted row (``benchmarks/lib/flops.py``). A row
    that is not ``trained`` (the indexer, the choice of the keys) has no
    backward pass: nothing of it is differentiated, and what the backward
    pass computes of it again never counts."""
    row = _counted_row(name, scope, params, flops, acts)
    if not trained:
        row["backward"] = {"flops": 0.0, "elements": 0, "step_elements": 0}
    return row


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs the selection keeps: position t attends
    ``min(topk, t + 1)`` keys."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def layers(cfg: dict, seq: int) -> list:
    """The cut model's rows per scope of the program, for one sequence of
    ``seq`` tokens, counting the **published** work: ``attention_selected``
    by the pairs the selection keeps (a dense masked product computes 4.3
    times as many at 16,384 tokens, and reads as that much headroom),
    ``attention_indexer`` by its three projections and the causal square of
    16 x 64 products, ``attention_select`` as the float32 scores of the
    causal square read once (two elements of the compute type a score) and
    no operation: the least any exact choice of the top-k must touch.
    ``params`` add up to the model."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    vocab, held = cfg["vocab_size"], cfg["num_experts"]
    published = cfg["published"]["num_experts"]
    k, n = cfg["num_experts_per_tok"], cfg["num_hidden_layers"]
    sa = cfg["sa_config"]
    j, e = sa["indexer_num_heads"], sa["indexer_head_dim"]
    width = cfg["moe_intermediate_size"]
    expert = 3 * h * width
    proj = h * (2 * heads * d + 2 * kv * d) + 2 * d      # and the QK-norm
    index = h * (j * e + e + j) + 2 * e                  # and the LayerNorm
    square = seq * (seq + 1) // 2
    pairs = selected_pairs(seq, sa["topk"])
    routed_slots = seq * k * held / published            # expected, a layer
    return [
        _row("embed", "embed", vocab * h, 0, 2 * seq * h),
        _row("attention_proj", "attention", n * proj, 2.0 * seq * n * proj,
             n * 4 * seq * h),
        _row("attention_indexer", "attention/indexer", n * index,
             n * (2.0 * seq * index + 2.0 * square * j * e),
             n * (seq * (h + j * e + e + j) + 2 * square), trained=False),
        _row("attention_select", "attention/select", 0, 0, n * 2 * square,
             trained=False),
        _row("attention_selected", "attention/selected", 0,
             n * 2.0 * 2 * pairs * d * heads,
             n * (seq * d * (2 * heads + 2 * kv) + 2 * pairs * heads)),
        _row("router", "router", n * h * published,
             2.0 * seq * n * h * published, n * seq * (h + published)),
        _row("experts", "experts", n * held * expert,
             2.0 * routed_slots * n * expert,
             n * (routed_slots * (2 * h + 3 * width) + seq * h)),
        _row("lm_head", "lm_head", h * vocab, 2.0 * seq * h * vocab,
             seq * (h + 2 * vocab)),
        _row("norms", "-", (2 * n + 1) * h, 0, (2 * n + 1) * 2 * seq * h),
    ]
