"""Plain float32 reference of the Laguna decoder family (``Laguna-S-2.1``,
https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json): forward,
per-token cross-entropy and gradients in straightforward ``jax.numpy``, every
product at ``jax.default_matmul_precision("highest")``. Nothing is imported
from the program; no kernel, no cache, no batching (one sequence at a time).

``cfg`` is the configuration's file (``benchmarks/configs/laguna_s21_fed.json``)
or any dictionary with the same keys: the published ``config.json``'s, with the
counts a chip holds in place of the published ones. Widths and counts that the
weights state (heads, experts held, vocabulary rows, router outputs) are read
from the weights' shapes, so one function serves the uncut model and any
chip's share of it; ``first_expert`` says which of the router's experts the
held ones are.

A decoder block, as published (keys of ``config.json`` in brackets)::

    h = x + attention(rms_norm(x))            pre-norm residual   [assumed 2]
    y = h + mlp(rms_norm(h))                  dense in layer 0 [mlp_only_layers],
                                              sparse elsewhere
    rms_norm(x) = x / sqrt(mean(x^2) + eps) * w                   [rms_norm_eps]

    attention: q, k, v = x W_q, x W_k, x W_v, no biases [attention_bias], heads
      of 128 [head_dim], one KV head for each group of query heads
      [num_attention_heads_per_layer / num_key_value_heads]; rotary embedding
      [rope_parameters]: on ``full_attention`` layers YaRN (theta 500000,
      factor 128, original 8192, beta 32 / 1, cos and sin scaled by
      attention_factor) on the first half of each head [partial_rotary_factor
      0.5], on ``sliding_attention`` layers plain RoPE (theta 10000) on the
      whole head; rotate-half pairing (i, i + rot/2) as in the family's public
      code; scores / sqrt(128), causal, and on sliding layers only keys with
      0 <= i - j < 512 [sliding_window]; softmax; per-head gate [gating]
      g = sigmoid(x W_g), one scalar a query head, times that head's output
      before the output projection W_o                            [assumed 5]
    dense and expert MLPs: (silu(x W_gate) * (x W_up)) W_down      [assumed 1]
    sparse MLP: p = softmax(x W_r) over all router outputs [num_experts 256],
      in float32; the 10 largest [num_experts_per_tok], renormalised to sum 1
      [norm_topk_prob], times 2.5 [moe_routed_scaling_factor], weight on the
      expert's output [moe_apply_router_weight_on_input false], no soft cap
      [moe_router_logit_softcapping 0]                            [assumed 3]
      + one shared expert of width 1024, added ungated            [assumed 4]
    logits = rms_norm(x) W_head, embedding and head untied [tie_word_embeddings]

``assumed`` (the published config is silent; set by the Qwen-MoE family's
convention, whose keys the config uses): (1) SwiGLU with silu in every MLP;
(2) pre-norm residual blocks, no QK-norm; (3) the router's softmax over all
logits in float32, then top-k, renormalised, scaled; (4) the shared expert
added ungated; (5) headwise output gating (arXiv:2505.06708).

Departures from the published description, each because of the chip's share
(``model-configs`` guide, section 4) or of memory:

* a share's partial results: the held experts' part of the routed sum (what
  the absent experts would add is left out), the held heads' part of the
  attention output (the output projection's rows of those heads), logits and
  loss over the held vocabulary rows alone. Sums over all shares, with the
  shared expert counted once, give the uncut layer (``tests/test_decoder.py``).
* the loss ignores targets below 0 (the last position has no next token).
* attention is the masked full product computed in blocks of ``q_block``
  queries against every key; with ``remat=True`` each block's scores are
  computed again in the backward pass (``jax.checkpoint`` around the block,
  the one recomputation here: the probabilities of 8192 x 8192 scores for
  nine heads in five layers would take 10 GB), and each held expert, which
  is evaluated densely on every token and weighted by its routing weight
  (zero where the token was not routed to it), likewise.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 1024


def _highest(fn):
    """``fn`` traced with every product at the highest precision: on a TPU a
    float32 product otherwise runs in one bfloat16 pass."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


# -- the forward pass --------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_inv_freq(rope: dict, head_dim: int):
    """``(inv_freq [rot/2], rot, scale)`` of one layer kind's rotary
    embedding: the rotated width, and what cos and sin are multiplied by."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    base = float(rope["rope_theta"])
    exponents = [i / rot for i in range(0, rot, 2)]
    freqs = [base ** -e for e in exponents]
    if rope["rope_type"] == "default":
        return freqs, rot, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor, original = rope["factor"], rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return rot * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(freqs):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        # ramp 0: the frequency turns often inside the original context and
        # is kept (extrapolation); ramp 1: it is divided by the factor
        out.append(f * (1 - ramp) + f / factor * ramp)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return out, rot, float(scale)


def apply_rope(x, rope: dict, head_dim: int):
    """Rotary embedding of ``x [S, heads, head_dim]`` at positions 0..S-1."""
    inv_freq, rot, scale = rope_inv_freq(rope, head_dim)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


@_highest
def attention(p, x, cfg, kind: str, q_block=Q_BLOCK, remat=False):
    """This share's part of the attention output ``[S, hidden]``."""
    s_len, d = x.shape[0], cfg["head_dim"]
    rope = cfg["rope_parameters"][kind]
    q = apply_rope((x @ p["q_proj"]).reshape(s_len, -1, d), rope, d)
    k = apply_rope((x @ p["k_proj"]).reshape(s_len, -1, d), rope, d)
    v = (x @ p["v_proj"]).reshape(s_len, -1, d)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    window = cfg["sliding_window"] if kind == "sliding_attention" else s_len
    k_pos = jnp.arange(s_len)[None, :]

    def block(q_blk, start):
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k) / math.sqrt(d)
        q_pos = start + jnp.arange(q_blk.shape[0])[:, None]
        seen = (k_pos <= q_pos) & (q_pos - k_pos < window)
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
    gate = jax.nn.sigmoid(x @ p["gate_proj"])            # [S, heads]
    return (out * gate[..., None]).reshape(s_len, -1) @ p["o_proj"]


@_highest
def swiglu(p, x):
    return (jax.nn.silu(x @ p["gate_proj"]) * (x @ p["up_proj"])) \
        @ p["down_proj"]


@_highest
def route(router, x, cfg):
    """``(weights [S, k], experts [S, k])``: the routing of every token over
    all of the router's experts."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p * cfg["moe_routed_scaling_factor"], top_e


@_highest
def sparse_mlp(p, x, cfg, first_expert=0, remat=False):
    """The shared expert's output plus the held experts' part of the routed
    sum; the second value is the routing ``[S, k]``."""
    top_p, top_e = route(p["router"], x, cfg)
    out = swiglu(p["shared_expert"], x)

    def expert(weights, x, share):
        return swiglu(weights, x) * share[:, None]

    if remat:
        expert = jax.checkpoint(expert)
    held = p["experts"]["gate_proj"].shape[0]

    def add(out, held_expert):      # one loop over the held experts
        weights, e = held_expert
        share = jnp.sum(jnp.where(top_e == first_expert + e, top_p, 0.0), -1)
        return out + expert(weights, x, share), None

    out, _ = jax.lax.scan(add, out, (p["experts"], jnp.arange(held)))
    return out, top_e


@_highest
def hidden_states(params, tokens, cfg, first_expert=0, remat=False):
    """The last block's output ``[S, hidden]`` for one sequence of token ids
    (drawn from the held vocabulary rows), and each sparse layer's routing."""
    x = params["embed"][tokens]
    routing = []
    for layer, kind, mlp in zip(params["layers"], cfg["layer_types"],
                                cfg["mlp_layer_types"]):
        eps = cfg["rms_norm_eps"]
        x = x + attention(layer["attention"], rms_norm(x, layer["attn_norm"],
                                                       eps),
                          cfg, kind, remat=remat)
        h = rms_norm(x, layer["mlp_norm"], eps)
        if mlp == "dense":
            x = x + swiglu(layer["mlp"], h)
        else:
            y, top_e = sparse_mlp(layer["mlp"], h, cfg, first_expert, remat)
            x = x + y
            routing.append(top_e)
    return x, routing


@_highest
def forward(params, tokens, cfg, first_expert=0, remat=False):
    """``(logits [S, V_held], routing)`` of one sequence."""
    x, routing = hidden_states(params, tokens, cfg, first_expert, remat)
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    return x @ params["lm_head"], routing


def token_cross_entropy(logits, targets):
    """Mean over the positions with a target (``targets >= 0``) of the
    cross-entropy over the held vocabulary rows."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[:, None], axis=-1)[:, 0]
    w = (targets >= 0).astype(logits.dtype)
    return -jnp.sum(picked * w) / jnp.maximum(jnp.sum(w), 1.0)


def loss_and_logits(params, tokens, targets, cfg, first_expert=0,
                    remat=False):
    logits, routing = forward(params, tokens, cfg, first_expert, remat)
    return token_cross_entropy(logits, targets), (logits, routing)


def sgd_step(params, tokens, targets, cfg, lr, clip, first_expert=0,
             remat=False):
    """One step of plain SGD on one sequence, as a client of the federated
    job takes it (no momentum, no weight decay): the gradient of the token
    cross-entropy, scaled down to the norm ``clip`` where it is longer
    (``torch.nn.utils.clip_grad_norm_``: times ``clip / (norm + 1e-6)``, at
    most 1), times ``lr`` off the parameters. Returns ``(parameters, loss,
    logits, routing)``, the last three at the parameters it was given. A
    round of federated averaging is this step a sequence at every site,
    each site from the round's parameters, and the sites' mean weighted by
    their numbers of sequences (``benchmarks/families/tokens.py`` does that
    sum over the leaves it compares)."""
    (loss, (logits, routing)), grads = jax.value_and_grad(
        loss_and_logits, has_aux=True)(params, tokens, targets, cfg,
                                       first_expert, remat)
    norm = jnp.sqrt(sum(jnp.sum(g * g)
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = lr * jnp.minimum(1.0, clip / (norm + 1e-6))
    return jax.tree_util.tree_map(lambda p, g: p - scale * g, params,
                                  grads), loss, logits, routing


# -- the program's tree ------------------------------------------------------

def from_system(tree):
    """The program's parameter tree (``models/decoder.py``; or a gradient of
    that shape) as this file's: the blocks ``layers_<i>`` become a list, the
    leaves keep their names."""
    n = sum(1 for key in tree if key.startswith("layers_"))
    return {"embed": tree["embed"], "final_norm": tree["final_norm"],
            "lm_head": tree["lm_head"],
            "layers": [tree[f"layers_{i}"] for i in range(n)]}


# one leaf of each new kind, as (path in this file's tree) per name; the
# layer indices are those of the five-layer cut (0 full and dense, 1-3
# sliding, 4 full, 1-4 sparse)
GRAD_LEAVES = {
    "expert_up_last": ("layers", -1, "mlp", "experts", "up_proj"),
    "router_layer1": ("layers", 1, "mlp", "router"),
    "q_proj_sliding": ("layers", 1, "attention", "q_proj"),
    "q_proj_full": ("layers", -1, "attention", "q_proj"),
    "head_gate": ("layers", 1, "attention", "gate_proj"),
    "shared_expert_up": ("layers", 1, "mlp", "shared_expert", "up_proj"),
    "dense_down": ("layers", 0, "mlp", "down_proj"),
    "lm_head": ("lm_head",),
}


# -- operations and elements, from shapes alone ------------------------------

def _row(name, scope, params, flops, acts, weight_grad=True):
    """A counted row (``benchmarks/lib/flops.py``): ``flops`` forward per
    sequence (a multiply-add is 2), twice that backward (the weights'
    gradient and the input's; recomputed operations never count); ``acts``
    activation elements read and written per sequence going forward, twice
    that going backward; the weights once a step forward, and backward read
    once and their gradient written once."""
    return {"name": name, "kind": "counted", "scope": scope, "params": params,
            "forward": {"flops": float(flops), "elements": acts,
                        "step_elements": params},
            "backward": {"flops": 2.0 * flops, "elements": 2 * acts,
                         "step_elements": 2 * params if weight_grad else 0}}


def attended_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal layer scores: position i sees
    ``min(i + 1, window)`` keys."""
    full = min(seq, window)
    return full * (full + 1) // 2 + (seq - full) * window


def layers(cfg: dict, seq: int) -> list:
    """The cut model's rows per scope of the program, for one sequence of
    ``seq`` tokens: ``embed``, ``attention`` (projections and gate; the scores
    and the weighted sum of values apart as ``attention_full`` and
    ``attention_window``), ``router``, ``experts`` (at the expected load: each
    token's ``num_experts_per_tok`` slots fall on a held expert with
    probability held / published), ``shared_expert``, ``dense_mlp``,
    ``lm_head``, and the norms' weights. ``params`` add up to the model."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"]
    vocab, held = cfg["vocab_size"], cfg["num_experts"]
    published = cfg["published"]["num_experts"]
    k = cfg["num_experts_per_tok"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    shared = 3 * h * cfg["shared_expert_intermediate_size"]
    dense = 3 * h * cfg["intermediate_size"]
    n_sparse = sum(1 for m in cfg["mlp_layer_types"] if m == "sparse")
    n_dense = len(cfg["mlp_layer_types"]) - n_sparse
    proj = 0
    scores = {"full_attention": 0.0, "sliding_attention": 0.0}
    score_acts = dict(scores)
    for kind, heads in zip(cfg["layer_types"],
                           cfg["num_attention_heads_per_layer"]):
        proj += h * (2 * heads * d + 2 * kv * d + heads)
        window = cfg["sliding_window"] if kind == "sliding_attention" else seq
        pairs = attended_pairs(seq, window)
        scores[kind] += 2.0 * 2 * pairs * d * heads      # q k^T and p v
        score_acts[kind] += seq * d * (2 * heads + 2 * kv) + 2 * pairs * heads
    routed_slots = seq * k * held / published            # expected, a layer
    width = cfg["moe_intermediate_size"]
    rows = [
        _row("embed", "embed", vocab * h, 0, 2 * seq * h),
        _row("attention_proj", "attention", proj, 2.0 * seq * proj,
             len(cfg["layer_types"]) * 4 * seq * h),
        _row("attention_full", "attention/full", 0, scores["full_attention"],
             score_acts["full_attention"]),
        _row("attention_window", "attention/window", 0,
             scores["sliding_attention"], score_acts["sliding_attention"]),
        _row("router", "router", n_sparse * h * published,
             2.0 * seq * n_sparse * h * published,
             n_sparse * seq * (h + published)),
        _row("experts", "experts", n_sparse * held * expert,
             2.0 * routed_slots * n_sparse * expert,
             n_sparse * (routed_slots * (2 * h + 3 * width) + seq * h)),
        _row("shared_expert", "shared_expert", n_sparse * shared,
             2.0 * seq * n_sparse * shared,
             n_sparse * seq * (2 * h + 3 * cfg[
                 "shared_expert_intermediate_size"])),
        _row("dense_mlp", "dense_mlp", n_dense * dense,
             2.0 * seq * n_dense * dense,
             n_dense * seq * (2 * h + 3 * cfg["intermediate_size"])),
        _row("lm_head", "lm_head", h * vocab, 2.0 * seq * h * vocab,
             seq * (h + 2 * vocab)),
        _row("norms", "-", (2 * len(cfg["layer_types"]) + 1) * h, 0,
             (2 * len(cfg["layer_types"]) + 1) * 2 * seq * h),
    ]
    return rows
