"""The Laguna decoder family: a causal language model of pre-norm blocks with
full and sliding-window attention layers of different head counts, a per-head
output gate, one leading dense SwiGLU MLP and sparse MLPs (a router over all
published experts, a shared expert) after it.

The published configuration is ``CONFIGS["laguna_s"]`` (Laguna-S-2.1,
https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json, keys as
published); ``laguna_tiny`` keeps its structure at a size the CPU tests run.
What ONE CHIP holds of a model is :class:`Share`: the depth kept (leading
layers; the rest lie on further chips as pipeline stages), over how many
chips the routed experts of a layer are divided, and over how many the heads
and the vocabulary. The chip then computes its partial results: its experts'
part of the routed sum (what the absent experts would add is left out), its
heads' part of the attention output, logits over its vocabulary rows. Nothing
here stands in for the absent chips or their traffic; sums over all shares,
the shared expert counted once, give the uncut layer (tests/test_decoder.py).

The config is silent on five things, set by the Qwen-MoE family's convention
(whose keys it uses) and listed as ``assumed`` in
``benchmarks/reference/laguna_s.py``, the plain reference the tests hold this
file to: SwiGLU (silu) MLPs; pre-norm residuals without QK-norm; the router's
softmax over all logits in float32, then top-k, renormalised, scaled; the
shared expert added ungated; headwise output gating ``sigmoid(x W_g)``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

_PERIOD = ["full_attention"] + ["sliding_attention"] * 3

CONFIGS = {
    "laguna_s": {
        "model_type": "laguna",
        "vocab_size": 100352,
        "hidden_size": 3072,
        "intermediate_size": 12288,
        "num_hidden_layers": 48,
        "num_attention_heads": 48,
        "num_key_value_heads": 8,
        "head_dim": 128,
        "max_position_embeddings": 1048576,
        "attention_bias": False,
        "rms_norm_eps": 1e-06,
        "num_experts": 256,
        "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024,
        "norm_topk_prob": True,
        "decoder_sparse_step": 1,
        "mlp_only_layers": [0],
        "tie_word_embeddings": False,
        "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1}},
        "layer_types": _PERIOD * 12,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0,
    },
    # the same structure for the CPU tests: the published layer pattern, 16
    # experts top-4, 2 KV heads with 4 (full) or 6 (sliding) query heads,
    # window 8; no width of it means anything
    "laguna_tiny": {
        "model_type": "laguna",
        "vocab_size": 64,
        "hidden_size": 32,
        "intermediate_size": 64,
        "num_hidden_layers": 8,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 16,
        "max_position_embeddings": 256,
        "attention_bias": False,
        "rms_norm_eps": 1e-06,
        "num_experts": 16,
        "num_experts_per_tok": 4,
        "moe_intermediate_size": 16,
        "shared_expert_intermediate_size": 16,
        "norm_topk_prob": True,
        "decoder_sparse_step": 1,
        "mlp_only_layers": [0],
        "tie_word_embeddings": False,
        "gating": "per-head",
        "sliding_window": 8,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 10000, "rope_type": "yarn", "factor": 4,
                "original_max_position_embeddings": 16, "beta_slow": 1,
                "beta_fast": 4, "attention_factor": 1.1386294361119891,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 100,
                "partial_rotary_factor": 1}},
        "layer_types": _PERIOD * 2,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 7,
        "gating_types": ["per_head"] * 8,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [4, 6, 6, 6] * 2,
        "moe_router_logit_softcapping": 0,
    },
}

# queries of a full layer are scored in blocks of this many, each against the
# keys up to its own end, so the scores of a long sequence never exist whole
FULL_ATTENTION_BLOCK = 1024
EXPERT_STATS = "expert_stats"   # the collection the sparse MLP sows into


@dataclasses.dataclass(frozen=True)
class Share:
    """What one chip holds: the first ``layers`` layers (0: all), the routed
    experts divided over ``expert_shards`` chips, heads and vocabulary over
    ``tensor_shards``; ``index`` is this chip's place among those that share
    a layer (it picks the held experts' ids; weights are the chip's own)."""
    layers: int = 0
    expert_shards: int = 1
    tensor_shards: int = 1
    index: int = 0


def held_config(name: str, share: Share = Share()) -> dict:
    """The published configuration ``name`` with the counts ``share`` holds
    in place of the published ones (no width changes), the published counts
    under ``published`` and the held experts' first id under
    ``first_expert``."""
    cfg = dict(CONFIGS[name])
    n = share.layers or cfg["num_hidden_layers"]
    t, e = share.tensor_shards, share.expert_shards
    cut = ("num_hidden_layers", "num_experts", "num_attention_heads",
           "num_key_value_heads", "vocab_size")
    for key, parts in (("num_attention_heads", t), ("num_key_value_heads", t),
                       ("vocab_size", t), ("num_experts", e)):
        if cfg[key] % parts:
            raise ValueError(f"{name}: {key} {cfg[key]} does not divide "
                             f"over {parts} chips")
    if not 0 < n <= cfg["num_hidden_layers"]:
        raise ValueError(f"{name}: {n} layers of {cfg['num_hidden_layers']}")
    cfg["published"] = {key: cfg[key] for key in cut}
    cfg.update(
        num_hidden_layers=n, num_experts=cfg["num_experts"] // e,
        num_attention_heads=cfg["num_attention_heads"] // t,
        num_key_value_heads=cfg["num_key_value_heads"] // t,
        vocab_size=cfg["vocab_size"] // t,
        num_attention_heads_per_layer=[
            h // t for h in cfg["num_attention_heads_per_layer"][:n]])
    for key in ("layer_types", "mlp_layer_types", "gating_types"):
        cfg[key] = cfg[key][:n]
    cfg["first_expert"] = (share.index % e) * cfg["num_experts"]
    return cfg


def rope_tables(rope: dict, head_dim: int, length: int):
    """``(cos, sin, rot)``: float32 tables ``[length, rot / 2]`` of one layer
    kind's rotary embedding and the width it turns. ``yarn`` blends each
    frequency between itself (it turns often inside the original context)
    and itself over ``factor`` (it does not), and scales cos and sin by
    ``attention_factor``."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    freq = float(rope["rope_theta"]) ** -(np.arange(0, rot, 2) / rot)
    scale = 1.0
    if rope["rope_type"] == "yarn":
        base, original = rope["rope_theta"], \
            rope["original_max_position_embeddings"]

        def correction_dim(rotations):
            return rot * math.log(original / (rotations * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rope["beta_slow"])), rot - 1)
        ramp = np.clip((np.arange(rot // 2) - low)
                       / ((high - low) or 0.001), 0.0, 1.0)
        freq = freq * (1 - ramp) + freq / rope["factor"] * ramp
        scale = rope.get("attention_factor") or \
            0.1 * math.log(rope["factor"]) + 1.0
    angles = np.arange(length)[:, None] * freq[None, :]
    return (np.cos(angles) * scale).astype(np.float32), \
        (np.sin(angles) * scale).astype(np.float32), rot


def _rotate(x, cos, sin, rot):
    """Rotary embedding of ``x [B, S, ..., head_dim]`` (pairs ``i, i + rot/2``
    of the first ``rot`` features), in float32."""
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (rot // 2,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1 = x[..., :rot // 2].astype(jnp.float32)
    x2 = x[..., rot // 2:rot].astype(jnp.float32)
    turned = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., rot:]], axis=-1)


def _attend(q, k, v, seen):
    """Softmax attention of grouped queries ``q [..., Q, n, g, d]`` over
    ``k, v [..., K, n, d]`` where ``seen [..., Q, K]``; scores and softmax in
    float32."""
    scores = jnp.einsum("...qngd,...knd->...ngqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    seen = seen[..., None, None, :, :]
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    return jnp.einsum("...ngqk,...knd->...qngd", probs.astype(v.dtype), v)


def full_attention(q, k, v, block=FULL_ATTENTION_BLOCK):
    """Causal attention, ``q [B, S, n, g, d]`` over ``k, v [B, S, n, d]``:
    blocks of queries, each against the keys up to its own end (the part of
    the square above the diagonal is never computed), each block's scores
    computed again in the backward pass."""
    s_len = q.shape[1]

    @jax.checkpoint
    def one(q_blk, k_upto, v_upto):
        start = k_upto.shape[1] - q_blk.shape[1]
        q_pos = start + jnp.arange(q_blk.shape[1])[:, None]
        return _attend(q_blk, k_upto, v_upto,
                       jnp.arange(k_upto.shape[1])[None, :] <= q_pos)

    return jnp.concatenate(
        [one(q[:, i:i + block], k[:, :i + block], v[:, :i + block])
         for i in range(0, s_len, block)], axis=1)


def window_attention(q, k, v, window: int):
    """Causal attention in which position i sees the keys ``i - window < j
    <= i``, computed as a band: the sequence in blocks of ``window``, each
    block of queries against its own keys and the block before."""
    b, s_len = q.shape[:2]
    if s_len <= window:
        return full_attention(q, k, v)
    pad = -s_len % window
    if pad:
        q, k, v = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                   for a in (q, k, v))
    nb = (s_len + pad) // window
    q = q.reshape((b, nb, window) + q.shape[2:])

    def with_previous(a):
        a = a.reshape((b, nb, window) + a.shape[2:])
        before = jnp.pad(a[:, :-1], [(0, 0), (1, 0)] + [(0, 0)] * (a.ndim - 2))
        return jnp.concatenate([before, a], axis=2)

    q_pos = window + jnp.arange(window)[:, None]
    k_pos = jnp.arange(2 * window)[None, :]
    seen = (k_pos <= q_pos) & (q_pos - k_pos < window)
    # the first block has no block before it
    seen = seen[None] & ((jnp.arange(nb) > 0)[:, None, None]
                         | (k_pos >= window)[None])
    out = _attend(q, with_previous(k), with_previous(v), seen)
    return out.reshape((b, nb * window) + out.shape[3:])[:, :s_len]


def _weight(module, name, shape):
    return module.param(name, nn.initializers.normal(0.02), shape)


def rms_norm(x, w, eps: float):
    """``x / sqrt(mean(x^2) + eps) * w``, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _norm_weight(module, name, width):
    return module.param(name, nn.initializers.ones, (width,))


class Attention(nn.Module):
    """The held heads' part of one attention layer's output."""
    kind: str
    q_heads: int
    kv_heads: int
    head_dim: int
    window: int
    rope: Tuple     # the layer kind's rope_parameters as sorted items

    @nn.compact
    def __call__(self, x):
        b, s_len, hidden = x.shape
        n, d = self.kv_heads, self.head_dim
        group = self.q_heads // n
        with jax.named_scope("attention"):
            q = x @ _weight(self, "q_proj", (hidden, self.q_heads * d))
            k = x @ _weight(self, "k_proj", (hidden, n * d))
            v = x @ _weight(self, "v_proj", (hidden, n * d))
            gate = jax.nn.sigmoid(
                (x @ _weight(self, "gate_proj", (hidden, self.q_heads))
                 ).astype(jnp.float32))
            cos, sin, rot = rope_tables(dict(self.rope), d, s_len)
            q = _rotate(q.reshape(b, s_len, n, group, d), cos, sin, rot)
            k = _rotate(k.reshape(b, s_len, n, d), cos, sin, rot)
            v = v.reshape(b, s_len, n, d)
            if self.kind == "sliding_attention":
                with jax.named_scope("window"):
                    out = window_attention(q, k, v, self.window)
            else:
                with jax.named_scope("full"):
                    out = full_attention(q, k, v)
            out = out * gate.reshape(b, s_len, n, group, 1).astype(out.dtype)
            return out.reshape(b, s_len, -1) @ _weight(
                self, "o_proj", (self.q_heads * d, hidden))


class SwiGLU(nn.Module):
    width: int

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        gate = x @ _weight(self, "gate_proj", (hidden, self.width))
        up = x @ _weight(self, "up_proj", (hidden, self.width))
        return (jax.nn.silu(gate) * up) @ _weight(
            self, "down_proj", (self.width, hidden))


class ExpertWeights(nn.Module):
    """The held experts' weights, one leading axis."""
    held: int
    width: int

    @nn.compact
    def __call__(self, hidden):
        return {"gate_proj": _weight(self, "gate_proj",
                                     (self.held, hidden, self.width)),
                "up_proj": _weight(self, "up_proj",
                                   (self.held, hidden, self.width)),
                "down_proj": _weight(self, "down_proj",
                                     (self.held, self.width, hidden))}


def routed_part(rows: int, chunks: int, tokens, w, order, slot_weight, sizes,
                top_k: int):
    """The held experts' part of the routed sum ``[tokens, hidden]``, exact
    for any routing: the slots, sorted by held expert (``order``; ``sizes``
    slots an expert), pass in chunks of ``rows`` rows, as many as the held
    slots' count needs and at most ``chunks`` (the worst case). A chunk
    gathers its rows, passes them through the grouped SwiGLU
    (``jax.lax.ragged_dot``), weights them and scatter-adds them back. Rows
    past the held slots go in as zeros and the chunk's last group is
    stretched over them, so they come out as zeros and no row lies outside
    every group (the grouped product leaves such rows undefined on a TPU,
    in both passes). A chunk is thus computed whole: a step's time is a step
    function of the held slots' count and not a line through it.

    The loop over the chunks has its own derivative rule: going backward
    the chunks pass once more, each computing its activations again, and
    the gradients of ``tokens``, ``w`` and ``slot_weight`` add up over them.
    (Differentiated by JAX a ``lax.scan`` keeps one copy of the tokens and
    the weights a chunk: 4 GiB at the published widths.)"""
    def one(lo, slots, tokens, w, slot_weight):
        order, ends = slots
        starts, count = ends - jnp.diff(ends, prepend=0), ends[-1]
        mine = jax.lax.dynamic_slice(order, (lo,), (rows,))
        token_of = mine // top_k
        groups = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo,
                                                          lo + rows)
        groups = groups.at[-1].add(rows - jnp.sum(groups))
        valid = (lo + jnp.arange(rows) < count)[:, None]
        xs = jnp.where(valid, tokens[token_of], 0)
        gate = jax.lax.ragged_dot(xs, w["gate_proj"], groups)
        up = jax.lax.ragged_dot(xs, w["up_proj"], groups)
        ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w["down_proj"],
                                groups)
        ys = ys * slot_weight[mine][:, None].astype(ys.dtype)
        return jnp.zeros_like(tokens).at[token_of].add(ys)

    def over_chunks(add_chunk, count, total):
        """``total`` with ``add_chunk(lo, total)`` applied for the first
        chunk and for every further one that holds a held slot. The first
        stands outside the loop, where the compiler fuses it with its
        neighbours (inside it a round took 1 % longer: my chip runs, PR 28);
        the loop takes as many turns as the count needs, none at the usual
        load, and no derivative is taken through it."""
        return jax.lax.fori_loop(
            1, (count + rows - 1) // rows,
            lambda c, t: add_chunk(c * rows, t),
            add_chunk(0, total))

    @jax.custom_vjp
    def routed(slots, *operands):
        return over_chunks(
            lambda lo, total: total + one(lo, slots, *operands),
            slots[1][-1], jnp.zeros_like(operands[0]))

    def backward(saved, g):
        slots, operands = saved

        def add_chunk(lo, total):
            _, vjp = jax.vjp(lambda *a: one(lo, slots, *a), *operands)
            return jax.tree_util.tree_map(jnp.add, total, vjp(g))

        return (None,) + over_chunks(
            add_chunk, slots[1][-1],
            jax.tree_util.tree_map(jnp.zeros_like, operands))

    routed.defvjp(lambda slots, *operands: (
        routed(slots, *operands), (slots, operands)), backward)
    order = jnp.pad(order, (0, max(0, rows * chunks - order.shape[0])))
    return routed((order, jnp.cumsum(sizes)), tokens, w, slot_weight)


class SparseMLP(nn.Module):
    """A router over all published experts, the held experts' part of the
    routed sum, and the shared expert.

    Every token is routed over all ``n_experts`` logits with the published
    top-k, renormalisation and scale. Of its k slots those that fall on the
    experts held here (ids ``first_expert .. first_expert + held``) are
    computed: slots sorted by held expert, then :func:`routed_part`.
    **No capacity factor, no dropped slot**: the slots pass in chunks of
    ``usual_load`` times the expected number of held slots (``tokens * k *
    held / n_experts``), as many chunks as the count needs, up to the
    ``tokens * min(k, held)`` slots that can fall on the held experts at
    most. At the usual load one chunk runs, and it is computed whole:
    how many slots fall on the held experts differs by half between seeds
    and drifts as the router trains (my chip runs, PR 28: ``rounds_per_s``
    spread 2-4 % over six seeds where the grouped product followed the
    count, against the 0.5 % the benchmark admits a cell with)."""
    n_experts: int
    top_k: int
    renormalise: bool
    scale: float
    first_expert: int
    held: int
    width: int
    shared_width: int
    usual_load: int = 4

    def buffer_rows(self, tokens: int):
        """``(rows, chunks)`` of the held slots' buffer: the rows of a chunk
        and the chunks that hold the worst case."""
        worst = tokens * min(self.top_k, self.held)
        rows = min(worst, self.usual_load * -(
            -tokens * self.top_k * self.held // self.n_experts))
        return rows, -(-worst // rows)

    @nn.compact
    def __call__(self, x):
        tokens = x.reshape(-1, x.shape[-1])
        with jax.named_scope("router"):
            logits = jnp.dot(
                tokens, _weight(self, "router", (x.shape[-1], self.n_experts)),
                preferred_element_type=jnp.float32)
            top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                         self.top_k)
            if self.renormalise:
                top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
            top_p = top_p * self.scale
            local = (top_e - self.first_expert).reshape(-1)
            local = jnp.where((local >= 0) & (local < self.held), local,
                              self.held)          # not held here: sorts last
            sizes = jnp.zeros((self.held + 1,), jnp.int32).at[local].add(
                1)[:self.held]
            order = jnp.argsort(local, stable=True)
        with jax.named_scope("experts"):
            w = ExpertWeights(self.held, self.width, name="experts")(
                x.shape[-1])
            routed = routed_part(*self.buffer_rows(tokens.shape[0]), tokens,
                                 w, order, top_p.reshape(-1), sizes,
                                 self.top_k)
        with jax.named_scope("shared_expert"):
            shared = SwiGLU(self.shared_width, name="shared_expert")(tokens)
        # free unless the caller opens the collection (obs/expert_load.py)
        self.sow(EXPERT_STATS, "held_counts", sizes)
        self.sow(EXPERT_STATS, "top_experts", top_e)
        return (routed + shared).reshape(x.shape)


class Block(nn.Module):
    cfg: Tuple      # held_config(...) as nested sorted items (hashable)
    layer: int

    @nn.compact
    def __call__(self, x):
        cfg = _thaw(self.cfg)
        kind = cfg["layer_types"][self.layer]
        eps, hidden = cfg["rms_norm_eps"], x.shape[-1]
        x = x + Attention(
            kind, cfg["num_attention_heads_per_layer"][self.layer],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], _freeze(cfg["rope_parameters"][kind]),
            name="attention")(
                rms_norm(x, _norm_weight(self, "attn_norm", hidden), eps))
        h = rms_norm(x, _norm_weight(self, "mlp_norm", hidden), eps)
        if cfg["mlp_layer_types"][self.layer] == "dense":
            with jax.named_scope("dense_mlp"):
                return x + SwiGLU(cfg["intermediate_size"], name="mlp")(h)
        return x + SparseMLP(
            cfg["published"]["num_experts"], cfg["num_experts_per_tok"],
            cfg["norm_topk_prob"], cfg["moe_routed_scaling_factor"],
            cfg["first_expert"], cfg["num_experts"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], name="mlp")(h)


class Decoder(nn.Module):
    """``tokens [B, S]`` int32 (ids of the held vocabulary rows) ->
    float32 logits ``[B, S, V_held]``. Each block is rematerialised
    (``nn.remat``): the backward pass keeps the blocks' inputs and computes
    one block's activations at a time."""
    cfg: Tuple

    @property
    def num_classes(self) -> int:
        return _thaw(self.cfg)["vocab_size"]

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        cfg = _thaw(self.cfg)
        hidden, vocab = cfg["hidden_size"], cfg["vocab_size"]
        with jax.named_scope("embed"):
            x = jnp.take(_weight(self, "embed", (vocab, hidden)), tokens,
                         axis=0)
        for i in range(cfg["num_hidden_layers"]):
            x = nn.remat(Block)(self.cfg, i, name=f"layers_{i}")(x)
        x = rms_norm(x, _norm_weight(self, "final_norm", hidden),
                     cfg["rms_norm_eps"])
        with jax.named_scope("lm_head"):
            return jnp.dot(x, _weight(self, "lm_head", (hidden, vocab)),
                           preferred_element_type=jnp.float32)


def _freeze(value):
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    if isinstance(value, list):
        return ("__list__",) + tuple(_freeze(v) for v in value)
    return value


def _thaw(value):
    if isinstance(value, tuple):
        if value[:1] == ("__list__",):
            return [_thaw(v) for v in value[1:]]
        return {k: _thaw(v) for k, v in value}
    return value


def decoder(name: str, share: Share = Share()) -> Decoder:
    """The zoo's model ``name`` as one chip's ``share`` of it."""
    return Decoder(_freeze(held_config(name, share)))
