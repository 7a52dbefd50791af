"""Plain float32 reference of ``Phi-4-mini-flash-reasoning``
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json,
``model_type`` ``phi4flash``; the SambaY decoder-hybrid-decoder of
arXiv:2507.06607): forward, per-token cross-entropy, gradients, one SGD step
in straightforward ``jax.numpy``, every product at
``jax.default_matmul_precision("highest")``. Nothing is imported from the
program; no kernel, no cache, no batching (one sequence at a time), no
padding, **and no chunked algorithm: the Mamba mixer is the recurrence
itself, token by token** (a ``lax.scan`` over the sequence, rematerialised by
segments so that its backward fits). Attention is written with explicit
masks (window, full, cross), in blocks of rows so that 16,384 tokens fit on
one chip. What it shares with the other decoder references (the causal conv,
the loss in blocks, a counted row) it takes from them.

``cfg`` is the configuration's file
(``benchmarks/configs/phi4_mini_flash_fed.json``) with the counts a chip
holds laid over it, or any dictionary with the same keys. Counts that the
weights state (heads, MLP columns, GMU channels, vocabulary rows) are read
from the weights' shapes, so one function serves the uncut model and any
chip's share of it.

The equations are the family's published description and modelling code,
written from memory (no network here): each stands under ``ASSUMED`` and in
the configuration's file. ``x`` is ``[T, 2560]``, l the published layer
index; keys of ``config.json`` in brackets::

    layer kinds [num_hidden_layers 32, mb_per_layer 2]: Mamba where
      l % 2 == 0, attention elsewhere; l < 16 the self-decoder (Mamba,
      window attention); l = 16 a Mamba whose y is the memory m; l = 17
      full attention whose k and v are shared; l > 17 the cross-decoder (a
      GMU where a Mamba would stand, cross attention elsewhere)   [assumed 1]
    block:  h = x + mixer(LN_1(x)); out = h + mlp(LN_2(h))
      LN(x) = (x - mean) / sqrt(var + 1e-5) * w + b       [layer_norm_eps]
    mlp(x) = W_2 (silu(x W_g) * x W_u), 10240 columns [intermediate_size,
      hidden_act silu; mlp_bias false]: the published fc1 split in two
    mamba(x): [u, z] = x W_in, W_in [2560, 2 x 5120]           [assumed 2]
      u = silu(conv4(u) + b_conv), causal depthwise, 4 taps
      [delta, B, C] = u W_x, W_x [5120, 160 + 16 + 16]
      delta = softplus(delta W_dt + b_dt) [T, 5120]; A = -exp(A_log)
        [5120, 16]
      per channel d and state n, h zero at the start:
        h_t[d, n] = exp(delta_t[d] A[d, n]) h_{t-1}[d, n]
                    + delta_t[d] B_t[n] u_t[d]
        y_t[d] = sum_n h_t[d, n] C_t[n] + D[d] u_t[d]
      out = (y * silu(z)) W_out, W_out [5120, 2560]; memory m = y   [3]
    gmu(x) = (m * silu(x W_1)) W_2, W_1 [2560, 5120], W_2 [5120, 2560]
    attention (differential; 40 query and 20 KV heads of 64): [assumed 4]
      q = x W_q; k, v = x W_k, x W_v (cross: k, v are layer 17's); no bias,
      no rotary; query pair p (heads 2p, 2p + 1) reads KV pair j = p // 2
      A1 = softmax(q_2p k_2j^T / 8 + mask), A2 = softmax(q_2p+1 k_2j+1^T / 8
        + mask); o_p = (A1 - lam A2) [v_2j | v_2j+1]
      o_p = rmsnorm_128(o_p) * w_subln * (1 - lam_init); out = o W_o
      lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,
        lam_init = 0.8 - 0.6 exp(-0.3 l)
      mask: window i - 512 < j <= i [sliding_window]; full and cross j <= i
    logits = LN_f(x) E^T, E the embedding [tie_word_embeddings]

``ASSUMED`` (the catalog's row gives the keys and not the equations): (1)
the index rule of the layer kinds; (2) the Mamba-1 mixer's sizes, the
family's configuration defaults (state 16, conv 4, expansion 2, dt rank
ceil(2560 / 16) = 160); (3) that the memory is ``y`` after D's skip and
before the z gate; (4) differential attention's pairing (interleaved: with
random weights the layout changes nothing), no bias in its projections, the
norm over a pair's 128 channels with eps 1e-5 and the window's convention.
``embd_pdrop`` and ``resid_pdrop`` are 0 (no dropout), ``lm_head_bias`` and
``mlp_bias`` false; ``max_position_embeddings`` changes nothing.

Departures from the published description:

* none in the mathematics. The leaves are drawn by the program from the
  seed (the reference is given the same weights): ``A_log`` S4D-real's
  ``log(1 .. 16)`` for every channel, ``delta`` log-uniform in [1e-3, 1e-1]
  through the inverse softplus into ``b_dt``, ``D`` ones, the taps and their
  bias uniform in +-1/2, the lambda vectors normal(0, 0.1), every norm
  weight 1 and bias 0, every matrix normal(0, 0.02).
* a share's partial results: the held heads' part of an attention layer's
  output, the held columns' part of the MLP's ``W_2`` sum, the held
  channels' part of a GMU's (they read the same channels of the memory,
  from ``first_gmu_channel`` on), logits and loss over the held vocabulary
  rows. The Mamba mixer is held whole. Sums over all shares give the uncut
  layer (``tests/test_decoder_phi4_flash.py``).
* the loss ignores targets below 0 (the last position has no next token).
* ``faults`` (controls, for the benchmark's tests and its chip controls):
  ``no_carry`` zeroes the recurrence's state every ``mamba_chunk_size``
  tokens; ``bf16_state`` rounds the state to bfloat16 after every token;
  ``one_map`` leaves the second map out (``lam`` 0); ``no_memory`` gives the
  GMUs a memory of zeros.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .falcon_h1 import causal_conv
from .laguna_s import _row as _counted_row
from .laguna_s import _highest
from .qwen3_next import blocked_cross_entropy

Q_BLOCK = 256       # query rows of an attention layer's scores at once
SEGMENT = 128       # tokens of the recurrence whose states are kept at once
ASSUMED = ("layer_index_rule", "mamba_sizes", "memory_before_gate",
           "differential_pairs_and_norm")
FAULTS = ("no_carry", "bf16_state", "one_map", "no_memory")


def layer_types(cfg: dict) -> list:
    """The published kinds of the layers this configuration holds (from
    ``first_layer`` on), by the rule above."""
    n, every = cfg["published"]["num_hidden_layers"], cfg["mb_per_layer"]
    half, first = n // 2, cfg.get("first_layer", 0)

    def kind(i):
        if i < half:
            return "mamba" if i % every == 0 else "window"
        if i in (half, half + 1):
            return ("memory", "full")[i - half]
        return "gmu" if i % every == 0 else "cross"

    return [kind(i) for i in range(first, first + cfg["num_hidden_layers"])]


# -- the forward pass --------------------------------------------------------

def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def to_bfloat16(x):
    """float32 ``x`` rounded to the nearest bfloat16 (ties to even), by its
    bits: a cast to bfloat16 and back is a pair of converts that XLA's TPU
    compiler may drop as excess precision (it did, on a TPU v5e). The
    gradient passes as if unrounded: the bits carry none, and a state kept
    in bfloat16 still has one."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    rounded = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                           jnp.float32)
    return x + jax.lax.stop_gradient(rounded - x)


def recurrence(u, delta, a, b, c, d_skip, reset_every=0, bf16_state=False,
               segment=SEGMENT):
    """``y [S, D]`` of the recurrence, one token after another, with ``u,
    delta [S, D]``, ``a [D, N]``, ``b, c [S, N]``, ``d_skip [D]``, the state
    ``[D, N]`` zero before the sequence. The sequence passes in segments of
    ``segment`` tokens, each computed again going backward, so that one
    segment's states and the segments' first ones are all that is ever
    kept. ``reset_every`` > 0 zeroes the state before every token whose
    position it divides; ``bf16_state`` rounds it to bfloat16 after every
    token (controls)."""
    s_len = u.shape[0]

    def token(state, at):
        t, u_t, dt_t, b_t, c_t = at
        if reset_every:
            state = jnp.where(t % reset_every == 0, 0.0, state)
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        if bf16_state:
            state = to_bfloat16(state)
        return state, jnp.sum(state * c_t[None, :], axis=-1) + d_skip * u_t

    @jax.checkpoint
    def run(state, tokens):
        return jax.lax.scan(token, state, tokens)

    if s_len % segment:
        segment = s_len
    tokens = jax.tree_util.tree_map(
        lambda t: t.reshape((s_len // segment, segment) + t.shape[1:]),
        (jnp.arange(s_len), u, delta, b, c))
    _, y = jax.lax.scan(run, jnp.zeros(a.shape, u.dtype), tokens)
    return y.reshape(u.shape)


@_highest
def mamba(p, x, cfg, faults=()):
    """The Mamba mixer's output ``[S, hidden]`` and its ``y`` (the memory,
    where the layer is the memory's), of the block's normed input."""
    n, rank = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    u, z = jnp.split(x @ p["in_proj"], 2, axis=-1)
    u = jax.nn.silu(causal_conv(u, p["conv"], p["conv_bias"]))
    delta, b, c = jnp.split(u @ p["x_proj"], [rank, rank + n], axis=-1)
    delta = jax.nn.softplus(delta @ p["dt_proj"] + p["dt_bias"])
    y = recurrence(u, delta, -jnp.exp(p["A_log"]), b, c, p["D"],
                   cfg["mamba_chunk_size"] if "no_carry" in faults else 0,
                   "bf16_state" in faults)
    return (y * jax.nn.silu(z)) @ p["out_proj"], y


@_highest
def gmu(p, x, memory, cfg):
    """The held channels' part of a gated memory unit's output."""
    width, first = p["in_proj"].shape[1], cfg.get("first_gmu_channel", 0)
    held = memory[:, first:first + width]
    return (held * jax.nn.silu(x @ p["in_proj"])) @ p["out_proj"]


@_highest
def attention(p, x, cfg, kind, layer, kv=None, q_block=Q_BLOCK,
              remat=False, faults=()):
    """This share's part of a differential attention layer's output ``[S,
    hidden]`` and its ``(k, v)`` (``[S, KV heads, 64]`` each): the layer's
    own, or ``kv`` for cross attention. ``layer`` is the published index."""
    s_len, d = x.shape[0], cfg["hidden_size"] // cfg["published"][
        "num_attention_heads"]
    start = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = jnp.exp(jnp.dot(p["lambda_q1"], p["lambda_k1"])) \
        - jnp.exp(jnp.dot(p["lambda_q2"], p["lambda_k2"])) + start
    if "one_map" in faults:
        lam = 0.0
    q = (x @ p["q_proj"]).reshape(s_len, -1, 2, d)          # [S, pairs, 2, d]
    if kv is None:
        kv = tuple((x @ p[name]).reshape(s_len, -1, d)
                   for name in ("k_proj", "v_proj"))
    k, v = (t.reshape(s_len, -1, 2, d) for t in kv)
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v.reshape(s_len, -1, 2 * d), group, axis=1)
    k_pos = jnp.arange(s_len)[None, :]
    window = cfg["sliding_window"] if kind == "window" else s_len

    def rows(q_blk, first_row):
        i = first_row + jnp.arange(q_blk.shape[0])[:, None]
        seen = (k_pos <= i) & (k_pos > i - window)

        def softmax_map(m):
            scores = jnp.einsum("qpd,kpd->pqk", q_blk[:, :, m], k[:, :, m]) \
                / math.sqrt(d)
            return jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)

        out = jnp.einsum("pqk,kpe->qpe", softmax_map(0) - lam * softmax_map(1),
                         v)
        out = out * jax.lax.rsqrt(jnp.mean(out * out, -1, keepdims=True)
                                  + cfg["layer_norm_eps"])
        return out * p["subln"] * (1 - start)

    if remat:
        rows = jax.checkpoint(rows)
    if s_len % q_block == 0 and s_len > q_block:
        out = jax.lax.map(
            lambda blk: rows(*blk),
            (q.reshape((-1, q_block) + q.shape[1:]),
             jnp.arange(0, s_len, q_block)))
    else:
        out = jnp.stack([rows(q[i:i + q_block], jnp.asarray(i))
                         for i in range(0, s_len, q_block)])
    return out.reshape(s_len, -1) @ p["o_proj"], kv


@_highest
def mlp(p, x):
    """This share's columns' part of the MLP's output."""
    return (jax.nn.silu(x @ p["gate_proj"]) * (x @ p["up_proj"])) \
        @ p["down_proj"]


def hidden_states(params, tokens, cfg, remat=False, faults=()):
    """``x [S, hidden]`` after the final norm, of one sequence of token ids
    (drawn from the held vocabulary rows)."""
    eps = cfg["layer_norm_eps"]
    first = cfg.get("first_layer", 0)
    x = params["embed"][tokens]
    memory = kv = None
    for i, (layer, kind) in enumerate(zip(params["layers"],
                                          layer_types(cfg))):
        h = layer_norm(x, layer["attn_norm"], layer["attn_norm_bias"], eps)
        if kind in ("mamba", "memory"):
            mixer = functools.partial(mamba, cfg=cfg, faults=faults)
            if remat:
                mixer = jax.checkpoint(mixer)
            out, y = mixer(layer["mamba"], h)
            if kind == "memory":
                memory = jnp.zeros_like(y) if "no_memory" in faults else y
        elif kind == "gmu":
            out = gmu(layer["gmu"], h, memory, cfg)
        else:
            out, made = attention(layer["attention"], h, cfg, kind, first + i,
                                  kv if kind == "cross" else None,
                                  remat=remat, faults=faults)
            if kind == "full":
                kv = made
        x = x + out
        h = layer_norm(x, layer["mlp_norm"], layer["mlp_norm_bias"], eps)
        feed = jax.checkpoint(mlp) if remat else mlp
        x = x + feed(layer["mlp"], h)
    return layer_norm(x, params["final_norm"], params["final_norm_bias"], eps)


def forward(params, tokens, cfg, first_expert=0, remat=False, faults=()):
    """``(logits [S, V_held], routing)``: the head is the embedding
    (tied). A dense model routes nothing: ``routing`` is the one the
    benchmark's shared check compares for every decoder, that of the dense
    MLP read as one expert, held, that every token chooses (``[[S, 1]]``
    of zeros); ``first_expert`` is that check's, unused."""
    del first_expert
    x = hidden_states(params, tokens, cfg, remat, faults)
    with jax.default_matmul_precision("highest"):
        return x @ params["embed"].T, one_expert(tokens.shape[0])


def one_expert(tokens: int) -> list:
    """The dense MLP as a routing: one layer, one expert, every token."""
    return [jnp.zeros((tokens, 1), jnp.int32)]


def loss_and_logits(params, tokens, targets, cfg, first_expert=0,
                    remat=False, faults=(), stride=1):
    """The loss (its logits in blocks), the logits at every ``stride``-th
    position and the routing."""
    del first_expert
    x = hidden_states(params, tokens, cfg, remat, faults)
    loss = blocked_cross_entropy(x, params["embed"].T, targets)
    with jax.default_matmul_precision("highest"):
        logits = x[::stride] @ params["embed"].T
    return loss, (logits, one_expert(tokens.shape[0]))


def sgd_step(params, tokens, targets, cfg, lr, clip, first_expert=0,
             remat=False, faults=(), stride=1):
    """One step of plain SGD on one sequence, as ``laguna_s.sgd_step``: the
    gradient of the token cross-entropy, scaled down to the norm ``clip``
    where it is longer (times ``clip / (norm + 1e-6)``, at most 1), times
    ``lr`` off the parameters. Returns ``(parameters, loss, logits,
    routing)``, the last three at the parameters it was given, the logits
    at every ``stride``-th position."""
    (loss, (logits, routing)), grads = jax.value_and_grad(
        loss_and_logits, has_aux=True)(params, tokens, targets, cfg,
                                       first_expert, remat, faults, stride)
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
            "final_norm_bias": tree["final_norm_bias"],
            "layers": [tree[f"layers_{i}"] for i in range(n)]}


# the lambda vectors of the ten-layer stage's five attention layers (window,
# window, full, cross, cross): read over the round as the other leaves are,
# and judged together, as one vector, by the family's check
# (families/tokens_flash.py): one layer's lambda gradient is one scalar,
# dL/dlam, times a fixed vector, and that scalar lies near 0 on some seeds,
# where a sound program's rounding is all there is of the change
LAMBDA_LEAVES = {f"lambda_q1_{i}": ("layers", i, "attention", "lambda_q1")
                 for i in (1, 3, 5, 7, 9)}
# one trained leaf of each kind, as (path in this file's tree) per name; the
# layer indices are those of the stage 12-21 (Mamba, window, Mamba, window,
# the memory's Mamba, full, GMU, cross, GMU, cross): layer 4's W_in feeds
# the GMUs through the memory, layer 5's W_k feeds the two cross layers
GRAD_LEAVES = {
    "mamba_in_proj": ("layers", 0, "mamba", "in_proj"),
    "mamba_conv_taps": ("layers", 0, "mamba", "conv"),
    "mamba_x_proj": ("layers", 0, "mamba", "x_proj"),
    "mamba_dt_proj": ("layers", 0, "mamba", "dt_proj"),
    "mamba_A_log": ("layers", 0, "mamba", "A_log"),
    "mamba_dt_bias": ("layers", 0, "mamba", "dt_bias"),
    "mamba_out_proj": ("layers", 0, "mamba", "out_proj"),
    "memory_in_proj": ("layers", 4, "mamba", "in_proj"),
    "window_q_proj": ("layers", 1, "attention", "q_proj"),
    "full_k_proj": ("layers", 5, "attention", "k_proj"),
    "full_v_proj": ("layers", 5, "attention", "v_proj"),
    "gmu_in_proj": ("layers", 6, "gmu", "in_proj"),
    "cross_q_proj": ("layers", 7, "attention", "q_proj"),
    "mlp_up": ("layers", -1, "mlp", "up_proj"),
    "embed": ("embed",),
    **LAMBDA_LEAVES,
}


# -- operations and elements, from shapes alone ------------------------------

def attended_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal layer scores: position i sees
    ``min(i + 1, window)`` keys."""
    full = min(seq, window)
    return full * (full + 1) // 2 + (seq - full) * window


def layers(cfg: dict, seq: int) -> list:
    """The cut model's rows per scope of the program, for one sequence of
    ``seq`` tokens: ``embed``; the Mamba mixers as ``mamba_proj`` (``W_in``,
    ``W_x``, ``W_dt``, ``W_out`` and the conv; by bytes x, the projections'
    outputs and the output) and ``mamba_scan`` (**the recurrence: its two
    multiply-adds a channel, state and token by operations; u, delta, B, C
    in and y out once each by bytes**, what a kernel for the scan would
    move); the GMUs (``gmu``); ``attention_proj`` (q, k, v and o of the
    window and full layers, q and o of the cross layers), ``attention_full``
    (the full layer's and **the cross layers'** causal pairs) and
    ``attention_window`` (the band's pairs), both maps of a pair counted at
    the published width 64 (``(A1 - lam A2) V`` as one product over a
    pair's 128 channels: 4 x 64 operations a head and pair, the padded lanes
    waste) and by bytes q, k, v and the output (no score crosses HBM, as the
    flash kernels run it); ``dense_mlp`` over the held columns, ``lm_head``
    (the tied embedding) and the norms' weights and biases. ``params`` add
    up to the held model."""
    h = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // cfg["published"]["num_attention_heads"]
    vocab = cfg["vocab_size"]
    kinds = layer_types(cfg)
    count = {k: kinds.count(k) for k in ("mamba", "memory", "window", "full",
                                         "gmu", "cross")}
    n_mamba = count["mamba"] + count["memory"]
    ch = cfg["mamba_expand"] * h
    state, rank, taps = (cfg["mamba_d_state"], cfg["mamba_dt_rank"],
                         cfg["mamba_d_conv"])
    columns = cfg.get("mlp_columns", cfg["intermediate_size"])
    gmu_ch = cfg.get("gmu_channels", ch)
    n = len(kinds)
    mamba_mat = h * 2 * ch + ch * (rank + 2 * state) + rank * ch + ch * h
    mamba_small = ch * (taps + 1) + ch * state + 2 * ch      # A_log, b_dt, D
    own = count["window"] + count["full"]        # layers with k and v
    proj = own * h * (2 * heads * d + 2 * kv * d) + count["cross"] * h * (
        2 * heads * d) + (own + count["cross"]) * (4 * d + 2 * d)
    full_pairs = (count["full"] + count["cross"]) * seq * (seq + 1) // 2
    window_pairs = count["window"] * attended_pairs(seq,
                                                    cfg["sliding_window"])
    attn_bytes = seq * d * (2 * heads + 2 * kv)
    return [
        _counted_row("embed", "embed", vocab * h, 0, 2 * seq * h),
        _counted_row("mamba_proj", "mamba",
                     n_mamba * (mamba_mat + mamba_small),
                     2.0 * seq * n_mamba * mamba_mat,
                     n_mamba * seq * (h + 2 * ch + ch + rank + 2 * state + h)),
        _counted_row("mamba_scan", "mamba/scan", 0,
                     n_mamba * 4.0 * seq * ch * state,
                     n_mamba * seq * (3 * ch + 2 * state)),
        _counted_row("gmu", "gmu", count["gmu"] * 2 * h * gmu_ch,
                     2.0 * seq * count["gmu"] * 2 * h * gmu_ch,
                     count["gmu"] * seq * (2 * h + 3 * gmu_ch)),
        _counted_row("attention_proj", "attention", proj,
                     2.0 * seq * (own * h * (2 * heads * d + 2 * kv * d)
                                  + count["cross"] * h * 2 * heads * d),
                     (own + count["cross"]) * 4 * seq * h),
        _counted_row("attention_full", "attention/full", 0,
                     2.0 * 2 * full_pairs * d * heads,
                     (count["full"] + count["cross"]) * attn_bytes),
        _counted_row("attention_window", "attention/window", 0,
                     2.0 * 2 * window_pairs * d * heads,
                     count["window"] * attn_bytes),
        _counted_row("dense_mlp", "dense_mlp", n * 3 * h * columns,
                     2.0 * seq * n * 3 * h * columns,
                     n * seq * (2 * h + 3 * columns)),
        _counted_row("lm_head", "lm_head", 0, 2.0 * seq * h * vocab,
                     seq * (h + 2 * vocab)),
        _counted_row("norms", "-", (2 * n + 1) * 2 * h, 0,
                     (2 * n + 1) * 2 * seq * h),
    ]
