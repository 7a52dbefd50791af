"""Plain float32 reference of ``Falcon-H1-34B-Instruct``
(https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json,
``model_type`` ``falcon_h1``): forward, per-token cross-entropy, gradients,
one SGD step in straightforward ``jax.numpy``, every product at
``jax.default_matmul_precision("highest")``. Nothing is imported from the
program; no kernel, no cache, no batching (one sequence at a time), **and no
chunked algorithm: the state-space mixer is the recurrence itself, token by
token** (a ``lax.scan`` over the sequence, rematerialised by segments so that
its backward fits). What it shares with the other decoder references (the
norm, the rotary embedding, the loss, a counted row) it takes from
``laguna_s.py``.

``cfg`` is the configuration's file
(``benchmarks/configs/falcon_h1_34b_fed.json``) with the counts a chip holds
laid over it, or any dictionary with the same keys. Counts that the weights
state (attention heads, state-space heads and groups, MLP columns,
vocabulary rows) are read from the weights' shapes, so one function serves
the uncut model and any chip's share of it.

The equations are transformers' ``modeling_falcon_h1.py``'s, written from
memory (no network here): each stands under ``ASSUMED`` and in the
configuration's file. ``h`` is ``[T, 5120]``; no ``Linear`` has a bias
(``attention_bias``, ``mamba_proj_bias``, ``mlp_bias``, ``projectors_bias``
false); keys of ``config.json`` in brackets::

    h = embed[ids] * 5.657                          [embedding_multiplier]
    block:  n = rms_norm_in(h)
            h = h + ssm(n) * 0.0884 + attn(n * 1) * 0.0375
              [ssm_out_multiplier, attention_in_multiplier,
               attention_out_multiplier]: ONE normed input, two mixers side
              by side, both outputs added                      [assumed 1]
            h = h + mlp(rms_norm_ff(h))
      rms_norm(x) = x / sqrt(mean(x^2) + 1e-5) * w             [rms_norm_eps]
    attn(x): q, k, v = x W_q, (x W_k) * 0.01105, x W_v  [key_multiplier];
      20 / 4 / 4 heads of 128 [num_attention_heads, num_key_value_heads,
      head_dim]; RoPE over the whole head, rotate-half pairs (i, i + 64),
      f_i = 1e11 ** (-2 i / 128) [rope_theta; rope_scaling null]; causal
      softmax at scale 128 ** -0.5, a KV head shared by 5 query heads; W_o
      [2560, 5120]; no gate, no QK-norm, no window             [assumed 2]
    ssm(x): p = ((x * 0.25) W_in) * m         [ssm_in_multiplier]
      W_in [5120, 9248]; 9248 = 4096 (z) + 4096 (x) + 512 (B: 2 groups of
      256) + 512 (C) + 32 (dt), in that order [mamba_d_ssm, mamba_n_groups,
      mamba_d_state, mamba_n_heads]; m the constant vector that holds
      ssm_multipliers[0..4] over those five segments           [assumed 3]
      [x, B, C] = silu(conv([x, B, C]) + bias): a causal depthwise
        convolution of 4 taps [mamba_d_conv] with a bias [mamba_conv_bias]
        over the 5120 channels of x, B, C together, c[t] = w[:, 0] u[t-3] +
        w[:, 1] u[t-2] + w[:, 2] u[t-1] + w[:, 3] u[t], u zero before the
        sequence                                               [assumed 4]
      dt = softplus(dt + dt_bias) [T, 32]; A = -exp(A_log) [32]
      per head j of group g(j) = j // 16, state S [128, 256] [mamba_d_head,
        mamba_d_state], zero at the start:
          S_t = exp(dt_t A) S_{t-1} + dt_t * outer(x_t, B_t^g)
          y_t = S_t C_t^g + D * x_t                            [assumed 5]
      y = y * silu(z); an RMSNorm over each GROUP's 2048 channels with a
        learned weight [4096] [mamba_rms_norm true, mamba_norm_before_gate
        false]; W_out [4096, 5120]                             [assumed 6]
      dt_bias, A_log, D are [32] and float32
    mlp(x) = (W_down (silu((W_gate x) * 0.1768) * (W_up x))) * 0.01116
      [mlp_multipliers], width 21504 [intermediate_size]       [assumed 7]
    logits = (rms_norm_final(h) W_head) * 1/128  [lm_head_multiplier];
      embedding and head untied [tie_word_embeddings]

``ASSUMED`` (the catalog's row gives the keys and not the equations): (1)
the block's form, both mixers on the one normed input and where the three
multipliers stand; (2) the attention, ``key_multiplier`` on the keys'
projection before the rotary; (3) ``in_proj``'s column order and that the
five ``ssm_multipliers`` scale its segments; (4) the conv over x, B, C
together, its taps' order, its bias, the silu; (5) Mamba-2's recurrence with
``dt`` through a softplus and ``D``'s skip; (6) the gate before a grouped
RMSNorm; (7) where the two ``mlp_multipliers`` stand. ``mamba_use_mlp``
(every block has its MLP), ``num_logits_to_keep``, ``attn_layer_indices:
null``, ``mamba_chunk_size`` (the chunk is an algorithm's, not the model's)
and ``mamba_expand`` change nothing in these equations.

Departures from the published description:

* none in the mathematics. **The small leaves are drawn as Mamba-2 draws
  them** (by the program, from the seed; the reference is given the same
  weights): ``A`` uniform in [1, 16], ``dt`` log-uniform in [1e-3, 1e-1]
  through the inverse softplus into ``dt_bias``, ``D`` ones, the conv's taps
  and bias uniform in +-1/2 (a depthwise ``Conv1d``'s default): with
  normal(0, 0.02) leaves every state would die inside its chunk and no check
  could see what a chunked algorithm carries between chunks.
* a share's partial results, as in ``laguna_s.py``: the held heads' part of
  the attention output, the held state-space heads' (whole groups: their
  scan and their norm are local), the held columns' part of the MLP's
  ``W_down`` sum, logits and loss over the held vocabulary rows. Sums over
  all shares give the uncut layer (``tests/test_decoder_falcon_h1.py``).
* the loss ignores targets below 0 (the last position has no next token).
* attention is the masked full product in blocks of ``q_block`` queries
  against every key; with ``remat=True`` a block's scores, an MLP's
  activations and a mixer's are computed again going backward.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .laguna_s import _row as _counted_row
from .laguna_s import _highest, apply_rope, rms_norm, token_cross_entropy

Q_BLOCK = 1024
SEGMENT = 128       # tokens of the recurrence whose states are kept at once
ASSUMED = ("block_two_mixers", "attention_key_multiplier",
           "in_proj_order_and_multipliers", "conv_over_x_b_c", "recurrence",
           "gated_group_norm", "mlp_multipliers")


# -- the forward pass --------------------------------------------------------

@_highest
def attention(p, x, cfg, q_block=Q_BLOCK, remat=False):
    """This share's part of the attention output ``[S, hidden]``, of the
    block's normed input ``x``."""
    s_len, d = x.shape[0], cfg["head_dim"]
    rope = {"rope_theta": cfg["rope_theta"], "rope_type": "default"}
    x = x * cfg["attention_in_multiplier"]
    q = apply_rope((x @ p["q_proj"]).reshape(s_len, -1, d), rope, d)
    k = apply_rope(((x @ p["k_proj"]) * cfg["key_multiplier"]).reshape(
        s_len, -1, d), rope, d)
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
        out = jax.lax.map(
            lambda blk: block(*blk),
            (q.reshape((-1, q_block) + q.shape[1:]),
             jnp.arange(0, s_len, q_block))).reshape(q.shape)
    else:
        out = jnp.concatenate([block(q[i:i + q_block], jnp.asarray(i))
                               for i in range(0, s_len, q_block)], axis=0)
    return out.reshape(s_len, -1) @ p["o_proj"]


def causal_conv(u, taps, bias):
    """``c[t] = sum_j taps[:, j] u[t - (K - 1 - j)] + bias`` of ``u [S, C]``
    with ``taps [C, K]``, ``u`` zero before the sequence: the sum written
    out, tap by tap (the last tap weighs the current token)."""
    n_taps = taps.shape[1]
    out = taps[:, n_taps - 1] * u + bias
    for back in range(1, n_taps):       # the token ``back`` before
        before = jnp.concatenate([jnp.zeros_like(u[:back]), u[:-back]])
        out = out + taps[:, n_taps - 1 - back] * before
    return out


def recurrence(x, dt, a, b, c, d_skip, reset_every=0, segment=SEGMENT):
    """``y [S, H, P]`` of the recurrence, one token after another: per head
    ``S_t = exp(dt_t a) S_{t-1} + dt_t outer(x_t, b_t)``, ``y_t = S_t c_t +
    d_skip x_t`` with ``x [S, H, P]``, ``dt [S, H]``, ``a, d_skip [H]`` and
    ``b, c [S, G, N]`` (head ``j`` reads group ``j // (H / G)``), the state
    zero before the sequence. The sequence passes in segments of ``segment``
    tokens, each computed again going backward, so that one segment's
    states and the segments' first ones are all that is ever kept.
    ``reset_every`` > 0 (a control: the carry between chunks left out)
    zeroes the state before every token whose position it divides."""
    s_len, heads = dt.shape
    per = heads // b.shape[1]

    def token(state, at):
        t, x_t, dt_t, b_t, c_t = at
        if reset_every:
            state = jnp.where(t % reset_every == 0, 0.0, state)
        b_t, c_t = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) \
            + d_skip[:, None] * x_t

    @jax.checkpoint
    def run(state, tokens):
        return jax.lax.scan(token, state, tokens)

    if s_len % segment:
        segment = s_len
    tokens = jax.tree_util.tree_map(
        lambda t: t.reshape((s_len // segment, segment) + t.shape[1:]),
        (jnp.arange(s_len), x, dt, b, c))
    start = jnp.zeros(x.shape[1:] + (b.shape[-1],), x.dtype)
    _, y = jax.lax.scan(run, start, tokens)
    return y.reshape(x.shape)


@_highest
def ssm(p, x, cfg, reset_every=0):
    """This share's part of the state-space mixer's output ``[S, hidden]``,
    of the block's normed input ``x``."""
    s_len = x.shape[0]
    heads, d_head = p["A_log"].shape[0], cfg["mamba_d_head"]
    n, ch = cfg["mamba_d_state"], p["A_log"].shape[0] * cfg["mamba_d_head"]
    group_states = (p["in_proj"].shape[1] - 2 * ch - heads) // 2
    groups = group_states // n
    m = jnp.concatenate([
        jnp.full((width,), value, x.dtype) for width, value in zip(
            (ch, ch, group_states, group_states, heads),
            cfg["ssm_multipliers"])])
    proj = ((x * cfg["ssm_in_multiplier"]) @ p["in_proj"]) * m
    z, xbc, dt = jnp.split(proj, [ch, 2 * ch + 2 * group_states], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv"], p["conv_bias"]))
    u, b, c = jnp.split(xbc, [ch, ch + group_states], axis=-1)
    y = recurrence(
        u.reshape(s_len, heads, d_head), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]), b.reshape(s_len, groups, n),
        c.reshape(s_len, groups, n), p["D"], reset_every)
    y = y.reshape(s_len, groups, -1) * jax.nn.silu(z).reshape(
        s_len, groups, -1)
    y = rms_norm(y, p["norm"].reshape(groups, -1), cfg["rms_norm_eps"])
    return y.reshape(s_len, ch) @ p["out_proj"]


@_highest
def mlp(p, x, cfg):
    """This share's columns' part of the MLP's output."""
    m_gate, m_down = cfg["mlp_multipliers"]
    return ((jax.nn.silu((x @ p["gate_proj"]) * m_gate) * (x @ p["up_proj"]))
            @ p["down_proj"]) * m_down


@_highest
def block(layer, x, cfg, remat=False, reset_every=0):
    """One decoder block: both mixers on the one normed input, then the
    MLP."""
    eps = cfg["rms_norm_eps"]

    def mixer(p, h):
        return ssm(p, h, cfg, reset_every)

    def feed(p, h):
        return mlp(p, h, cfg)

    if remat:
        mixer, feed = jax.checkpoint(mixer), jax.checkpoint(feed)
    h = rms_norm(x, layer["attn_norm"], eps)
    x = x + mixer(layer["ssm"], h) * cfg["ssm_out_multiplier"] \
        + attention(layer["attention"], h, cfg, remat=remat) \
        * cfg["attention_out_multiplier"]
    return x + feed(layer["mlp"], rms_norm(x, layer["mlp_norm"], eps))


@_highest
def forward(params, tokens, cfg, remat=False, reset_every=0):
    """``logits [S, V_held]`` of one sequence of token ids (drawn from the
    held vocabulary rows)."""
    x = params["embed"][tokens] * cfg["embedding_multiplier"]
    for layer in params["layers"]:
        x = block(layer, x, cfg, remat, reset_every)
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    return (x @ params["lm_head"]) * cfg["lm_head_multiplier"]


def loss_and_logits(params, tokens, targets, cfg, remat=False):
    logits = forward(params, tokens, cfg, remat)
    return token_cross_entropy(logits, targets), logits


def sgd_step(params, tokens, targets, cfg, lr, clip, remat=False):
    """One step of plain SGD on one sequence, as ``laguna_s.sgd_step``: the
    gradient of the token cross-entropy, scaled down to the norm ``clip``
    where it is longer (times ``clip / (norm + 1e-6)``, at most 1), times
    ``lr`` off the parameters. Returns ``(parameters, loss, logits)``, the
    last two at the parameters it was given."""
    (loss, logits), grads = jax.value_and_grad(loss_and_logits, has_aux=True)(
        params, tokens, targets, cfg, remat)
    norm = jnp.sqrt(sum(jnp.sum(g * g)
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = lr * jnp.minimum(1.0, clip / (norm + 1e-6))
    return jax.tree_util.tree_map(lambda p, g: p - scale * g, params,
                                  grads), loss, logits


def weighted_mean(trees, counts):
    """The sites' new parameters folded: their mean weighted by the sites'
    numbers of sequences (a round of federated averaging)."""
    total = float(sum(counts))
    return jax.tree_util.tree_map(
        lambda *leaves: sum(leaf * (n / total)
                            for leaf, n in zip(leaves, counts)), *trees)


# -- the program's tree ------------------------------------------------------

def from_system(tree):
    """The program's parameter tree (``models/decoder.py``; or a gradient of
    that shape) as this file's: the blocks ``layers_<i>`` become a list, the
    leaves keep their names."""
    n = sum(1 for key in tree if key.startswith("layers_"))
    return {"embed": tree["embed"], "final_norm": tree["final_norm"],
            "lm_head": tree["lm_head"],
            "layers": [tree[f"layers_{i}"] for i in range(n)]}


# one trained leaf of each kind, as (path in this file's tree) per name; the
# layer indices are those of the four-layer cut (the mixer's of layer 1, the
# attention's of layer 2, the MLP's of the last)
GRAD_LEAVES = {
    "ssm_in_proj": ("layers", 1, "ssm", "in_proj"),
    "ssm_conv_taps": ("layers", 1, "ssm", "conv"),
    "ssm_A_log": ("layers", 1, "ssm", "A_log"),
    "ssm_dt_bias": ("layers", 1, "ssm", "dt_bias"),
    "ssm_D": ("layers", 1, "ssm", "D"),
    "ssm_norm": ("layers", 1, "ssm", "norm"),
    "ssm_out_proj": ("layers", 1, "ssm", "out_proj"),
    "q_proj": ("layers", 2, "attention", "q_proj"),
    "k_proj": ("layers", 2, "attention", "k_proj"),
    "mlp_up": ("layers", -1, "mlp", "up_proj"),
    "embed": ("embed",),
    "lm_head": ("lm_head",),
}


# -- operations and elements, from shapes alone ------------------------------

def scan_flops(seq: int, heads: int, groups: int, d_head: int, state: int,
               chunk: int) -> float:
    """Forward operations of the chunked algorithm over ``seq`` tokens (a
    multiply-add is 2): per token and group the chunk's scores ``c . b``
    (``2 chunk state``); per token and head the quadratic form against the
    chunk's inputs (``2 chunk d_head``), its part of the chunk's own end
    state and its reading of the entering state (``2 d_head state`` each)."""
    return float(seq) * (groups * 2 * chunk * state
                         + heads * (2 * chunk * d_head + 4 * d_head * state))


def layers(cfg: dict, seq: int) -> list:
    """The cut model's rows per scope of the program, for one sequence of
    ``seq`` tokens: ``embed``; the state-space mixers as ``ssm_proj`` (the
    two projections), ``ssm_conv`` (four taps, the bias and the silu: 13
    operations a channel and token, and by bytes the channels read and
    written), ``ssm_scan`` (the chunked algorithm's operations at
    ``mamba_chunk_size``, :func:`scan_flops`, and by bytes x, B, C, dt and z
    in and y out, once each a pass: what a fused kernel would move) and
    ``ssm_norm`` (the gate and the grouped norm, by bytes); ``attention_proj``
    and ``attention_full`` (**the causal pairs by operations, q, k, v and the
    output by bytes: no score crosses HBM**, as the flash kernels run it);
    ``dense_mlp`` over the held columns, ``lm_head`` and the norms' weights.
    ``params`` add up to the model."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    vocab, n = cfg["vocab_size"], cfg["num_hidden_layers"]
    s_heads, groups = cfg["mamba_n_heads"], cfg["mamba_n_groups"]
    d_head, state = cfg["mamba_d_head"], cfg["mamba_d_state"]
    taps = cfg["mamba_d_conv"]
    ch, gs = s_heads * d_head, groups * state
    width = 2 * ch + 2 * gs + s_heads           # in_proj's columns
    mixed = ch + 2 * gs                         # the conv's channels
    columns = cfg.get("mlp_columns", cfg["intermediate_size"])
    ssm_proj = h * width + ch * h
    proj = h * (2 * heads * d + 2 * kv * d)
    pairs = seq * (seq + 1) // 2
    return [
        _counted_row("embed", "embed", vocab * h, 0, 2 * seq * h),
        _counted_row("ssm_proj", "ssm", n * ssm_proj,
                     2.0 * seq * n * ssm_proj,
                     n * seq * (2 * h + width + ch)),
        _counted_row("ssm_conv", "ssm/conv", n * mixed * (taps + 1),
                     n * seq * mixed * (2.0 * taps + 5),
                     n * seq * 2 * mixed),
        _counted_row("ssm_scan", "ssm/scan", n * 3 * s_heads,
                     n * scan_flops(seq, s_heads, groups, d_head, state,
                                    cfg["mamba_chunk_size"]),
                     n * seq * (3 * ch + 2 * gs + s_heads)),
        _counted_row("ssm_norm", "ssm/norm", n * ch, n * seq * ch * 8.0,
                     n * seq * 2 * ch),
        _counted_row("attention_proj", "attention", n * proj,
                     2.0 * seq * n * proj, n * 4 * seq * h),
        _counted_row("attention_full", "attention/full", 0,
                     n * 2.0 * 2 * pairs * d * heads,
                     n * seq * d * (2 * heads + 2 * kv)),
        _counted_row("dense_mlp", "dense_mlp", n * 3 * h * columns,
                     2.0 * seq * n * 3 * h * columns,
                     n * seq * (2 * h + 3 * columns)),
        _counted_row("lm_head", "lm_head", h * vocab, 2.0 * seq * h * vocab,
                     seq * (h + 2 * vocab)),
        _counted_row("norms", "-", (2 * n + 1) * h, 0,
                     (2 * n + 1) * 2 * seq * h),
    ]
