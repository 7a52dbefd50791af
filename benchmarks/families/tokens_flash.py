"""The ``tokens_flash`` family: a dense SambaY decoder-hybrid-decoder
language model (Mamba-1 selective scans and differential attention in a
self-decoder; a cross-decoder whose layers read one full layer's K and V and
one Mamba layer's output through gated memory units), trained federated on
shards of token ids as the ``tokens`` family's are (its cohort generator and
cohort keys are that family's; what a family brings is in
:mod:`benchmarks.families`).

A configuration states the model as its published ``config.json`` does, under
the same keys (``mb_per_layer``, ``sliding_window``, ``layer_norm_eps`` and the
rest at the top level, never cut; beside them ``differential_attention`` and
the five ``mamba_*`` keys, on which the published file is silent); the counts
ONE CHIP holds a share of under ``held`` (depth, attention heads, the MLP's
columns, the GMU's channels, vocabulary rows), their published values under
``published``; ``first_layer`` is the published index of the stage's first
layer, ``first_gmu_channel`` the first memory channel the chip's GMUs read.
:func:`model_config` lays ``held`` over the rest: the dictionary the
reference takes.

The check is ``tokens_linear``'s :func:`two_part_check` with this family's
tables. That check compares a routing on both sides, and a dense model
routes nothing: each side is handed the dense MLP read as one expert, held,
that every token chooses (``reference.one_expert``; on the program's side
:func:`_dense_routing`), so ``routing`` reads 0 by construction and
witnesses nothing, and the expert-load gauges it sets read 1 (no metric of
this family's cell reads them).
"""
from __future__ import annotations

import math

from .tokens import COHORT_KEYS, _at, make_cohort  # noqa: F401
from .tokens_linear import CHANGE_FLOOR_ULPS, two_part_check

# the published config.json's keys that no cut touches, the six stated
# beside them, and the groups that state the chip's share
CONFIG_KEYS = {
    "differential_attention", "embd_pdrop", "hidden_act", "hidden_size",
    "intermediate_size", "layer_norm_eps", "lm_head_bias",
    "mamba_chunk_size", "mamba_d_conv", "mamba_d_state", "mamba_dt_rank",
    "mamba_expand", "max_position_embeddings", "mb_per_layer", "mlp_bias",
    "model_type", "resid_pdrop", "sliding_window", "tie_word_embeddings",
    "held", "published", "first_layer", "first_gmu_channel"}
HELD_KEYS = {"num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "mlp_columns", "gmu_channels",
             "vocab_size"}


def model_config(config: dict) -> dict:
    """The configuration with what the chip holds laid over it: the model's
    description as the reference takes it (with the shared check's
    ``first_expert``, which a dense model does not use)."""
    if set(config["held"]) != HELD_KEYS:
        raise ValueError(f"'held' states {sorted(config['held'])}, not "
                         f"{sorted(HELD_KEYS)}")
    return {**config, **config["held"], "first_expert": 0}


# Limits of the comparison with the plain float32 reference (every product
# at the highest precision, the Mamba mixers as the token-by-token
# recurrence, attention with explicit masks), same weights, read by
# ``two_part_check`` (families/tokens_linear.py says what each number is).
#
# Each limit stands between the program's largest reading over nine seeds
# on a TPU v5e (2147490461-66 and 2147492101-03, the scan token by token in
# chunks of 64) and what a control that must come out not correct reads:
# the e4m3 control (the reference with every weight matrix rounded to e4m3,
# the nearest precision below the bfloat16 the configuration states, in the
# program's forward pass: ``benchmarks/tests/control_flash.py``, seed
# 2147490461), the half fold (the reference's own fold of half the sites,
# ``round_controls.half_the_clients`` of every report; the smallest of six
# seeds) and a state left unchanged (1 for every leaf and for ``lambda``).
# The e4m3 control leaves the round the program's own, so its round numbers
# are the program's. The bfloat16-state control (the program's scan and the
# reference's recurrence with the state rounded to bfloat16 after every
# token, seed 2147492101) is not correct by ``mamba_x_proj`` alone.
#
#                   program, largest   e4m3        half fold  bf16 state  limit
#   loss               8.1e-5          6.2e-5      -          5.9e-6      4e-4
#   logits             0.0321          0.335       -          0.0497      0.08
#   routing            0 (dense)       0           -          0           0
#   mamba_in_proj      0.0233          (program's) 0.265      0.0305      0.1
#   mamba_conv_taps    0.0238                      0.268      0.0309      0.1
#   mamba_x_proj       0.0504                      0.274      0.119       0.1
#   mamba_dt_proj      0.0303                      0.353      0.0772      0.1
#   mamba_A_log        0.0252                      0.070      0.0333      0.08
#   mamba_dt_bias      0.0260                      0.094      0.0475      0.08
#   mamba_out_proj     0.0229                      0.250      0.0295      0.1
#   memory_in_proj     0.0250                      0.302      0.0329      0.1
#   window_q_proj      0.0289                      0.334      0.0357      0.1
#   full_k_proj        0.0233                      0.266      0.0287      0.1
#   full_v_proj        0.0154                      0.130      0.0126      0.1
#   gmu_in_proj        0.0224                      0.280      0.0302      0.1
#   cross_q_proj       0.0232                      0.262      0.0284      0.1
#   mlp_up             0.0205                      0.221      0.0269      0.1
#   embed              0.0221                      0.250      0.0289      0.1
#   fold               0.0024                      1.0        0.0010      0.25
#   round_loss         9.7e-5                      -          1.1e-4      0.002
#   lambda             see below                              0.0096      0.25
#
# * loss and round_loss have no upper reading: no control read moves them
#   over the program's own readings (loss 6.3e-7 to 8.1e-5 over nine seeds,
#   it swings by a hundred; e4m3 reads 6.2e-5 on its seed; round_loss 6.3e-6
#   to 9.7e-5). They take the limits of the accepted dense hybrid cell
#   (families/tokens_hybrid.py: 4e-4) and of the harness's other families
#   (0.002), and decide nothing that the other numbers do not.
# * logits: 0.0256-0.0321 (bfloat16 products over ten layers, the largest
#   reference logit about 1); e4m3 reads 0.335. The limit is 2.5 times the
#   largest reading and under a quarter of e4m3's: the e4m3 control is not
#   correct by ``logits`` alone.
# * the matrices: the program reads 0.009-0.030 on every seed (systematic
#   rounding of the bfloat16 products) and ``mamba_x_proj`` 0.016-0.050;
#   each limit stands twice or more over the largest reading and under
#   every half fold's reading and 1.
# * ``mamba_A_log`` and ``mamba_dt_bias`` move by less than the floor of
#   four float32 ulps a round at rate 0.02 (``change_over_floor`` 0.11-0.64
#   and 0.17-0.27): their readings are the floor's, the carry's witnesses
#   are ``mamba_x_proj`` and ``mamba_dt_proj`` (whose gradients run
#   through the decays of every chunk) and the gauge ``mamba_chunk_carry``
#   (0.152-0.156). Their limit is 3.1 times the largest reading; a half
#   fold reads the floor's there too (0.070-0.257, 0.094-0.140), and
#   ``fold`` is what sees a dropped site.
# * ``lambda`` (:func:`lambda_error`): the five attention layers' lambda
#   vectors over the round as one vector. One layer's vector alone read
#   0.0002-0.109 over six seeds (its change 155-3,134 floors); the five
#   over site 0's step alone read 0.008-0.023 on three seeds (their change
#   790-1,193 floors of the five), and 1.0 with no gradient through lam
#   (the ``frozen_lambda`` control, which is correct by every other
#   number). Over the round's two sites, as read here, it was not run on
#   the chip. The limit is ten times the largest reading and a quarter of 1.
# * ``fold``: the harness's other families' limit (0.25), far over the
#   program's readings; the half fold reads 1.
TOLERANCE = {
    "loss": 4e-4, "logits": 0.08, "routing": 0.0,
    "mamba_in_proj": 0.1, "mamba_conv_taps": 0.1, "mamba_x_proj": 0.1,
    "mamba_dt_proj": 0.1, "mamba_A_log": 0.08, "mamba_dt_bias": 0.08,
    "mamba_out_proj": 0.1, "memory_in_proj": 0.1, "window_q_proj": 0.1,
    "full_k_proj": 0.1, "full_v_proj": 0.1, "gmu_in_proj": 0.1,
    "cross_q_proj": 0.1, "mlp_up": 0.1, "embed": 0.1, "round_loss": 0.002,
    "fold": 0.25, "lambda": 0.25,
}
# the leaves ``fold`` is read on: the dense matrices
FOLD_LEAVES = ("mamba_in_proj", "mamba_out_proj", "memory_in_proj",
               "window_q_proj", "full_k_proj", "gmu_in_proj", "cross_q_proj",
               "mlp_up", "embed")


def _dense_routing(apply_fn):
    """``apply_fn`` whose collections, where the caller opens them, hold
    besides what the model sowed the dense MLP read as a routing: one
    layer, one expert, held, chosen by every token (what
    ``reference.one_expert`` gives on the reference's side)."""
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.obs.expert_load import COLLECTION

    def apply(tree, x, train, rng, mutable=False):
        out = apply_fn(tree, x, train=train, rng=rng, mutable=mutable)
        if not mutable:
            return out
        logits, sown = out
        tokens = x.shape[0] * x.shape[1]
        stats = {**sown.get(COLLECTION, {}), "dense_mlp": {
            "held_counts": (jnp.full((1,), tokens, jnp.int32),),
            "top_experts": (jnp.zeros((tokens, 1), jnp.int32),)}}
        return logits, {**sown, COLLECTION: stats}

    return apply


def lambda_error(report: dict, params, ref) -> tuple:
    """``lambda``: the round's ``lambda_q1`` vectors of all the attention
    layers (``ref.LAMBDA_LEAVES``), which ``two_part_check`` read one by one
    and held to no limit, read as one vector: the distance of the program's
    new vector from the reference's over the norm of the reference's change
    (at least ``CHANGE_FLOOR_ULPS`` float32 ulps of the vector), as the
    shared check reads a leaf. Each layer's entry leaves the report; returns
    the distance and the change in units of its floor.

    One layer's vector is a poor leaf: its gradient is one scalar,
    ``dL/dlam``, times a fixed vector, and that scalar lies near 0 on some
    seeds, where the rounding of a sound program is all there is of the
    change (one layer's alone read 0.0002-0.0104 on five seeds and 0.109 on
    a sixth, its change 155 floors against 1,094-3,134: a TPU v5e). The
    five layers' scalars are drawn apart, and the norm of the five lies
    near 0 far more rarely than any one of them."""
    import numpy as np

    ulps = CHANGE_FLOOR_ULPS * float(np.finfo(np.float32).eps)
    floors = report["round_controls"]["change_over_floor"]
    off = moved = old = 0.0
    for name, path in ref.LAMBDA_LEAVES.items():
        entry = report.pop(name)
        norm = float(np.linalg.norm(_at(params, (f"layers_{path[1]}",)
                                        + path[2:])))
        floor = ulps * norm + 1e-30
        # the shared check's error is the distance over max(change, floor)
        change = floors[name] * floor
        off += (entry["error"] * max(change, floor)) ** 2
        moved += change ** 2
        old += norm ** 2
    floor = ulps * old ** 0.5 + 1e-30
    return (off ** 0.5 / max(moved ** 0.5, floor), moved ** 0.5 / floor)


def reference_check(algo, params, ref, config: dict) -> dict:
    """``two_part_check`` with this family's tables, the program's model
    handed the dense routing (:func:`_dense_routing`). Before it, the
    program's own ``record_expert_load`` sets ``mamba_chunk_carry`` in the
    program's registry from one forward, as the program's runner does after
    ``init_state`` (under ``mamba_carry`` in the report)."""
    from neuroimagedisttraining_tpu.obs import (metrics as obs_metrics,
                                                trace as obs_trace)
    from neuroimagedisttraining_tpu.obs.expert_load import record_expert_load

    with obs_trace.span("expert_load"):
        carry = record_expert_load(algo, params, obs_metrics.get_registry())
    plain = algo.apply_fn
    algo.apply_fn = _dense_routing(plain)
    # each layer's lambda vector read, and held only to being finite
    # (the number held to a limit is the five together: lambda_error)
    one_by_one = dict.fromkeys(ref.LAMBDA_LEAVES, math.inf)
    try:
        report = two_part_check(algo, params, ref, config,
                                {**TOLERANCE, **one_by_one}, FOLD_LEAVES,
                                model_config(config))
    finally:
        algo.apply_fn = plain
    error, floors = lambda_error(report, params, ref)
    ok = bool(math.isfinite(error) and error <= TOLERANCE["lambda"])
    report["lambda"] = {"error": error, "tolerance": TOLERANCE["lambda"],
                        "ok": ok}
    report["ok"] = report["ok"] and ok
    report["round_controls"]["change_over_floor"]["lambda"] = floors
    report["mamba_carry"] = carry
    return report


def layers(ref, config: dict) -> list:
    """The reference's counted rows for one sequence of the configuration's
    cohort, for ``lib/flops.py``."""
    return ref.layers(model_config(config),
                      config["cohort"]["sequence_length"])
