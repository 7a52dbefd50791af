"""The ``tokens_shortconv`` family: a decoder language model most of whose
token mixing is a gated short convolution, with full attention in one layer
of four and a sigmoid router that chooses by its scores plus a selection
bias, trained federated on shards of token ids as the ``tokens`` family's
are (its cohort generator and cohort keys are that family's; what a family
brings is in :mod:`benchmarks.families`).

A configuration states the model as its published ``config.json`` does, under
the same keys (``conv_L_cache``, ``num_dense_layers``, ``norm_eps``,
``use_expert_bias`` and the rest at the top level, never cut; beside them
``qk_norm``, ``scoring_func`` and ``tie_word_embeddings``, on which the
published file is silent); the counts ONE
CHIP holds a share of under ``held``, their published values under
``published`` (``conv_channels``: the channels of a conv layer's three
streams, ``hidden_size`` published); ``first_expert`` is the id of the first
routed expert held. :func:`model_config` lays ``held`` over the rest: the
dictionary the reference takes.
"""
from __future__ import annotations

import numpy as np

from .tokens import COHORT_KEYS, _at, make_cohort  # noqa: F401

# the published config.json's keys that no cut touches, ``qk_norm``,
# ``scoring_func`` and ``tie_word_embeddings`` (no published keys: the
# program's constant states them beside those, the file under ``assumed``
# too), and the three groups that state the chip's share
CONFIG_KEYS = {
    "conv_L_cache", "conv_bias", "hidden_size", "intermediate_size",
    "max_position_embeddings", "model_type", "moe_intermediate_size",
    "norm_eps", "norm_topk_prob", "num_dense_layers", "num_experts_per_tok",
    "qk_norm", "rope_theta", "routed_scaling_factor", "scoring_func",
    "tie_word_embeddings", "use_expert_bias", "held", "published",
    "first_expert"}
# what ``held`` (and ``published``) state
HELD_KEYS = {"num_hidden_layers", "num_experts", "num_attention_heads",
             "num_key_value_heads", "vocab_size", "layer_types",
             "conv_channels"}
LOGIT_STRIDE = 128                  # logits compared at every 128th position


def model_config(config: dict) -> dict:
    """The configuration with what the chip holds laid over it: the model's
    description as the reference takes it."""
    if set(config["held"]) != HELD_KEYS:
        raise ValueError(f"'held' states {sorted(config['held'])}, not "
                         f"{sorted(HELD_KEYS)}")
    return {**config, **config["held"]}


# Limits of the comparison with the plain float32 reference (every product
# at the highest precision), same weights; the comparison is the ``tokens``
# family's (FORWARD on the first training sequence of site 0: loss, logits,
# ``routing`` = the share of (token, sparse layer) pairs whose four experts
# differ; ONE ROUND of the program's own compiled round on the round's sites
# against the reference's own SGD steps and weighted mean, per leaf the norm
# of the difference over the norm of the reference's change:
# families/tokens.py has the definitions), with of its own:
#
# logits: at every 128th position whose routing agrees with the reference's
#   in every layer.
# every sparse layer's ``expert_bias``: the largest absolute change over the
#   round, which must be 0.0 (the bias enters the choice only, so no gradient
#   reaches it, and the fold of two equal sites is 0.5 b + 0.5 b).
# fold: of each dense leaf, how far the program's new value lies from the
#   reference's fold of all the round's sites toward its fold of the first
#   half of them, along the line between the two: ``<new - all, half - all>
#   / |half - all|^2``. 0 is the fold of all, 1 the fold of the first half
#   alone, -1 that of the second at two equal sites; the number is the
#   largest magnitude over the seven dense leaves. It reads the fold's
#   weights and little else: a leaf's rounding does not lie along that line.
#
# Each limit stands between the program's largest reading over seeds on the
# chip and three controls that must come out not correct
# (``benchmarks/tests/control_shortconv.py``): the reference with every
# weight matrix rounded to e4m3 (the nearest precision below the bfloat16
# the configuration states) in the program's place; THE BIAS LEFT OUT of the
# choice; THE TAPS LEFT OUT (the convolution's current tap alone): the last
# two in the reference that stands in for the forward pass and, planted in
# ``decoder.choose_experts`` / ``decoder.short_conv`` before the program is
# built, in the compiled round; and, for the round's fold, a fourth: HALF
# THE CLIENTS folded (below). Readings (my chip runs, PR 35, PERF.md
# section 6: the program on fifteen seeds, 2147484801-10 and ..31-35; each
# control on seeds 2147484811 and ..12):
#
#                      program, largest   e4m3             no bias          no taps        limit
#   loss                   0.00012        0.00100 0.00062  0.00020 0.00006  0.0037 0.0035  0.0004
#   logits                 0.0124         (too few positions agree in every control)      0.04
#   routing                0.050          0.420   0.400    0.346   0.391    0.951  0.961   0.2
#   agreeing positions     99 of 128      13      21       24      20       1      1       at least 38
#
# ``loss`` keeps the accepted families' limit, 3.2 times the largest of the
# fifteen readings; here it separates the rounding too (e4m3 reads 1.5 and
# 2.5 times the limit), though not the bias, which moves no loss: that
# control comes out not correct by ``routing`` (the share of pairs the bias
# swaps, the gauge ``expert_bias_swap_share`` 0.33-0.39) and the agreeing
# positions, as e4m3 does. ``logits`` keeps the ``tokens_selected`` family's
# limit, 3.2 times the largest reading: no control leaves enough agreeing
# positions to read it.
#
# The round: the program's largest reading over the fifteen seeds; the round
# compiled with the bias left out and with the taps left out (two seeds
# each); what a fold of half the clients reads (the smallest over the
# program's first ten runs, from the reference's own locals; the median
# beside it); what a state left unchanged reads:
#
#                      program, largest   no bias        no taps       half the clients   unchanged   limit
#   conv_in_proj           0.038          0.062  0.073   1.01   0.99   0.078 (0.136)        1.0       0.12
#   conv_taps              0.034          0.064  0.068   1.05   0.97   0.077 (0.134)        1.0       0.12
#   conv_out_proj          0.033          0.060  0.075   1.03   0.98   0.080 (0.139)        1.0       0.12
#   q_proj                 0.021          0.045  0.053   1.30   1.17   0.060 (0.124)        1.0       0.07
#   q_layernorm            0.034          0.049  0.054   1.37   1.29   0.105 (0.127)        1.0       0.11
#   dense_up               0.018          0.040  0.048   0.55   0.54   0.047 (0.080)        1.0       0.055
#   embed                  0.017          0.037  0.046   0.56   0.54   0.050 (0.082)        1.0       0.05
#   router_first           0.241          0.329  0.316   1.69   2.51   0.095 (0.142)        1.0       0.7
#   expert_up_last         0.124          0.172  0.276   1.42   1.26   0.083 (0.116)        1.0       0.4
#   round_loss             0.00010        0.00010 0.00002 0.0039 0.0040  -                  -         0.002
#   every expert_bias      0.0            0.0            0.0           -                    -         0.0
#   fold                   0.050          0.064          3.19          0.9991 1.0122      -         0.25
#
# Every limit of the round stands about three times over the largest of
# fifteen readings, the more room on that side since fresh seeds read higher
# (the first ten seeds' largest conv reading was 0.028, the next five's
# 0.038), and far under what a round without the convolution (0.5-2.5) or a
# state left unchanged (1.0) reads. The seven dense leaves read 0.009-0.038.
# The two sites' updates are much alike on this cohort, so even a fold of
# half the clients moves a leaf by a tenth of its change only: it lies over
# the limits of ``dense_up`` and ``embed`` on most seeds and over the conv
# leaves' on half, and under every one on some, so the leaves' differences
# do not see a site dropped from the fold. ``fold`` does, on every seed:
# the program reads 0.004-0.050 on ten seeds (2147484851-56, ..71-73, ..84;
# ``q_layernorm``, 64 numbers, reads the largest on every one, the other six
# dense leaves under 0.006 on nine and up to 0.028 on one), and the half
# fold planted through the harness at the cell's size
# (``control_shortconv.py half_fold``: the program's own compiled round with
# the second site's count 0; seeds 2147484861, ..62 and ..81) 0.9991, 1.0122
# and 0.9971, every dense leaf within 0.990-1.012 (the leaves' differences
# there 0.078-0.18: over their limits on these seeds). The limit 0.25 is five
# times the program's largest and a quarter of what the fault reads; a fold
# whose weights are off by a quarter of one site's reads it too. A test
# plants the half fold at the tiny size as well. The router's leaf and the held experts' swing with the 4-5 % of
# the pairs whose fourth expert the bfloat16 activations swap
# (``router_first`` 0.024-0.132 on fourteen seeds, 0.241 on one;
# ``expert_up_last`` 0.017-0.042 on eleven, 0.080-0.124 on four): they
# catch the missing taps and an unchanged state, not the missing bias, whose
# witness is ``routing``. ``round_loss`` keeps the accepted cells' limit,
# twenty times its largest reading; the round without taps reads twice the
# limit.
#
# * routing: 4 of 32 sigmoid scores plus a bias; the scores at the cut lie
#   about 0.025 apart and a bfloat16 logit moves a score by about 0.002, so
#   one pair in twenty-five swaps its fourth expert for the fifth (Keye's 8
#   of 128: one in ten). An 8-bit path swaps two pairs in five; leaving the
#   bias out swaps as many as the bias does, a third.
# * logits, loss, the round's leaves: as in families/tokens.py.
TOLERANCE = {
    "loss": 0.0004, "logits": 0.04, "routing": 0.2,
    "conv_in_proj": 0.12, "conv_taps": 0.12, "conv_out_proj": 0.12,
    "q_proj": 0.07, "q_layernorm": 0.11, "dense_up": 0.055,
    "router_first": 0.7, "expert_up_last": 0.4, "embed": 0.05,
    "round_loss": 0.002, "fold": 0.25,
}
BIAS_LIMIT = 0.0        # of every ``expert_bias_layer<i>``
# the leaves ``fold`` is read on: the dense ones (the router's and the held
# experts' differences swing with the pairs whose fourth expert swaps)
FOLD_LEAVES = ("conv_in_proj", "conv_taps", "conv_out_proj", "q_proj",
               "q_layernorm", "dense_up", "embed")
MIN_AGREEING = 0.3      # of the compared positions


def reference_check(algo, params, ref, config: dict) -> dict:
    """The system against the plain float32 reference ``ref``, same weights,
    in the two parts of the ``tokens`` family's check (the forward pass on
    the first training sequence of site 0; ONE ROUND of the program's own
    compiled round on the first ``clients_per_round`` sites, every site
    holding its first sequence in each of its rows, against the reference's
    own SGD steps and weighted mean), with every selection bias held to no
    change at all. On the way the program's own function sets the
    expert-load gauges, ``expert_bias_swap_share`` among them, in the
    program's registry, as the program's runner does after ``init_state``."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.core.losses import make_loss_fn
    from neuroimagedisttraining_tpu.obs import (metrics as obs_metrics,
                                                trace as obs_trace)
    from neuroimagedisttraining_tpu.obs.expert_load import (
        COLLECTION, set_expert_load, stacked_stats)

    flags, config = config["flags"], model_config(config)
    data, sites = algo.data, algo.clients_per_round
    loss_fn = make_loss_fn(algo.loss_type)
    n_layers = config["num_hidden_layers"]
    frozen = ref.bias_leaves(config)
    tolerance = {**TOLERANCE, **{name: BIAS_LIMIT for name in frozen}}
    names = list(ref.GRAD_LEAVES) + list(frozen)
    r_paths = [{**ref.GRAD_LEAVES, **frozen}[n] for n in names]
    # ("layers", i, ...) -> ("layers_<i>", ...)
    s_paths = [(f"layers_{p[1] % n_layers}",) + tuple(p[2:])
               if p[0] == "layers" else p for p in r_paths]

    # -- the forward pass, and the gauges --------------------------------
    def system(tree, x, y):
        logits, sown = algo.apply_fn(tree, x[0, :1], train=False, rng=None,
                                     mutable=[COLLECTION])
        return (loss_fn(logits, y[0, :1]), logits[0, ::LOGIT_STRIDE],
                stacked_stats(sown))

    with obs_trace.span("expert_load"):
        s_loss, s_z, stats = jax.device_get(jax.jit(system)(
            params, data.x_train, data.y_train))
        load = set_expert_load(stats, obs_metrics.get_registry())

    # -- one round of the program ---------------------------------------
    # a state of the algorithm's own kind around the given parameters; the
    # folding round borrows its state, one that takes it gets a copy
    state = jax.eval_shape(algo.init_state, jax.random.PRNGKey(0)).replace(
        global_params=params, rng=jax.random.PRNGKey(0))
    if algo._donate:
        state = algo.clone_state(state)
    first_only = jax.jit(lambda a: jnp.broadcast_to(a[:, :1], a.shape))
    x_round, y_round = first_only(data.x_train), first_only(data.y_train)
    out = algo._round_jit(
        state, jnp.arange(sites, dtype=jnp.int32),
        jnp.asarray(0, jnp.float32), x_round, y_round, data.n_train)
    s_round_loss = float(out[1])
    s_new = [_at(out[0].global_params, p) for p in s_paths]
    del out, state      # the new global's other leaves go

    # -- the reference's round ------------------------------------------
    def plain(tree, x, y, site):
        new, loss, logits, routing = ref.sgd_step(
            tree, x[site, 0], y[site, 0], config, flags["lr"],
            flags["grad_clip"], config["first_expert"], remat=True)
        return new, loss, logits[::LOGIT_STRIDE], jnp.stack(routing)

    step = jax.jit(plain, donate_argnums=0)
    start = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))
    fold = jax.jit(lambda total, leaves, w: [
        t + w * leaf for t, leaf in zip(total, leaves)])
    r_params = ref.from_system(params)
    counts = np.asarray(data.n_train, np.float64)[:sites]
    r_total = [jnp.zeros_like(_at(r_params, p)) for p in r_paths]
    r_half, r_losses, first = None, [], None
    for site in range(sites):
        local, losses = start(r_params), []
        for _ in range(int(counts[site])):
            local, loss, z, route = step(local, x_round, y_round, site)
            losses.append(loss)
            first = first or jax.device_get((loss, z, route))
        r_losses.append(float(np.mean(jax.device_get(losses))))
        r_total = fold(r_total, [_at(local, p) for p in r_paths],
                       counts[site] / counts.sum())
        if site == (sites // 2 or 1) - 1:
            # what a fold of the first half of the sites alone would give
            r_half = fold([jnp.zeros_like(t) for t in r_total], r_total,
                          counts.sum() / counts[:site + 1].sum())
        del local
    r_loss, r_z, r_route = first

    differs = np.any(np.sort(stats["top_experts"], -1)
                     != np.sort(r_route, -1), axis=-1)
    agreeing = ~np.any(differs, axis=0)[::LOGIT_STRIDE]     # [positions]
    report = {"ok": True, "expert_load": load,
              "agreeing_positions": int(agreeing.sum()),
              "compared_positions": int(agreeing.size)}

    def put(name, err, finite=True):
        ok = bool(finite and np.isfinite(err) and err <= tolerance[name])
        report[name] = {"error": float(err), "tolerance": tolerance[name],
                        "ok": ok}
        report["ok"] = report["ok"] and ok

    put("loss", abs(float(s_loss) - float(r_loss))
        / max(1.0, abs(float(r_loss))), np.isfinite(s_loss))
    if agreeing.sum() >= max(1, MIN_AGREEING * agreeing.size):
        got, want = s_z[agreeing], r_z[agreeing]
        put("logits", np.max(np.abs(got - want))
            / max(1.0, np.max(np.abs(want))), np.isfinite(got).all())
    else:
        put("logits", 1e9)      # too few positions left to compare
    put("routing", differs.mean())

    @jax.jit
    def distances(old, new, want, half):
        """Of one leaf: the system's new value against the reference's, and
        the reference's fold of half the sites against its fold of all,
        each over the norm of the reference's change; how far the system's
        new value lies from the reference's fold of all toward its fold of
        half, along the line between the two; and the largest change of the
        system's leaf."""
        change = jnp.maximum(jnp.linalg.norm(want - old), 1e-30)
        off, line = new.astype(want.dtype) - want, half - want
        return (jnp.linalg.norm(off) / change,
                jnp.linalg.norm(line) / change,
                jnp.vdot(off, line) / jnp.maximum(jnp.vdot(line, line), 1e-30),
                jnp.isfinite(new).all(), jnp.max(jnp.abs(new - old)))

    halves, toward = {}, {}
    for i, name in enumerate(names):
        err, half, along, finite, moved = jax.device_get(distances(
            _at(params, s_paths[i]), s_new[i], r_total[i], r_half[i]))
        if name in frozen:
            put(name, moved, bool(finite))
        else:
            halves[name], toward[name] = half, along
            put(name, err, bool(finite))
    put("fold", max(abs(float(toward[name])) for name in FOLD_LEAVES))
    r_round_loss = float(np.mean(r_losses))
    put("round_loss", abs(s_round_loss - r_round_loss)
        / max(1.0, abs(r_round_loss)), np.isfinite(s_round_loss))
    # what the round's limits stand between (PERF.md section 6)
    report["round_controls"] = {
        "unchanged_state": 1.0,
        "half_the_clients": {k: float(v) for k, v in halves.items()},
        "toward_half": {k: float(v) for k, v in toward.items()}}
    return report


def layers(ref, config: dict) -> list:
    """The reference's counted rows for one sequence of the configuration's
    cohort, for ``lib/flops.py``."""
    return ref.layers(model_config(config),
                      config["cohort"]["sequence_length"])
