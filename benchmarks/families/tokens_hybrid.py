"""The ``tokens_hybrid`` family: a dense decoder language model whose every
block feeds one normed input to two mixers side by side, attention and a
state-space mixer with a recurrent state, trained federated on shards of
token ids as the ``tokens`` family's are (its cohort generator and cohort
keys are that family's; what a family brings is in
:mod:`benchmarks.families`).

A configuration states the model as its published ``config.json`` does, under
the same keys (the ``mamba_*`` keys, the muP multipliers and the rest at the
top level, never cut); the counts ONE CHIP holds a share of under ``held``,
their published values under ``published`` (``mlp_columns``: the columns of
the dense MLP held, ``intermediate_size`` published; the mixer's channels are
its held heads times ``mamba_d_head``). :func:`model_config` lays ``held``
over the rest: the dictionary the reference takes. A dense model routes
nothing: logits are compared at every 128th position, all of them.
"""
from __future__ import annotations

import numpy as np

from .tokens import COHORT_KEYS, _at, make_cohort  # noqa: F401

# the published config.json's keys that no cut touches, and the two groups
# that state the chip's share
CONFIG_KEYS = {
    "attention_bias", "attention_in_multiplier", "attention_out_multiplier",
    "attn_layer_indices", "embedding_multiplier", "head_dim", "hidden_act",
    "hidden_size", "intermediate_size", "key_multiplier",
    "lm_head_multiplier", "mamba_chunk_size", "mamba_conv_bias",
    "mamba_d_conv", "mamba_d_head", "mamba_d_ssm", "mamba_d_state",
    "mamba_expand", "mamba_norm_before_gate", "mamba_proj_bias",
    "mamba_rms_norm", "mamba_use_mlp", "max_position_embeddings", "mlp_bias",
    "mlp_expansion_factor", "mlp_multipliers", "model_type",
    "num_logits_to_keep", "projectors_bias", "rms_norm_eps", "rope_scaling",
    "rope_theta", "ssm_in_multiplier", "ssm_multipliers",
    "ssm_out_multiplier", "tie_word_embeddings", "held", "published"}
# what ``held`` (and ``published``) state
HELD_KEYS = {"num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "mamba_n_heads", "mamba_n_groups",
             "mlp_columns", "vocab_size"}
LOGIT_STRIDE = 128                  # logits compared at every 128th position


def model_config(config: dict) -> dict:
    """The configuration with what the chip holds laid over it: the model's
    description as the reference takes it."""
    if set(config["held"]) != HELD_KEYS:
        raise ValueError(f"'held' states {sorted(config['held'])}, not "
                         f"{sorted(HELD_KEYS)}")
    return {**config, **config["held"]}


# Limits of the comparison with the plain float32 reference (every product
# at the highest precision, the state-space mixer as the token-by-token
# recurrence), same weights. The comparison is the ``tokens`` family's in
# its two parts (FORWARD on the first training sequence of site 0; ONE ROUND
# of the program's own compiled round on the round's sites against the
# reference's own SGD steps and weighted mean, per leaf the norm of the
# difference over the norm of the reference's change; ``fold`` as
# families/tokens_shortconv.py defines it), with of its own:
#
# logits: at every 128th position (a dense model routes nothing, so every
#   one is compared), the largest difference over the largest reference
#   logit, WITHOUT the other families' floor of 1 under that: the head's
#   muP multiplier (``lm_head_multiplier`` 1/128) makes a seeded model's
#   logits a few hundredths, and under a floor of 1 no fault could move the
#   number past any limit.
# the leaves: one of each kind the model trains: the mixer's ``in_proj``,
#   conv taps, ``A_log``, ``dt_bias``, ``D``, norm weight and ``out_proj``;
#   ``q_proj`` and ``k_proj``; the MLP's ``up_proj``; ``embed`` and
#   ``lm_head`` (``reference/falcon_h1.py:GRAD_LEAVES``).
#
# the small float32 vectors: a leaf's difference is read over the larger of
#   the reference's change and four float32 ulps of the leaf (``4 eps
#   |old|``). ``dt_bias``'s gradient carries a factor ``dt`` (1e-3 to 1e-1)
#   and its values lie at -7 to -2, so that at any rate the model trains
#   stably at, a round moves it by under one ulp on four seeds of seven
#   (both sides then leave it bit for bit, and the plain ratio would read 0
#   there and 1 the day one side flips a bit the other does not). It binds
#   for ``dt_bias`` alone (``round_controls.change_over_floor`` in the
#   report: ``dt_bias`` 0.38-0.61 of the floor on three seeds, ``q_proj``
#   5.1-6.9, ``k_proj`` 11-15, ``A_log`` 20-62, every other leaf 55-4200).
#
# Each limit stands between the program's largest reading over seeds on the
# chip and four controls that must come out not correct
# (``benchmarks/tests/control_hybrid.py``), each on two seeds: the reference
# with every weight matrix rounded to e4m3 (the nearest precision below the
# bfloat16 the configuration states) in the program's place; THE CARRY
# BETWEEN CHUNKS LEFT OUT (every chunk starts from a zero state); THE SSM'S
# MULTIPLIERS LEFT OUT (``m`` = 1); ``key_multiplier`` LEFT OUT: the last
# three in the reference that stands in for the forward pass and, planted in
# the program before it is built, in the compiled round. Readings (my chip
# runs, PR 39, PERF.md section 6: the program on seven seeds, 2147485101 and
# ..121-26; each control on seeds 2147485111 and ..12; the half fold through
# the harness on ..113), before the floor under the small vectors:
#
#                  program, largest  e4m3           no carry       no ssm mult    no key mult    half fold  limit
#   loss               5.5e-7        0      0       0      0       1e-4   0       0      0       0          0.0004
#   logits             0.0077        0.0658 0.0667  0.100  0.044   0.465  0.454   0.106  0.107   0.0066     0.025
#   ssm_in_proj        0.0052        0.0050 0.0050  0.115  0.031   0.547  0.535   0.113  0.120   0.070      0.02
#   ssm_conv_taps      0.0064        0.0059 0.0065  0.191  0.060   2.12   2.29    0.128  0.134   0.113      0.025
#   ssm_A_log          0.0207        0.0044 0.0033  0.828  0.872   0.333  0.630   0.079  0.079   0.062      0.1
#   ssm_dt_bias        0.053         0.0077 0.034   1.20   5.71    0.517  1.19    0.185  0.152   0.048      0.25
#   ssm_D              0.0075        0.0068 0.0050  0.064  0.032   0.557  0.575   0.126  0.100   0.064      0.03
#   ssm_norm           0.0054        0.0055 0.0054  0.103  0.033   0.497  0.498   0.110  0.123   0.074      0.02
#   ssm_out_proj       0.0051        0.0050 0.0051  0.113  0.032   0.479  0.473   0.114  0.121   0.069      0.02
#   q_proj             0.0147        0.0123 0.0106  0.088  0.029   0.464  0.444   108.7  105.6   0.126      0.05
#   k_proj             0.0109        0.0089 0.0089  0.080  0.029   0.473  0.490   108.6  114.9   0.119      0.04
#   mlp_up             0.0066        0.0063 0.0064  0.082  0.031   0.467  0.462   0.110  0.113   0.068      0.025
#   embed              0.0041        0.0040 0.0041  0.059  0.023   0.414  0.418   0.170  0.201   0.069      0.015
#   lm_head            0.0038        0.0037 0.0038  0.057  0.022   0.342  0.341   0.080  0.082   0.068      0.015
#   fold               0.0088        0.0058 0.0090  0.0088 0.022   0.969  0.861   152.7  179.9   1.0095     0.25
#   round_loss         6.0e-7        0      0       0      0       1e-4   0       0      0       5e-7       0.002
#
# * logits: 3.2 times the largest of seven readings (0.0060-0.0077: the
#   bfloat16 products' rounding, alike on every seed); e4m3 reads 2.6 times
#   the limit on both seeds and is not correct by this number alone (under
#   it the round is the program's own), every other control reads over it.
# * the matrices and the norm weight: the program reads the same on every
#   seed to a few percent (``ssm_in_proj`` 0.0050-0.0052, ``embed``
#   0.0040-0.0041: systematic rounding of bfloat16 products, nothing that
#   swings), so each limit stands four times over the largest reading and
#   far under 1, what a state left unchanged reads, and under every
#   control's reading of it. ``q_proj`` and ``k_proj`` read twice the others
#   (their gradients pass the softmax) and vary by a quarter: 3.4 and 3.7
#   times.
# * ``A_log``: 0.0035-0.0207 over seven seeds (16 numbers: it swings); the
#   limit is 4.8 times the largest and an eighth of what the carry left out
#   reads. **The carry's witnesses are ``A_log`` and ``dt_bias``**: what a
#   chunk's entering state adds to an output is small beside the chunk's
#   own part and ``D``'s skip (one head in fifteen keeps its state across
#   128 tokens: ``ssm_chunk_carry`` 0.046-0.085), so logits and matrices
#   move by 3-19 % of a step only (still over their limits on both seeds),
#   but the decays' own gradient is what the state remembers: 0.83 and 0.87.
# * ``dt_bias``: 0 on four seeds (no bit moved on either side), 0.018-0.053
#   on three; with the floor one flipped ulp reads 0.06. The limit leaves
#   that five times of room; the controls' readings of it are not needed.
# * ``D``: 0.0032-0.0075, four times.
# * ``key_multiplier`` left out multiplies the keys by 90: ``q_proj`` and
#   ``k_proj`` read a hundred steps' length, ``fold`` 150.
# * ``fold``: the tokens_shortconv family's limit; the program reads
#   0.001-0.009, the half fold planted through the harness 1.0095 (and
#   there every matrix reads 0.05-0.13 too, over its limit).
# * ``loss``, ``round_loss``: the accepted cells' limits. They see nothing
#   here (the head's multiplier makes a seeded model's loss log(V) to six
#   digits whatever the blocks compute): the logits and the leaves are this
#   family's witnesses.
TOLERANCE = {
    "loss": 0.0004, "logits": 0.025,
    "ssm_in_proj": 0.02, "ssm_conv_taps": 0.025, "ssm_A_log": 0.1,
    "ssm_dt_bias": 0.25, "ssm_D": 0.03, "ssm_norm": 0.02,
    "ssm_out_proj": 0.02, "q_proj": 0.05, "k_proj": 0.04, "mlp_up": 0.025,
    "embed": 0.015, "lm_head": 0.015, "round_loss": 0.002, "fold": 0.25,
}
# under a leaf's change, in float32 ulps of the leaf (``eps |old|``)
CHANGE_FLOOR_ULPS = 4.0
# the leaves ``fold`` is read on: the matrices (a vector of 16 numbers lies
# where its rounding puts it)
FOLD_LEAVES = ("ssm_in_proj", "ssm_out_proj", "q_proj", "k_proj", "mlp_up",
               "embed", "lm_head")


def reference_check(algo, params, ref, config: dict) -> dict:
    """The system against the plain float32 reference ``ref``, same weights,
    in the two parts of the ``tokens`` family's check (the forward pass on
    the first training sequence of site 0; ONE ROUND of the program's own
    compiled round on the first ``clients_per_round`` sites, every site
    holding its first sequence in each of its rows, against the reference's
    own SGD steps and weighted mean). On the way the program's own function
    sets ``ssm_chunk_carry`` and ``ssm_dt_mean`` in the program's registry
    from what the forward sowed, as the program's runner does after
    ``init_state``."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.core.losses import make_loss_fn
    from neuroimagedisttraining_tpu.obs import (metrics as obs_metrics,
                                                trace as obs_trace)
    from neuroimagedisttraining_tpu.obs.expert_load import COLLECTION
    from neuroimagedisttraining_tpu.obs.ssm_carry import (carry_stats,
                                                          set_ssm_carry)

    flags, config = config["flags"], model_config(config)
    data, sites = algo.data, algo.clients_per_round
    loss_fn = make_loss_fn(algo.loss_type)
    n_layers = config["num_hidden_layers"]
    names = list(ref.GRAD_LEAVES)
    r_paths = [ref.GRAD_LEAVES[n] for n in names]
    # ("layers", i, ...) -> ("layers_<i>", ...)
    s_paths = [(f"layers_{p[1] % n_layers}",) + tuple(p[2:])
               if p[0] == "layers" else p for p in r_paths]

    # -- the forward pass, and the gauges --------------------------------
    def system(tree, x, y):
        logits, sown = algo.apply_fn(tree, x[0, :1], train=False, rng=None,
                                     mutable=[COLLECTION])
        return (loss_fn(logits, y[0, :1]), logits[0, ::LOGIT_STRIDE],
                carry_stats(sown))

    with obs_trace.span("expert_load"):
        s_loss, s_z, carry = jax.device_get(jax.jit(system)(
            params, data.x_train, data.y_train))
        gauges = set_ssm_carry(carry, obs_metrics.get_registry())

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
        new, loss, logits = ref.sgd_step(
            tree, x[site, 0], y[site, 0], config, flags["lr"],
            flags["grad_clip"], remat=True)
        return new, loss, logits[::LOGIT_STRIDE]

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
            local, loss, z = step(local, x_round, y_round, site)
            losses.append(loss)
            first = first or jax.device_get((loss, z))
        r_losses.append(float(np.mean(jax.device_get(losses))))
        r_total = fold(r_total, [_at(local, p) for p in r_paths],
                       counts[site] / counts.sum())
        if site == (sites // 2 or 1) - 1:
            # what a fold of the first half of the sites alone would give
            r_half = fold([jnp.zeros_like(t) for t in r_total], r_total,
                          counts.sum() / counts[:site + 1].sum())
        del local
    r_loss, r_z = first

    report = {"ok": True, "ssm_carry": gauges,
              "compared_positions": int(r_z.shape[0])}

    def put(name, err, finite=True):
        ok = bool(finite and np.isfinite(err) and err <= TOLERANCE[name])
        report[name] = {"error": float(err), "tolerance": TOLERANCE[name],
                        "ok": ok}
        report["ok"] = report["ok"] and ok

    put("loss", abs(float(s_loss) - float(r_loss))
        / max(1.0, abs(float(r_loss))), np.isfinite(s_loss))
    put("logits", np.max(np.abs(s_z - r_z))
        / max(float(np.max(np.abs(r_z))), 1e-30), np.isfinite(s_z).all())

    @jax.jit
    def distances(old, new, want, half):
        """Of one leaf: the system's new value against the reference's, and
        the reference's fold of half the sites against its fold of all,
        each over the norm of the reference's change (at least
        ``CHANGE_FLOOR_ULPS`` ulps of the leaf); how far the system's new
        value lies from the reference's fold of all toward its fold of half,
        along the line between the two."""
        floor = CHANGE_FLOOR_ULPS * jnp.finfo(jnp.float32).eps \
            * jnp.linalg.norm(old) + 1e-30
        moved = jnp.linalg.norm(want - old)
        change = jnp.maximum(moved, floor)
        off, line = new.astype(want.dtype) - want, half - want
        return (jnp.linalg.norm(off) / change,
                jnp.linalg.norm(line) / change,
                jnp.vdot(off, line) / jnp.maximum(jnp.vdot(line, line), 1e-30),
                jnp.isfinite(new).all(), moved / floor)

    halves, toward, floors = {}, {}, {}
    for i, name in enumerate(names):
        err, halves[name], toward[name], finite, floors[name] = \
            jax.device_get(distances(_at(params, s_paths[i]), s_new[i],
                                     r_total[i], r_half[i]))
        put(name, err, bool(finite))
    put("fold", max(abs(float(toward[name])) for name in FOLD_LEAVES))
    r_round_loss = float(np.mean(r_losses))
    put("round_loss", abs(s_round_loss - r_round_loss)
        / max(1.0, abs(r_round_loss)), np.isfinite(s_round_loss))
    # what the round's limits stand between (PERF.md section 6)
    report["round_controls"] = {
        "unchanged_state": 1.0,
        "half_the_clients": {k: float(v) for k, v in halves.items()},
        "toward_half": {k: float(v) for k, v in toward.items()},
        # the reference's change of each leaf in units of the floor under
        # it: under 1 the floor binds
        "change_over_floor": {k: float(v) for k, v in floors.items()}}
    return report


def layers(ref, config: dict) -> list:
    """The reference's counted rows for one sequence of the configuration's
    cohort, for ``lib/flops.py``."""
    return ref.layers(model_config(config),
                      config["cohort"]["sequence_length"])
