"""The ``tokens`` family: a decoder language model trained federated on
shards of token ids, per-token cross-entropy over the vocabulary rows a chip
holds. What a family brings is in :mod:`benchmarks.families`.

A configuration of this family states the model as its published
``config.json`` does, under the same keys: every width and setting at the top
level, as published and never cut; the counts that ONE CHIP holds a share of
(``model-configs`` guide, section 4: depth, routed experts, heads, vocabulary
rows, and the per-layer lists cut to the depth) under ``held``, their
published values under ``published``; ``first_expert`` is the id of the first
routed expert held. :func:`model_config` lays ``held`` over the rest: the
dictionary the reference takes.

**The cohort** is the program's own synthetic token shards
(``neuroimagedisttraining_tpu/data/tokens.py``): per site ``train_per_site``
training and ``test_per_site`` test sequences of ``sequence_length`` ids drawn
from the held vocabulary slice, one document a sequence, no padding; targets
are the next ids, the last position's is -1 (no target: the program's loss
and the reference's give it weight 0). Ids have Zipf marginals and a seeded
first-order structure, so that training lowers the loss (``check.state_check``
holds it to that). One jitted program makes all of it on the device from
``--seed``.
"""
from __future__ import annotations

import numpy as np

# the published config.json's keys that no cut touches, and the three groups
# that state the chip's share
CONFIG_KEYS = {
    "model_type", "hidden_size", "intermediate_size", "head_dim",
    "max_position_embeddings", "attention_bias", "rms_norm_eps",
    "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "norm_topk_prob",
    "decoder_sparse_step", "mlp_only_layers", "tie_word_embeddings",
    "gating", "sliding_window", "rope_parameters",
    "moe_apply_router_weight_on_input", "moe_routed_scaling_factor",
    "moe_router_logit_softcapping", "held", "published", "first_expert"}
# what ``held`` (and ``published``) state
HELD_KEYS = {
    "num_hidden_layers", "num_experts", "num_attention_heads",
    "num_key_value_heads", "vocab_size", "layer_types", "mlp_layer_types",
    "gating_types", "num_attention_heads_per_layer"}
COHORT_KEYS = {"n_sites", "train_per_site", "test_per_site",
               "sequence_length"}
LOGIT_STRIDE = 64                   # logits compared at every 64th position


def model_config(config: dict) -> dict:
    """The configuration with what the chip holds laid over it: the model's
    description as the reference takes it (``num_experts`` 8, and 256 under
    ``published``)."""
    if set(config["held"]) != HELD_KEYS:
        raise ValueError(f"'held' states {sorted(config['held'])}, not "
                         f"{sorted(HELD_KEYS)}")
    return {**config, **config["held"]}


def make_cohort(cohort: dict, config: dict, seed: int, sharding=None):
    """The cohort as the program's ``FederatedData``, on the device(s)
    ``sharding`` names (default: JAX's default device): the program's own
    synthetic token shards (``data/tokens.py``), one jitted program from the
    seed."""
    from neuroimagedisttraining_tpu.data.tokens import make_token_shards

    return make_token_shards(
        seed, cohort["n_sites"], config["held"]["vocab_size"],
        cohort["sequence_length"], cohort["train_per_site"],
        cohort["test_per_site"], sharding)


# Limits of the comparison with the plain float32 reference (every product
# at the highest precision), same weights. The system computes its products
# in bfloat16 (relative rounding 2^-9) with float32 accumulation and keeps
# softmax, norms, the router's probabilities and the loss in float32.
#
# FORWARD, on the first training sequence of site 0 (8192 tokens): for logits
# and loss the error is the largest difference over max(1, largest reference
# value); ``routing`` is the share of (token, sparse layer) pairs whose set
# of chosen experts differs.
#
# ONE ROUND, the program's own compiled round (``algo._round_jit``: the
# folding body, train mode, clip, SGD, the weighted fold) on the first
# ``clients_per_round`` sites against the reference's own steps and weighted
# mean (``reference.sgd_step``): for each leaf of ``ref.GRAD_LEAVES`` the
# norm of (the system's new leaf - the reference's) over the norm of the
# reference's change of that leaf. 1.0 is what a state left unchanged reads;
# ``round_controls`` in the report says what a fold of half the clients
# would read (from the reference's own locals). ``round_loss``: the round's
# train loss against the mean of the reference's step losses.
#
# Each limit lies between two readings on the chip (my chip runs, PR 28,
# PERF.md section 6). Forward: the largest of the program over 32 seeds, and
# the CONTROL, the reference itself with every weight matrix rounded to an
# 8-bit float (e4m3, the nearest precision below bfloat16) in the program's
# place (``benchmarks/tests/control_e4m3.py``), which has to come out as not
# correct and does, by ``routing``, ``logits`` and the agreeing positions:
#
#                      program, largest   control    limit
#   loss                   0.00019        0.00089    0.0004
#   logits                 0.0220         0.0508     0.033
#   routing                0.172          0.404      0.27
#   agreeing positions     52 of 128      20         at least 38
#
# The round (my chip runs, PR 28, 15 runs on seeds 2147484201-04, ..211-14
# and ..221-26): the program's largest reading, what a fold of half the
# clients reads (the smallest over those runs, from the reference's own
# locals), what a state left unchanged reads:
#
#                      program, largest   half the clients   unchanged   limit
#   expert_up_last         0.049             0.19               1.0       0.15
#   router_layer1          0.103             0.18               1.0       0.3
#   q_proj_sliding         0.043             0.22               1.0       0.12
#   q_proj_full            0.053             0.31               1.0       0.15
#   head_gate              0.029             0.14               1.0       0.08
#   shared_expert_up       0.034             0.16               1.0       0.09
#   dense_down             0.025             0.13               1.0       0.07
#   lm_head                0.021             0.12               1.0       0.06
#   round_loss             0.00029           -                  -         0.002
#
# A seed in nine reads twice what the others do in every leaf (the two
# sides' second steps start further apart), the router's leaf 0.10 where its
# median is 0.03: each limit stands nearly three times over the largest
# reading, since fresh seeds read higher. Half the clients then fails by
# seven leaves of the eight (the router's limit lies above its control) and
# an unchanged state by all; a step that is a fifth off (a wrong rate, decay
# or clip) reads a fifth in every leaf and fails by all but the router's.
# ``round_loss`` swings from 0.00002 to 0.0003 between seeds; no control was
# read for it: the leaves are the round's witnesses.
#
# * routing: the router picks 10 of 256 logits whose neighbours at the cut
#   lie about 0.05 apart at these widths (256 normal logits of deviation
#   1.1), and bfloat16 activations move a logit by about 0.01, so one pair
#   in six swaps its 10th expert for the 11th; a swap moves a token's routed
#   part by a tenth and everything behind it a little. Logits are therefore
#   compared on the positions whose routing agrees with the reference's in
#   every layer, and ``MIN_AGREEING`` of the compared positions must. An
#   8-bit path moves a logit four times as far: two pairs in five swap.
# * logits, loss: a path that accumulated in bfloat16 (3072 to 12288 terms)
#   would err by several percent of a logit, as the 8-bit one does.
# * the round's leaves: the held experts' and the router's feel the swapped
#   tokens (the tokens an expert sees differ by a few), so they read higher
#   and vary more between seeds than the dense leaves.
TOLERANCE = {
    "loss": 0.0004, "logits": 0.033, "routing": 0.27,
    "expert_up_last": 0.15, "router_layer1": 0.3, "q_proj_sliding": 0.12,
    "q_proj_full": 0.15, "head_gate": 0.08, "shared_expert_up": 0.09,
    "dense_down": 0.07, "lm_head": 0.06, "round_loss": 0.002,
}
MIN_AGREEING = 0.3      # of the compared positions: 38 of 128 at 8192 tokens


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def reference_check(algo, params, ref, config: dict) -> dict:
    """The system against the plain float32 reference ``ref``, same weights,
    in two parts (the table above): the model's forward pass on the first
    training sequence of site 0, and ONE ROUND of the program's own compiled
    round program on the first ``clients_per_round`` sites against the
    reference's own SGD steps and weighted mean, in the leaves of
    ``ref.GRAD_LEAVES``.

    In the round every site holds its first sequence in each of its rows:
    the program shuffles a site's sequences with its own keys, and so the
    order of a site's steps is no part of the comparison. On the way the
    program's own function sets the expert-load gauges in the program's
    registry (``obs/expert_load.py``), as the program's runner does after
    ``init_state``."""
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
        ok = bool(finite and np.isfinite(err) and err <= TOLERANCE[name])
        report[name] = {"error": float(err), "tolerance": TOLERANCE[name],
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
        each over the norm of the reference's change."""
        change = jnp.maximum(jnp.linalg.norm(want - old), 1e-30)
        return (jnp.linalg.norm(new.astype(want.dtype) - want) / change,
                jnp.linalg.norm(half - want) / change,
                jnp.isfinite(new).all())

    halves = {}
    for i, name in enumerate(names):
        err, halves[name], finite = jax.device_get(distances(
            _at(params, s_paths[i]), s_new[i], r_total[i], r_half[i]))
        put(name, err, bool(finite))
    r_round_loss = float(np.mean(r_losses))
    put("round_loss", abs(s_round_loss - r_round_loss)
        / max(1.0, abs(r_round_loss)), np.isfinite(s_round_loss))
    # what the round's limits stand between (PERF.md section 6)
    report["round_controls"] = {
        "unchanged_state": 1.0,
        "half_the_clients": {k: float(v) for k, v in halves.items()}}
    return report


def layers(ref, config: dict) -> list:
    """The reference's counted rows for one sequence of the configuration's
    cohort, for ``lib/flops.py``."""
    return ref.layers(model_config(config),
                      config["cohort"]["sequence_length"])
