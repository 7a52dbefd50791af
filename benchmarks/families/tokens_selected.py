"""The ``tokens_selected`` family: a decoder language model whose attention is
over the keys a learned indexer selects, trained federated on shards of token
ids as the ``tokens`` family's are (its cohort generator and cohort keys are
that family's; what a family brings is in :mod:`benchmarks.families`).

A configuration states the model as its published ``config.json`` does, under
the same keys (``sa_config``, ``rope_scaling``, ``rope_theta`` and the rest
at the top level, never cut); the counts ONE CHIP holds a share of under
``held``, their published values under ``published``; ``first_expert`` is the
id of the first routed expert held. :func:`model_config` lays ``held`` over
the rest: the dictionary the reference takes.
"""
from __future__ import annotations

import numpy as np

from .tokens import COHORT_KEYS, _at, make_cohort  # noqa: F401

# the published config.json's keys that no cut touches, ``qk_norm`` (no
# published key: the program's constant states it beside them, the file under
# ``assumed`` too), and the three groups that state the chip's share
CONFIG_KEYS = {
    "attention_bias", "decoder_sparse_step", "head_dim", "hidden_act",
    "hidden_size", "intermediate_size", "max_position_embeddings",
    "max_window_layers", "mlp_only_layers", "model_type",
    "moe_intermediate_size", "norm_topk_prob", "num_experts_per_tok",
    "qk_norm", "rms_norm_eps", "rope_scaling", "rope_theta", "sa_config",
    "sliding_window", "tie_word_embeddings", "use_sliding_window",
    "held", "published", "first_expert"}
# what ``held`` (and ``published``) state
HELD_KEYS = {"num_hidden_layers", "num_experts", "num_local_experts",
             "num_attention_heads", "num_key_value_heads", "vocab_size"}
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
# family's (FORWARD on the first training sequence of site 0; ONE ROUND of
# the program's own compiled round on the round's sites against the
# reference's own SGD steps and weighted mean: families/tokens.py has the
# definitions), with of its own:
#
# ``selection``: 1 - the mean over (query, layer) of |S_sys & S_ref| over the
#   larger of |S_sys| and |S_ref|, S the set of keys the query attends. Both
#   sides keep min(2048, t + 1) keys, so this is the share of the reference's
#   keys the program missed; the larger count stands below so that a program
#   that attends MORE than the selection (full attention in its place) does
#   not read 0.
# logits: at every 128th position whose routing agrees with the reference's
#   in every layer.
# the indexer's three matrices of the last layer: the largest absolute change
#   over the round, which must be 0.0 (no gradient reaches the indexer, and
#   the fold of two equal sites is 0.5 p + 0.5 p).
#
# Each limit stands between the program's largest reading over seeds on the
# chip and two controls that must come out not correct
# (``benchmarks/tests/control_selected.py``): the reference with every weight
# matrix rounded to e4m3 (the nearest precision below the bfloat16 the
# configuration states) in the program's place, and FULL ATTENTION in the
# selection's place (the mechanism left out): in the reference that stands
# in for the forward pass and, planted in ``decoder.select_keys`` before the
# program is built, in the compiled round. Readings (my chip runs, PR 33,
# PERF.md section 6: the program on fourteen seeds, 2147484601-04, ..611-16,
# ..631, ..642, ..644-45; each control on seeds 2147484605 and ..646):
#
#                      program, largest   e4m3              full attention    limit
#   loss                   0.00021        0.00080 0.00027   0.00044 0.00018   0.0004
#   logits                 0.0137         (too few positions agree)           0.04
#   routing                0.115          0.595   0.580     0.746   0.753     0.3
#   selection              0.0156         0.1005  0.0891    0.615   0.615     0.05
#   agreeing positions     81 of 128      8       2         17      18        at least 38
#
# ``loss`` separates nothing: the precision hardly moves it (a control reads
# under the program's largest on one seed and four times over it on the
# other). It keeps the accepted ``tokens`` family's limit, 1.9 times the
# largest of the fourteen readings (their mean is 0.00008), and NO control
# is held to fail by it: each comes out not correct by ``routing``, by
# ``selection`` and by the agreeing positions, on both seeds.
#
# The round: the program's largest reading; the round compiled with full
# attention in the selection's place (the two seeds); what a fold of half the
# clients reads (the smallest over the program's runs, from the reference's
# own locals); what a state left unchanged reads:
#
#                      program, largest   full attention   half the clients   unchanged   limit
#   expert_up_last         0.145          0.18   0.90          0.22             1.0       0.6
#   router_layer1          0.091          0.23   0.24          0.23             1.0       0.3
#   q_proj                 0.016          0.53   0.44          0.27             1.0       0.08
#   o_proj                 0.0066         0.055  0.060         0.12             1.0       0.02
#   q_norm                 0.024          0.57   0.39          0.25             1.0       0.09
#   lm_head                0.0061         0.110  0.099         0.105            1.0       0.03
#   embed                  0.0071         0.134  0.140         0.14             1.0       0.035
#   round_loss             0.00019        0.00014 0.00017      -                -         0.002
#   the indexer's three    0.0            0.0                  -                -         0.0
#
# The dense leaves read alike on every seed, and their limits stand three to
# six times over the largest reading and under what the faulty rounds read:
# a round that attends every visible key fails by ``q_proj``, ``o_proj``,
# ``q_norm``, ``lm_head`` and ``embed``, a fold of half the clients by the
# same five. (``o_proj`` stood at 0.04 before the faulty round was read:
# 1.4 times under its 0.055.) The held experts' leaf and the router's swing:
# the Zipf cohort routes alike (the fullest held expert draws 9 to 16 times
# the mean), so a held expert of the last layer may see a handful of tokens,
# and the tenth of the tokens whose routing the bfloat16 activations swap
# then moves its gradient by a large share of its own length: 0.010 to 0.049
# on thirteen seeds, 0.145 on one (where half the clients reads 1.0: one
# site's step is most of the change). Their limits stand three to four times
# over that and catch neither faulty round on every seed; an unchanged state
# (1.0) fails by every leaf. ``round_loss`` keeps the accepted cells' limit,
# ten times its largest reading.
#
# * selection: the program scores with bfloat16 indexer projections (float32
#   accumulation, float32 score): a key whose score lies within that
#   rounding of the 2048th swaps with a neighbour, 1.0-1.6 % of the keys; an
#   8-bit path swaps a tenth.
# * routing: 8 of 128 logits; a swap at the cut in one pair in ten, less
#   than Laguna's 10 of 256 (one in six). Logits are compared on the
#   positions whose routing agrees with the reference's in every layer; in
#   the controls too few agree to compare any.
# * logits, loss, the round's leaves: as in families/tokens.py.
TOLERANCE = {
    "loss": 0.0004, "logits": 0.04, "routing": 0.3, "selection": 0.05,
    "expert_up_last": 0.6, "router_layer1": 0.3, "q_proj": 0.08,
    "o_proj": 0.02, "q_norm": 0.09, "lm_head": 0.03, "embed": 0.035,
    "round_loss": 0.002,
    "indexer_q_proj": 0.0, "indexer_k_proj": 0.0, "indexer_weights_proj": 0.0,
}
MIN_AGREEING = 0.3      # of the compared positions


def reference_check(algo, params, ref, config: dict) -> dict:
    """The system against the plain float32 reference ``ref``, same weights,
    in the two parts of the ``tokens`` family's check (the forward pass on
    the first training sequence of site 0; ONE ROUND of the program's own
    compiled round on the first ``clients_per_round`` sites, every site
    holding its first sequence in each of its rows, against the reference's
    own SGD steps and weighted mean), with the selection compared beside the
    routing and the indexer held to no change at all. On the way the
    program's own functions set the expert-load and ``selected_key_share``
    gauges in the program's registry, as the program's runner does after
    ``init_state``."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.core.losses import make_loss_fn
    from neuroimagedisttraining_tpu.obs import (metrics as obs_metrics,
                                                trace as obs_trace)
    from neuroimagedisttraining_tpu.obs.expert_load import (
        COLLECTION, set_expert_load, stacked_stats)
    from neuroimagedisttraining_tpu.obs.selection import (
        key_share, set_selected_key_share, stacked_selection)

    flags, config = config["flags"], model_config(config)
    data, sites = algo.data, algo.clients_per_round
    loss_fn = make_loss_fn(algo.loss_type)
    n_layers = config["num_hidden_layers"]
    names = list(ref.GRAD_LEAVES) + list(ref.INDEXER_LEAVES)
    r_paths = [{**ref.GRAD_LEAVES, **ref.INDEXER_LEAVES}[n] for n in names]
    # ("layers", i, ...) -> ("layers_<i>", ...)
    s_paths = [(f"layers_{p[1] % n_layers}",) + tuple(p[2:])
               if p[0] == "layers" else p for p in r_paths]

    # -- the forward pass, and the gauges --------------------------------
    def system(tree, x, y):
        logits, sown = algo.apply_fn(tree, x[0, :1], train=False, rng=None,
                                     mutable=[COLLECTION])
        kept = stacked_selection(sown)[:, 0]            # [layers, S, S]
        return (loss_fn(logits, y[0, :1]), logits[0, ::LOGIT_STRIDE],
                stacked_stats(sown), key_share(kept[:, None])), kept

    with obs_trace.span("expert_load"):
        small, s_kept = jax.jit(system)(params, data.x_train, data.y_train)
        s_loss, s_z, stats, share = jax.device_get(small)
        registry = obs_metrics.get_registry()
        load = {**set_expert_load(stats, registry),
                **set_selected_key_share(share, registry)}

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
        new, loss, logits, routing, selection = ref.sgd_step(
            tree, x[site, 0], y[site, 0], config, flags["lr"],
            flags["grad_clip"], config["first_expert"], remat=True)
        return (new, (loss, logits[::LOGIT_STRIDE], jnp.stack(routing)),
                jnp.stack(selection))

    @jax.jit
    def missed(s_kept, r_kept):
        """The ``selection`` error of [layers, S, S] selections."""
        both = jnp.sum(s_kept & r_kept, axis=-1)
        most = jnp.maximum(jnp.sum(s_kept, axis=-1), jnp.sum(r_kept, axis=-1))
        return 1.0 - jnp.mean(both / most)

    step = jax.jit(plain, donate_argnums=0)
    start = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))
    fold = jax.jit(lambda total, leaves, w: [
        t + w * leaf for t, leaf in zip(total, leaves)])
    r_params = ref.from_system(params)
    counts = np.asarray(data.n_train, np.float64)[:sites]
    r_total = [jnp.zeros_like(_at(r_params, p)) for p in r_paths]
    r_half, r_losses, first, selection = None, [], None, None
    for site in range(sites):
        local, losses = start(r_params), []
        for _ in range(int(counts[site])):
            local, small, r_kept = step(local, x_round, y_round, site)
            losses.append(small[0])
            if first is None:
                first = jax.device_get(small)
                selection = float(missed(s_kept, r_kept))
                del s_kept
            del r_kept
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
    put("selection", selection)

    @jax.jit
    def distances(old, new, want, half):
        """Of one leaf: the system's new value against the reference's, and
        the reference's fold of half the sites against its fold of all,
        each over the norm of the reference's change; and the largest
        change of the system's leaf."""
        change = jnp.maximum(jnp.linalg.norm(want - old), 1e-30)
        return (jnp.linalg.norm(new.astype(want.dtype) - want) / change,
                jnp.linalg.norm(half - want) / change,
                jnp.isfinite(new).all(), jnp.max(jnp.abs(new - old)))

    halves = {}
    for i, name in enumerate(names):
        err, half, finite, moved = jax.device_get(distances(
            _at(params, s_paths[i]), s_new[i], r_total[i], r_half[i]))
        if name in ref.INDEXER_LEAVES:
            put(name, moved, bool(finite))
        else:
            halves[name] = half
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
