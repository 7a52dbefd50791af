"""The control readings behind the limits of ``families/tokens_shortconv.py``
(chip only; not collected by pytest):

    python3 benchmarks/tests/control_shortconv.py <seed> \
        [e4m3|no_bias|no_taps|half_fold]

runs the family's own ``reference_check`` of ``lfm2_8b_a1b_fed.longctx`` with
one fault in it and prints the report as one JSON line. The first three put
the plain reference in the place of the program's model, changed in one way. ``e4m3`` (default): every
weight matrix rounded to the 8-bit float e4m3, the nearest precision below the
bfloat16 the configuration states. ``no_bias``: the selection bias left out of
the choice of the experts. ``no_taps``: the convolution left out, its current
tap alone (``c = w[:, 2] * u``). The last two change the reference that stands
in for the forward pass AND the program itself (``scores_alone`` in
``decoder.choose_experts``' place, ``current_tap_alone`` in
``decoder.short_conv``'s, before the program is built), so that the compiled
round, which the check's second half drives, holds the fault too. Each has to
come out as not correct: ``e4m3`` and ``no_bias`` by a forward limit at least
(loss, logits, routing, the agreeing positions), ``no_taps`` by the round's
conv leaves as well. Under ``e4m3`` the round is the program's own and reads as
the program does. ``half_fold``: the program's own model and its own compiled
round, driven so that the later half of the round's sites weigh nothing in
the fold (their counts set to 0: a site dropped from the weighted mean); not
correct by ``fold``, which reads 1 there, whatever the leaves' differences
read on a cohort whose sites' updates are alike.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CONTROLS = ("e4m3", "no_bias", "no_taps", "half_fold")


def scores_alone(scores, bias, top_k):
    """``decoder.choose_experts`` without its bias."""
    import jax

    return jax.lax.top_k(scores, top_k)[1]


def current_tap_alone(u, w):
    """``decoder.short_conv`` without the tokens before the current one."""
    return w[:, -1] * u


def first_half_alone(round_fn):
    """The compiled round ``round_fn`` (``algo._round_jit``) with the later
    half of the round's sites left out of its fold."""
    import jax.numpy as jnp

    def faulty(state, sel, round_idx, x, y, n):
        return round_fn(state, sel, round_idx, x, y,
                        jnp.asarray(n).at[sel[len(sel) // 2:]].set(0))

    return faulty


def plant(decoder, control: str, put=setattr) -> None:
    """The fault ``control`` names put into the program's module (nothing
    for ``e4m3``, which no program holds, and for ``half_fold``, which is a
    fault of the fold and not of the model); build the program after it. A
    test hands its ``monkeypatch.setattr`` as ``put``."""
    if control == "no_bias":
        put(decoder, "choose_experts", scores_alone)
    elif control == "no_taps":
        put(decoder, "short_conv", current_tap_alone)


def stand_in(ref, cfg: dict, control: str):
    """An ``apply_fn`` that is the reference, changed as ``control`` says,
    sowing what the program's model sows."""
    import jax
    import jax.numpy as jnp
    from control_selected import to_e4m3

    def rounded(tree):
        if control != "e4m3":
            return tree
        return jax.tree_util.tree_map(
            lambda a: to_e4m3(a) if a.ndim > 1 else a, tree)

    first_sparse = cfg["num_dense_layers"]

    def apply_fn(tree, x, train, rng, mutable=False):
        logits, routing = ref.forward(
            ref.from_system(rounded(tree)), x[0], cfg, cfg["first_expert"],
            remat=True, mix=control != "no_taps", bias=control != "no_bias")
        held = jnp.zeros((cfg["num_experts"],), jnp.int32)
        sown = {"expert_stats": {
            f"layers_{first_sparse + i}": {"mlp": {
                "top_experts": (r,), "held_counts": (held,)}}
            for i, r in enumerate(routing)}}
        return (logits[None], sown) if mutable else logits[None]

    return apply_fn


def main(seed: int, control: str = "e4m3") -> int:
    import jax

    from benchmarks.families import tokens_shortconv as family
    from benchmarks.lib import harness, manifest
    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.models import decoder
    from neuroimagedisttraining_tpu.utils.compile_cache import (
        configure_compile_cache)

    if control not in CONTROLS:
        raise SystemExit(f"control {control!r}: one of {CONTROLS}")
    configure_compile_cache()
    plant(decoder, control)
    cell = manifest.load_cell("BENCHMARK.json", "lfm2_8b_a1b_fed.longctx")
    algo = harness.build(
        cell, parse_args(harness.program_flags(cell, seed)), seed)
    state = algo.init_state(jax.random.PRNGKey(seed))
    ref = harness.reference_of(cell)
    if control == "half_fold":
        algo._round_jit = first_half_alone(algo._round_jit)
    else:
        algo.apply_fn = stand_in(ref, family.model_config(cell.config),
                                 control)
    report = family.reference_check(algo, state.global_params, ref,
                                    cell.config)
    print(json.dumps({"control": control, "seed": seed, "report": report}))
    return 0 if not report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]), *sys.argv[2:3]))
