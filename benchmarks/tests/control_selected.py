"""The control readings behind the forward limits of
``families/tokens_selected.py`` (chip only; not collected by pytest):

    python3 benchmarks/tests/control_selected.py <seed> [e4m3|full_attention]

puts the plain reference in the place of the program's model in the family's
own ``reference_check`` of ``keye_vl2_fed.longctx``, changed in one of two
ways, and prints the report as one JSON line. ``e4m3`` (default): every weight
matrix rounded to the 8-bit float e4m3, the nearest precision below the
bfloat16 the configuration states. ``full_attention``: full causal attention in
the selection's place, the mechanism left out, in the reference that stands in
for the forward pass AND in the program itself (``every_visible_key`` in
``decoder.select_keys``' place before the program is built), so that the
compiled round, which the check's second half drives, trains on the wrong
keys too. Each has to come out as not correct by at least one forward limit
(loss, logits, routing, selection, the agreeing positions), and
``full_attention`` by the round's ``q_proj`` and ``o_proj`` as well. Under
``e4m3`` the round is the program's own and reads as the program does.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def to_e4m3(a):
    """``a`` rounded to the nearest 8-bit float e4m3 (four exponent bits,
    three of mantissa: steps of an eighth of the power of two below, of
    2^-9 under 2^-6, ties to even, at most 448), in float32 arithmetic. (On
    the chip a cast to ``float8_e4m3fn`` and back inside a jitted program
    rounded to bfloat16 and no further: my chip run, PR 33.)"""
    import jax.numpy as jnp

    step = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(a), 2.0 ** -6)))
                    - 3)
    return jnp.clip(jnp.round(a / step) * step, -448.0, 448.0)


def every_visible_key(select_keys):
    """``decoder.select_keys`` asked for as many keys as the row holds: full
    attention in the selection's place, inside the program."""
    return lambda scores, start, topk: select_keys(scores, start,
                                                   scores.shape[-1])


def stand_in(ref, cfg: dict, control: str):
    """An ``apply_fn`` that is the reference, changed as ``control`` says,
    sowing what the program's model sows."""
    import jax
    import jax.numpy as jnp

    def rounded(tree):
        if control != "e4m3":
            return tree
        return jax.tree_util.tree_map(
            lambda a: to_e4m3(a) if a.ndim > 1 else a, tree)

    def apply_fn(tree, x, train, rng, mutable=False):
        logits, routing, selection = ref.forward(
            ref.from_system(rounded(tree)), x[0], cfg, cfg["first_expert"],
            remat=True, select=control != "full_attention")
        held = jnp.zeros((cfg["num_experts"],), jnp.int32)
        sown = {"expert_stats": {
            f"layers_{i}": {"mlp": {"top_experts": (r,),
                                    "held_counts": (held,)},
                            "attention": {"selected_keys": (keep[None],)}}
            for i, (r, keep) in enumerate(zip(routing, selection))}}
        return (logits[None], sown) if mutable else logits[None]

    return apply_fn


def main(seed: int, control: str = "e4m3") -> int:
    import jax

    from benchmarks.families import tokens_selected as family
    from benchmarks.lib import harness, manifest
    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.models import decoder
    from neuroimagedisttraining_tpu.utils.compile_cache import (
        configure_compile_cache)

    configure_compile_cache()
    if control == "full_attention":
        decoder.select_keys = every_visible_key(decoder.select_keys)
    cell = manifest.load_cell("BENCHMARK.json", "keye_vl2_fed.longctx")
    algo = harness.build(
        cell, parse_args(harness.program_flags(cell, seed)), seed)
    state = algo.init_state(jax.random.PRNGKey(seed))
    ref = harness.reference_of(cell)
    algo.apply_fn = stand_in(ref, family.model_config(cell.config), control)
    report = family.reference_check(algo, state.global_params, ref,
                                    cell.config)
    print(json.dumps({"control": control, "seed": seed, "report": report}))
    return 0 if not report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]), *sys.argv[2:3]))
