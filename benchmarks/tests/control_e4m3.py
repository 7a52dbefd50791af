"""The control reading behind the forward limits of ``families/tokens.py``
(chip only; not collected by pytest):

    python3 benchmarks/tests/control_e4m3.py <seed> [float8_e4m3fn|bfloat16]

puts the plain reference, with every weight matrix rounded to the given type
(default: the 8-bit float e4m3, the nearest precision below the bfloat16 the
configuration states), in the place of the program's model in the family's
own ``reference_check`` of ``laguna_s21_fed.train`` and prints the report as
one JSON line. It has to come out as not correct, by at least one forward
limit (loss, logits, routing, agreeing positions). The round half of the
check drives the program's compiled round, which the control does not
replace: it reads as the program does.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(seed: int, dtype_name: str = "float8_e4m3fn") -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.families import tokens
    from benchmarks.lib import harness, manifest
    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.utils.compile_cache import (
        configure_compile_cache)

    configure_compile_cache()
    dtype = getattr(jnp, dtype_name)
    cell = manifest.load_cell("BENCHMARK.json", "laguna_s21_fed.train")
    algo = harness.build(
        cell, parse_args(harness.program_flags(cell, seed)), seed)
    state = algo.init_state(jax.random.PRNGKey(seed))
    ref, cfg = harness.reference_of(cell), tokens.model_config(cell.config)

    def rounded(tree):
        return jax.tree_util.tree_map(
            lambda a: a.astype(dtype).astype(jnp.float32) if a.ndim > 1
            else a, tree)

    def control(tree, x, train, rng, mutable=False):
        logits, routing = ref.forward(
            ref.from_system(rounded(tree)), x[0], cfg, cfg["first_expert"],
            remat=True)
        held = jnp.zeros((len(routing), cfg["num_experts"]), jnp.int32)
        sown = {"expert_stats": {
            f"layers_{i + 1}": {"mlp": {"top_experts": (r,),
                                        "held_counts": (held[i],)}}
            for i, r in enumerate(routing)}}
        return (logits[None], sown) if mutable else logits[None]

    algo.apply_fn = control
    report = tokens.reference_check(algo, state.global_params, ref,
                                    cell.config)
    print(json.dumps({"control": dtype_name, "seed": seed,
                      "report": report}))
    return 0 if not report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]), *sys.argv[2:3]))
