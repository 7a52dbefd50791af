"""The control readings behind the limits of ``families/tokens_hybrid.py``
(chip only; not collected by pytest):

    python3 benchmarks/tests/control_hybrid.py <seed> \
        [e4m3|no_carry|no_ssm_multipliers|no_key_multiplier|half_fold]

runs the family's own ``reference_check`` of ``falcon_h1_34b_fed.longctx``
with one fault in it and prints the report as one JSON line. The first four
put the plain reference in the place of the program's model, changed in one
way. ``e4m3`` (default): every weight matrix rounded to the 8-bit float
e4m3, the nearest precision below the bfloat16 the configuration states.
``no_carry``: the carry between chunks left out, every chunk of
``mamba_chunk_size`` tokens starting from a zero state. ``no_ssm_multipliers``:
the five ``ssm_multipliers`` left out (``m`` = 1). ``no_key_multiplier``:
``key_multiplier`` left out. The last three change the reference that stands
in for the forward pass AND the program itself (``no_state_enters`` in
``decoder.carried_states``' place, or the key set to ones in the program's
``CONFIGS`` entry, before the program is built), so that the compiled round,
which the check's second half drives, holds the fault too. Each has to come
out as not correct: ``e4m3`` by ``logits`` (under it the round is the
program's own and reads as the program does), the other three by the
round's leaves as well. ``half_fold``: the program's own model and its own
compiled round, driven so that the later half of the round's sites weigh
nothing in the fold; not correct by ``fold``, which reads 1 there.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CONTROLS = ("e4m3", "no_carry", "no_ssm_multipliers", "no_key_multiplier",
            "half_fold")
# the configuration's keys a control sets to ones, in the program's constant
# and in the dictionary the reference takes
LEFT_OUT = {"no_ssm_multipliers": {"ssm_multipliers": [1, 1, 1, 1, 1]},
            "no_key_multiplier": {"key_multiplier": 1}}


def no_state_enters(own, keep):
    """``decoder.carried_states`` with every chunk starting from zero."""
    import jax.numpy as jnp

    return jnp.zeros_like(own)


def plant(decoder, control: str, model: str, put=setattr) -> None:
    """The fault ``control`` names put into the program's module (nothing
    for ``e4m3``, which no program holds, and for ``half_fold``, which is a
    fault of the fold and not of the model); build the program after it. A
    test hands its ``monkeypatch.setattr`` as ``put``."""
    if control == "no_carry":
        put(decoder, "carried_states", no_state_enters)
    elif control in LEFT_OUT:
        put(decoder, "CONFIGS", {**decoder.CONFIGS, model: {
            **decoder.CONFIGS[model], **LEFT_OUT[control]}})


def stand_in(ref, cfg: dict, control: str):
    """An ``apply_fn`` that is the reference, changed as ``control`` says
    (it sows nothing: the check then sets no gauge)."""
    import jax
    from control_selected import to_e4m3

    def rounded(tree):
        if control != "e4m3":
            return tree
        return jax.tree_util.tree_map(
            lambda a: to_e4m3(a) if a.ndim > 1 else a, tree)

    cfg = {**cfg, **LEFT_OUT.get(control, {})}
    reset = cfg["mamba_chunk_size"] if control == "no_carry" else 0

    def apply_fn(tree, x, train, rng, mutable=False):
        logits = ref.forward(ref.from_system(rounded(tree)), x[0], cfg,
                             remat=True, reset_every=reset)
        return (logits[None], {}) if mutable else logits[None]

    return apply_fn


def main(seed: int, control: str = "e4m3") -> int:
    import jax
    from control_shortconv import first_half_alone

    from benchmarks.families import tokens_hybrid as family
    from benchmarks.lib import harness, manifest
    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.models import decoder
    from neuroimagedisttraining_tpu.utils.compile_cache import (
        configure_compile_cache)

    if control not in CONTROLS:
        raise SystemExit(f"control {control!r}: one of {CONTROLS}")
    configure_compile_cache()
    cell = manifest.load_cell("BENCHMARK.json", "falcon_h1_34b_fed.longctx")
    plant(decoder, control, cell.config["flags"]["model"])
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
