"""The control readings behind the limits of ``families/tokens_flash.py``
(chip only; not collected by pytest):

    python3 benchmarks/tests/control_flash.py <seed> \
        [e4m3|bf16_state|no_carry|one_map|no_memory|frozen_lambda|half_fold]

runs the family's own ``reference_check`` of ``phi4_mini_flash_fed.longctx``
with one fault in it and prints the report as one JSON line. The first five
put the plain reference in the place of the program's forward pass, changed
in one way. ``e4m3`` (default): every weight matrix rounded to the 8-bit
float e4m3, the nearest precision below the bfloat16 the configuration
states. ``bf16_state``: the Mamba recurrence's state rounded to bfloat16
after every token. ``no_carry``: the carry between chunks left out, every
chunk of ``mamba_chunk_size`` tokens starting from a zero state.
``one_map``: differential attention's second map left out. ``no_memory``:
the GMUs read a memory of zeros. ``bf16_state`` and ``no_carry`` change the
program too (``decoder.scan_token`` or ``decoder.selective_scan`` replaced
before the program is built), so that the compiled round, which the check's
second half drives, holds the fault; the other three change the reference's
forward pass alone, and the round is the program's own. The last two leave
the forward pass the program's own. ``frozen_lambda``: the program with no
gradient through differential attention's ``lam``
(``decoder.differential_maps`` replaced), so that its round never steps the
lambda vectors; not correct by ``lambda``, which reads 1 there.
``half_fold``: the program's own model and its own compiled round, driven
so that the later half of the round's sites weigh nothing in the fold; not
correct by ``fold``, which reads 1 there.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CONTROLS = ("e4m3", "bf16_state", "no_carry", "one_map", "no_memory",
            "frozen_lambda", "half_fold")
# the controls the program itself is changed for, before it is built
PLANTED = ("bf16_state", "no_carry", "frozen_lambda")
# those that put the reference, changed, in the place of the program's
# forward pass
STAND_IN = ("e4m3", "bf16_state", "no_carry", "one_map", "no_memory")


def bf16_state(step):
    """``decoder.scan_token`` with the state it returns rounded to bfloat16
    (by its bits: ``reference.phi4_flash.to_bfloat16``): the program's own
    scan, its state kept in bfloat16 after every token."""
    from benchmarks.reference.phi4_flash import to_bfloat16

    def rounded(h, decay, inflow):
        return to_bfloat16(step(h, decay, inflow))

    return rounded


def frozen_lambda(maps):
    """``decoder.differential_maps`` with no gradient through ``lam``: the
    forward pass as it was, the lambda vectors never stepped."""
    import jax

    def frozen(q, k, v, lam, *rest):
        return maps(q, k, v, jax.lax.stop_gradient(lam), *rest)

    return frozen


def chunks_from_zero(scan):
    """``decoder.selective_scan`` with every chunk starting from a zero
    state: each chunk a sequence of its own, run in sub-chunks of about the
    square root of its length, so that its backward keeps the states of one
    sub-chunk and those entering the sub-chunks (the chunks as sequences in
    whole chunks kept every token's state of the sequence at once: 25 GB for
    a round of 16,384 tokens, over the chip's 15.75)."""
    import math

    import jax.numpy as jnp

    def local(u, delta, a, b, c, d_skip, chunk):
        bsz, s_len = u.shape[:2]
        pad = -s_len % chunk
        ops = [jnp.pad(t, [(0, 0), (0, pad), (0, 0)]) for t in (u, delta, b,
                                                                 c)]
        nc = (s_len + pad) // chunk
        y, _ = scan(*(t.reshape((bsz * nc, chunk) + t.shape[2:])
                      for t in ops[:2]), a,
                    *(t.reshape((bsz * nc, chunk) + t.shape[2:])
                      for t in ops[2:]), d_skip, math.isqrt(chunk))
        # the share of a chunk's entering state that survives it, as the
        # scan itself reports it
        keep = jnp.mean(jnp.exp(jnp.sum(ops[1].reshape(
            bsz, nc, chunk, -1), axis=2)[..., None] * a), axis=(-2, -1))
        return y.reshape(bsz, nc * chunk, -1)[:, :s_len], keep

    return local


def plant(decoder, control: str, put=setattr) -> None:
    """The fault ``control`` names put into the program's module, where the
    program's tree has a place for it; build the program after it. A test
    hands its ``monkeypatch.setattr`` as ``put``."""
    if control == "bf16_state":
        put(decoder, "scan_token", bf16_state(decoder.scan_token))
    elif control == "no_carry":
        put(decoder, "selective_scan",
            chunks_from_zero(decoder.selective_scan))
    elif control == "frozen_lambda":
        put(decoder, "differential_maps",
            frozen_lambda(decoder.differential_maps))


def stand_in(ref, cfg: dict, control: str):
    """An ``apply_fn`` that is the reference, changed as ``control`` says;
    it sows nothing."""
    import jax
    from control_selected import to_e4m3

    def rounded(tree):
        if control != "e4m3":
            return tree
        return jax.tree_util.tree_map(
            lambda a: to_e4m3(a) if a.ndim > 1 else a, tree)

    faults = (control,) if control in ref.FAULTS else ()

    def apply_fn(tree, x, train, rng, mutable=False):
        logits, _ = ref.forward(ref.from_system(rounded(tree)), x[0], cfg,
                                remat=True, faults=faults)
        return (logits[None], {}) if mutable else logits[None]

    return apply_fn


def main(seed: int, control: str = "e4m3") -> int:
    import jax
    from control_shortconv import first_half_alone

    from benchmarks.families import tokens_flash as family
    from benchmarks.lib import harness, manifest
    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.models import decoder
    from neuroimagedisttraining_tpu.utils.compile_cache import (
        configure_compile_cache)

    if control not in CONTROLS:
        raise SystemExit(f"control {control!r}: one of {CONTROLS}")
    configure_compile_cache()
    cell = manifest.load_cell("BENCHMARK.json", "phi4_mini_flash_fed.longctx")
    plant(decoder, control)
    algo = harness.build(
        cell, parse_args(harness.program_flags(cell, seed)), seed)
    state = algo.init_state(jax.random.PRNGKey(seed))
    ref = harness.reference_of(cell)
    if control == "half_fold":
        algo._round_jit = first_half_alone(algo._round_jit)
    elif control in STAND_IN:
        algo.apply_fn = stand_in(ref, family.model_config(cell.config),
                                 control)
    report = family.reference_check(algo, state.global_params, ref,
                                    cell.config)
    print(json.dumps({"control": control, "seed": seed, "report": report}))
    return 0 if not report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]), *sys.argv[2:3]))
