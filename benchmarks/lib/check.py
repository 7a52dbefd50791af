"""The comparisons that decide ``correct``. Both run outside the measured
window, on the device the cell runs on, at the configuration's real sizes.
"""
from __future__ import annotations

import numpy as np

from . import phase

# Tolerances of the comparison with the plain float32 reference: for logits
# and loss the largest difference over max(1, largest reference value), for
# a gradient tensor the norm of the difference over the reference's norm.
# The system computes convs and matmuls in bfloat16 (8 bits of mantissa,
# relative rounding 2^-8) with float32 accumulation, normalisation statistics
# and loss. On the chip, over the seeds of PR 22's 44 runs (PERF.md,
# Findings), that measured at most 0.018 in a logit (PR 21: 0.0139), 0.0048
# in the loss and 0.033 in the last dense layer's gradient (ResNet_l3);
# each bound is 2.5-3x that.
# The stem kernel's gradient is another matter: it is the small remainder
# GroupNorm leaves of a sum over a quarter of a million positions, and
# bfloat16 rounding of the activations' gradients puts 0.21-0.36 of its norm
# on it (the same on the CPU at 69^3; in float32 the two sides agree to
# 2e-6). Its bound of 0.75 is 2x that, still below 1.0, which is what a
# gradient of zero or an unrelated one scores. A path computed in an 8-bit
# float or int8 rounds 16 times coarser and fails every one of the four.
TOLERANCE = {"logits": 0.05, "loss": 0.015, "stem_kernel": 0.75,
             "last_dense": 0.08}
# the repo's own SNIP mask tests hold the density to the ratio within 0.03
# (tests/test_salientgrads_e2e.py)
DENSITY_TOLERANCE = 0.03


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _with(tree, path, value):
    """A copy of the nested dicts and lists of ``tree`` with the leaf at
    ``path`` replaced."""
    if not path:
        return value
    copy = list(tree) if isinstance(tree, list) else dict(tree)
    copy[path[0]] = _with(tree[path[0]], path[1:], value)
    return copy


def _logits(out):
    out = out[0] if isinstance(out, (list, tuple)) else out
    return out.reshape(out.shape[0], -1)[:, 0]


def reference_check(algo, params, ref, config: dict) -> dict:
    """The system's model (phased input, its compute type) against the plain
    float32 reference ``ref`` on the first two training volumes of site 0,
    same weights, eval mode on both sides: logits, BCE loss, and the
    gradient of the loss in the stem kernel and the last dense layer (only
    those two are differentiated: the float32 weight gradients of every
    layer would double the reference's compile time)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import ops
    from neuroimagedisttraining_tpu.core.losses import make_loss_fn

    kernel, pad = config["stem"]["kernel"], config["stem"]["pad"]
    stored = np.asarray(algo.data.x_train[0, :2])
    y = jnp.asarray(np.asarray(algo.data.y_train[0, :2]))
    # the generator fills the conv's padding frame with noise; a volume has
    # zeros there, so the pair is re-derived from the dense volume
    dense = phase.recompose(stored.astype(np.float32), config["volume"], pad)
    phased = jnp.asarray(phase.decompose(dense, kernel, pad), stored.dtype)
    loss_fn = make_loss_fn(algo.loss_type)
    names = list(ref.GRAD_LEAVES)
    paths = [ref.GRAD_LEAVES[n] for n in names]

    def replaced(tree, which, leaves):
        for (s_path, r_path), leaf in zip(paths, leaves):
            tree = _with(tree, (s_path, r_path)[which], leaf)
        return tree

    def system(leaves, tree, x, labels):
        out = algo.apply_fn(replaced(tree, 0, leaves), x, train=False,
                            rng=None)
        return loss_fn(out, labels), _logits(out)

    def plain(leaves, tree, x, labels):
        z = ref.forward(replaced(tree, 1, leaves), x)
        return ops.bce_with_logits(z, labels), z

    params = jax.device_get(params)
    (s_loss, s_z), s_grad = jax.jit(jax.value_and_grad(system, has_aux=True))(
        [_at(params, s) for s, _ in paths], params, phased, y)
    r_params = ref.from_system(
        params, lambda w: phase.dense_stem_kernel(w, kernel))
    with jax.default_matmul_precision("highest"):
        (r_loss, r_z), r_grad = jax.jit(
            jax.value_and_grad(plain, has_aux=True))(
                [_at(r_params, r) for _, r in paths], r_params,
                jnp.asarray(dense), y)
    pairs = {"logits": (np.asarray(s_z), np.asarray(r_z), 1.0),
             "loss": (np.asarray(s_loss), np.asarray(r_loss), 1.0)}
    for name, got, want in zip(names, s_grad, r_grad):
        got = np.asarray(got)
        if name == "stem_kernel":
            got = phase.dense_stem_kernel(got, kernel)
        pairs[name] = (got, np.asarray(want), 0.0)
    report = {"reference_logits": np.asarray(r_z).tolist(),
              "system_logits": np.asarray(s_z).tolist(), "ok": True}
    for name, (got, want, floor) in pairs.items():
        if floor:   # logits, loss: the largest difference
            err = float(np.max(np.abs(got - want))) / max(
                floor, float(np.max(np.abs(want))))
        else:       # a gradient tensor: the difference's share of its norm
            err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        ok = bool(np.isfinite(got).all()) and err <= TOLERANCE[name]
        report[name] = {"error": err, "tolerance": TOLERANCE[name], "ok": ok}
        report["ok"] = report["ok"] and ok
    return report


def _named_leaves(tree):
    import jax

    return [(jax.tree_util.keystr(path), leaf) for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)]


def state_check(algo, state, chips: int, first_loss: float,
                last_loss: float) -> dict:
    """What must hold of the state after the window: the model is zero where
    the SNIP mask is, the mask keeps ``dense_ratio`` of the weights it
    covers, training lowered the loss (the cohort's signal is planted),
    everything lives on the backend's devices, and on several chips the
    cohort is spread over all of them."""
    import jax
    import jax.numpy as jnp

    report = {}
    mask = getattr(state, "mask", None)
    if mask is not None:
        leaked = [name for (name, p), (_, m) in zip(
            _named_leaves(state.global_params), _named_leaves(mask))
            if bool(jnp.any((m == 0) & (p != 0)))]
        kernels = [m for name, m in _named_leaves(mask)
                   if name.endswith("['kernel']")]
        density = float(sum(jnp.sum(m != 0) for m in kernels)
                        / sum(m.size for m in kernels))
        report["masked_weights_nonzero_in"] = leaked
        report["mask_density"] = density
        report["mask_ok"] = not leaked and abs(
            density - algo.dense_ratio) <= DENSITY_TOLERANCE
    report["train_loss_first"], report["train_loss_last"] = (
        first_loss, last_loss)
    report["loss_ok"] = bool(np.isfinite(last_loss)) and last_loss < first_loss
    platform = jax.default_backend()
    strays = [name for name, leaf in
              _named_leaves(state) + _named_leaves(algo.data)
              if isinstance(leaf, jax.Array)
              and {d.platform for d in leaf.devices()} != {platform}]
    report["off_device"] = strays
    holders = len({s.device for s in algo.data.x_train.addressable_shards})
    report["cohort_devices"] = holders
    report["placement_ok"] = not strays and holders == chips
    report["ok"] = all(v for k, v in report.items() if k.endswith("_ok"))
    return report
