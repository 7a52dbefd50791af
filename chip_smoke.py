"""chip_smoke.py: the flagship federated round on the attached TPU, end to end.

The quickest proof that the system still starts on the chip. One process,
through the entry points a user calls (``experiments.parse_args`` +
``run_experiment``), every flag not named below at its shipped default:

1. write a small real-shape ABCD cohort (8 sites x 12-16 volumes of
   121x145x121, planted label signal) with ``data.abcd.write_abcd_h5``;
2. SalientGrads on it: ``--model 3dcnn --layout s2d --compute_dtype
   bfloat16``, SNIP mask pass, 3 rounds, eval (global + personal halves)
   and an orbax checkpoint every round;
3. resume that checkpoint lineage through one fused 2-round block
   (``--fuse_rounds 2``), the other round driver;
4. the phased bf16 model against the dense f32 reference model on two
   cohort volumes, same weights (``ops.s2d.convert_alexnet3d_params``).

It fails unless JAX's default backend is a TPU, every array of the cohort
and of the final state lives on TPU devices, the cohort is spread over as
many devices as the runner's default placement promises, losses and
accuracies are finite, the train loss falls, and no checkpoint save failed.
Any phase that raises ends the run with a non-zero exit code; nothing is
caught and passed over. The last line of standard output is
``{"ok": true, "device": {...}}`` with the device as JAX reports it; the
line before it carries the set-up and per-round seconds and the
persistent-compile-cache counters.

    python chip_smoke.py        # on a TPU host; exits non-zero anywhere else
"""
from __future__ import annotations

import json
import logging
import os
import shutil
import sys
import tempfile
import time

VOLUME = (121, 145, 121)   # the ABCD volume (data/abcd.py:ABCD_VOLUME_SHAPE)
SITE_SIZES = (16, 14, 12, 16, 13, 15, 12, 16)  # volumes per site, uneven
BATCH = 8
ROUNDS = 3
FUSE = 2


def write_cohort(path, volume=VOLUME, site_sizes=SITE_SIZES, seed=0):
    """Seeded real-shape cohort with a planted sex signal (the recipe of
    tests/test_abcd_disk_e2e.py), so training has a gradient to follow."""
    import numpy as np

    from neuroimagedisttraining_tpu.data.abcd import write_abcd_h5

    rng = np.random.default_rng(seed)
    n = sum(site_sizes)
    y = rng.integers(0, 2, size=n)
    X = rng.random((n,) + tuple(volume), dtype=np.float32) * 0.1
    X += 0.2 * y[:, None, None, None].astype(np.float32)
    site = np.repeat(np.arange(len(site_sizes)), site_sizes)
    write_abcd_h5(path, X, y, site)
    return X[:2]


def _require(cond, msg):
    if not cond:
        raise SystemExit(f"chip_smoke: FAIL: {msg}")


EVAL_KEYS = ("global_acc", "global_loss", "personal_acc", "personal_loss")


def _finite_record(rec, where, keys=("train_loss",) + EVAL_KEYS):
    import numpy as np

    for key in keys:
        _require(key in rec, f"{where}: record has no {key!r}: {rec}")
        _require(np.isfinite(float(rec[key])),
                 f"{where}: {key} is not finite: {rec[key]}")


def _assert_placed(tree, platform, what):
    """Every array leaf of ``tree`` lives on ``platform`` devices only."""
    import jax

    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if not isinstance(leaf, jax.Array):
            continue
        off = {d.platform for d in leaf.devices()} - {platform}
        _require(not off, f"{what}{jax.tree_util.keystr(path)} lives on "
                 f"{sorted(off)}, not on {platform}")


def _require_saves_ok(out, where):
    """The runner counts a failed orbax save and carries on
    (utils/checkpoint.py); here one failed save fails the run."""
    with open(out["stat_path"] + ".json") as f:
        recovery = json.load(f)["fault_recovery"]
    _require(recovery["checkpoint_save_failures"] == 0.0,
             f"{where}: checkpoint saves failed: {recovery}")


def _span_starts(tracer, name):
    """Start times (s, tracer clock) of the spans called ``name``."""
    return [ev["ts"] / 1e6 for ev in tracer.events if ev["name"] == name]


def _compile_report(registry):
    """Persistent-cache counters and backend-compile seconds, total and
    per entry point (the obs span open when the compile fired)."""
    snap = registry.snapshot()
    out = {}
    for k in ("cache_hits", "cache_misses", "compile_requests_use_cache"):
        m = snap.get("compile_cache_" + k) or {}
        out[k] = {"total": m.get("value", 0.0), **m.get("labeled", {})}
    backend = snap.get("compile_backend_s") or {}
    return {
        "compile_cache": out,
        "compile_backend_s": {
            "sum": round(backend.get("value", {}).get("sum", 0.0), 2),
            **{k: round(v.get("sum", 0.0), 2)
               for k, v in backend.get("labeled", {}).items()}},
    }


def model_parity(volume, x):
    """Logits of the phased (s2d) model in bf16 against the dense f32
    reference model on the volumes ``x``, same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuroimagedisttraining_tpu.models import (
        create_model,
        init_params,
        make_apply_fn,
    )
    from neuroimagedisttraining_tpu.ops.s2d import (
        convert_alexnet3d_params,
        phase_decompose,
    )

    dense = create_model("3dcnn", num_classes=1)
    p_dense = init_params(dense, jax.random.PRNGKey(0),
                          tuple(volume) + (1,))
    ref = jax.jit(lambda p, v: make_apply_fn(dense)(
        p, v, train=False, rng=None))(p_dense, jnp.asarray(x[..., None]))
    got = jax.jit(lambda p, v: make_apply_fn(
        create_model("3dcnn_s2d", num_classes=1),
        compute_dtype=jnp.bfloat16)(p, v, train=False, rng=None))(
            convert_alexnet3d_params(p_dense),
            jnp.asarray(phase_decompose(x)))
    ref, got = np.asarray(ref), np.asarray(got)
    _require(ref.shape == got.shape == (len(x), 1),
             f"logit shapes {ref.shape} vs {got.shape}")
    err = float(np.max(np.abs(ref - got)))
    # bf16 convs/matmuls with f32 accumulation against an f32 reference:
    # 8 mantissa bits through 7 layers; logits are O(0.1-1)
    tol = 5e-2 * max(1.0, float(np.max(np.abs(ref))))
    _require(np.isfinite(got).all() and err <= tol,
             f"phased bf16 logits {got.ravel()} vs dense f32 "
             f"{ref.ravel()}: max abs err {err} > {tol}")
    return {"max_abs_err": err, "tol": tol, "ref": ref.ravel().tolist()}


def run(workdir, volume=VOLUME, site_sizes=SITE_SIZES, batch=BATCH,
        extra_args=()):
    """Phases 1-4 under ``workdir``; returns the report dict. ``main``
    calls it at full size; a debugging harness may call it smaller."""
    from neuroimagedisttraining_tpu.obs import (
        compile as obs_compile,
        metrics as obs_metrics,
        trace as obs_trace,
    )
    from neuroimagedisttraining_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    # the runner turns console logging up to INFO; orbax's per-save
    # chatter would push the phase results out of the captured tail
    logging.getLogger("absl").setLevel(logging.WARNING)
    report = {"cache_dir": configure_compile_cache()}
    registry = obs_metrics.MetricsRegistry()
    watch = obs_compile.CompileWatch(registry).install()
    try:
        _phases(workdir, volume, site_sizes, batch, extra_args, report)
    finally:
        watch.uninstall()
        obs_trace.set_tracer(None)
    report.update(_compile_report(registry))
    return report


def _phases(workdir, volume, site_sizes, batch, extra_args, report):
    import jax

    from neuroimagedisttraining_tpu.experiments import (
        parse_args,
        run_experiment,
    )
    from neuroimagedisttraining_tpu.obs import (
        memory as obs_memory,
        trace as obs_trace,
    )
    from neuroimagedisttraining_tpu.parallel.mesh import fit_client_devices

    platform = jax.default_backend()
    tracer = obs_trace.Tracer(annotate=False)
    obs_trace.set_tracer(tracer)
    t_origin = time.perf_counter()

    def now():  # seconds on the tracer's clock (its origin = construction)
        return time.perf_counter() - t_origin

    cohort = os.path.join(workdir, "final_dataset_smoke.h5")
    first_two = write_cohort(cohort, volume, site_sizes)
    report["cohort_write_s"] = round(now(), 2)
    n_sites = len(site_sizes)

    common = [
        "--algo", "salientgrads", "--model", "3dcnn",
        "--dataset", "abcd_site", "--data_dir", cohort,
        "--client_num_in_total", "0", "--layout", "s2d",
        "--compute_dtype", "bfloat16", "--batch_size", str(batch),
        "--frac", "1.0", "--dense_ratio", "0.5",
        "--frequency_of_the_test", "1",
        "--checkpoint_dir", os.path.join(workdir, "ckpt"),
        "--results_dir", os.path.join(workdir, "results"),
        "--log_dir", os.path.join(workdir, "log"),
        *extra_args,
    ]

    # -- phase 2: SNIP + ROUNDS python-loop rounds, eval + checkpoint each
    t_run = now()
    out = run_experiment(parse_args(common + ["--comm_round", str(ROUNDS)]))
    t_done = now()
    rounds = [h for h in out["history"] if h["round"] >= 0]
    _require([h["round"] for h in rounds] == list(range(ROUNDS)),
             f"expected rounds 0..{ROUNDS - 1}, got {out['history']}")
    for h in rounds:
        _finite_record(h, f"round {h['round']}")
    _finite_record(out["final_eval"], "final eval", EVAL_KEYS)
    losses = [float(h["train_loss"]) for h in rounds]
    _require(losses[-1] < losses[0],
             f"train loss did not fall over {ROUNDS} rounds: {losses}")

    algo, state = out["algo"], out["state"]
    _assert_placed(algo.data, platform, "algo.data")
    _assert_placed(state, platform, "state")
    n_visible = len(jax.devices())
    want = fit_client_devices(n_sites, n_visible)
    holders = {s.device for s in algo.data.x_train.addressable_shards}
    _require(len(holders) == want,
             f"x_train shards sit on {len(holders)} device(s); the runner's "
             f"default placement over {n_visible} visible device(s) is "
             f"{want}")
    gp_leaf = jax.tree_util.tree_leaves(state.global_params)[0]
    _require(gp_leaf.is_fully_replicated,
             f"global_params is not replicated: {gp_leaf.sharding}")
    report["placement"] = {
        "client_chunk": algo.client_chunk,  # the runner's --client_chunk 0
        "x_train_devices": len(holders),
        "x_train_sharding": str(algo.data.x_train.sharding),
        "x_train_shard_shape": list(
            algo.data.x_train.addressable_shards[0].data.shape),
        "global_params_sharding": str(gp_leaf.sharding),
        "device_memory": obs_memory.device_memory(),
    }
    if want > 1:
        # the aggregation must be a cross-device reduce in the compiled
        # round program, not a gather to one device
        import jax.numpy as jnp

        d = algo.data
        hlo = algo._round_jit.lower(
            state, jnp.arange(n_sites, dtype=jnp.int32),
            jnp.asarray(0, jnp.float32), d.x_train, d.y_train,
            d.n_train).compile().as_text()
        report["placement"]["round_hlo_all_reduces"] = hlo.count(
            "all-reduce(") + hlo.count("all-reduce-start(")
        _require(report["placement"]["round_hlo_all_reduces"] > 0,
                 "no all-reduce in the compiled round program")

    _require_saves_ok(out, "loop")
    ident_dirs = os.listdir(os.path.join(workdir, "ckpt"))
    _require(len(ident_dirs) == 1, f"checkpoint lineages: {ident_dirs}")
    steps = sorted(int(s) for s in os.listdir(
        os.path.join(workdir, "ckpt", ident_dirs[0])) if s.isdigit())
    _require(steps and steps[-1] == ROUNDS,
             f"no orbax step {ROUNDS} on disk: {steps}")

    starts = _span_starts(tracer, "round")
    loop_end = _span_starts(tracer, "finalize")[0]
    edges = starts + [loop_end]
    report["loop"] = {
        # set-up: cohort read, build, init, the SNIP pass and its compile
        "setup_to_round0_dispatch_s": round(starts[0] - t_run, 2),
        # compile of the round and both eval programs lands in round 0
        "round_s": [round(b - a, 3) for a, b in zip(edges, edges[1:])],
        "time_to_first_round_done_s": round(edges[1] - t_run, 2),
        "total_s": round(t_done - t_run, 2),
        "train_loss": losses,
        "global_acc": [float(h["global_acc"]) for h in rounds],
        "personal_acc": [float(h["personal_acc"]) for h in rounds],
    }
    del out, algo, state, rounds  # free the cohort before the second run

    # -- phase 3: the same lineage through one fused block ---------------
    n_before = len(tracer.events)
    t_run = now()
    out = run_experiment(parse_args(
        common + ["--comm_round", str(ROUNDS + FUSE), "--resume",
                  "--fuse_rounds", str(FUSE)]))
    t_done = now()
    fused = [h for h in out["history"] if h["round"] >= 0]
    _require([h["round"] for h in fused] ==
             list(range(ROUNDS, ROUNDS + FUSE)),
             f"fused block should cover rounds {ROUNDS}.."
             f"{ROUNDS + FUSE - 1}: {out['history']}")
    for h in fused:
        _finite_record(h, f"fused round {h['round']}")
    _assert_placed(out["state"], platform, "fused state")
    _assert_placed(out["algo"].data, platform, "fused algo.data")
    _require_saves_ok(out, "fused")
    report["fused"] = {
        "total_s": round(t_done - t_run, 2),
        "spans_s": {ev["name"]: round(ev["dur"] / 1e6, 3)
                    for ev in tracer.events[n_before:]
                    if ev["name"].startswith("fused_block")},
        "train_loss": [float(h["train_loss"]) for h in fused],
        "global_acc": [float(h["global_acc"]) for h in fused],
    }
    del out, fused

    # -- phase 4: phased bf16 model vs the dense f32 reference -----------
    report["parity_bf16_s2d_vs_f32_dense"] = model_parity(volume, first_two)
    report["total_s"] = round(now(), 2)


def main() -> int:
    try:
        import jax
        import jaxlib

        import neuroimagedisttraining_tpu  # noqa: F401  (the program)
    except ImportError as e:
        print(f"chip_smoke: FAIL: cannot import the program: {e}",
              file=sys.stderr)
        return 1
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: FAIL: no TPU: jax.default_backend() is "
              f"{backend!r} ({jax.devices()}); this check runs on the chip "
              "only", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # wheel named otherwise
        libtpu = "unknown"
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
          f"count: {device['count']}  jax {jax.__version__}  jaxlib "
          f"{jaxlib.__version__}  libtpu {libtpu}", flush=True)

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        report = run(workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    report["device"] = device
    print(json.dumps({"chip_smoke_report": report}), flush=True)
    out_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke_report.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
