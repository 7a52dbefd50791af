"""Runs one cell once: build, warm up, measure, check, trace.

Everything here is driven by the cell's data files (``manifest.load_cell``).
The program is built through its own functions (``experiments.parse_args``,
``runner.build_algorithm``, ``runner.maybe_shard``) and driven through its
own round driver (``FedAlgorithm.run``); every flag the configuration and
the traffic mix do not name stays at its shipped default.
"""
from __future__ import annotations

import importlib
import json
import logging
import math
import os
import statistics
import time
import traceback

from . import check, cohort, flops, manifest, peaks, reduce_trace

TRACED_ROUNDS = 3
GIB = 2.0 ** 30
SETUP_SPANS = ("cohort", "build", "init_state", "reference_check", "warmup")


def program_flags(cell: manifest.Cell, seed: int) -> list:
    """The program's command line for this cell: the configuration's flags,
    the traffic's, and what the cell fixes (its sites, its chips, the seed).
    An unknown flag is the program's parser's error."""
    flags = {**cell.config["flags"], **cell.traffic["flags"],
             "client_num_in_total": cell.cohort["n_sites"],
             "mesh_devices": cell.chips, "seed": seed}
    argv = []
    for key, value in flags.items():
        argv += [f"--{key}", str(value)]
    return argv


def _memory(devices, key: str) -> int:
    """The largest ``key`` of ``memory_stats`` over ``devices`` (0 where the
    backend keeps none, as the CPU's)."""
    return max((d.memory_stats() or {}).get(key, 0) for d in devices)


def _compiles(registry) -> dict:
    """Totals of the program's ``CompileWatch``, and per entry point (the
    program's span open at the time)."""
    snap = registry.snapshot()
    backend = snap.get("compile_backend_s") or {}
    out = {"programs": _programs(registry),
           "compile_s": backend.get("value", {}).get("sum", 0.0),
           "compile_s_by_entry": {
               k: v.get("sum", 0.0)
               for k, v in backend.get("labeled", {}).items()}}
    for key in ("cache_hits", "cache_misses"):
        m = snap.get("compile_cache_" + key) or {}
        out[key] = m.get("value", 0.0)
        out[key + "_by_entry"] = m.get("labeled", {})
    return out


def _programs(registry) -> int:
    """Programs compiled or loaded from the cache so far."""
    return registry.distribution("compile_backend_s").count


def _losses(history) -> list:
    return [float(rec["train_loss"]) for rec in history]


def _bad_rounds(history, sampled: int) -> int:
    """Rounds of ``history`` that count as failed: a train loss that is not
    finite, or every sampled client quarantined by the guard."""
    return sum(1 for rec in history
               if not math.isfinite(float(rec["train_loss"]))
               or rec.get("clients_quarantined", 0) >= sampled)


def reference_of(cell: manifest.Cell):
    """The configuration's plain reference, found by name."""
    return importlib.import_module(
        "benchmarks.reference." + cell.config["reference"])


def build(cell: manifest.Cell, args, seed: int):
    """The cell's cohort on its chips and the algorithm over it, through the
    runner's own ``build_algorithm`` and ``maybe_shard``."""
    from jax.sharding import NamedSharding, PartitionSpec

    from neuroimagedisttraining_tpu.experiments import runner
    from neuroimagedisttraining_tpu.obs import trace as obs_trace
    from neuroimagedisttraining_tpu.parallel import make_mesh
    from neuroimagedisttraining_tpu.parallel.mesh import fit_client_devices

    n_mesh = fit_client_devices(cell.cohort["n_sites"], cell.chips)
    sharding = None
    if n_mesh > 1:  # made where the runner's own placement will put it
        sharding = NamedSharding(make_mesh(n_mesh), PartitionSpec("clients"))
    with obs_trace.span("cohort"):
        data = cohort.make_cohort(cell.cohort, cell.config["volume"],
                                  cell.config["stem"], seed, sharding)
    with obs_trace.span("build"):
        algo, _ = runner.build_algorithm(args, args.algo, data=data)
        runner.maybe_shard(algo, args)
    return algo


def measure(rounds, state, registry, seconds: float, block: int,
            sampled: int) -> tuple:
    """The window: whole blocks of ``block`` rounds until ``seconds`` have
    passed, each ending in the round driver's own flush. A block that
    compiles or raises counts whole as failed, and one that raises ends the
    window; nothing is caught and continued. The rate is taken from the
    median block: every block is the same seeded work, and the one-chip
    machine's host stalls now and then (PR 22: one block of 5.0 s among
    2.339 s ones), which a mean over the window would read as a slower
    program."""
    w = {"attempted": 0, "failed": 0, "crashed": False, "compiles": 0,
         "train_loss": [], "block_seconds": [], "block_rounds": block}
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        before = _programs(registry)
        t_block = time.perf_counter()
        w["attempted"] += block
        try:
            state, hist = rounds(block, state)
        except Exception:
            traceback.print_exc()
            w["failed"] += block
            w["crashed"] = True
            break
        w["block_seconds"].append(time.perf_counter() - t_block)
        w["train_loss"] += _losses(hist)
        compiled = _programs(registry) - before
        w["compiles"] += compiled
        w["failed"] += block if compiled else _bad_rounds(hist, sampled)
    w["seconds"] = time.perf_counter() - start
    w["rounds"] = len(w["train_loss"])
    w["rounds_per_s"] = block / statistics.median(w["block_seconds"]) \
        if w["block_seconds"] else 0.0
    return state, w


def traced_metrics(cell, algo, rounds, state, hlo: str, trace_dir: str,
                   counters: dict, details: dict) -> dict:
    """A few more steady rounds under the profiler, reduced to the cell's
    per-layer metrics by their readers. Returns what a traced result adds:
    ``metrics``, ``breakdown`` and the device's busy and window seconds."""
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # the program's spans are enough
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("traced_rounds"):
            rounds(TRACED_ROUNDS, state)
    finally:
        jax.profiler.stop_trace()
    steps = algo.hp.local_steps * algo.clients_per_round
    tr = reduce_trace.load(trace_dir, devices=cell.chips,
                           rounds=TRACED_ROUNDS,
                           op_names=reduce_trace.hlo_op_names(hlo))
    ctx = {
        "trace": tr,
        "counters": {
            **counters, "chips": cell.chips,
            "steps_per_round_per_chip": steps / cell.chips,
            "samples_per_round": steps * algo.hp.batch_size,
            "samples_per_s_per_chip": counters["rounds_per_s"] * steps
            * algo.hp.batch_size / cell.chips},
        "layers": reference_of(cell).layers(cell.config["volume"]),
        "batch": algo.hp.batch_size,
        "itemsize": jnp.dtype(algo.compute_dtype or jnp.float32).itemsize,
        "peaks": peaks.peaks_for(jax.devices()[0].device_kind),
        "details": details.setdefault("readers", {}),
    }
    details["flops_per_sample"] = flops.train_flops_per_sample(ctx["layers"])
    metrics = {}
    for entry, spec in cell.per_layer:
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return {"metrics": metrics, "busy_s": tr.busy_s, "window_s": tr.window_s,
            "breakdown": {"device_ops": tr.top_ops(10),
                          "idle_gaps": tr.top_gaps(10)}}


def run_cell(manifest_path: str, name: str, seed: int, seconds: float,
             trace: bool, t0: float, trace_dir: str) -> tuple:
    """One run of cell ``name``. Returns the contract's result object and a
    dictionary of details for the earlier lines and ``out/<cell>.json``.
    ``t0`` is the ``time.perf_counter()`` of process start."""
    import jax

    from neuroimagedisttraining_tpu.experiments import parse_args
    from neuroimagedisttraining_tpu.obs import (
        compile as obs_compile,
        metrics as obs_metrics,
        trace as obs_trace,
    )

    cell = manifest.load_cell(manifest_path, name)
    devices = jax.devices()
    if len(devices) < cell.chips:
        raise SystemExit(f"cell {name} needs {cell.chips} chip(s); JAX "
                         f"finds {len(devices)}: {devices}")
    devices = devices[:cell.chips]
    logging.getLogger("absl").setLevel(logging.WARNING)
    # the program's spans name the entry point a compile belongs to, and
    # ride into the profiler's trace as annotations
    tracer = obs_trace.Tracer(annotate=True)
    obs_trace.set_tracer(tracer)
    registry = obs_metrics.MetricsRegistry()
    watch = obs_compile.CompileWatch(registry).install()
    details = {"cell": name, "seed": seed, "argv": program_flags(cell, seed)}
    try:
        # -- set-up: cohort, build, init, reference check, warm-up --------
        args = parse_args(details["argv"])
        algo = build(cell, args, seed)
        jax.block_until_ready(algo.data.x_train)
        cohort_bytes = _memory(devices, "bytes_in_use")
        with obs_trace.span("init_state"):
            state = algo.init_state(jax.random.PRNGKey(seed))
        with obs_trace.span("reference_check"):
            details["reference_check"] = check.reference_check(
                algo, state.global_params, reference_of(cell), cell.config)
        eval_every = args.frequency_of_the_test

        def rounds(n, st):
            return algo.run(n, eval_every=eval_every, state=st,
                            finalize=False, fuse_rounds=args.fuse_rounds)

        # warm up every program the window will run: blocks of at least two
        # rounds (the incremental personal eval and, on a mesh, the second
        # compile of the round come in round 1) until one compiles nothing
        warm, warm_losses = max(2, eval_every, args.fuse_rounds), []
        with obs_trace.span("warmup"):
            while True:
                before = _programs(registry)
                state, hist = rounds(warm, state)
                warm_losses += _losses(hist)
                if _programs(registry) == before:
                    break
        jax.block_until_ready(state)
        setup_s = time.perf_counter() - t0
        details["setup"] = {
            **_compiles(registry), "setup_s": setup_s,
            "spans_s": {e["name"]: e["dur"] / 1e6 for e in tracer.events
                        if e["name"] in SETUP_SPANS},
            "warmup_losses": warm_losses, "client_chunk": algo.client_chunk,
            "cohort_bytes": cohort_bytes}

        state, w = measure(rounds, state, registry, seconds,
                           cell.traffic["block_rounds"],
                           algo.clients_per_round)
        details["window"] = w
        peak = _memory(devices, "peak_bytes_in_use")
        details["memory_stats"] = [d.memory_stats() for d in devices]
        values = {"rounds_per_s": w["rounds_per_s"],
                  "peak_hbm_gib": peak / GIB, "setup_s": setup_s}
        result = {"correct": False, "attempted": w["attempted"],
                  "failed": w["failed"],
                  "metrics": {e["name"]: {"value": values[e["name"]],
                                          "unit": e["unit"]}
                              for e in cell.end_to_end},
                  "device": {"platform": devices[0].platform,
                             "kind": devices[0].device_kind,
                             "count": cell.chips, "memory_peak_bytes": peak}}
        if w["crashed"]:
            return result, details

        # -- after the window: the state, and on several chips the program -
        report = details["state_check"] = check.state_check(
            algo, state, cell.chips, warm_losses[0], w["train_loss"][-1])
        hlo = None
        if trace or cell.chips > 1:
            compiled = _round_program(algo, state)
            hlo = compiled.as_text()
            mem = compiled.memory_analysis()
            details["round_program_bytes"] = {
                k: getattr(mem, k + "_size_in_bytes")
                for k in ("argument", "temp", "output", "alias")}
        if cell.chips > 1:
            n = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
            report["round_all_reduces"] = n
            report["ok"] = report["ok"] and n > 0
        result["correct"] = bool(details["reference_check"]["ok"]
                                 and report["ok"])
        if trace:
            counters = {k: v for k, v in details["setup"].items()
                        if isinstance(v, (int, float))}
            counters["rounds_per_s"] = values["rounds_per_s"]
            traced = traced_metrics(cell, algo, rounds, state, hlo,
                                    trace_dir, counters, details)
            result["metrics"] = traced["metrics"]
            result["breakdown"] = traced["breakdown"]
            result["device"].update(busy_s=traced["busy_s"],
                                    window_s=traced["window_s"])
        return result, details
    finally:
        watch.uninstall()
        obs_trace.set_tracer(None)


def _round_program(algo, state):
    """The round program as it is compiled for ``state`` (a cache hit after
    the warm-up). The ``op_name`` metadata of its optimised HLO places each
    instruction under the program's ``named_scope``s, and its memory analysis
    holds the temporaries, which the allocator's watermark leaves out."""
    import jax.numpy as jnp

    d = algo.data
    extra = (d.x_test, d.y_test, d.n_test) if algo.eval_cache else ()
    sel = jnp.arange(algo.clients_per_round, dtype=jnp.int32)
    return algo._round_jit.lower(
        state, sel, jnp.asarray(0, jnp.float32), d.x_train, d.y_train,
        d.n_train, *extra).compile()


def write_details(path: str, result: dict, details: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"result": result, **details}, f, indent=1)
