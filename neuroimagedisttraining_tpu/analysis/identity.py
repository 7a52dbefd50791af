"""Identity-inertness gate: the flag registry vs ``run_identity``.

The run-identity string is the experiment tracking key, the log
filename, and (via ``for_checkpoint``) the checkpoint-lineage key
(``experiments/config.py:run_identity``). Two standing contracts hang
off it:

* telemetry never forks lineage — no ``--obs_*`` / ``--flight_*`` /
  ``--slo_*`` flag may enter the identity string (obs is bit-inert by
  construction, so an obs ablation must resume / compare against the
  same lineage);
* every behavior-splitting flag that *should* key the lineage does —
  the r5 ``track_personal`` and the topk-residual migrations were both
  "a flag changed state structure, the identity must split" events
  caught by hand.

This analyzer enforces both **statically**: it parses the flag registry
(every ``add_argument``/``_add_once`` site) and the set of ``args``
attributes ``run_identity`` actually reads (including the
``_IDENTITY_EXTRAS`` table), then cross-references against the
:data:`FLAG_CLASSES` classification:

* ``identity`` — must be read by ``run_identity`` (drift = finding);
* ``inert`` — must NOT be read (leak = finding): telemetry, logging,
  runtime-placement, and scheduling-only knobs whose on/off is
  bit-identical or output-only;
* ``unkeyed`` — training-affecting but deliberately outside the
  identity string (reference CLI parity: the reference's identity
  string doesn't key them either, so sweeps over them need ``--tag``).
  Must NOT be read; promoting one to identity means moving it to
  ``identity`` here *and* adding it to ``run_identity`` in the same
  commit.

A flag in no bucket fails the gate: every new flag must be classified
at birth. The hard rule — obs/flight prefixes never identity-bearing —
is enforced regardless of the table, so a misedited table cannot
authorize a telemetry leak.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from .findings import Finding

#: flag-name prefixes that are telemetry by contract: never identity
INERT_PREFIXES = ("obs", "flight", "slo")

#: flag -> (class, one-line reason). Classes: identity | inert | unkeyed.
FLAG_CLASSES: Dict[str, Tuple[str, str]] = {
    # -- identity-bearing (read by run_identity) ---------------------------
    "algo": ("identity", "leading identity component"),
    "dataset": ("identity", "identity component"),
    "model": ("identity", "identity component"),
    "client_num_in_total": ("identity", "c<N> component"),
    "frac": ("identity", "frac<f> component"),
    "comm_round": ("identity", "r<N> (log identity only; checkpoint "
                               "identity drops it for resume-with-"
                               "larger-budget)"),
    "epochs": ("identity", "e<N> component"),
    "batch_size": ("identity", "bs<N> component"),
    "lr": ("identity", "lr<f> component"),
    "seed": ("identity", "seed<N> component"),
    "dense_ratio": ("identity", "algo extra (_IDENTITY_EXTRAS)"),
    "itersnip_iteration": ("identity", "algo extra (_IDENTITY_EXTRAS)"),
    "cs": ("identity", "algo extra (_IDENTITY_EXTRAS)"),
    "active": ("identity", "algo extra (_IDENTITY_EXTRAS)"),
    "anneal_factor": ("identity", "algo extra (_IDENTITY_EXTRAS)"),
    "each_prune_ratio": ("identity", "algo extra (_IDENTITY_EXTRAS)"),
    "lamda": ("identity", "algo extra (_IDENTITY_EXTRAS)"),
    "n_groups": ("identity", "algo extra (_IDENTITY_EXTRAS)"),
    "stratified_sampling": ("identity", "strat-<mode> lineage split"),
    "stratified_mode": ("identity", "strat-<mode> lineage split"),
    "defense_type": ("identity", "def<type> lineage split"),
    "norm_bound": ("identity", "defense nb<f> component"),
    "stddev": ("identity", "weak-DP sd<f> component"),
    "robust_agg": ("identity", "ragg<kind> — the robust statistic "
                               "replaces the weighted mean, splits "
                               "both lineages"),
    "robust_trim": ("identity", "rtrim<f> trimmed_mean component"),
    "robust_krum_f": ("identity", "rkf<n> krum-family component"),
    "fault_spec": ("identity", "flt... — injection changes the state "
                               "trajectory, splits both lineages"),
    "watchdog": ("identity", "wd... — retries change the trajectory"),
    "watchdog_loss": ("identity", "watchdog threshold in wd..."),
    "watchdog_norm": ("identity", "watchdog threshold in wd..."),
    "max_round_retries": ("identity", "watchdog retry budget in wd..."),
    "batching": ("identity", "'wr' metric-lineage split (checkpoint "
                             "state interchangeable)"),
    "augment": ("identity", "'noaug' metric-lineage split"),
    "eval_clients": ("identity", "evK<N> metric-protocol split"),
    "agg_impl": ("identity", "agg<impl> numerics split (topk also "
                             "splits checkpoints via the residual)"),
    "agg_hier_wire": ("identity", "hw<wire> numerics split"),
    "agg_hier_inner": ("identity", "hi<N> numerics split"),
    "agg_topk_density": ("identity", "tk<d> both-lineage split "
                                     "(residual is trajectory)"),
    "agg_topk_sample": ("identity", "tks<N> both-lineage split"),
    "data_dtype": ("identity", "dt<dtype> numerics split"),
    "final_finetune": ("identity", "'noft' protocol split"),
    "track_personal": ("identity", "'nopers' state-structure split"),
    "eval_cache": ("identity", "'evcache' state-structure + eval-"
                               "protocol split (r5/topk pattern)"),
    "lm_layers": ("identity", "lm<L>e<E>t<T>: a chip's share of a "
                              "decoder model is another model"),
    "lm_expert_shards": ("identity", "lm...e<E>: experts held"),
    "lm_tensor_shards": ("identity", "lm...t<T>: heads and vocabulary "
                                     "rows held"),
    "lm_vocab_shards": ("identity", "v<V> after lm...t<T>: vocabulary rows "
                                    "held where heads divide otherwise"),
    "lm_ssm_shards": ("identity", "s<S> after lm...t<T>: state-space heads "
                                  "and groups held"),
    "lm_mlp_shards": ("identity", "m<M> after lm...t<T>: a dense MLP's "
                                  "columns held"),
    "lm_first_layer": ("identity", "f<F> after lm...t<T>: the stage's "
                                   "first layer"),
    "global_test": ("identity", "'-g' reference-parity tag"),
    "tag": ("identity", "explicit identity suffix"),
    # -- inert (telemetry / logging / placement / scheduling-only) ---------
    "obs": ("inert", "telemetry never forks lineage (bit-inert off/on)"),
    "obs_jsonl": ("inert", "telemetry output path"),
    "obs_sample_every": ("inert", "telemetry cadence"),
    "obs_tb_dir": ("inert", "telemetry output path"),
    "obs_numerics": ("inert", "in-jit telemetry, pure readout"),
    "obs_comm": ("inert", "comm telemetry, pure readout"),
    "obs_catalog": ("inert", "fleet run-catalog append at session "
                             "close, pure readout"),
    "slo_spec": ("inert", "online SLO evaluation, pure readout over "
                          "flushed records (bit-inert off, trajectory-"
                          "identical on)"),
    "slo_enforce": ("inert", "exit-code verdict only — never touches "
                             "state or records"),
    "flight_recorder": ("inert", "post-mortem capture, pure readout"),
    "flight_window": ("inert", "flight-recorder window size"),
    "flight_profile": ("inert", "flight-recorder profiler capture"),
    "trace_dir": ("inert", "host span trace output path"),
    "profile_dir": ("inert", "XLA profiler output path"),
    "log_dir": ("inert", "log output path"),
    "logfile": ("inert", "log filename override"),
    "results_dir": ("inert", "stat_info output path"),
    "checkpoint_dir": ("inert", "checkpoint location, not lineage key"),
    "resume": ("inert", "resume switch; lineage decides identity"),
    "data_dir": ("inert", "dataset root path"),
    "frequency_of_the_test": ("inert", "eval cadence changes which "
                                       "rounds record eval, not state"),
    "ci": ("inert", "smoke-mode round clamp for CI"),
    "gpu": ("inert", "reference CLI compat, inert here"),
    "type": ("inert", "reference CLI compat, dead in reference too"),
    "client_chunk": ("inert", "HBM chunking, bit-identical math"),
    "fuse_rounds": ("inert", "fused==unfused is bit-pinned "
                             "(tests/test_fused_rounds.py)"),
    "agg_bucket_size": ("inert", "bucketing is exact off-mesh and "
                                 "association-only on-mesh (pinned)"),
    "agg_overlap": ("inert", "scheduling freedom only, bit-identical "
                             "per bucket (pinned)"),
    "agg_kernels": ("inert", "xla-vs-pallas kernel backend — bit-exact "
                             "by the tie-break contract (ops/"
                             "topk_select.py: every backend converges "
                             "to the same integer threshold fixed "
                             "point; the fused quantize+reduce shares "
                             "the XLA chain's rng/scale/dot spelling; "
                             "tests/test_pallas_kernels.py pins "
                             "pallas==xla bitwise)"),
    "retry_backoff_s": ("inert", "timing only, never state"),
    "multihost_timeout_s": ("inert", "init handshake timing"),
    "multihost_retries": ("inert", "init handshake retries"),
    "multihost": ("inert", "process-placement switch"),
    "coordinator_address": ("inert", "process placement"),
    "num_processes": ("inert", "process placement"),
    "process_id": ("inert", "process placement"),
    "mesh_devices": ("inert", "device placement, bit-identical math"),
    "mesh_space": ("inert", "spatial sharding placement"),
    "remat": ("inert", "rematerialization trades FLOPs for HBM, "
                       "bit-identical results"),
    "donate_state": ("inert", "buffer aliasing only — bit-identical "
                              "outputs (tests/test_donation.py pins "
                              "donated==undonated)"),
    "client_store": ("inert", "row residency only — streamed cohorts "
                              "are bit-identical to device residency "
                              "(tests/test_client_store.py pins "
                              "resident==streamed)"),
    "store_hot_clients": ("inert", "host LRU capacity — residency/"
                                   "eviction knob, never values"),
    # federated deployment (fed/): the MODE and its policy knobs change
    # the trained model; the role/topology/timing knobs name where the
    # same computation runs
    "fed_mode": ("identity", "sync-vs-buffered changes the aggregation "
                             "policy and hence the trained model"),
    "fed_sites": ("identity", "the site partition shapes buffered "
                              "deltas (and the deployment lineage)"),
    "fed_buffer_k": ("identity", "FedBuff flush depth — which deltas "
                                 "average together"),
    "fed_staleness_bound": ("identity", "which late deltas fold vs "
                                        "drop — changes the model"),
    "fed_replay": ("identity", "pinned arrival order IS the buffered "
                               "trajectory"),
    "fed_site_faults": ("identity", "real-process drops/straggles "
                                    "change which deltas exist"),
    "fed_role": ("inert", "names WHICH process this is, not what the "
                          "federation computes"),
    "fed_backend": ("inert", "transport choice; the wire is "
                             "bit-transparent (tests/test_fed_wire.py)"),
    "fed_site_rank": ("inert", "process placement"),
    "fed_endpoints": ("inert", "process placement"),
    "fed_timeout_s": ("inert", "wall-clock degradation budget — "
                               "timing, not policy"),
    "fed_retries": ("inert", "send retry budget, timing only"),
    "fed_backoff_s": ("inert", "send retry backoff, timing only"),
    "fed_trace": ("inert", "trace output path"),
    "fed_out": ("inert", "federation output path"),
    # serving plane (serve/): ALL serve_* flags are inert — serving
    # reads trained models, it never enters the training computation
    # (the fed_role precedent: names WHICH process this is)
    "serve_role": ("inert", "names WHICH serving process this is; "
                            "serving never trains"),
    "serve_backend": ("inert", "transport choice; the push wire is "
                               "bit-transparent "
                               "(tests/test_serve_push.py)"),
    "serve_endpoints": ("inert", "process placement"),
    "serve_requests": ("inert", "synthetic load volume — read-only "
                                "inference traffic"),
    "serve_rps": ("inert", "open-loop traffic rate, timing only"),
    "serve_batch": ("inert", "micro-batch slab width — inference "
                             "batching, never values"),
    "serve_linger_ms": ("inert", "batch coalescing window, timing "
                                 "only"),
    "serve_zipf": ("inert", "traffic popularity skew — load shape, "
                            "read-only"),
    "serve_wire": ("inert", "push codec; reconstruction is "
                            "bit-identical to the disk checkpoint by "
                            "the shared-decode contract"),
    "serve_push_every": ("inert", "push cadence — staleness/timing, "
                                  "not what gets trained"),
    "serve_ckpt_dir": ("inert", "servable checkpoint output path"),
    "serve_out": ("inert", "serving output path"),
    "serve_trace": ("inert", "request trace output path"),
    "serve_replay": ("inert", "replays a request stream — inference "
                              "inputs, not training"),
    "serve_store": ("inert", "row residency only — the client_store "
                             "precedent, resident==streamed"),
    "serve_timeout_s": ("inert", "drain/ack wait budget, timing only"),
    "serve_probe_every": ("inert", "read-only eval probe on the "
                                   "serving worker — telemetry, "
                                   "never training"),
    "serve_workers": ("inert", "checkpoint fan-out width — every "
                               "subscriber adopts the SAME encoded "
                               "pushes; the trained model never "
                               "changes"),
    # cross-process distributed tracing (obs/xtrace.py): pure
    # telemetry — tracing off is byte-inert on every wire, tracing on
    # adds control-plane headers the decode path ignores
    "xtrace": ("inert", "span telemetry + clock-sync frames; decode "
                        "ignores the headers, payloads untouched "
                        "(tests/test_xtrace.py pins the roundtrip)"),
    "xtrace_dir": ("inert", "trace stream output path"),
    # live fleet telemetry (obs/live.py, obs/prom.py): heartbeats off
    # is byte-inert on every wire; on adds hb_* control-plane headers
    # the decode path ignores (the xtrace gating precedent)
    "obs_heartbeat_every": ("inert", "liveness frames + hb_* headers; "
                                     "decode ignores them, payloads "
                                     "untouched (tests/test_live.py "
                                     "pins the transparency)"),
    "obs_prom_port": ("inert", "/metrics HTTP exposition — pure "
                               "readout of the registry snapshot"),
    "obs_watch_every": ("inert", "obs watch refresh cadence, "
                                 "tool-side only"),
    "obs_watch_color": ("inert", "obs watch ANSI rendering, "
                                 "tool-side only"),
    "save_masks": ("inert", "stat_info output only"),
    "record_mask_diff": ("inert", "stat_info output only"),
    "public_portion": ("inert", "inert in the reference too"),
    "strict_avg": ("inert", "inert in the reference too"),
    # -- unkeyed (training-affecting, deliberately outside the identity
    #    string — reference parity; sweeps over these use --tag) ----------
    "partition_method": ("unkeyed", "reference identity omits it"),
    "partition_alpha": ("unkeyed", "reference identity omits it"),
    "client_optimizer": ("unkeyed", "reference identity omits it"),
    "lr_decay": ("unkeyed", "reference identity omits it"),
    "momentum": ("unkeyed", "reference identity omits it"),
    "wd": ("unkeyed", "reference identity omits it"),
    "grad_clip": ("unkeyed", "reference identity omits it"),
    "layout": ("unkeyed", "storage layout, bit-compatible numerics "
                          "pinned by tests"),
    "compute_dtype": ("unkeyed", "mixed-precision ablations use --tag "
                                 "(candidate for promotion)"),
    "snip_mask": ("unkeyed", "dense-control ablation, reference "
                             "identity omits it (use --tag)"),
    "guard": ("unkeyed", "auto-follows fault_spec; bit-identical on "
                         "clean rounds — explicit --guard 0 chaos "
                         "ablations must use --tag (documented)"),
    "local_epochs": ("unkeyed", "ditto personal-leg epochs, reference "
                                "identity omits it"),
    "val_fraction": ("unkeyed", "fedfomo val split, reference "
                                "identity omits it"),
    "erk_power_scale": ("unkeyed", "dispfl mask init, reference "
                                   "identity omits it"),
    "dis_gradient_check": ("unkeyed", "dispfl variant switch, "
                                      "reference identity omits it"),
    "uniform": ("unkeyed", "dispfl sparsity layout, reference "
                           "identity omits it"),
    "different_initial": ("unkeyed", "dispfl mask init, reference "
                                     "identity omits it"),
    "diff_spa": ("unkeyed", "dispfl density cycling, reference "
                            "identity omits it"),
    "static": ("unkeyed", "dispfl frozen-mask mode, reference "
                          "identity omits it"),
    "dist_thresh": ("unkeyed", "subavg pruning threshold, reference "
                               "identity omits it"),
    "acc_thresh": ("unkeyed", "subavg pruning threshold, reference "
                              "identity omits it"),
}


def _config_path(pkg_root: str) -> str:
    return os.path.join(pkg_root, "experiments", "config.py")


def collect_flags(config_source: str) -> Dict[str, int]:
    """Every registered flag name -> first definition line, from
    ``add_argument``/``_add_once`` call sites."""
    tree = ast.parse(config_source)
    flags: Dict[str, int] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else "")
        if name not in ("add_argument", "_add_once"):
            continue
        for arg in node.args:
            if isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str) and \
                    arg.value.startswith("--"):
                flags.setdefault(arg.value[2:], node.lineno)
    return flags


def identity_reads(config_source: str) -> Dict[str, int]:
    """Flag names ``run_identity`` reads -> line: ``args.<name>``
    attribute loads, ``getattr(args, "<name>", ...)`` string constants,
    and the ``_IDENTITY_EXTRAS`` table values."""
    tree = ast.parse(config_source)
    reads: Dict[str, int] = {}
    fn = None
    extras = None
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and \
                node.name == "run_identity":
            fn = node
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and \
                        t.id == "_IDENTITY_EXTRAS":
                    extras = node.value
    if fn is None:
        raise ValueError("config source has no run_identity function")
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "args":
            reads.setdefault(node.attr, node.lineno)
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Name) and \
                node.func.id == "getattr" and len(node.args) >= 2 and \
                isinstance(node.args[0], ast.Name) and \
                node.args[0].id == "args" and \
                isinstance(node.args[1], ast.Constant):
            reads.setdefault(str(node.args[1].value), node.lineno)
    if extras is not None:
        # only the dict VALUES are flag names; the keys are algo names
        # (a future flag sharing an algo name must not read as "read")
        value_nodes = extras.values if isinstance(extras, ast.Dict) \
            else [extras]
        for value in value_nodes:
            for sub in ast.walk(value):
                if isinstance(sub, ast.Constant) and \
                        isinstance(sub.value, str) and \
                        sub.value.isidentifier():
                    reads.setdefault(sub.value, extras.lineno)
    return reads


def audit_config_source(
    config_source: str,
    classes: Optional[Dict[str, Tuple[str, str]]] = None,
    config_file: str = "neuroimagedisttraining_tpu/experiments/config.py",
) -> List[Finding]:
    """Cross-reference flags, identity reads, and the classification."""
    classes = FLAG_CLASSES if classes is None else classes
    flags = collect_flags(config_source)
    reads = identity_reads(config_source)
    out: List[Finding] = []

    def finding(rule: str, name: str, line: int, msg: str) -> Finding:
        return Finding(rule=rule, file=config_file, line=line,
                       detail=name, message=msg)

    for name, line in sorted(flags.items()):
        cls = classes.get(name, (None, ""))[0]
        read_line = reads.get(name)
        hard_inert = name.split("_")[0] in INERT_PREFIXES
        if hard_inert and read_line is not None:
            out.append(finding(
                "identity-leak", name, read_line,
                f"--{name}: telemetry flag read by run_identity — obs/"
                "flight flags never fork run or checkpoint lineage "
                "(the obs bit-inertness contract)"))
            continue
        if cls is None:
            out.append(finding(
                "identity-unclassified", name, line,
                f"--{name}: not classified in analysis.identity."
                "FLAG_CLASSES — every new flag declares at birth "
                "whether it keys the run identity (identity), is "
                "telemetry/placement (inert), or is deliberately "
                "unkeyed (reference parity, sweeps use --tag)"))
        elif cls == "identity" and read_line is None:
            out.append(finding(
                "identity-drift", name, line,
                f"--{name}: classified identity-bearing but "
                "run_identity never reads it — add it to the identity "
                "string or reclassify"))
        elif cls in ("inert", "unkeyed") and read_line is not None:
            out.append(finding(
                "identity-leak", name, read_line,
                f"--{name}: classified {cls} but run_identity reads "
                "it — either reclassify to identity or remove the "
                "read (an accidental lineage fork)"))
    # classification entries for flags that no longer exist rot the
    # table the same way stale baselines rot the baseline
    for name in sorted(classes):
        if name not in flags:
            out.append(finding(
                "identity-stale-class", name, 0,
                f"FLAG_CLASSES entry {name!r} matches no registered "
                "flag (flag removed? delete the entry)"))
    return out


def audit_package(pkg_root: str) -> List[Finding]:
    path = _config_path(pkg_root)
    with open(path) as f:
        src = f.read()
    pkg = os.path.basename(os.path.abspath(pkg_root))
    return audit_config_source(
        src, config_file=f"{pkg}/experiments/config.py")
