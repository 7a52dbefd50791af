"""Experiment runner: flags -> data -> model -> algorithm -> train loop.

The rebuild of the reference's per-algorithm ``main_<algo>.py`` wiring
(``main_sailentgrads.py:194-279``): seed, load data, create model, construct
the API object, ``.train()``. One runner serves all nine algorithms; the
per-algo mains are thin wrappers selecting the algorithm and its extra flags.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import pickle
import random
from typing import Any, Dict, Optional, Sequence

import numpy as np

from .config import parse_args, run_identity
from .logging_utils import add_run_file_logger, configure_console

logger = logging.getLogger(__name__)


def seed_everything(seed: int) -> None:
    """python/numpy seeding (main_sailentgrads.py:263-267; torch/cudnn
    determinism maps to JAX's deterministic-by-default PRNG keys)."""
    random.seed(seed)
    np.random.seed(seed)


# phased-stem twins of the reference models, with each stem's
# (kernel, pad) decomposition spec (ops/s2d.py)
S2D_TWINS = {"3dcnn": "3dcnn_s2d", "3dresnet": "3dresnet_s2d",
             "small3dcnn": "small3dcnn_s2d"}
S2D_SPECS = {"3dcnn_s2d": (5, 0), "3dresnet_s2d": (3, 3),
             "small3dcnn_s2d": (3, 1)}


def build_data(args: argparse.Namespace, client_filter=None):
    from ..data import load_federated_data

    kwargs: Dict[str, Any] = {}
    if args.dataset.lower() in ("synthetic", "abcd_synth"):
        # CI-scale default; real ABCD shapes come from the .h5 itself
        kwargs["sample_shape"] = (8, 8, 8, 1)
        kwargs["samples_per_client"] = max(args.batch_size, 16)
    elif args.dataset.lower() == "token_shards":
        kwargs["vocab"] = _decoder_share(args)[1]["vocab_size"]
        kwargs["sequence_length"] = 128     # the synthetic stand-in's
        kwargs["samples_per_client"] = max(args.batch_size, 2)
    elif _is_abcd_h5(args.dataset):
        kwargs["layout"] = getattr(args, "layout", "channels")
        if kwargs["layout"] == "s2d":
            # decompose for the stem the resolved model actually has
            mk = S2D_TWINS.get(args.model, args.model)
            kwargs["s2d_spec"] = S2D_SPECS.get(mk)
        if client_filter is not None:
            kwargs["client_filter"] = client_filter
    return load_federated_data(
        args.dataset,
        data_dir=args.data_dir,
        client_number=args.client_num_in_total,
        partition_method=args.partition_method,
        partition_alpha=args.partition_alpha,
        val_fraction=getattr(args, "val_fraction", 0.0),
        seed=42,  # the reference's fixed split seed (data_loader.py:67-102)
        **kwargs,
    )


def _decoder_share(args):
    """``(Share kwargs, held configuration)`` of a decoder model
    (``models/decoder.py``) from the ``--lm_*`` flags; ``(None, None)`` for
    any other model."""
    from ..models import decoder

    if args.model.lower() not in decoder.CONFIGS:
        return None, None
    share = dict(layers=args.lm_layers, expert_shards=args.lm_expert_shards,
                 tensor_shards=args.lm_tensor_shards,
                 vocab_shards=args.lm_vocab_shards,
                 ssm_shards=args.lm_ssm_shards,
                 mlp_shards=args.lm_mlp_shards)
    return share, decoder.held_config(args.model.lower(),
                                      decoder.Share(**share))


def _is_abcd_h5(dataset: str) -> bool:
    """The cohort-file datasets whose loaders take a ``layout`` (the
    synthetic stand-ins always store NDHWC)."""
    return dataset.lower() in ("abcd", "abcd_site", "abcd_rescale")


def _dataset_augmentable(dataset: str) -> bool:
    """Whether this dataset's loader declares the reference's
    RandomCrop+flip train transform — delegated to the data package's
    single source of truth (the lineage guard needs the answer BEFORE the
    data loads; ``_check_augment_consistency`` re-verifies it against the
    actually-built algorithm after the load)."""
    from ..data import dataset_is_augmentable

    return dataset_is_augmentable(dataset)


def _check_augment_consistency(args, algo) -> None:
    """Post-build safety net for the pre-load guess above: if the guard's
    dataset->augmentable mapping ever drifts from what the loader actually
    declared (aug_pad_value) and the algorithm wired, fail loudly instead
    of letting checkpoint metadata contradict the guard's model."""
    expected = bool(getattr(args, "augment", 1)) \
        and _dataset_augmentable(args.dataset)
    actual = algo.augment_fn is not None
    if expected != actual and args.checkpoint_dir:
        raise SystemExit(
            f"augmentability mapping drift: the lineage guard assumed "
            f"augment={int(expected)} for dataset {args.dataset!r} but the "
            f"built algorithm has augment={int(actual)} — update "
            "data.AUGMENTABLE_DATASETS to match the loader")


def _resolve_lineage_semantics(args, meta: dict, last: int,
                               directory: str,
                               algo_name: str = "") -> None:
    """Reconcile this run's training semantics (batching mode, CIFAR
    augmentation) with an existing checkpoint lineage BEFORE the algorithm
    is built — both knobs are baked into the jitted kernels at build time.

    A sidecar value of None means the lineage predates the knob's sidecar
    entry, which pins its semantics: pre-round-3 lineages trained with
    with-replacement draws, pre-round-4 CIFAR lineages trained without
    augmentation. Continuing a lineage under a different (since-flipped)
    default would silently mix semantics mid-lineage (ADVICE r3), so: on
    resume, a DEFAULTED knob adapts to the lineage's semantics (with a
    warning) — whether the lineage recorded them or is sidecar-less-pinned
    — so the same defaulted resume command keeps working after checkpoints
    start recording the adapted value; an explicit mismatch, or any fresh
    run that would overwrite the lineage round by round, is refused.
    """
    def _adopt_or_refuse(knob, lineage_val, here_val, explicit,
                         provenance, fix):
        """One lineage knob: equal -> no-op; defaulted resume -> adopt the
        lineage's value (warning); explicit mismatch or overwriting fresh
        run -> refuse with knob-specific guidance."""
        if lineage_val == here_val:
            return
        if args.resume and not explicit:
            logger.warning(
                "lineage has %s=%s (%s); continuing with those semantics "
                "instead of the current default", knob, lineage_val,
                provenance)
            setattr(args, knob, lineage_val)
            return
        action = ("resuming it" if args.resume
                  else "a fresh run overwriting it round by round")
        raise SystemExit(
            f"checkpoint dir {directory} holds a {knob}={lineage_val} "
            f"lineage up to round {last}; {action} with {knob}={here_val} "
            f"would mix training semantics. {fix}")

    lineage_b = meta.get("batching") or "replacement"  # None = pre-round-3
    _adopt_or_refuse(
        "batching", lineage_b, getattr(args, "batching", "epoch"),
        getattr(args, "batching_explicit", True),
        "recorded" if meta.get("batching") else
        "pre-round-3 sidecar-less, the only semantics it can have",
        f"Pass --batching {lineage_b} to continue it, or start a fresh "
        "lineage (--tag or a different --checkpoint_dir).")

    pa = meta.get("augment")
    lineage_a = int(bool(pa))  # None = pre-round-4 lineage: un-augmented
    here_a = int(bool(getattr(args, "augment", 1))
                 and _dataset_augmentable(args.dataset))
    _adopt_or_refuse(
        "augment", lineage_a, here_a,
        getattr(args, "augment_explicit", True),
        "recorded" if pa is not None else
        "pre-round-4 sidecar-less, the only semantics it can have",
        f"Pass --augment {lineage_a} to continue it, or start a fresh "
        "lineage (--tag or a different --checkpoint_dir).")

    # SalientGrads only: its state grew the personal_params stack in
    # round 5 under the SAME default identity (fedavg lineages split on
    # the 'nopers' tag from day one, so their structure always matches
    # their identity). A sidecar-less lineage (track_personal None)
    # predates the stack — its checkpoints hold 3-field states that
    # cannot be restored into the 4-field template, and the personal
    # models' history is unrecoverable, so a defaulted resume continues
    # under the lineage's own (personal-less) protocol. NOTE the remedy
    # is the defaulted resume, NOT an explicit --track_personal 0: that
    # flag adds the 'nopers' tag to the CHECKPOINT identity (it must —
    # fedavg's two modes store different state structures), which would
    # point at a different, empty lineage dir.
    if algo_name == "salientgrads":
        tp = meta.get("track_personal")
        _adopt_or_refuse(
            "track_personal", int(bool(tp)),  # None = pre-r5: no stack
            int(bool(getattr(args, "track_personal", 1))),
            getattr(args, "track_personal_explicit", True),
            "recorded" if tp is not None else
            "pre-round-5 sidecar-less: its states have no personal stack",
            "Resume WITHOUT --track_personal to continue it under the "
            "lineage's own protocol, or start a fresh lineage (--tag or "
            "a different --checkpoint_dir) for the other mode.")


def infer_loss_type(args: argparse.Namespace, class_num: int,
                    x_dtype=None) -> str:
    """ABCD/3D path uses BCE-with-logits (my_model_trainer.py:191-206);
    CIFAR path uses CE (fedavg/my_model_trainer.py:38-67); integer inputs
    are token shards (data/tokens.py): per-token CE over the vocabulary."""
    if x_dtype is not None and np.issubdtype(x_dtype, np.integer):
        return "token_ce"
    if args.model.startswith("3d") and class_num == 2:
        return "bce"
    if args.dataset.lower().startswith(("abcd", "synthetic")) and class_num == 2:
        return "bce"
    return "ce"


def build_algorithm(args: argparse.Namespace, algo_name: str, data=None):
    import jax

    from ..algorithms import ALGORITHMS
    from ..core.state import HyperParams
    from ..models import create_model

    # validate the layout/dataset/model coupling BEFORE any data IO so a
    # mismatched combination dies with an actionable message, not a shape
    # error (or worse, silent training on misinterpreted tensors)
    layout = getattr(args, "layout", "channels")
    model_key = args.model
    if layout != "channels" and not _is_abcd_h5(args.dataset):
        raise SystemExit(
            f"--layout {layout} requires an ABCD cohort dataset "
            "(abcd | abcd_site | abcd_rescale); other loaders store NDHWC")
    if layout == "s2d":
        model_key = S2D_TWINS.get(model_key, model_key)
        if model_key not in S2D_SPECS:
            raise SystemExit(
                f"--layout s2d feeds phase-decomposed input that only the "
                f"s2d-stem models consume; --model {model_key} would "
                "misread the phase axis. Use --model "
                f"{'/'.join(S2D_TWINS)} (auto-mapped) or drop --layout s2d")
    elif model_key in S2D_SPECS:
        raise SystemExit(
            f"--model {model_key} consumes phase-decomposed input; pair it "
            f"with --layout s2d (got --layout {layout})")

    if getattr(args, "client_optimizer", "sgd") != "sgd":
        # the reference's trainers implement only SGD (any other value
        # crashes there with an undefined optimizer, my_model_trainer.py:45)
        raise SystemExit(
            f"--client_optimizer {args.client_optimizer!r}: only 'sgd' is "
            "implemented (reference parity; the reference crashes on "
            "anything else too)")
    if data is None:
        data = build_data(args)
    n_space = max(1, getattr(args, "mesh_space", 1))
    if n_space > 1:
        # pad volume depth BEFORE model construction so init sees the
        # padded sample shape (parallel/spatial.py)
        from ..parallel.spatial import pad_federated_depth

        data = pad_federated_depth(data, n_space)
    ddt = getattr(args, "data_dtype", "")
    if ddt:
        import jax.numpy as jnp

        dt = jnp.dtype(ddt)

        def cast(x):
            if x is None:
                return None
            if isinstance(x, jax.Array):
                return jnp.asarray(x, dt)
            return np.asarray(x).astype(dt)  # host-side (ml_dtypes bf16)

        data = data.replace(x_train=cast(data.x_train),
                            x_test=cast(data.x_test),
                            x_val=cast(data.x_val))
    loss_type = infer_loss_type(args, data.class_num, data.x_train.dtype)
    num_outputs = 1 if loss_type == "bce" else data.class_num
    share, _ = _decoder_share(args)
    if (share is None) != (loss_type != "token_ce"):
        raise SystemExit(
            f"--model {args.model} with --dataset {args.dataset}: token "
            "shards (integer inputs) train the decoder models, and only "
            "them")
    if share is not None and args.frequency_of_the_test:
        raise SystemExit(
            "evaluation of a token cohort (perplexity each round) is not "
            "implemented; pass --frequency_of_the_test 0")
    model = create_model(model_key, num_classes=num_outputs, **(share or {}))

    from ..parallel.multihost import host_client_counts

    counts = host_client_counts(data.n_train)  # multi-host-safe fetch
    batching = getattr(args, "batching", "epoch")
    if batching == "epoch":
        # reference semantics: each client iterates its own loader —
        # ceil(n_i/batch) shuffled batches per epoch (my_model_trainer.py:
        # 194-216). The static scan bound is the largest client's count;
        # smaller clients' excess steps are masked no-ops (core/trainer.py).
        n_bound = int(np.max(counts))
        steps_per_epoch = max(1, -(-n_bound // args.batch_size))
    else:  # legacy with-replacement draws: uniform mean-derived step count
        steps_per_epoch = max(1, int(np.mean(counts)) // args.batch_size)
    hp = HyperParams(
        lr=args.lr, lr_decay=args.lr_decay, momentum=args.momentum,
        weight_decay=args.wd, grad_clip=args.grad_clip,
        local_epochs=args.epochs, steps_per_epoch=steps_per_epoch,
        batch_size=args.batch_size, batching=batching,
    )

    common = dict(
        loss_type=loss_type, frac=args.frac, seed=args.seed,
        # a decoder's grouped expert product cannot be vmapped over
        # clients: they train one at a time, whatever the device
        client_chunk=(args.client_chunk
                      or (1 if share is not None else None)
                      or _auto_client_chunk(args, data.num_clients)),
        compute_dtype=getattr(args, "compute_dtype", "") or None,
        channel_inject=(layout == "flat" and _is_abcd_h5(args.dataset)),
        remat_local=bool(getattr(args, "remat", 0)),
        eval_clients=getattr(args, "eval_clients", 0),
        # "auto" applies only to datasets whose loader set aug_pad_value
        # (cifar10/100, tiny) — the reference's always-on train transform
        augment="auto" if getattr(args, "augment", 1) else False,
        agg_impl=getattr(args, "agg_impl", "dense"),
        agg_bucket_size=getattr(args, "agg_bucket_size", 0),
        agg_topk_density=getattr(args, "agg_topk_density", 0.1),
        agg_topk_sample=getattr(args, "agg_topk_sample", 0),
        agg_hier_wire=getattr(args, "agg_hier_wire", "bf16"),
        agg_hier_inner=getattr(args, "agg_hier_inner", 0),
        agg_overlap=bool(getattr(args, "agg_overlap", 1)),
        agg_kernels=getattr(args, "agg_kernels", "xla"),
        fault_spec=getattr(args, "fault_spec", ""),
        # None = let the algorithm auto-resolve (on iff faults injected);
        # parse_args always resolves the sentinel in derive()
        guard=(bool(args.guard)
               if getattr(args, "guard", None) is not None else None),
        obs_numerics=bool(getattr(args, "obs_numerics", 0)),
        # state-ownership protocol (on by default — bit-identical
        # aliasing; only donate_supported algorithms consume it)
        donate_state=bool(getattr(args, "donate_state", 1)),
        # population-scale client store (core/client_store.py):
        # host/disk-resident per-client rows, streamed cohort residency.
        # Bit-identical to device residency — never enters identity.
        client_store=getattr(args, "client_store", "device"),
        store_hot_clients=getattr(args, "store_hot_clients", 64),
        robust_agg=getattr(args, "robust_agg", "none"),
        robust_trim=getattr(args, "robust_trim", 0.2),
        robust_krum_f=getattr(args, "robust_krum_f", 0),
        # norm_krum's clip bound rides the existing --norm_bound flag
        # (it IS the norm_diff_clipping bound, applied per-row in-jit)
        robust_norm_bound=getattr(args, "norm_bound", 5.0),
    )
    store_mode = getattr(args, "client_store", "device")
    if store_mode != "device":
        if algo_name not in ("fedavg", "salientgrads", "ditto"):
            raise SystemExit(
                f"--client_store {store_mode} streams the per-client "
                "state rows (personal stack / topk residual) through "
                "the central round entry; only fedavg/salientgrads/"
                f"ditto thread the streamed slab ({algo_name} does not)")
        if args.frac >= 1.0:
            raise SystemExit(
                f"--client_store {store_mode} exists to keep only the "
                "SAMPLED cohort device-resident; full participation "
                "(--frac 1.0) touches every row every round — run "
                "device-resident instead")
        if getattr(args, "eval_clients", 0):
            raise SystemExit(
                f"--client_store {store_mode} routes personal eval "
                "through the store-backed cache; the sampled-eval "
                "subset (--eval_clients) composes poorly with it — "
                "use one or the other")
        if not getattr(args, "track_personal", 1) and \
                getattr(args, "agg_impl", "dense") != "topk":
            raise SystemExit(
                f"--client_store {store_mode} with --track_personal 0 "
                "has no per-client rows to store: the personal stack "
                "is untracked and no topk error-feedback residual "
                "exists (--agg_impl is not 'topk'). Drop "
                "--client_store (nothing scales with C) or track "
                "something per-client")
        if max(1, getattr(args, "fuse_rounds", 1) or 1) > 1 and \
                getattr(args, "frequency_of_the_test", 0):
            raise SystemExit(
                f"--client_store {store_mode} with --fuse_rounds K "
                "runs block-union slabs; the fused IN-GRAPH eval "
                "(--frequency_of_the_test > 0) needs the full resident "
                "[C] personal stack — pass --frequency_of_the_test 0 "
                "(eval at the end) or --fuse_rounds 1")
    if (getattr(args, "fault_spec", "") or getattr(args, "guard", 0)) \
            and algo_name not in ("fedavg", "salientgrads", "ditto"):
        raise SystemExit(
            "--fault_spec/--guard protect the CENTRAL aggregation round "
            f"(fedavg/salientgrads/ditto); {algo_name} has no central "
            "aggregate to guard")
    if getattr(args, "robust_agg", "none") != "none" and \
            algo_name not in ("fedavg", "salientgrads", "ditto"):
        raise SystemExit(
            f"--robust_agg {args.robust_agg} replaces the CENTRAL "
            f"weighted mean (fedavg/salientgrads/ditto); {algo_name} "
            "has no central aggregate to robustify")
    if getattr(args, "eval_cache", 0):
        if algo_name not in ("fedavg", "salientgrads"):
            raise SystemExit(
                "--eval_cache caches the per-client personal-eval "
                "terms in algorithm state; only fedavg/salientgrads "
                f"carry the personal stack it indexes ({algo_name} "
                "does not)")
        if not getattr(args, "track_personal", 1):
            raise SystemExit(
                "--eval_cache needs the personal stack; it cannot "
                "combine with --track_personal 0")
        if getattr(args, "eval_clients", 0):
            raise SystemExit(
                "--eval_cache indexes the full cohort; the sampled-"
                "eval subset (--eval_clients) composes poorly with it "
                "— use one or the other")
    if getattr(args, "obs_numerics", 0) and \
            algo_name not in ("fedavg", "salientgrads"):
        raise SystemExit(
            "--obs_numerics threads the in-jit numerics telemetry "
            "through the central-aggregate round outputs "
            f"(fedavg/salientgrads); {algo_name} does not thread them")
    if getattr(args, "obs_comm", 0):
        if not getattr(args, "obs", 0):
            raise SystemExit(
                "--obs_comm rides the obs session (per-round JSONL + "
                "registry); pass --obs 1")
        if algo_name not in ("fedavg", "salientgrads", "ditto"):
            raise SystemExit(
                "--obs_comm models the CENTRAL aggregation wire "
                f"(fedavg/salientgrads/ditto); {algo_name} has no "
                "central aggregate to price")
    agg_impl = getattr(args, "agg_impl", "dense")
    if agg_impl != "dense" and algo_name not in (
            "fedavg", "salientgrads", "ditto"):
        raise SystemExit(
            f"--agg_impl {agg_impl} routes the CENTRAL weighted mean "
            f"(fedavg/salientgrads/ditto); {algo_name} has no central "
            "aggregate")
    if agg_impl == "sparse" and algo_name != "salientgrads":
        raise SystemExit(
            "--agg_impl sparse needs a static sparsity mask; only "
            "salientgrads (fixed SNIP mask) supports it")
    if agg_impl == "topk" and algo_name not in ("fedavg", "salientgrads"):
        raise SystemExit(
            "--agg_impl topk carries an error-feedback residual in "
            "algorithm state; only fedavg/salientgrads thread it "
            f"({algo_name} does not)")
    if agg_impl == "hier" and \
            getattr(args, "agg_hier_wire", "bf16") == "sparse" and \
            algo_name != "salientgrads":
        raise SystemExit(
            "--agg_hier_wire sparse compresses the cross-slice hop to a "
            "static mask's live coordinates; only salientgrads (fixed "
            "SNIP mask) supports it")
    defense = None
    if getattr(args, "defense_type", "none") != "none":
        from ..robust import RobustAggregator

        if algo_name not in ("fedavg", "salientgrads"):
            raise SystemExit(
                f"--defense_type {args.defense_type} guards the global "
                "aggregation of fedavg/salientgrads; "
                f"{algo_name} has no central aggregate to defend")
        defense = RobustAggregator(
            defense_type=args.defense_type,
            norm_bound=args.norm_bound, stddev=args.stddev)

    extra: Dict[str, Any] = {}
    if algo_name == "salientgrads":
        extra = dict(dense_ratio=args.dense_ratio,
                     itersnip_iterations=args.itersnip_iteration,
                     defense=defense,
                     snip_mask=bool(getattr(args, "snip_mask", 1)),
                     stratified_sampling=bool(
                         getattr(args, "stratified_sampling", 0)),
                     stratified_mode=getattr(args, "stratified_mode",
                                             "exact"),
                     track_personal=bool(
                         getattr(args, "track_personal", 1)),
                     eval_cache=bool(getattr(args, "eval_cache", 0)))
    elif algo_name == "fedavg":
        extra = dict(defense=defense,
                     track_personal=bool(
                         getattr(args, "track_personal", 1)),
                     eval_cache=bool(getattr(args, "eval_cache", 0)))
    elif algo_name == "dispfl":
        extra = dict(dense_ratio=args.dense_ratio,
                     anneal_factor=args.anneal_factor,
                     neighbor_mode=args.cs, active=args.active,
                     static_masks=bool(args.static),
                     total_rounds=args.comm_round,
                     erk_power_scale=args.erk_power_scale,
                     sparsity_distribution=(
                         "uniform" if getattr(args, "uniform", False)
                         else "erk"),
                     different_initial=getattr(args, "different_initial",
                                               False),
                     diff_spa=getattr(args, "diff_spa", False),
                     dis_gradient_check=getattr(args, "dis_gradient_check",
                                                False),
                     # frequency_of_the_test=0 disables ALL eval cost,
                     # including the reference's per-round local tests
                     record_local_tests=bool(
                         getattr(args, "frequency_of_the_test", 1)))
    elif algo_name == "dpsgd":
        extra = dict(neighbor_mode=args.cs)
    elif algo_name == "subavg":
        extra = dict(each_prune_ratio=args.each_prune_ratio,
                     dist_thresh=args.dist_thresh,
                     acc_thresh=args.acc_thresh,
                     dense_ratio=args.dense_ratio)
    elif algo_name == "ditto":
        personal_hp = None
        if getattr(args, "local_epochs", 0):
            personal_hp = hp.replace(local_epochs=args.local_epochs)
        extra = dict(lamda=args.lamda, personal_hp=personal_hp)
    elif algo_name == "turboaggregate":
        extra = dict(n_groups=args.n_groups)

    cls = ALGORITHMS[algo_name]
    algo = cls(model, data, hp, **common, **extra)
    if share is not None and (algo.client_chunk != 1
                              or algo._stack_readers()
                              or algo_name not in ("fedavg", "salientgrads")):
        raise SystemExit(
            f"--model {args.model} trains in the round that folds each "
            "client into the weighted sum (fedavg or salientgrads, "
            "--client_chunk 1, --track_personal 0): its expert layer's "
            "grouped product runs one client at a time. Asked for instead: "
            f"--algo {algo_name} --client_chunk {algo.client_chunk} "
            + " ".join(algo._stack_readers()))
    return algo, data


def build_multihost_data(args: argparse.Namespace):
    """Per-process data path for a multi-process run: size the clients mesh
    BEFORE any volume IO, load only this process's clients (ABCD cohort
    files support this natively — lazy h5 reads), and assemble the global
    client-sharded pytree. Returns (mesh, global_data) or (None, None)
    when not applicable."""
    import jax

    from ..parallel import (
        local_client_indices,
        make_multihost_mesh,
        shard_federated_data_global,
    )

    if jax.process_count() <= 1:
        return None, None

    def pad_local(local):
        n_space = max(1, getattr(args, "mesh_space", 1))
        if n_space <= 1:
            return local
        from ..parallel.spatial import pad_federated_depth

        # pad on host BEFORE lifting to global device arrays; the later
        # build_algorithm pad is then a no-op
        return pad_federated_depth(local, n_space)

    if _is_abcd_h5(args.dataset):
        if args.dataset.lower() == "abcd_site" or not args.client_num_in_total:
            from ..data.abcd import abcd_site_count

            n_clients = abcd_site_count(args.data_dir)
        else:
            n_clients = args.client_num_in_total
        mesh = make_multihost_mesh(
            n_space=max(1, getattr(args, "mesh_space", 1)),
            num_clients=n_clients,
            max_client_devices=args.mesh_devices or None)
        idx = local_client_indices(n_clients, mesh)
        local = pad_local(build_data(args, client_filter=idx))
        return mesh, shard_federated_data_global(local, n_clients, mesh)
    # other datasets: every process loads the (small) dataset, keeps its
    # clients, and contributes them to the global arrays
    data = build_data(args)
    n_clients = data.num_clients
    mesh = make_multihost_mesh(
        n_space=max(1, getattr(args, "mesh_space", 1)),
        num_clients=n_clients,
        max_client_devices=args.mesh_devices or None)
    idx = local_client_indices(n_clients, mesh)
    local = pad_local(jax.tree_util.tree_map(
        lambda x: np.asarray(x)[idx], data))
    return mesh, shard_federated_data_global(local, n_clients, mesh)


def _client_mesh_devices(args: argparse.Namespace, n_clients: int) -> int:
    """Devices the single-process ``clients`` mesh axis will span: all the
    visible ones (``--mesh_devices 0``), less the ``space`` axis, fitted
    to a divisor of the client count."""
    import jax

    from ..parallel.mesh import fit_client_devices

    per_space = len(jax.devices()) // max(1, getattr(args, "mesh_space", 1))
    return fit_client_devices(
        n_clients, min(args.mesh_devices or per_space, per_space))


def _auto_client_chunk(args: argparse.Namespace,
                       n_clients: int) -> Optional[int]:
    """``--client_chunk 0``: how many clients one device trains at once.

    Vmapped clients carry their own weights, so XLA cannot fold them into
    one convolution batch; running them side by side buys no arithmetic
    intensity and multiplies the live activations by the client count
    (8 full-volume AlexNet3D clients at batch 8: 15.3 GB of temporaries,
    over a v5e's 15.75 GB once the cohort is resident). So where ONE
    device holds every client and reports a memory limit, clients map one
    at a time (``lax.map``, still one program). A ``clients`` mesh
    spreads clients over devices and keeps the full vmap for whatever GSPMD
    partitions (the SNIP pass, the personal eval) — a ``lax.map`` over a
    sharded client axis would visit the devices in turn; backends that
    report no limit (CPU) keep it too. The mesh round's local training runs
    inside a ``shard_map`` over ``clients``, where each chip is again one
    device holding its own sites: ``FedAlgorithm._train_clients`` applies
    this rule there, per chip (PR 29)."""
    import jax

    if jax.process_count() > 1 or n_clients < 2 \
            or _client_mesh_devices(args, n_clients) > 1:
        return None
    stats = jax.devices()[0].memory_stats() or {}
    return 1 if stats.get("bytes_limit") else None


def maybe_shard(algo, args: argparse.Namespace):
    """Place the client-stacked data on a ``clients[, space]`` mesh so the
    vmapped round runs SPMD over devices (SURVEY §7 design stance). With
    ``--mesh_space N`` each volume's depth is sharded over a second mesh
    axis (the context-parallel slot, SURVEY §5.7) and XLA GSPMD inserts the
    conv halo exchanges."""
    import jax

    from ..parallel import make_mesh
    from ..parallel.mesh import shard_federated_hybrid

    n_space = max(1, getattr(args, "mesh_space", 1))
    avail = len(jax.devices())
    if n_space > avail:
        raise SystemExit(
            f"--mesh_space {n_space} needs at least that many devices "
            f"(have {avail})")
    n_dev = _client_mesh_devices(args, algo.num_clients)
    if n_dev <= 1 and n_space == 1:
        return None
    mesh = make_mesh(n_dev, n_space)
    algo.data = shard_federated_hybrid(algo.data, mesh)
    return mesh


def save_stat_info(args: argparse.Namespace, identity: str,
                   history, final_eval, extras=None,
                   cost=None, eval_client_ids=None,
                   avg_inference_flops: float = 0.0,
                   fault_counters=None, obs_metrics=None) -> Optional[str]:
    """End-of-run artifact: stat_info pickle under
    ``<results_dir>/<dataset>/<identity>`` (subavg_api.py:218-221)."""
    if not args.results_dir:
        return None
    out_dir = os.path.join(args.results_dir, args.dataset)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, identity)
    stat_info = {
        "config": vars(args),
        "history": history,
        "final_eval": {k: float(v) for k, v in final_eval.items()
                       if np.ndim(v) == 0},
        "global_test_acc": [h.get("global_acc") for h in history
                            if "global_acc" in h],
        "person_test_acc": [h.get("personal_acc") for h in history
                            if "personal_acc" in h],
        # DisPFL per-round local-test series around local training
        # (dispfl_api.py:150-155,269,301)
        "old_mask_test_acc": [h["old_mask_test_acc"] for h in history
                              if "old_mask_test_acc" in h],
        "new_mask_test_acc": [h["new_mask_test_acc"] for h in history
                              if "new_mask_test_acc" in h],
        # stat_info cost counters (sailentgrads_api.py:334-346)
        "sum_training_flops": getattr(cost, "sum_training_flops", 0.0),
        "sum_comm_params": getattr(cost, "sum_comm_params", 0),
        "avg_inference_flops": avg_inference_flops,
    }
    if fault_counters is not None:
        # run-level fault/recovery totals (clients_dropped,
        # clients_quarantined, rounds_retried/skipped,
        # checkpoint_save_failures)
        stat_info["fault_recovery"] = dict(fault_counters)
    if obs_metrics is not None:
        # end-of-run obs registry snapshot (obs/export.py metrics.json
        # payload) — merged into stat_info so one artifact carries both
        # the learning curves and the run's telemetry
        stat_info["obs_metrics"] = obs_metrics
    if eval_client_ids is not None:
        # sampled-eval mode: per-client eval outputs are indexed by subset
        # position; persist the client-id mapping alongside them
        stat_info["eval_client_ids"] = [int(i) for i in eval_client_ids]
    json_safe_keys = list(stat_info)  # extras are pickle-only: the JSON
    # sidecar would stringify (and numpy would elide) large mask arrays
    stat_info.update(extras or {})
    with open(path, "wb") as f:
        pickle.dump(stat_info, f)
    with open(path + ".json", "w") as f:
        json.dump({k: stat_info[k] for k in json_safe_keys}, f,
                  default=str, indent=1)
    return path


def _ckpt_metadata(args, algo, cost):
    """Checkpoint metadata sidecar (shared by the per-round and
    block-boundary save sites — a key consumed by
    _resolve_lineage_semantics or the cost-sidecar restore must appear in
    BOTH or fused<->unfused lineage resume breaks)."""
    return {"cost": cost.snapshot_totals(),
            "batching": getattr(args, "batching", "epoch"),
            "augment": algo.augment_fn is not None,
            "track_personal": bool(getattr(args, "track_personal", 1)),
            # diagnostic only (topk lineages already split identity):
            # records which impl wrote this lineage's states
            "agg_impl": algo.agg_impl,
            # diagnostic only (evcache lineages already split identity)
            "eval_cache": bool(getattr(algo, "eval_cache", False)),
            # diagnostic only (residency modes are bit-identical and
            # share one lineage; store-backed steps additionally carry
            # a store_<step>.npz row-snapshot sidecar)
            "client_store": getattr(algo, "client_store", "device")}


def _cost_round_record(algo, cost, samples_per_client, state):
    """One round's cost record (stat_info counters, shared by the unfused
    and fused loops): reuse the constant record when masks are static
    (skips the device->host param pull), else snapshot the state."""
    if cost.per_round and not algo.masks_evolve:
        return cost.record_repeat()
    cost_params, cost_mask = algo.cost_snapshot(state)
    if cost_params is None:
        return None
    return cost.record_round(
        cost_params, cost_mask,
        n_clients=algo.cost_trained_clients_per_round(),
        samples_per_client=samples_per_client)


def _run_fused_rounds(algo, algo_name, state, start_round, total, block,
                      ev_every, cost, samples_per_client, history,
                      ckpt_mgr=None, args=None, counters=None,
                      obs_session=None, obs_fault_counts=None,
                      flight=None):
    """The runner's fused round loop (--fuse_rounds K): the shared
    block driver (FedAlgorithm._fused_block_loop) plus the runner's cost
    accounting. Masks are static here (evolving-mask algorithms are
    refused), so ONE post-round snapshot prices every round — taken from
    the emitting block's output state, whose nonzero pattern matches the
    unfused loop's post-round-0 snapshot (a zero-init bias is nonzero
    after any trained round; masked weights are exact zeros either
    way).

    Checkpoints coarsen to BLOCK granularity: the unfused loop saves
    after every round, this loop saves each block's output state at its
    boundary round (same (round -> state) contract, so fused and unfused
    lineages resume each other; a resume simply starts at the last saved
    boundary)."""
    def on_record(r, rec, state_out):
        crec = _cost_round_record(algo, cost, samples_per_client, state_out)
        if crec is not None:
            rec["sum_training_flops"] = crec["sum_training_flops"]
            rec["sum_comm_params"] = crec["sum_comm_params"]
        if counters is not None:
            counters.update(rec)
        history.append(rec)
        if flight is not None:
            # before record_round: SLO event-bus triggers fire there,
            # and their bundles must see this round in the window
            flight.observe_record(rec)
        if obs_session is not None:
            # fused records arrive at the block flush point, already
            # materialized — the JSONL write forces no device sync
            obs_session.record_round(
                rec, extra=(obs_fault_counts(r)
                            if obs_fault_counts is not None and r >= 0
                            else None))
        logger.info("%s round %d: %s", algo_name, r, rec)

    def on_block(end_round, state_out):
        if ckpt_mgr is not None:
            # store-backed lineage: the block's staged row writebacks
            # ride the same boundary as a store_<step>.npz sidecar
            # (snapshot_save commits staged rows first — the fused-flush
            # writeback path)
            ckpt_mgr.save(end_round, state_out,
                          metadata=_ckpt_metadata(args, algo, cost),
                          store=getattr(algo, "_store", None))

    # with obs on, fused records get round_time_s stamped at flush
    # boundaries (block wall split evenly — the documented fused
    # semantics), matching the unfused loop's DeferredRecords(timed=
    # obs) rule; off keeps the pre-obs record shape exactly. The
    # per-round comm_agg_share stamp (obs/comm.py) divides by it.
    return algo._fused_block_loop(
        state, start_round, total, block, ev_every, on_record,
        on_block=on_block, timed=obs_session is not None)


def run_experiment(args: argparse.Namespace,
                   algo_name: Optional[str] = None) -> Dict[str, Any]:
    import jax

    algo_name = algo_name or getattr(args, "algo", "fedavg")
    if getattr(args, "serve_role", ""):
        # serving plane (serve/): the checkpoint-streaming inference
        # worker / publisher pair — its own lifecycle, obs session, and
        # refusal cluster. Dispatched before the fed runtime (the two
        # roles refuse each other) and before checkpoint/obs setup: the
        # serve runtime owns all of it
        from ..serve.runtime import run_serving

        configure_console()
        seed_everything(args.seed)
        return run_serving(args, algo_name)
    if getattr(args, "fed_role", ""):
        # distributed federation (fed/): a genuinely multi-process
        # deployment — its own round loop, obs streams, and lifecycle.
        # Dispatched before checkpoint/obs setup: the fed runtime owns
        # all of it (and refuses the in-process features it can't honor)
        from ..fed.runtime import run_federated

        configure_console()
        seed_everything(args.seed)
        return run_federated(args, algo_name)
    ckpt_mgr = None
    log_handler = None
    obs_session = None
    from ..obs import trace as obs_trace
    try:
        # Reconcile batching/augment semantics with any existing checkpoint
        # lineage FIRST: an adapted knob (e.g. a defaulted resume flipping
        # to --batching replacement / --augment 0) must flow into the run
        # identity below, so the adapted run's logs and stat_info land
        # under the matching 'wr'/'noaug'-tagged lineage, not the default
        # one.
        if args.checkpoint_dir:
            from ..utils.checkpoint import CheckpointManager

            ckpt_mgr = CheckpointManager(
                args.checkpoint_dir,
                run_identity(args, algo_name, for_checkpoint=True))
            last = ckpt_mgr.latest_step()
            if last is not None:
                _resolve_lineage_semantics(
                    args, ckpt_mgr.load_metadata(last) or {}, last,
                    ckpt_mgr.directory, algo_name)
        identity = run_identity(args, algo_name)
        configure_console()
        log_handler = add_run_file_logger(
            args.log_dir, getattr(args, "logfile", "") or identity)
        logger.info("run identity: %s", identity)
        seed_everything(args.seed)

        mh_mesh = None
        if getattr(args, "multihost", False):
            from ..parallel import initialize_distributed

            coord = getattr(args, "coordinator_address", "") or None
            nproc = getattr(args, "num_processes", 0) or None
            pid = getattr(args, "process_id", -1)
            if initialize_distributed(
                    coordinator_address=coord, num_processes=nproc,
                    process_id=pid if pid >= 0 else None,
                    timeout_s=getattr(args, "multihost_timeout_s", 0.0)
                    or None,
                    max_retries=getattr(args, "multihost_retries", 2)):
                mh_mesh, gdata = build_multihost_data(args)
            else:
                # --multihost was explicit; training alone while believing
                # we're a pod is the worst failure mode (ADVICE r1)
                raise SystemExit(
                    "--multihost: no multi-process runtime came up "
                    "(jax.process_count() == 1). On TPU pods launch via the "
                    "pod runtime; elsewhere pass --coordinator_address/"
                    "--num_processes/--process_id explicitly.")

        if getattr(args, "slo_spec", "") and not getattr(args, "obs", 0):
            raise SystemExit(
                "--slo_spec rides the obs session (per-round record "
                "hook, events stream, registry); pass --obs 1")
        if getattr(args, "slo_enforce", 0) and \
                not getattr(args, "slo_spec", ""):
            raise SystemExit(
                "--slo_enforce needs objectives to enforce; pass "
                "--slo_spec (inline DSL or a spec file)")
        if getattr(args, "flight_recorder", ""):
            from ..obs.recorder import parse_triggers

            if parse_triggers(args.flight_recorder)["slo"] and \
                    not getattr(args, "slo_spec", ""):
                # the 'slo' trigger rides the event bus, which only
                # exists with an engine — arming it spec-less would be
                # a silent never-fires no-op, the exact failure mode
                # the parse-time trigger validation exists to prevent
                raise SystemExit(
                    "--flight_recorder slo captures SLO breach/burn/"
                    "FAILING events; pass --slo_spec to arm the "
                    "engine that emits them")
        if getattr(args, "obs", 0):
            # telemetry session: registry + tracer + sinks (obs/). Built
            # AFTER identity is fixed (obs knobs never enter the
            # identity, so telemetry cannot fork a lineage) and AFTER
            # any jax.distributed init — ObsSession reads
            # jax.process_index() for the only-process-0-exports rule,
            # and touching the backend BEFORE initialize_distributed
            # would both abort the multihost handshake and mis-rank
            # every host as 0
            from ..obs.export import ObsSession

            jsonl = getattr(args, "obs_jsonl", "") or os.path.join(
                args.results_dir or ".", args.dataset,
                identity + ".obs.jsonl")
            # online SLO engine (--slo_spec, obs/slo.py): incremental
            # objective evaluation + typed event bus at the record
            # hook. Pure readout — like every obs knob it never enters
            # identity; off, the session produces byte-identical
            # artifacts to pre-SLO behavior.
            slo_engine = None
            if getattr(args, "slo_spec", ""):
                from ..obs.slo import SloEngine, load_slo_spec

                slo_engine = SloEngine(load_slo_spec(args.slo_spec))
            # fleet run catalog (--obs_catalog, obs/catalog.py): the
            # append-only runs_index.jsonl entry written at session
            # close. All entry fields are computable upfront: the
            # stat_info JSON sidecar path is deterministic, and the
            # checkpoint lineage key is already reconciled above.
            cat_path, cat_info = "", None
            if getattr(args, "obs_catalog", 1) and args.results_dir:
                from ..obs import catalog as obs_catalog
                from ..utils.records import git_sha as _git_sha

                cat_path = obs_catalog.catalog_path(args.results_dir)
                cat_info = {
                    "config": vars(args),
                    "checkpoint_identity": run_identity(
                        args, algo_name, for_checkpoint=True),
                    "git_sha": _git_sha(),
                    "stat_json": os.path.join(
                        args.results_dir, args.dataset,
                        identity + ".json"),
                }
            obs_session = ObsSession(
                jsonl_path=jsonl,
                trace_dir=getattr(args, "trace_dir", ""),
                identity=identity,
                sample_every=getattr(args, "obs_sample_every", 1),
                tb_dir=getattr(args, "obs_tb_dir", ""),
                comm=bool(getattr(args, "obs_comm", 0)),
                slo=slo_engine,
                # events stream rides BESIDE the round stream, derived
                # from the jsonl path (not the identity) so an
                # explicit --obs_jsonl override — e.g. a resume with a
                # larger --comm_round, whose identity differs — keeps
                # the two streams continuous together
                events_path=((jsonl[:-len(".obs.jsonl")]
                              if jsonl.endswith(".obs.jsonl")
                              else jsonl) + ".events.jsonl"
                             if slo_engine is not None else ""),
                catalog_path=cat_path, catalog_info=cat_info)
            logger.info("obs: per-round JSONL -> %s", jsonl)
            if slo_engine is not None:
                logger.info(
                    "obs slo: %d objective(s) armed, events -> %s",
                    len(slo_engine.objectives),
                    obs_session.events_path)

        with obs_trace.span("build"):
            if mh_mesh is not None:
                algo, data = build_algorithm(args, algo_name, data=gdata)
                mesh = mh_mesh
            else:
                algo, data = build_algorithm(args, algo_name)
                mesh = maybe_shard(algo, args)
                # keep no handle on the pre-placement cohort: the loaders
                # build it on the default device, and a live reference
                # would pin a second full copy there for the whole run
                data = algo.data
        if mesh is not None:
            logger.info("sharding clients over mesh %s", dict(mesh.shape))
        _check_augment_consistency(args, algo)
        if obs_session is not None and \
                getattr(algo, "_store", None) is not None:
            # client-store residency ledger: host-cache/disk bytes,
            # hit/miss/prefetch counters and cumulative gather ms join
            # the round-boundary memory watermark samples (JSONL +
            # registry) — the mem-flat-in-C acceptance readout
            obs_session.memory.attach_extra(algo._store.stats)

        # obs-only fault-trace stamper: fault draws are pure functions of
        # (seed, round, client id), so the deterministic replay
        # (obs/health.py) counts this round's effective stragglers /
        # Byzantine clients host-side — the analyzer's attribution
        # source. Never touches the record the obs-off path sees.
        obs_fault_counts = None
        if obs_session is not None and getattr(args, "fault_spec", ""):
            from ..obs.health import make_fault_counts_fn

            obs_fault_counts = make_fault_counts_fn(
                args.fault_spec, args.seed, algo.num_clients,
                algo.clients_per_round)

        # anomaly flight recorder (obs/recorder.py): bounded post-mortem
        # bundles on guard quarantine / watchdog rollback / drift
        # triggers. Reads only already-materialized records at the
        # flush point; like every obs knob it never enters identity.
        flight = None
        if getattr(args, "flight_recorder", ""):
            from ..obs.recorder import FlightRecorder

            flight = FlightRecorder(
                os.path.join(args.results_dir or ".", args.dataset),
                identity, spec=args.flight_recorder,
                window=getattr(args, "flight_window", 16),
                profile_retry=bool(getattr(args, "flight_profile", 0)),
                num_clients=algo.num_clients,
                clients_per_round=algo.clients_per_round)
            logger.info("flight recorder armed -> %s", flight.dir)
            if obs_session is not None and \
                    obs_session.event_bus is not None:
                # the 'slo' trigger adapter: the recorder rides the
                # typed event bus, freezing a bundle on SLO breach /
                # budget burn / FAILING transition events
                obs_session.event_bus.subscribe(flight.observe_event)

        state = None
        start_round = 0
        if ckpt_mgr is not None and args.resume:
            hints = []
            if getattr(args, "agg_impl", "dense") == "topk":
                hints.append(
                    "(agg_impl='topk' states carry the error-feedback "
                    "residual stack; topk lineages live under their own "
                    "'aggtopk' checkpoint identity and are not "
                    "interchangeable with other impls')")
            if getattr(args, "eval_cache", 0):
                hints.append(
                    "(--eval_cache states carry the per-client eval "
                    "cache; evcache lineages live under their own "
                    "checkpoint identity and are not interchangeable "
                    "with cache-less ones)")
            if getattr(algo, "_store", None) is not None:
                hints.append(
                    "(--client_store lineages keep the per-client rows "
                    "in a store_<step>.npz sidecar next to each step; "
                    "a step without a loadable sidecar is skipped)")
            # store mode: init_state registers the store fields the
            # sidecar load below validates against, then snapshot_load
            # replaces the fresh rows with the checkpointed ones
            restored = ckpt_mgr.restore_latest(
                algo.init_state(jax.random.PRNGKey(args.seed)),
                schema_hint=" ".join(hints),
                store=getattr(algo, "_store", None))
            if restored is not None:
                state, start_round = restored
                logger.info("resumed from round %d", start_round)
                if obs_session is not None and start_round > 0:
                    # rebuild the SLO engine's estimator/budget/health
                    # state from the run's own JSONL (deterministic —
                    # the engine is a pure function of the record
                    # stream); emission is suppressed, the events
                    # stream already holds those rounds
                    replayed = obs_session.slo_replay_from_stream(
                        start_round)
                    if replayed:
                        logger.info(
                            "obs slo: rebuilt engine state from %d "
                            "recorded round(s) (health=%s)", replayed,
                            obs_session.slo.health)

        if state is None:
            with obs_trace.span("init_state"):
                state = algo.init_state(jax.random.PRNGKey(args.seed))
        state = algo.place_state(state)
        if _decoder_share(args)[0] is not None:
            # how the first batch's routed slots fall on the held experts
            # and, where a layer selects its keys, how much of the causal
            # square it keeps (one forward; a model that sows nothing sets
            # no gauge)
            from ..obs import metrics as obs_metrics
            from ..obs.expert_load import record_expert_load

            with obs_trace.span("expert_load"):
                record_expert_load(
                    algo, state.global_params,
                    obs_session.registry if obs_session is not None
                    else obs_metrics.get_registry())

        # comm telemetry (--obs_comm): price the aggregation wire ONCE —
        # the analytical model from the params template + live mask
        # density, plus the measured probe (one timed aggregation of a
        # shape-matched synthetic cohort through the algorithm's own
        # agg path; pure readout, bit-inert). The session joins the
        # static comm_* metrics onto every JSONL line.
        wire_model = None
        if obs_session is not None and getattr(args, "obs_comm", 0):
            from ..obs import comm as obs_comm

            wire_model = obs_comm.WireCostModel.from_algorithm(
                algo, state)
            comm_metrics = wire_model.round_metrics()
            # one probe, one synthetic cohort: timed agg ms plus the
            # no-trace fallback's AOT cost-analysis numbers
            # (obs/devtrace.py's share_from_cost_analysis consumes the
            # flops/bytes against a round program's cost when no
            # profiler capture exists)
            probe = obs_comm.probe_aggregate(algo, state=state)
            comm_metrics["comm_agg_ms"] = probe["agg_ms"]
            for ck, mk in (("flops", "comm_agg_flops"),
                           ("bytes_accessed",
                            "comm_agg_bytes_accessed")):
                if isinstance(probe.get(ck), (int, float)):
                    comm_metrics[mk] = float(probe[ck])
            obs_session.set_comm_metrics(comm_metrics)
            logger.info(
                "obs comm: %s wire %.2f MB/agg (density %.3f), probed "
                "agg %.2f ms", algo.agg_impl,
                comm_metrics["comm_bytes_wire"] / 1e6,
                comm_metrics["comm_density"],
                comm_metrics["comm_agg_ms"])

        if args.profile_dir:
            from ..utils.profiling import trace_one_round

            trace_one_round(algo, state, args.profile_dir)
            if wire_model is not None:
                # device-trace attribution (obs/devtrace.py): collective
                # vs compute time from the jax.profiler capture, written
                # as the <identity>.devtrace.json sidecar the analyzer's
                # comm section reads. Best-effort: a truncated trace
                # must not kill the run.
                from ..obs import devtrace as obs_devtrace

                try:
                    summary = obs_devtrace.analyze_profile_dir(
                        args.profile_dir,
                        modeled_bytes=wire_model.bytes_for(
                            algo.agg_impl))
                    if summary.get("present") and obs_session.exports \
                            and obs_session.jsonl_path:
                        path = obs_devtrace.write_summary(
                            summary, os.path.join(
                                os.path.dirname(obs_session.jsonl_path)
                                or ".", identity + ".devtrace.json"))
                        obs_session.registry.gauge(
                            "comm_devtrace_agg_share").set(
                            summary["totals"]["agg_share"])
                        logger.info(
                            "obs comm: devtrace %.1f%% collective -> %s",
                            100 * summary["totals"]["agg_share"], path)
                except Exception:
                    logger.warning("devtrace attribution failed",
                                   exc_info=True)

        # per-round cost accounting (stat_info's sum_training_flops /
        # sum_comm_params, sailentgrads_api.py:137-138,334-346)
        from ..utils.flops import CostTracker

        cost = CostTracker(model=algo.model,
                           sample_shape=algo.init_sample_shape,
                           sample_dtype=algo.init_sample_dtype)
        samples_per_client = algo.hp.local_steps * algo.hp.batch_size
        if getattr(args, "batching", "epoch") == "epoch":
            # epoch batching: each client consumes its own n_i samples per
            # epoch (the reference's epochs*samples approximation,
            # sailentgrads/client.py:70-76); cohort mean is the per-client
            # stand-in for the sampled subset
            from ..parallel.multihost import host_client_counts

            samples_per_client = algo.hp.local_epochs * int(
                np.mean(host_client_counts(data.n_train)))
        if start_round > 0:
            # semantics reconciliation already ran pre-build
            # (_resolve_lineage_semantics); only the cost sidecar is left
            meta = (ckpt_mgr.load_metadata(start_round)
                    if ckpt_mgr is not None else None)
            cost_meta = (meta or {}).get("cost") or {}
            if "sum_training_flops" in cost_meta:
                # exact totals persisted at save time (required for
                # evolving-mask algorithms whose replayed rounds had
                # different densities than the restored state)
                cost.restore_totals(cost_meta)
            else:
                # legacy checkpoint without a sidecar: estimate the
                # pre-checkpoint rounds from the restored state's snapshot
                # (exact for static masks)
                cost_params, cost_mask = algo.cost_snapshot(state)
                if cost_params is not None:
                    cost.record_round(
                        cost_params, cost_mask,
                        n_clients=algo.cost_trained_clients_per_round(),
                        samples_per_client=samples_per_client)
                    for _ in range(start_round - 1):
                        cost.record_repeat()

        history = []
        final_eval = None
        # one-round-deferred metric materialization (shared with
        # FedAlgorithm.run — utils/records.py): round r's record is
        # floated+logged only after round r+1's programs are
        # dispatched, so the host never drains the device queue to read
        # a metric
        from ..utils.records import DeferredRecords, RunCounters, to_float

        # fault/recovery accounting: per-round counters accumulated into
        # stat_info (clients_dropped / clients_quarantined), mirrored
        # into the obs registry when a session is live
        counters = RunCounters(
            registry=obs_session.registry if obs_session else None)

        # per-round obs-only enrichment (per-site eval vectors), keyed by
        # round and joined to the JSONL line at the deferred flush point
        obs_extra: Dict[int, Dict[str, Any]] = {}

        def _obs_extra_for(rec):
            r = rec.get("round")
            extra = obs_extra.pop(r, None)
            if obs_fault_counts is not None and isinstance(r, int) \
                    and r >= 0:
                extra = dict(extra or {})
                # a watchdog-retried round's ACCEPTED attempt trained
                # the re-drawn cohort (nonce = the record's retry count)
                extra.update(obs_fault_counts(
                    r, retry=int(rec.get("rounds_retried") or 0)))
            return extra

        def _emit(rec):
            # counters accumulate at FLUSH time, when DeferredRecords has
            # already materialized the record's device scalars — counting
            # in the round loop would host-sync the guard counters every
            # round and defeat the one-round-deferred pipelining. The obs
            # JSONL write shares the same flush point for the same reason.
            counters.update(rec)
            if flight is not None:
                # records are materialized at this point: trigger
                # evaluation (guard counters, drift) is sync-free.
                # BEFORE record_round: the SLO engine's event-bus
                # triggers fire inside record_round, and their bundles
                # must find THIS round's record already in the window
                flight.observe_record(rec)
            if obs_session is not None:
                obs_session.record_round(rec, extra=_obs_extra_for(rec))
            logger.info("%s round %s: %s", algo_name, rec["round"], rec)

        # with obs on, records also get round_time_s stamped at flush
        # boundaries (sum over the run = wall time, attribution ±1 round
        # — the honest semantics under deferred fetching); off keeps the
        # pre-obs record shape exactly
        deferred = DeferredRecords(log=_emit,
                                   timed=obs_session is not None)

        fuse = max(1, getattr(args, "fuse_rounds", 1) or 1)
        watchdog = None
        if getattr(args, "watchdog", 0):
            # host-side divergence watchdog with rollback-retry
            # (robust/recovery.py). Per-round host control is exactly what
            # fusion removes, so the combination is refused outright.
            if fuse > 1:
                raise SystemExit(
                    "--watchdog rolls rounds back and retries them — "
                    "per-round host control that --fuse_rounds removes; "
                    "use --fuse_rounds 1 (or --watchdog 0)")
            from ..robust.recovery import RoundWatchdog

            retries = getattr(args, "max_round_retries", 2)
            if algo.clients_per_round == algo.num_clients and retries:
                # full participation has no alternative cohort to
                # re-sample, and run_round is deterministic in
                # (state, round) — a retry would re-run the identical
                # failed computation; go straight to the skip verdict
                logger.info(
                    "watchdog: full participation — retries are "
                    "deterministic re-runs, short-circuiting to skip")
                retries = 0
            watchdog = RoundWatchdog(
                max_retries=retries,
                backoff_s=getattr(args, "retry_backoff_s", 0.0),
                loss_threshold=getattr(args, "watchdog_loss", 0.0),
                norm_threshold=getattr(args, "watchdog_norm", 0.0),
                ckpt_mgr=ckpt_mgr,
                template_fn=lambda: algo.init_state(
                    jax.random.PRNGKey(args.seed)),
                store=getattr(algo, "_store", None))
        if fuse > 1:
            # K-round fused programs (FedAlgorithm.run_rounds_fused): one
            # dispatch + one metric fetch per block. Per-round host
            # control is exactly what fusion removes, so features that
            # need it either coarsen to block granularity (checkpoints
            # save at block boundaries) or are refused outright.
            if not algo.supports_fused:
                raise SystemExit(
                    f"--fuse_rounds: {algo_name} has data-dependent "
                    "per-round host work (FedFomo's accumulated-weight-"
                    "biased neighbor draw / TurboAggregate's interactive "
                    "share protocol); supported: fedavg, salientgrads, "
                    "ditto, local, dpsgd, dispfl(--static)")
            if algo.masks_evolve:
                raise SystemExit(
                    f"--fuse_rounds: {algo_name}'s per-round cost "
                    "accounting snapshots evolving masks; use "
                    "--fuse_rounds 1")
            state = _run_fused_rounds(
                algo, algo_name, state, start_round,
                max(start_round, args.comm_round), fuse,
                args.frequency_of_the_test or 0, cost,
                samples_per_client, history,
                ckpt_mgr=ckpt_mgr, args=args, counters=counters,
                obs_session=obs_session,
                obs_fault_counts=obs_fault_counts, flight=flight)
            final_eval = None  # re-evaluated once below

        try:
            from ..robust import recovery as _recovery

            r = start_round
            end_round = (start_round if fuse > 1
                         else max(start_round, args.comm_round))
            while r < end_round:
                attempt_nonce = 0
                if watchdog is not None:
                    # retry attempts re-sample the cohort (nonce 0 = the
                    # reference's seeded draw, bit-compatible)
                    attempt_nonce = watchdog.retries_at(r)
                    algo.set_retry_nonce(attempt_nonce)
                prof_dir = (flight.take_retry_profile(r)
                            if flight is not None else None)
                if prof_dir is not None:
                    # flight recorder (--flight_profile): device-trace
                    # the watchdog RETRY attempt into its bundle —
                    # best-effort, once per run
                    flight.start_profile(prof_dir)
                # under the ownership protocol the attempt CONSUMES its
                # input; with a watchdog in play the pre-round state IS
                # last-good and must survive the attempt — hand the
                # attempt a borrowed clone (robust/recovery.py)
                attempt = (watchdog.attempt_input(algo, state)
                           if watchdog is not None else state)
                with obs_trace.step_span("round", r):
                    # NOTE: dispatch-time span (the round program is
                    # async); wall attribution lives in round_time_s at
                    # the deferred flush — see obs/trace.py caveat
                    new_state, rec = algo.run_round(attempt, r)
                record = {"round": r, **dict(rec)}
                if watchdog is not None:
                    verdict = watchdog.judge(r, record, new_state, state)
                    if prof_dir is not None:
                        # the judge materialized the attempt's metrics,
                        # so the retry's device work is in the trace
                        flight.stop_profile()
                        prof_dir = None
                    if flight is not None and verdict != _recovery.OK:
                        # rollback/skip verdicts never reach the
                        # deferred emitter (RETRY) or mark degraded
                        # rounds (SKIP): capture from the verdict path,
                        # with THIS attempt's cohort nonce — the record
                        # carries no rounds_retried yet, and a re-drawn
                        # cohort replayed at nonce 0 would attribute
                        # the drift to clients that never ran
                        flight.note_watchdog(r, verdict, record,
                                             retry=attempt_nonce)
                    if verdict == _recovery.RETRY:
                        # faults observed in the discarded attempt still
                        # happened — count them here (the record never
                        # reaches the deferred emitter); the watchdog
                        # already host-synced this attempt's metrics, so
                        # this adds no extra sync
                        counters.update(record)
                        # store mode: the attempt STAGED its trained
                        # rows into the client store pre-judge — drop
                        # them with the attempt (the rollback's
                        # no-poison rule extended to host/disk rows)
                        algo.store_discard()
                        # the pre-round state in hand IS last-good; the
                        # checkpoint lineage (saved only after OK/SKIP
                        # verdicts) backs it for cross-process recovery
                        state = watchdog.rollback(state)
                        continue
                    if verdict == _recovery.SKIP:
                        new_state = state  # degrade: carry last-good
                        algo.store_discard()  # same no-poison rule
                        record["round_skipped"] = 1.0
                    record.update(watchdog.round_counters())
                if prof_dir is not None:  # no watchdog judge ran
                    flight.stop_profile()
                state = new_state
                crec = _cost_round_record(
                    algo, cost, samples_per_client, state)
                if crec is not None:
                    record["sum_training_flops"] = crec["sum_training_flops"]
                    record["sum_comm_params"] = crec["sum_comm_params"]
                final_eval = None  # state changed; any cached eval is stale
                if args.frequency_of_the_test and \
                        (r + 1) % args.frequency_of_the_test == 0:
                    with obs_trace.span("eval"):
                        final_eval = algo.evaluate(state)
                    record.update({
                        k: v for k, v in final_eval.items()
                        if not k.startswith("acc_per")})
                    if obs_session is not None and \
                            "acc_per_client" in final_eval:
                        # per-site series (obs/health.py): joins the
                        # JSONL line only, at the deferred flush — the
                        # history record shape stays obs-off-identical
                        obs_extra[r] = {"acc_per_client":
                                        final_eval["acc_per_client"]}
                history.append(record)
                deferred.push(record)  # counters accumulate at flush
                if ckpt_mgr is not None:
                    ckpt_mgr.save(r + 1, state,
                                  metadata=_ckpt_metadata(args, algo, cost),
                                  store=getattr(algo, "_store", None))
                r += 1
            if watchdog is not None:
                algo.set_retry_nonce(0)
        except BaseException:
            deferred.flush_safely()  # emit the last completed round
            raise
        deferred.flush()

        fin_rec = None
        # checkpoints are saved inside the round loop (pre-finalize), so a
        # resumed run — even one with no rounds left — re-runs finalize
        # from the same pre-finalize state and reproduces the original
        # metrics; no double fine-tune is possible
        if getattr(args, "final_finetune", 1):
            with obs_trace.span("finalize"):
                state, fin_rec = algo.finalize(state)
        if fin_rec is not None:
            # the reference's final fine-tune record (round -1)
            record = {k: v if k in ("round", "finetune") else to_float(v)
                      for k, v in fin_rec.items()}
            history.append(record)
            if obs_session is not None:
                # the round=-1 final record joins the JSONL stream too
                obs_session.record_round(record)
            logger.info("%s final: %s", algo_name, record)
            # only a finalize that actually TRAINED counts toward the
            # FLOPs/comm counters (FedAvg's fine-tune marks its record
            # with finetune=True; SalientGrads's finalize is the
            # reference's eval-only final _test_on_all_clients)
            if record.get("finetune"):
                cost_params, cost_mask = algo.cost_snapshot(state)
                if cost_params is not None:
                    cost.record_round(cost_params, cost_mask,
                                      n_clients=algo.num_clients,
                                      samples_per_client=samples_per_client)
            # finalize() already evaluated the post-fine-tune state; reuse
            # its metrics instead of re-running the full-cohort evals
            final_eval = {k: v for k, v in fin_rec.items()
                          if k not in ("round", "finetune")}
        if final_eval is None:  # last round wasn't an eval round
            # (a token cohort has no evaluation yet: build_algorithm)
            final_eval = {} if algo.loss_type == "token_ce" \
                else algo.evaluate(state)
        extras = {}
        if getattr(args, "save_masks", False) and hasattr(state, "masks"):
            # dispfl_api.py:177-183: final boolean masks in stat_info
            extras["final_masks"] = jax.tree_util.tree_map(
                lambda m: np.asarray(m, np.bool_), state.masks)
        if getattr(args, "record_mask_diff", False) and \
                hasattr(algo, "mask_distance_matrix"):
            # dispfl_api.py:170-175: pairwise mask hamming matrix
            extras["mask_distance_matrix"] = np.asarray(
                algo.mask_distance_matrix(state))
        # avg per-sample inference FLOPs of the final (masked) model(s) —
        # record_avg_inference_flops (sailentgrads_api.py:319-332);
        # per-client-mask algorithms average over the cohort. Only computed
        # when a stat_info artifact will actually be written (it can pull
        # every client's params to host).
        avg_inf = 0.0
        if args.results_dir:
            from ..utils.flops import avg_inference_flops

            try:
                avg_inf = avg_inference_flops(
                    algo.model, state, algo.init_sample_shape,
                    algo.num_clients, algo.cost_snapshot)
            except Exception:  # cost model unavailable on exotic models
                logger.debug("inference-FLOPs counting skipped",
                             exc_info=True)
        fault_totals = counters.summary()
        if watchdog is not None:
            fault_totals.update(watchdog.totals())
        if ckpt_mgr is not None:
            fault_totals["checkpoint_save_failures"] = float(
                ckpt_mgr.save_failures)
        if flight is not None:
            fs = flight.summary()
            if fs["bundles"] or fs["triggers_skipped"]:
                logger.info("flight recorder: %d bundle(s), %d "
                            "trigger(s) over budget: %s",
                            len(fs["bundles"]), fs["triggers_skipped"],
                            fs["bundles"])
            if obs_session is not None:
                obs_session.registry.gauge("flight_bundles").set(
                    float(len(fs["bundles"])))
        obs_snapshot = None
        if obs_session is not None:
            for k, v in fault_totals.items():
                # run-level totals (incl. watchdog/checkpoint counters
                # that never flow through per-round records) land in the
                # registry before the final snapshot
                obs_session.registry.gauge("fault_recovery_" + k).set(v)
            obs_snapshot = obs_session.finish()
            if obs_session.metrics_json_path:
                logger.info("obs: metrics.json -> %s",
                            obs_session.metrics_json_path)
            if obs_session.trace_path:
                logger.info("obs: Perfetto trace -> %s",
                            obs_session.trace_path)
        stat_path = save_stat_info(
            args, identity, history, final_eval, extras, cost=cost,
            eval_client_ids=(np.asarray(algo._eval_idx)
                             if algo._eval_idx is not None else None),
            avg_inference_flops=avg_inf,
            fault_counters=fault_totals, obs_metrics=obs_snapshot)
        if obs_session is not None and obs_session.slo is not None:
            from ..obs import slo as slo_mod

            health = obs_session.slo.health
            if health != slo_mod.OK:
                logger.warning("obs slo: run ended %s (breached: %s)",
                               health.upper(),
                               ", ".join(obs_session.slo.breached)
                               or "none currently")
            if getattr(args, "slo_enforce", 0) and \
                    health == slo_mod.FAILING:
                # every artifact above is already on disk — the
                # nonzero exit is the verdict, not a crash
                raise SystemExit(
                    f"--slo_enforce: run {identity} ended FAILING "
                    "(error budget exhausted; see "
                    f"{obs_session.events_path or 'the events stream'}"
                    " and metrics.json slo_* gauges)")
        return {
            "identity": identity,
            "history": history,
            "final_eval": final_eval,
            "stat_path": stat_path,
            "state": state,
            "algo": algo,
        }
    finally:
        if obs_session is not None:
            # idempotent: restores the null tracer + closes the JSONL
            # sink even when the run died mid-round (every flushed round
            # is already on disk — the writer flushes per line)
            obs_session.close()
        if ckpt_mgr is not None:
            ckpt_mgr.close()
        from .logging_utils import remove_run_file_logger

        remove_run_file_logger(log_handler)


def main(argv: Optional[Sequence[str]] = None,
         algo: Optional[str] = None) -> Dict[str, Any]:
    from ..utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = parse_args(argv, algo)
    return run_experiment(args, algo)
