"""Benchmark: federated rounds/sec on the canonical ABCD-shaped workload.

Run on real TPU hardware by the driver. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Workload (BASELINE.md north star): SalientGrads-style federated round on
full-size ABCD volumes (121x145x121), AlexNet3D, 8 site-clients on the
available chip(s) — broadcast, vmapped local SGD (5 steps x batch 8 per
client), weighted aggregation, all one jitted program.

``vs_baseline`` is the raw ratio against the BASELINE.json north star of
10 federated rounds/sec — a 32-client v4-32 target this single-chip bench
cannot demonstrate, so it reads well below 1 here by construction. The
hardware-normalized auxiliary number ``client_rounds_per_sec_per_chip``
in ``extra`` (target basis: 10 = 10 rounds/sec x 32 clients / 32 chips)
shows how the per-chip work rate compares without assuming anything about
multi-chip scaling. The reference itself publishes no throughput numbers
(BASELINE.md).
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

N_CLIENTS = 8
# 40 = STEPS*BATCH: under the default epoch batching (each client consumes
# exactly ceil(n_i/batch) shuffled batches per epoch, core/trainer.py) the
# round runs the same 5 full batches per client the r1/r2 benches timed
SAMPLES_PER_CLIENT = 40
VOLUME = (121, 145, 121)  # canonical ABCD volume (stored phase-decomposed)
BATCH = 8
STEPS = 5
TARGET_ROUNDS_PER_SEC = 10.0  # BASELINE.json north star (v4-32)
MODEL_KEY = "3dcnn_s2d"  # tests override with a CI-scale model


def _device_synth_data(n_clients, n, shape, key, uneven=False,
                       model_key=None, test_per_client=None):
    """Generate the federated dataset directly on device (HBM-resident).

    ``model_key`` picks the stored sample shape (phased for the s2d
    twins via the runner's S2D_SPECS table — the one source of truth);
    it defaults to the module-global MODEL_KEY for the bench's own use.
    Callers importing this from scripts should pass it explicitly (an r4
    A/B was invalidated by the global defaulting to the AlexNet twin).

    ``uneven=True`` draws per-client counts in [n/2, n] (deterministic) so
    ``_full_batches()`` is False and the masked-epoch machinery — per-
    example batch weights + no-op step selects, what real uneven ABCD
    cohorts exercise — is actually priced (ADVICE r3).

    ``test_per_client`` (default n//4): HBM control for big cohorts. The
    whole construction runs as ONE jitted program so the signal-planting
    add never materializes a second cohort-sized buffer — at C=32 the
    padded train cohort alone is ~11.7 GB of the v5e's 15.75 GB (the
    (…,8,61) phased tail lane-pads 61->128, ~2.1x), so a top-level
    two-step build OOMs before the first round."""
    from neuroimagedisttraining_tpu.data.types import FederatedData
    from neuroimagedisttraining_tpu.experiments.runner import S2D_SPECS
    from neuroimagedisttraining_tpu.ops.s2d import phased_sample_shape

    model_key = model_key or MODEL_KEY
    # volumes live in the TPU-fast phase-decomposed layout (ops/s2d.py),
    # stored bf16 (the compute dtype — skips the per-step convert/relayout);
    # random phased tensors are distributionally the same workload
    spec = S2D_SPECS.get(model_key)
    if spec is not None:
        sshape = phased_sample_shape(shape, kernel=spec[0], pad=spec[1])
    else:
        sshape = tuple(shape) + (1,)
    m = test_per_client or max(4, n // 4)

    def build(k):
        kx, ky, ktx, kty = jax.random.split(k, 4)

        def planted(kk_x, kk_y, rows):
            y = jax.random.bernoulli(
                kk_y, 0.5, (n_clients, rows)).astype(jnp.int32)
            x = jax.random.normal(
                kk_x, (n_clients, rows) + sshape, jnp.bfloat16)
            # plant a mean-shift signal so losses stay realistic
            shift = y[(...,) + (None,) * len(sshape)].astype(x.dtype)
            return x + 0.75 * (shift * 2 - 1), y

        x, y = planted(kx, ky, n)
        # independent test draw (same planted distribution) instead of a
        # slice-copy of train rows: a slice would briefly hold train +
        # test + slice temp, and cannot be smaller than n//4 rows without
        # changing the train cohort
        xt, yt = planted(ktx, kty, m)
        return x, y, xt, yt

    x, y, xt, yt = jax.jit(build)(key)
    if uneven:
        counts = jnp.asarray(
            np.random.RandomState(0).randint(n // 2, n + 1, n_clients),
            jnp.int32)
    else:
        counts = jnp.full((n_clients,), n, jnp.int32)
    return FederatedData(
        x_train=x, y_train=y, n_train=counts,
        x_test=xt, y_test=yt,
        n_test=jnp.full((n_clients,), m, jnp.int32),
        class_num=2,
    )


def _sync_state(state):
    """Wait for the device: fetch one scalar that depends on the state."""
    leaves = jax.tree_util.tree_leaves(
        getattr(state, "global_params", state))
    return float(leaves[0].sum())


def _emit_result(result):
    """Print the one-JSON-line contract AND append the result to the
    durable ``results/bench_history.jsonl`` trajectory (metric, value,
    extra, git SHA) that ``obs/regress.py`` / ``scripts/perf_gate.py``
    gate against. History append is best-effort: a read-only checkout
    must never fail the bench."""
    print(json.dumps(result))
    try:
        import os

        from neuroimagedisttraining_tpu.obs import regress

        root = os.path.dirname(os.path.abspath(__file__))
        regress.append_history(
            os.path.join(root, "results", "bench_history.jsonl"),
            result, source="bench", repo_root=root)
    except Exception as e:  # pragma: no cover - disk/permissions
        import sys

        # stderr, NOT stdout: the one-JSON-line stdout contract feeds
        # `bench.py | tail -1 | perf_gate.py --from-json -`
        print(f"# bench history append skipped: {e}", file=sys.stderr,
              flush=True)
    return result


def _timed_rounds(algo, state, n_rounds=10, eval_every_round=False):
    """Shared timing harness: one warmup/compile round, then n timed.
    ``eval_every_round`` also runs the full per-round eval protocol inside
    the timed region (frequency_of_the_test=1 — the reference evaluates
    every round by default, sailentgrads_api.py:141-143), so the returned
    rate prices the O(clients) eval cost instead of footnoting it. Since
    r5 that protocol includes BOTH halves of the reference's
    _test_on_all_clients: the global model on every client's local test
    set AND every client's personal model on its own test set
    (sailentgrads_api.py:238,262-283) — the personal half carries
    per-client weights, so it cannot use the 80-wide shared-params
    batching the global half gets.

    Metric fetches are delayed ONE round (mirrored in FedAlgorithm.run):
    a blocking per-round scalar fetch would drain the device queue before
    the next round is dispatched — deferring the host transfer by one
    round keeps the queue full while still fetching every round's
    metrics."""
    def _acc(ev):
        return ev["global_acc"] if "global_acc" in ev else ev["personal_acc"]

    from neuroimagedisttraining_tpu.obs import metrics as obs_metrics

    # ownership: the harness CONSUMES the state chain (run_round donates
    # under donate_state); callers re-running several harnesses from one
    # saved state pass algo.clone_state(state) — the borrow API
    state, _ = algo.run_round(state, 0)
    if eval_every_round:
        float(_acc(algo.evaluate(state)))  # compile outside timed region
    _sync_state(state)
    prev = None
    # the timed section lives in the obs registry (obs/metrics.py): the
    # rate is computed from the registry's recorded section time, so
    # repeated harness calls also leave a timing distribution behind
    reg = obs_metrics.get_registry()
    with reg.timer("bench_timed_rounds" +
                   ("_eval" if eval_every_round else "")) as tm:
        for r in range(1, n_rounds + 1):
            state, _ = algo.run_round(state, r)
            if eval_every_round:
                if prev is not None:
                    float(_acc(prev))
                prev = algo.evaluate(state)
        if prev is not None:
            float(_acc(prev))
        _sync_state(state)
    return n_rounds / tm.elapsed


def _timed_rounds_fused(algo, state, n_rounds=10, eval_every=0):
    """Timing harness for the fused round loop (run_rounds_fused): the
    whole timed region is ONE K-round jitted program — dispatch, then
    materialize every round's metrics at the end, exactly what the
    product's ``run(fuse_rounds=K)`` driver does per block. Three warmups
    replay the TIMED call verbatim (same start_round); the timed block
    runs rounds [K, 2K) from the same initial state."""
    from neuroimagedisttraining_tpu.obs import metrics as obs_metrics

    # ownership: each fused dispatch CONSUMES its input state under
    # donate_state, and the warmups + timed call all replay the SAME
    # call — so every dispatch gets a borrowed clone (cloned OUTSIDE
    # the timed region; the caller's state survives for later cells)
    donating = getattr(algo, "_donate", False)

    def borrowed():
        return algo.clone_state(state) if donating else state

    for w in range(3):
        state_w, ys = algo.run_rounds_fused(borrowed(), n_rounds,
                                            n_rounds,
                                            eval_every=eval_every)
        ys.materialize()
        _sync_state(state_w)
    s_in = borrowed()
    with obs_metrics.get_registry().timer("bench_timed_rounds_fused") \
            as tm:
        state, ys = algo.run_rounds_fused(s_in, n_rounds, n_rounds,
                                          eval_every=eval_every)
        # one transfer materializes every round's metrics; the packed
        # stack is a scan output, so its arrival proves the block completed
        ys.materialize()
    return n_rounds / tm.elapsed


def main(uneven: bool = False, test_per_client: int = None):
    from neuroimagedisttraining_tpu.algorithms import SalientGrads
    from neuroimagedisttraining_tpu.core.state import HyperParams
    from neuroimagedisttraining_tpu.models import create_model

    data = _device_synth_data(
        N_CLIENTS, SAMPLES_PER_CLIENT, VOLUME, jax.random.PRNGKey(0),
        uneven=uneven, test_per_client=test_per_client,
    )
    model = create_model(MODEL_KEY, num_classes=1)
    import os
    hp = HyperParams(
        lr=1e-3, lr_decay=0.998, momentum=0.9, weight_decay=5e-4,
        grad_clip=10.0, local_epochs=1, steps_per_epoch=STEPS,
        batch_size=BATCH,
        # default: the product's reference-exact epoch batching;
        # BENCH_BATCHING=replacement isolates its cost for A/B
        batching=os.environ.get("BENCH_BATCHING", "epoch"),
    )
    # On fewer devices than clients, chunk client concurrency to fit HBM
    # (see FedAlgorithm._vmap_clients); a pod runs the full client vmap.
    n_dev = len(jax.devices())
    # Full client vmap: XLA folds the client axis into the conv batch dim
    # (effective batch 64), ~3x the MXU throughput of per-client chunks.
    # Fits single-chip HBM because volumes are stored channel-less (a
    # resident (...,121,1) cohort would tile-pad 8-16x in HBM).
    # per-client weights block cross-client conv batching, so chunked
    # concurrency only adds memory pressure: chunk=1 measured fastest on a
    # single chip (1.40 r/s vs 1.25 at chunk=4; chunk=8 OOMs). On a pod
    # (device per client) the full vmap shards clients across chips.
    chunk = None if n_dev >= N_CLIENTS else 1
    mesh = None
    if n_dev > 1:
        # multi-chip: shard the client axis over the devices so the SAME
        # script measures the real distributed round (vmapped local train
        # per chip + cross-chip weighted-sum aggregation over ICI)
        from neuroimagedisttraining_tpu.parallel import (
            make_mesh,
            shard_over_clients,
        )
        from neuroimagedisttraining_tpu.parallel.mesh import (
            fit_client_devices,
        )

        rows = fit_client_devices(N_CLIENTS, n_dev)
        if rows > 1:
            mesh = make_mesh(rows)
            data = shard_over_clients(data, mesh)
            # full client vmap: anything else (lax.map chunking) would
            # serialize clients and idle the other chips; per-chip
            # concurrency is N_CLIENTS/rows
            chunk = None
    import os
    if os.environ.get("BENCH_CHUNK"):  # perf-tuning override
        chunk = int(os.environ["BENCH_CHUNK"]) or None
    remat = bool(int(os.environ.get("BENCH_REMAT", "0")))
    fused = bool(int(os.environ.get("BENCH_FUSED", "0")))
    # donate_state: the state-ownership protocol (the product default —
    # the round's [C, model] stack aliases in place instead of being
    # re-allocated); harness re-runs from `state` go through the
    # clone_state borrow API below
    algo = SalientGrads(model, data, hp, loss_type="bce", frac=1.0, seed=0,
                        client_chunk=chunk, dense_ratio=0.5,
                        itersnip_iterations=1, compute_dtype="bfloat16",
                        remat_local=remat, fused_kernels=fused,
                        donate_state=True)
    state = algo.init_state(jax.random.PRNGKey(0))  # includes the SNIP pass
    def _try_fused(a, s, **kw):
        """Fused-spelling timing, or None when the K-round program does
        not fit: at C=32 full volume XLA materializes an extra full-
        cohort copy for the scan's while loop (the unfused per-round
        program does not), so the fused spelling OOMs exactly when the
        cohort fills HBM — fall back to the loop numbers and record the
        gap."""
        try:
            return _timed_rounds_fused(a, s, **kw)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e) and \
                    "Ran out of memory" not in str(e):
                raise
            print("# fused spelling OOMs at this scale; loop numbers only",
                  flush=True)
            return None

    rps_loop = _timed_rounds(algo, algo.clone_state(state))
    # eval-inclusive rate: the same workload at frequency_of_the_test=1
    # — since r5 this prices the FULL reference protocol (global +
    # per-client personal models, sailentgrads_api.py:262-283)
    rps_with_eval_loop = _timed_rounds(algo, algo.clone_state(state),
                                       n_rounds=8,
                                       eval_every_round=True)
    # fused round loop (run_rounds_fused): K rounds as one program —
    # semantically identical (tests/test_fused_rounds.py), dispatch/fetch
    # amortized. The headline is the better of the two spellings; both
    # are recorded. (_timed_rounds_fused borrows per dispatch itself.)
    rps_fused = _try_fused(algo, state, n_rounds=10)
    rps_with_eval_fused = _try_fused(algo, state, n_rounds=8, eval_every=1)
    # the donated fused runs rebound algo.data to the aliased outputs;
    # re-read it so the instances below see valid arrays, not the
    # donated originals
    data = algo.data
    # --eval_cache cell: the in-state incremental personal eval — the
    # eval_every=1 protocol pays O(trained-clients) forwards per round
    # instead of O(C) per eval (full participation here makes it a
    # wash on FORWARD count; the win it prices is the per-round eval
    # program shrinking to the cache re-reduce)
    algo_ec = SalientGrads(model, data, hp, loss_type="bce", frac=1.0,
                           seed=0, client_chunk=chunk, dense_ratio=0.5,
                           itersnip_iterations=1,
                           compute_dtype="bfloat16",
                           remat_local=remat, fused_kernels=fused,
                           donate_state=True, eval_cache=True)
    state_ec = algo_ec.init_state(jax.random.PRNGKey(0))
    rps_eval_cache_fused = _try_fused(algo_ec, state_ec, n_rounds=8,
                                      eval_every=1)
    rps_eval_cache_loop = _timed_rounds(
        algo_ec, algo_ec.clone_state(state_ec), n_rounds=8,
        eval_every_round=True)
    data = algo_ec.data
    rps_eval_cache = max(x for x in (rps_eval_cache_loop,
                                     rps_eval_cache_fused)
                         if x is not None)
    # secondary: the global-only half (what r3/r4 benches priced) — a
    # personal-less instance isolates the personal half's cost
    algo_g = SalientGrads(model, data, hp, loss_type="bce", frac=1.0,
                          seed=0, client_chunk=chunk, dense_ratio=0.5,
                          itersnip_iterations=1, compute_dtype="bfloat16",
                          remat_local=remat, fused_kernels=fused,
                          track_personal=False, donate_state=True)
    state_g = algo_g.init_state(jax.random.PRNGKey(0))
    # best-of-both-spellings, SAME selection rule as the full-protocol
    # number — mixing spellings would corrupt the personal-half delta
    # these two numbers exist to isolate
    rps_g_fused = _try_fused(algo_g, state_g, n_rounds=8, eval_every=1)
    rps_g_loop = _timed_rounds(algo_g, state_g, n_rounds=8,
                               eval_every_round=True)
    rps_eval_global_only = max(
        x for x in (rps_g_loop, rps_g_fused) if x is not None)
    rounds_per_sec = max(x for x in (rps_loop, rps_fused) if x is not None)
    rps_with_eval = max(x for x in (rps_with_eval_loop, rps_with_eval_fused)
                        if x is not None)
    samples_per_round = N_CLIENTS * STEPS * BATCH
    n_chips = len(jax.devices())
    # target basis: 10 rounds/sec x 32 clients / 32 chips (v4-32 north
    # star) = 10 client-rounds/sec/chip; see module docstring
    client_rounds_per_sec_per_chip = rounds_per_sec * N_CLIENTS / n_chips
    result = {
        "metric": (
            f"salientgrads_rounds_per_sec_abcd_alexnet3d_{N_CLIENTS}clients"
            if MODEL_KEY == "3dcnn_s2d" else
            f"salientgrads_rounds_per_sec_abcd_{MODEL_KEY}_"
            f"{N_CLIENTS}clients")
        + ("_uneven" if uneven else ""),
        "value": round(rounds_per_sec, 4),
        "unit": "rounds/sec",
        "vs_baseline": round(rounds_per_sec / TARGET_ROUNDS_PER_SEC, 4),
        "extra": {
            # full reference eval protocol (global + personal halves)
            "rounds_per_sec_eval_every_1": round(rps_with_eval, 4),
            # same protocol with the in-state incremental eval cache
            # (--eval_cache): the RESULTS.md Round-14 A/B cell
            "rounds_per_sec_eval_every_1_eval_cache": round(
                rps_eval_cache, 4),
            # global-only eval (the r3/r4 definition), kept as secondary
            "rounds_per_sec_eval_every_1_global_only": round(
                rps_eval_global_only, 4),
            "rounds_per_sec_python_loop": round(rps_loop, 4),
            # None = the fused spelling OOMs at this scale (see _try_fused)
            "rounds_per_sec_fused": (
                round(rps_fused, 4) if rps_fused is not None else None),
            "rounds_per_sec_eval_every_1_python_loop": round(
                rps_with_eval_loop, 4),
            "rounds_per_sec_eval_every_1_fused": (
                round(rps_with_eval_fused, 4)
                if rps_with_eval_fused is not None else None),
            "client_samples_per_sec": round(rounds_per_sec * samples_per_round, 2),
            "client_rounds_per_sec_per_chip": round(
                client_rounds_per_sec_per_chip, 2),
            "baseline_basis": "10 client-rounds/sec/chip (v4-32 north star)",
            "n_devices": n_chips,
            "client_mesh_devices": (
                int(mesh.shape["clients"]) if mesh is not None else 1),
            "volume": list(VOLUME),
            "clients": N_CLIENTS,
            "local_steps": STEPS,
            "batch": BATCH,
        },
    }
    return _emit_result(result)


def tracked_config(name: str):
    """Secondary BASELINE.json tracked configs (BENCH_CONFIG=<name>);
    the default invocation keeps the primary one-JSON-line contract."""
    import os

    global MODEL_KEY, VOLUME, N_CLIENTS, BATCH, STEPS
    if name == "cifar":
        # the reference's canonical CIFAR config (Jobs/salientgrads...
        # 70sps.sh:40-53): SalientGrads, resnet18(GroupNorm), 100 clients,
        # frac 0.1 (10 trained/round), bs 16, 5 local epochs, dir alpha=0.3
        # class skew — timed on a CIFAR-shaped synthetic cohort (the real
        # batches are not in this environment; timing depends on shapes,
        # not labels). 500 samples/client = the 50k/100 split.
        import numpy as np

        from neuroimagedisttraining_tpu.algorithms import SalientGrads
        from neuroimagedisttraining_tpu.core.state import HyperParams
        from neuroimagedisttraining_tpu.data.types import FederatedData
        from neuroimagedisttraining_tpu.models import create_model

        n_clients, n_per, bs, epochs = 100, 500, 16, 5
        kx, ky = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (n_clients, n_per, 32, 32, 3),
                              jnp.bfloat16)
        y = jax.random.randint(ky, (n_clients, n_per), 0, 10)
        m = 100  # proportional test resample scale (10k/100)
        from neuroimagedisttraining_tpu.data.cifar import (
            CIFAR10_MEAN,
            CIFAR10_STD,
            black_pad_value,
        )

        data = FederatedData(
            x_train=x, y_train=y,
            n_train=jnp.full((n_clients,), n_per, jnp.int32),
            x_test=x[:, :m], y_test=y[:, :m],
            n_test=jnp.full((n_clients,), m, jnp.int32), class_num=10,
            # the reference augments every CIFAR training batch
            # (cifar10/data_loader.py:46-50) — price it here too (r4)
            aug_pad_value=black_pad_value(CIFAR10_MEAN, CIFAR10_STD))
        model = create_model("resnet18", num_classes=10)
        hp = HyperParams(lr=0.1, lr_decay=0.998, momentum=0.9,
                         weight_decay=5e-4, grad_clip=10.0,
                         local_epochs=epochs,
                         steps_per_epoch=-(-n_per // bs), batch_size=bs)
        # chunk=1 measured fastest (0.662 r/s vs 0.592 full vmap on the
        # v5e): per-client weights block cross-client conv batching, as on
        # the ABCD path. BENCH_CHUNK overrides for tuning.
        chunk = int(os.environ.get("BENCH_CHUNK", "1")) or None
        algo = SalientGrads(model, data, hp, loss_type="ce", frac=0.1,
                            seed=0, dense_ratio=0.3, itersnip_iterations=1,
                            compute_dtype="bfloat16", client_chunk=chunk)
        state = algo.init_state(jax.random.PRNGKey(0))
        rps = _timed_rounds(algo, state, n_rounds=3)
        result = {
            "metric": ("salientgrads_rounds_per_sec_cifar_resnet18gn_"
                       "100clients_frac0.1"),
            "value": round(rps, 4),
            "unit": "rounds/sec",
            "vs_baseline": 0.0,  # reference publishes no number
            "extra": {"clients": n_clients, "trained_per_round": 10,
                      "local_epochs": epochs, "batch": bs,
                      "steps_per_epoch": -(-n_per // bs)},
        }
        return _emit_result(result)
    if name == "resnet3d":
        # 3D-ResNet on full-size volumes (BASELINE "3D-ResNet full cohort").
        # Phased-stem twin since r4: the k3/s2/p3 stem at C_in=1 was 66% of
        # the step; the s2d restatement measures 0.80 vs 0.60 r/s dense
        # (exactness-tested, tests/test_s2d.py; RESULTS.md tracked table).
        # BENCH_DENSE=1 runs the reference-layout model for A/B.
        MODEL_KEY, VOLUME = "3dresnet_s2d", (121, 145, 121)
        if os.environ.get("BENCH_DENSE"):
            MODEL_KEY = "3dresnet"
        return main()
    if name == "agg":
        # the aggregation term at REAL parameter scale on the REAL chip
        # (VERDICT r3 item 2): per weighted-sum of the 2.58M-param
        # AlexNet3D tree over 32 stacked client models. On one chip there
        # is no ICI hop — this is the HBM-bound contraction floor; the
        # cross-chip all-reduce adds ~0.2 ms at v4 ICI (BASELINE.md),
        # and the CPU-mesh dryrun measures GSPMD-vs-shard_map parity.
        import sys

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from __graft_entry__ import _agg_realparams_probe

        from neuroimagedisttraining_tpu.parallel import make_mesh

        # largest mesh <= 8 devices that divides the 32-client axis
        # (shard_map needs exact divisibility)
        n_dev = max(d for d in (8, 4, 2, 1) if d <= len(jax.devices()))
        mesh = make_mesh(n_dev)
        d = _agg_realparams_probe(mesh, n_dev, raw=True)
        # the agg-subsystem micro-bench (parallel/collectives.py): dense
        # vs bucketed-psum vs low-precision wires vs mask-aware sparse,
        # same 32-client real-parameter workload (honored 0.5-density
        # SNIP-style mask) — the before/after behind --agg_impl
        from neuroimagedisttraining_tpu.parallel.collectives import (
            agg_microbench,
        )

        for k, v in agg_microbench(mesh if n_dev > 1 else None).items():
            # the probe and the microbench share workload-descriptor keys
            # (n_params/n_clients/n_devices) by construction; if their
            # defaults ever diverge, keep both instead of silently
            # relabeling the probe's measurements
            if k in d and d[k] != v:
                d[f"microbench_{k}"] = v
            else:
                d[k] = v
        result = {
            "metric": "weighted_sum_aggregation_ms_alexnet3d_32clients",
            "value": round(d["gspmd_ms"], 3),
            "unit": "ms/aggregation",
            "vs_baseline": 0.0,  # term measurement, not a rate
            "extra": {k: (round(v, 3) if isinstance(v, float) else v)
                      for k, v in d.items()},
        }
        return _emit_result(result)
    if name == "clients32":
        # the primary workload at the NORTH-STAR client count (C=32) on
        # the one real chip (VERDICT r4 weak #4): measures the scan-length
        # and cohort-residency scaling directly instead of assuming
        # linearity from the 8-client cell. The padded train cohort is
        # ~11.7 GB of 15.75 GB HBM, so the test split shrinks to
        # 4 volumes/client (eval-inclusive extras are therefore NOT
        # comparable to the 8-client cell's 10-volume test shards; the
        # primary eval-free rate is the tracked number).
        N_CLIENTS = 32
        return main(test_per_client=4)
    if name == "cohort":
        # Cohort-scale cell (ROADMAP Open item 2 / ISSUE 9): C=32/64/
        # 128/256 synthetic small-model cohorts on one chip through the
        # DONATED fused path with the in-state eval cache — the
        # "hundreds of clients per chip" configuration whose OOM line
        # this PR's fused-carry restructure moves. Per-round trained
        # work is held constant (8 clients/round at every C) so the
        # sweep isolates cohort RESIDENCY: rounds/sec plus the peak-
        # device-memory ledger (obs/memory.py — memory_stats peak on
        # TPU/GPU, live-arrays watermark on CPU), both appended to the
        # gated results/bench_history.jsonl (perf_gate prefix rules:
        # cohort_mem_bytes_* lower-is-better).
        from neuroimagedisttraining_tpu.algorithms import FedAvg
        from neuroimagedisttraining_tpu.core.state import HyperParams
        from neuroimagedisttraining_tpu.models import create_model
        from neuroimagedisttraining_tpu.obs import memory as obs_memory
        from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
        from neuroimagedisttraining_tpu.obs import regress

        sizes = tuple(int(c) for c in os.environ.get(
            "BENCH_COHORTS", "32,64,128,256").split(","))
        n_per, vol = 8, (16, 16, 16)
        block = int(os.environ.get("BENCH_COHORT_BLOCK", "4"))
        rounds = int(os.environ.get("BENCH_COHORT_ROUNDS", "8"))
        # at least one whole block, and whole blocks only (a remainder
        # would make the timed region's round count disagree with the
        # dispatched blocks; flooring to zero would append a 0.0
        # rounds/sec cell to the gated history)
        rounds = max(block, rounds - rounds % block)
        hp = HyperParams(lr=1e-3, momentum=0.9, local_epochs=1,
                         steps_per_epoch=2, batch_size=4)
        model = create_model("small3dcnn", num_classes=1)
        root = os.path.dirname(os.path.abspath(__file__))
        history = os.path.join(root, "results", "bench_history.jsonl")
        cells = {}
        for n_clients in sizes:
            data = _device_synth_data(
                n_clients, n_per, vol, jax.random.PRNGKey(0),
                model_key="small3dcnn", test_per_client=4)
            algo = FedAvg(model, data, hp, loss_type="bce",
                          frac=min(1.0, 8.0 / n_clients), seed=0,
                          compute_dtype="bfloat16", donate_state=True,
                          eval_cache=True)
            state = algo.init_state(jax.random.PRNGKey(0))
            # warmup block (compile), then timed whole blocks
            state, ys = algo.run_rounds_fused(state, 0, block,
                                              eval_every=1)
            ys.materialize()
            _sync_state(state)
            with obs_metrics.get_registry().timer(
                    f"bench_cohort_c{n_clients}") as tm:
                r0 = block
                while r0 < block + rounds:
                    state, ys = algo.run_rounds_fused(
                        state, r0, block, eval_every=1)
                    r0 += block
                ys.materialize()
                _sync_state(state)
            rps = rounds / tm.elapsed
            devs = obs_memory.device_memory()
            # the GATED per-cell number is bytes_in_use sampled while
            # THIS cohort is live (earlier cohorts were deleted, so it
            # attributes to this C). peak_bytes_in_use is a PROCESS-
            # LIFETIME high-watermark on memory_stats backends — it
            # never resets between cells, so a big early cell would
            # bleed into every later cell's gate; it stays
            # informational in the extras only.
            in_use = max((d["bytes_in_use"] for d in devs), default=0)
            peak = max((d.get("peak_bytes_in_use", d["bytes_in_use"])
                        for d in devs), default=0)
            cells[f"c{n_clients}"] = {
                "rounds_per_sec": round(rps, 4),
                "mem_bytes": int(in_use),
                "mem_peak_process_bytes": int(peak),
                "mem_source": devs[0]["source"] if devs
                else "unavailable",
            }
            for metric, value, unit in (
                    (f"cohort_rounds_per_sec_c{n_clients}", rps,
                     "rounds/sec"),
                    (f"cohort_mem_bytes_c{n_clients}", float(in_use),
                     "bytes")):
                try:
                    regress.append_history(
                        history, {"metric": metric, "value": value,
                                  "unit": unit},
                        source="bench_cohort", repo_root=root)
                except Exception as e:  # read-only checkout
                    import sys

                    print(f"# cohort history append skipped: {e}",
                          file=sys.stderr, flush=True)
            del data, algo, state, ys  # free this cohort before the next
        # Population cells (ISSUE 14): C=1k/4k/16k through the
        # --client_store host streamed-residency path — only the S=8
        # sampled rows (and the fused block's row union) ever reach
        # device, so HBM stays flat in C while the resident cells above
        # grow linearly. Data is HOST numpy (the residency contract:
        # per-round slabs device_put on demand), volumes shrink to 8^3 /
        # 2 samples per client so the 16k cohort's host footprint stays
        # tens of MB. Three gated series per cell: rounds/sec, the
        # device-memory ledger (expected FLAT — the acceptance curve in
        # RESULTS.md), and the new store_gather_ms_* host->device
        # gather timing (per-round mean; lower-is-better prefix).
        from neuroimagedisttraining_tpu.data.synthetic import (
            make_synthetic_federated,
        )

        pop_sizes = tuple(int(c) for c in os.environ.get(
            "BENCH_POP_COHORTS", "1024,4096,16384").split(",") if c)
        for n_clients in pop_sizes:
            data = make_synthetic_federated(
                seed=0, n_clients=n_clients, samples_per_client=2,
                test_per_client=1, sample_shape=(8, 8, 8, 1),
                class_num=2, loss_type="bce")
            algo = FedAvg(model, data, hp, loss_type="bce",
                          frac=8.0 / n_clients, seed=0,
                          donate_state=True,
                          client_store="host", store_hot_clients=64)
            state = algo.init_state(jax.random.PRNGKey(0))
            # warmup block (compile; store mode refuses in-graph eval,
            # so blocks run eval_every=0), then timed whole blocks
            state, ys = algo.run_rounds_fused(state, 0, block,
                                              eval_every=0)
            ys.materialize()
            _sync_state(state)
            g0 = algo._store.stats()["store_gather_ms"]
            with obs_metrics.get_registry().timer(
                    f"bench_pop_c{n_clients}") as tm:
                r0 = block
                while r0 < block + rounds:
                    state, ys = algo.run_rounds_fused(
                        state, r0, block, eval_every=0)
                    r0 += block
                ys.materialize()
                _sync_state(state)
            rps = rounds / tm.elapsed
            gather_ms = (algo._store.stats()["store_gather_ms"] - g0) \
                / rounds
            devs = obs_memory.device_memory()
            in_use = max((d["bytes_in_use"] for d in devs), default=0)
            cells[f"pop_c{n_clients}"] = {
                "rounds_per_sec": round(rps, 4),
                "mem_bytes": int(in_use),
                "store_gather_ms": round(gather_ms, 3),
                "mem_source": devs[0]["source"] if devs
                else "unavailable",
            }
            for metric, value, unit in (
                    (f"cohort_rounds_per_sec_pop_c{n_clients}", rps,
                     "rounds/sec"),
                    (f"cohort_mem_bytes_pop_c{n_clients}",
                     float(in_use), "bytes"),
                    (f"store_gather_ms_c{n_clients}", gather_ms,
                     "ms/round")):
                try:
                    regress.append_history(
                        history, {"metric": metric, "value": value,
                                  "unit": unit},
                        source="bench_cohort", repo_root=root)
                except Exception as e:  # read-only checkout
                    import sys

                    print(f"# cohort history append skipped: {e}",
                          file=sys.stderr, flush=True)
            del data, algo, state, ys
        biggest = f"c{max(sizes)}"
        result = {
            "metric": ("fedavg_cohort_rounds_per_sec_small3dcnn_"
                       f"{biggest}_fused_evcache"),
            "value": cells[biggest]["rounds_per_sec"],
            "unit": "rounds/sec",
            "vs_baseline": 0.0,  # scaling cell, not a rate target
            "extra": {"cells": cells, "block": block,
                      "trained_per_round": 8, "volume": list(vol),
                      "pop_volume": [8, 8, 8],
                      "n_devices": len(jax.devices())},
        }
        return _emit_result(result)
    if name == "uneven":
        # primary workload with uneven shards ([20,40] samples/client): the
        # masked epoch path — per-example weights, no-op step selects —
        # priced instead of assumed (ADVICE r3; the primary cell's equal
        # 40-sample shards take the full_batches fast path)
        return main(uneven=True)
    if name == "byzantine":
        # Byzantine-robust 64-client FedAvg with weak-DP defense
        from neuroimagedisttraining_tpu.algorithms import FedAvg
        from neuroimagedisttraining_tpu.core.state import HyperParams
        from neuroimagedisttraining_tpu.models import create_model
        from neuroimagedisttraining_tpu.robust import RobustAggregator

        MODEL_KEY = "small3dcnn"  # shallow CNN; channel-ful storage path
        n_clients = 64
        data = _device_synth_data(n_clients, STEPS * BATCH, (61, 73, 61),
                                  jax.random.PRNGKey(0))
        model = create_model("small3dcnn", num_classes=1)
        hp = HyperParams(lr=1e-3, momentum=0.9, local_epochs=1,
                         steps_per_epoch=STEPS, batch_size=BATCH)
        # chunk=16 measured best at the real shape (r4 interleaved sweep:
        # 8/16/32 = 0.60/0.63/0.63 r/s; the full 64-client vmap did not
        # compile at this volume). Defense and the personal-model
        # stack are free (on/off within noise) — RESULTS.md r4 anatomy.
        algo = FedAvg(model, data, hp, loss_type="bce", frac=1.0, seed=0,
                      compute_dtype="bfloat16",
                      client_chunk=int(os.environ.get("BENCH_CHUNK", "16"))
                      or None,
                      defense=RobustAggregator("weak_dp", norm_bound=5.0,
                                               stddev=0.025))
        state = algo.init_state(jax.random.PRNGKey(0))
        rps = _timed_rounds(algo, state)
        result = {
            "metric": "byzantine_robust_fedavg_rounds_per_sec_64clients",
            "value": round(rps, 4),
            "unit": "rounds/sec",
            "vs_baseline": 0.0,  # no published number; tracked config
        }
        return _emit_result(result)
    raise SystemExit(f"unknown BENCH_CONFIG {name!r}")


if __name__ == "__main__":
    import os as _os

    from neuroimagedisttraining_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    cfg = _os.environ.get("BENCH_CONFIG", "")
    if cfg:
        tracked_config(cfg)
    else:
        main()
