#!/usr/bin/env python
"""Real-data validation runbook (VERDICT r3 item 8).

Every convergence number in RESULTS.md (section 4) is synthetic planted-signal because
the real cohorts are not in the build environment. When they ARE present,
this is the one command that validates the framework on them:

    python scripts/validate_real_data.py \
        [--abcd_h5 /path/final_dataset_3000subs.h5] \
        [--cifar_dir /path/with/cifar-10-batches-py] \
        [--tiny_dir /path/tiny-imagenet-200] \
        [--rounds 3] [--full]

Per dataset it runs:
  * ABCD — (a) a layout A/B: one FedAvg round from the same seed under
    --layout channels and --layout s2d must produce the same loss/accuracy
    (the TPU-fast phased-stem path is exactness-tested on synthetic
    volumes; this re-proves it on the real file), then (b) the canonical
    SalientGrads config (main_sailentgrads.py:36-109: 3DCNN, batch 16,
    lr 1e-3 decay 0.998, 2 local epochs, frac 0.5, dense_ratio 0.5, BCE)
    for --rounds rounds (--full: the reference's 200).
  * CIFAR-10 — the canonical CIFAR cell
    (Jobs/salientgradssparsitywith100iteration70sps.sh:40-53: resnet18(GN),
    dir alpha=0.3, batch 16, lr 0.1, 5 local epochs, 100 clients, frac
    0.1), training-time augmentation on (the reference default).
  * tiny-imagenet — same recipe at the tiny scale.

Prints one JSON summary line per dataset and exits non-zero on any
failure. `tests/test_real_data.py` runs the same entry skip-if-absent so
the suite shows a visible `SKIPPED (real ... not present)` marker.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _run(argv, algo=None):
    from neuroimagedisttraining_tpu.experiments.config import parse_args
    from neuroimagedisttraining_tpu.experiments.runner import run_experiment

    args = parse_args(argv)
    return run_experiment(args, algo)


def validate_abcd(h5_path: str, rounds: int) -> dict:
    import numpy as np

    out = {"dataset": "abcd", "path": h5_path}

    # (a) layout A/B: channels vs s2d from the same seed — one round each
    common = ["--algo", "fedavg", "--model", "3dcnn", "--dataset", "abcd",
              "--data_dir", h5_path, "--frac", "1.0", "--epochs", "1",
              "--batch_size", "4", "--comm_round", "1", "--seed", "0",
              "--client_chunk", "1", "--frequency_of_the_test", "1",
              "--results_dir", "", "--log_dir", "", "--track_personal", "0",
              "--final_finetune", "0"]
    res_ch = _run(common + ["--layout", "channels"])
    res_s2d = _run(common + ["--layout", "s2d"])
    acc_ch = res_ch["history"][-1]["global_acc"]
    acc_s2d = res_s2d["history"][-1]["global_acc"]
    out["layout_ab"] = {"channels_acc": acc_ch, "s2d_acc": acc_s2d}
    # same seed + exact stem equivalence => identical training; allow
    # float32 reduction-order noise across the two compiled programs
    if abs(acc_ch - acc_s2d) > 0.02:
        raise SystemExit(
            f"layout A/B mismatch on real ABCD: channels acc {acc_ch:.4f} "
            f"vs s2d acc {acc_s2d:.4f} — the phased-stem path deviates on "
            "this cohort; file a bug with this file's site histogram")

    # (b) canonical SalientGrads config (main_sailentgrads.py:36-109)
    t0 = time.time()
    res = _run([
        "--algo", "salientgrads", "--model", "3dcnn", "--dataset", "abcd",
        "--data_dir", h5_path, "--layout", "s2d",
        "--compute_dtype", "bfloat16", "--client_chunk", "1",
        "--frac", "0.5", "--epochs", "2", "--batch_size", "16",
        "--lr", "0.001", "--lr_decay", "0.998", "--dense_ratio", "0.5",
        "--comm_round", str(rounds), "--seed", "0",
        "--frequency_of_the_test", "1",
        "--results_dir", "", "--log_dir", ""])
    hist = res["history"]
    out["canonical"] = {
        "rounds": len(hist),
        "rounds_per_sec": round(len(hist) / max(1e-9, time.time() - t0), 4),
        "final_global_acc": hist[-1].get("global_acc"),
        "final_train_loss": hist[-1].get("train_loss"),
    }
    accs = [h["global_acc"] for h in hist
            if h.get("global_acc") is not None]
    if not accs or not np.isfinite(accs[-1]):
        raise SystemExit("canonical ABCD run produced no finite accuracy")
    return out


def validate_cifar(cifar_dir: str, rounds: int) -> dict:
    t0 = time.time()
    res = _run([
        "--algo", "salientgrads", "--model", "resnet18", "--dataset",
        "cifar10", "--data_dir", cifar_dir,
        "--partition_method", "dir", "--partition_alpha", "0.3",
        "--client_num_in_total", "100", "--frac", "0.1",
        "--epochs", "5", "--batch_size", "16", "--lr", "0.1",
        "--lr_decay", "0.998", "--dense_ratio", "0.3",
        "--compute_dtype", "bfloat16", "--client_chunk", "1",
        "--comm_round", str(rounds), "--seed", "0",
        "--frequency_of_the_test", "1",
        "--results_dir", "", "--log_dir", ""])
    hist = res["history"]
    return {"dataset": "cifar10", "path": cifar_dir,
            "rounds": len(hist),
            "rounds_per_sec": round(len(hist) / max(1e-9, time.time() - t0),
                                    4),
            "final_global_acc": hist[-1].get("global_acc"),
            "augmented": True}


def validate_tiny(tiny_dir: str, rounds: int) -> dict:
    t0 = time.time()
    res = _run([
        "--algo", "fedavg", "--model", "resnet18", "--dataset",
        "tiny_imagenet", "--data_dir", tiny_dir,
        "--partition_method", "dir", "--partition_alpha", "0.3",
        "--client_num_in_total", "16", "--frac", "0.25",
        "--epochs", "1", "--batch_size", "16", "--lr", "0.1",
        "--comm_round", str(rounds), "--seed", "0",
        "--frequency_of_the_test", "1", "--track_personal", "0",
        "--final_finetune", "0",
        "--results_dir", "", "--log_dir", ""])
    hist = res["history"]
    return {"dataset": "tiny_imagenet", "path": tiny_dir,
            "rounds": len(hist),
            "rounds_per_sec": round(len(hist) / max(1e-9, time.time() - t0),
                                    4),
            "final_global_acc": hist[-1].get("global_acc")}


def discover_abcd(root: str):
    hits = sorted(glob.glob(os.path.join(root, "final_dataset_*subs.h5")))
    return hits[-1] if hits else None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--abcd_h5", default="",
                   help="preprocessed cohort final_dataset_<N>subs.h5")
    p.add_argument("--cifar_dir", default="",
                   help="dir containing cifar-10-batches-py")
    p.add_argument("--tiny_dir", default="",
                   help="tiny-imagenet-200 root (train/ + val/)")
    p.add_argument("--data_root", default="data",
                   help="auto-discovery root when the explicit paths are "
                        "not given")
    p.add_argument("--rounds", type=int, default=3,
                   help="rounds per canonical config (smoke default)")
    p.add_argument("--full", action="store_true",
                   help="reference-length runs (ABCD 200 / CIFAR 500 "
                        "rounds, main_sailentgrads.py:90 / Jobs sweep)")
    args = p.parse_args(argv)

    abcd = args.abcd_h5 or discover_abcd(args.data_root)
    cifar = args.cifar_dir or (
        args.data_root if os.path.isdir(
            os.path.join(args.data_root, "cifar-10-batches-py")) else "")
    tiny = args.tiny_dir or (
        os.path.join(args.data_root, "tiny-imagenet-200")
        if os.path.isdir(os.path.join(args.data_root, "tiny-imagenet-200"))
        else "")

    ran = 0
    if abcd and os.path.exists(abcd):
        r = args.rounds if not args.full else 200
        print(json.dumps(validate_abcd(abcd, r)))
        ran += 1
    else:
        print(json.dumps({"dataset": "abcd", "skipped":
                          "no final_dataset_*subs.h5 found"}))
    if cifar:
        r = args.rounds if not args.full else 500
        print(json.dumps(validate_cifar(cifar, r)))
        ran += 1
    else:
        print(json.dumps({"dataset": "cifar10", "skipped":
                          "no cifar-10-batches-py found"}))
    if tiny:
        print(json.dumps(validate_tiny(tiny, args.rounds)))
        ran += 1
    if not ran:
        print("no real datasets found — nothing validated", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
