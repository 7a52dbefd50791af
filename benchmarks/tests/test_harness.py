"""The CPU rehearsal: the harness's own functions at an 8^3 volume (the zoo's
CI model, a configuration no cell uses), on four virtual devices for the mesh
path. It proves control flow, the shape of the last line and the
``failed``/``correct`` logic; nothing it measures is a device number, and the
command itself has no such mode."""
import os
import shutil
import subprocess
import sys
import time

import pytest
from conftest import BENCH, MANIFEST, REPO
from test_reduce_trace import hand_built_trace

from benchmarks.lib import harness, manifest, peaks, reduce_trace

E2E = {"rounds_per_s", "peak_hbm_gib", "setup_s"}


def rehearse(manifest_path, cell, tmp_path, trace=False, seconds=0.5):
    return harness.run_cell(manifest_path, cell, seed=3, seconds=seconds,
                            trace=trace, t0=time.perf_counter(),
                            trace_dir=str(tmp_path / "trace"))


@pytest.mark.parametrize("cell,chips", [("tiny.train", 1),
                                        ("tiny.protocol", 1),
                                        ("tiny.mesh4", 4)])
def test_untraced_run(tiny_manifest, tmp_path, cell, chips):
    result, details = rehearse(tiny_manifest, cell, tmp_path)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["metrics"]) == E2E
    assert all(set(m) == {"value", "unit"} for m in result["metrics"].values())
    assert result["correct"] is True and result["failed"] == 0
    block = manifest.load_cell(tiny_manifest, cell).traffic["block_rounds"]
    assert result["attempted"] > 0 and result["attempted"] % block == 0
    assert result["attempted"] == details["window"]["rounds"]
    assert details["window"]["compiles"] == 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["count"] == chips
    assert details["state_check"]["cohort_devices"] == chips
    assert details["reference_check"]["ok"]
    assert abs(details["state_check"]["mask_density"] - 0.5) < 0.03
    if chips > 1:
        assert details["state_check"]["round_all_reduces"] > 0


def test_traced_run_reports_per_layer_metrics(tiny_manifest, tmp_path,
                                              monkeypatch):
    """On the CPU the profiler's trace has no TPU plane; the reduction is fed
    the hand-built trace, the rest of the traced path is the real one."""
    seen = {}

    def load(trace_dir, devices, rounds, op_names):
        assert reduce_trace.newest_xplane(trace_dir).endswith(".xplane.pb")
        seen.update(rounds=rounds, scoped=sum(
            "/local_train/" in v for v in op_names.values()))
        return hand_built_trace(rounds)

    monkeypatch.setattr(reduce_trace, "load", load)
    # the shares below are arithmetic on made-up times against made-up peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    result, details = rehearse(tiny_manifest, "tiny.protocol", tmp_path,
                               trace=True)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert seen["rounds"] == harness.TRACED_ROUNDS and seen["scoped"] > 0
    cell = manifest.load_cell(tiny_manifest, "tiny.protocol")
    names = {e["name"] for e, _ in cell.per_layer}
    assert set(result["metrics"]) <= names and not set(
        result["metrics"]) & E2E
    assert {"eval_ms_per_round", "local_train_ms_per_round",
            "device_idle_share", "train_mfu", "local_step_roofline",
            "cache_misses", "cohort_hbm_gib"} <= set(result["metrics"])
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert details["readers"]["roofline"]["layers"]
    assert result["correct"] and result["failed"] == 0


@pytest.mark.parametrize("fault", ["nan", "raise"])
def test_failed_rounds_are_counted(tiny_manifest, tmp_path, monkeypatch,
                                   fault):
    from neuroimagedisttraining_tpu.algorithms.base import FedAlgorithm

    real, calls = FedAlgorithm.run, []

    def flaky(self, *args, **kwargs):
        calls.append(1)
        if len(calls) == 5 and fault == "raise":
            raise RuntimeError("injected")
        state, history = real(self, *args, **kwargs)
        if len(calls) == 5:
            history[1]["train_loss"] = float("nan")
        return state, history

    monkeypatch.setattr(FedAlgorithm, "run", flaky)
    result, details = rehearse(tiny_manifest, "tiny.train", tmp_path)
    block = details["window"]["block_rounds"]
    if fault == "nan":
        assert result["failed"] == 1 and result["attempted"] > block
    else:   # the block that raised counts whole, and the window ends there
        assert result["failed"] == block
        assert result["attempted"] == details["window"]["rounds"] + block
        assert result["correct"] is False
    assert set(result["metrics"]) == E2E


def test_the_command_refuses_the_cpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
           "alexnet3d_abcd.train", "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    out = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 1 and out.stdout == ""
    assert "no TPU" in out.stderr
    # and a directory with the benchmark alone has no program to run
    shutil.copy(MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    cmd[1] = str(tmp_path / "benchmarks" / "run.py")
    out = subprocess.run(cmd, env=env, cwd=tmp_path, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 1 and out.stdout == ""
    assert "cannot import the program" in out.stderr
