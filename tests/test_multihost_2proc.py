"""Two-process jax.distributed smoke test over localhost.

Genuinely exercises the multi-host path (coordinator handshake, per-process
client ownership, global array assembly from process-local shards, a full
cross-DCN-shaped FedAvg round) with two OS processes of 4 CPU devices each —
the closest a single machine gets to a two-host pod.
"""
import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
import numpy as np

import jax

# the virtual CPU mesh, fixed before ANY backend init
jax.config.update("jax_platforms", "cpu")

from neuroimagedisttraining_tpu.parallel import (
    initialize_distributed,
    local_client_indices,
    make_multihost_mesh,
    shard_federated_data_global,
)

port, pid = sys.argv[1], int(sys.argv[2])
ok = initialize_distributed(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid)
assert ok, "two-process runtime did not come up"
assert jax.process_count() == 2
assert len(jax.devices()) == 8  # 4 local per process

from neuroimagedisttraining_tpu.algorithms import FedAvg
from neuroimagedisttraining_tpu.core.state import HyperParams
from neuroimagedisttraining_tpu.data import make_synthetic_federated
from neuroimagedisttraining_tpu.models import create_model

N = 8
mesh = make_multihost_mesh(num_clients=N)
idx = local_client_indices(N, mesh)
assert len(idx) == 4, idx  # each process owns half the clients

# every process builds the same deterministic cohort, keeps only its rows
full = make_synthetic_federated(
    n_clients=N, samples_per_client=16, test_per_client=8,
    sample_shape=(8, 8, 8, 1), loss_type="bce", class_num=2, seed=7)
local = jax.tree_util.tree_map(lambda x: np.asarray(x)[idx], full)
gdata = shard_federated_data_global(local, N, mesh)

model = create_model("small3dcnn", num_classes=1)
hp = HyperParams(lr=0.05, lr_decay=1.0, momentum=0.9, weight_decay=0.0,
                 grad_clip=10.0, local_epochs=1, steps_per_epoch=2,
                 batch_size=8)
algo = FedAvg(model, gdata, hp, loss_type="bce", frac=1.0, seed=0)
state = algo.init_state(jax.random.PRNGKey(0))
state, metrics = algo.run_round(state, 0)
loss = float(metrics["train_loss"])
assert np.isfinite(loss)
ev = algo.evaluate(state)
print(f"RANK{pid} OK loss={loss:.6f} acc={float(ev['global_acc']):.4f}",
      flush=True)
"""


@pytest.mark.slow
def test_two_process_multihost_fedavg(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("JAX_NUM_CPU_DEVICES", None)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")

    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            cwd=repo_root, text=True)
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {pid} failed:\n{out}"
        assert f"RANK{pid} OK" in out, out
    # both controllers must agree on the aggregated loss bit-for-bit
    l0 = outs[0].split("loss=")[1].split()[0]
    l1 = outs[1].split("loss=")[1].split()[0]
    assert l0 == l1, (l0, l1)


def _derive_space_worker():
    subs = [
        ("mesh = make_multihost_mesh(num_clients=N)",
         "mesh = make_multihost_mesh(n_space=2, num_clients=N)"),
        ("N = 8", "N = 4"),
        ("assert len(idx) == 4, idx  # each process owns half the clients",
         "assert len(idx) == 2, idx  # 4 clients over 2 procs, 2 space cols\n"
         "assert dict(mesh.shape) == {'clients': 4, 'space': 2}, mesh.shape"),
    ]
    out = _WORKER
    for old, new in subs:
        assert old in out, f"_WORKER drifted; substitution lost: {old!r}"
        out = out.replace(old, new)
    return out


_WORKER_SPACE = _derive_space_worker()


@pytest.mark.slow
def test_two_process_multihost_hybrid_space_mesh(tmp_path):
    """Multihost + --mesh_space: the (clients, space) mesh spans both
    processes, volume depth is sharded over the space axis
    (shard_federated_data_global hybrid spec), and a real FedAvg round
    agrees bit-for-bit on both controllers."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    script = tmp_path / "worker_space.py"
    script.write_text(_WORKER_SPACE)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("JAX_NUM_CPU_DEVICES", None)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")

    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            cwd=repo_root, text=True)
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {pid} failed:\n{out}"
        assert f"RANK{pid} OK" in out, out
    l0 = outs[0].split("loss=")[1].split()[0]
    l1 = outs[1].split("loss=")[1].split()[0]
    assert l0 == l1, (l0, l1)
