"""Real 2-process distributed tests.

Everything else in the suite simulates multi-device on one process
(conftest's 8 virtual CPU devices). These tests launch TWO actual OS
processes connected through ``jax.distributed`` on a localhost
coordinator — the shape the reference runs as 4 nodes × 4 GPUs via
``TorchDistributor`` (``deep_learning/2...py:460-470``) — and assert:

- both processes see the global topology (2 processes, 2 devices);
- a jitted reduction over a process-spanning mesh produces the global
  answer on both (the cross-process collective actually ran);
- ``cur_shard/shard_count`` reader shards cover the table disjointly
  across *processes* (not just simulated devices);
- a ``HostTrials`` sweep driven from process 0 evaluates trials on a
  worker served by process 1 (control plane crosses the boundary);
- (slow) a full multi-host ``dsst train`` epoch: per-process reader
  shards assembled into the global batch on a process-spanning mesh.
"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pyarrow as pa
import pytest

CHILD = Path(__file__).parent / "mp_child.py"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_pair(tmp_path, data, extra_args=(), n=2):
    # The parent pytest process forces 8 simulated devices via XLA_FLAGS;
    # children must not inherit that (1 CPU device per process).
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    )
    # Children import the package from the repo root.
    repo_root = str(Path(__file__).parent.parent)
    env["PYTHONPATH"] = os.pathsep.join(
        [repo_root] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    coordinator = f"127.0.0.1:{_free_port()}"
    procs = [
        subprocess.Popen(
            [
                sys.executable, str(CHILD),
                "--coordinator", coordinator,
                "--process-id", str(pid),
                "--num-processes", str(n),
                "--data", str(data),
                "--workdir", str(tmp_path),
                *extra_args,
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(n)
    ]
    outputs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300 * max(1, n // 2))
            outputs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outputs):
        assert p.returncode == 0, f"child failed:\n{out[-3000:]}"
    return [
        json.loads((tmp_path / f"result_{i}.json").read_text())
        for i in range(n)
    ]


def _id_table(tmp_path):
    from dss_ml_at_scale_tpu.data import write_delta

    table = pa.table({"id": pa.array(np.arange(16, dtype=np.int64))})
    data = tmp_path / "table"
    write_delta(table, data, max_rows_per_file=4)
    return data


def test_two_process_distributed_smoke(tmp_path):
    results = _launch_pair(tmp_path, _id_table(tmp_path))
    for r in results:
        assert r["process_count"] == 2
        assert r["global_devices"] == 2
        assert r["local_devices"] == 1
        # sum over devices: proc0 contributes 1.0, proc1 contributes 2.0
        assert r["global_sum"] == 3.0
    # Disjoint shard coverage across processes, union = whole table.
    ids0, ids1 = set(results[0]["ids"]), set(results[1]["ids"])
    assert ids0.isdisjoint(ids1)
    assert ids0 | ids1 == set(range(16))
    # The HPO sweep ran on the other process's worker.
    assert results[0]["hpo_ok_trials"] == 4
    assert -5.0 <= results[0]["hpo_best_x"] <= 5.0


@pytest.mark.slow
def test_four_process_distributed(tmp_path):
    """N>2 coordination on localhost — the reference's flagship shape is
    4 nodes x 4 GPUs (``deep_learning/2...py:460-470``); this exercises
    the N=4 process topology end to end: 4-device global mesh with a
    cross-process collective, 4-way disjoint reader shards, and a
    HostTrials sweep scheduling onto THREE worker processes."""
    results = _launch_pair(tmp_path, _id_table(tmp_path), n=4)
    for r in results:
        assert r["process_count"] == 4
        assert r["global_devices"] == 4
        assert r["local_devices"] == 1
        # sum over devices: process i contributes i+1 -> 1+2+3+4
        assert r["global_sum"] == 10.0
    shards = [set(r["ids"]) for r in results]
    for i in range(4):
        for j in range(i + 1, 4):
            assert shards[i].isdisjoint(shards[j])
    assert set().union(*shards) == set(range(16))
    # Sweep spread across the 3 workers; every trial succeeded.
    assert results[0]["hpo_ok_trials"] == 8
    assert -5.0 <= results[0]["hpo_best_x"] <= 5.0


@pytest.mark.slow
def test_two_process_training(tmp_path):
    sys.path.insert(0, str(Path(__file__).parent))
    from test_end_to_end import _jpeg

    from dss_ml_at_scale_tpu.data import write_delta

    rng = np.random.default_rng(0)
    labels = rng.integers(0, 4, 64)
    images = pa.table({
        "content": pa.array([_jpeg(rng, l) for l in labels], type=pa.binary()),
        "label_index": pa.array(labels.astype(np.int64)),
    })
    train_data = tmp_path / "images"
    write_delta(images, train_data, max_rows_per_file=16)

    results = _launch_pair(
        tmp_path, _id_table(tmp_path),
        extra_args=["--train-data", str(train_data)],
    )
    # Multi-host DP training: steps/epoch = rows // (batch x world)
    # = 64 // (8 x 2) = 4, identical on both ranks, finite loss.
    for r in results:
        assert r["train_rc"] == 0
        assert r["train_steps"] == 4
        assert np.isfinite(r["train_loss"])
