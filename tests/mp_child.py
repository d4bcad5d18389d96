"""Child program for the real 2-process smoke test (test_multiprocess.py).

Each of the two OS processes runs this: connect via
``initialize_distributed`` (the reference launches its ranks with
``TorchDistributor`` + NCCL rendezvous env,
``deep_learning/2.distributed-data-loading-petastorm.py:460-470``; here
rendezvous is ``jax.distributed`` over a localhost coordinator), then
exercise every cross-process seam the framework has:

- topology: global device count spans both processes;
- data plane: a jitted global-sum over a process-spanning mesh (XLA
  inserts the cross-process all-reduce — Gloo on CPU, ICI/DCN on TPU);
- data loading: ``cur_shard=process_index / shard_count=2`` epoch with
  coverage written out so the parent can assert disjoint union;
- control plane: process 1 serves trials, process 0 drives a
  ``HostTrials`` TPE sweep against it over TCP.

Not a pytest file — launched by tests/test_multiprocess.py.
"""

import argparse
import json
import time
from pathlib import Path


def _wait_for(path: Path, timeout: float = 120.0) -> None:
    deadline = time.monotonic() + timeout
    while not path.exists():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out waiting for {path}")
        time.sleep(0.05)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--data", required=True)
    ap.add_argument("--workdir", required=True)
    ap.add_argument(
        "--train-data", default=None,
        help="JPEG Delta table; when set, both processes also run a "
        "multi-host `dsst train` epoch over it",
    )
    args = ap.parse_args()
    workdir = Path(args.workdir)

    import jax

    # Force the CPU platform in-process, whatever the caller's
    # environment says (tests/conftest.py does the same): these children
    # never need a chip.
    jax.config.update("jax_platforms", "cpu")

    from dss_ml_at_scale_tpu.runtime import (
        initialize_distributed,
        local_topology,
        make_mesh,
    )

    initialize_distributed(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    topo = local_topology()
    result = {
        "process_index": topo.process_index,
        "process_count": topo.process_count,
        "global_devices": topo.global_device_count,
        "local_devices": topo.local_device_count,
    }

    # -- data plane: global reduction across both processes' devices ------
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dss_ml_at_scale_tpu.runtime.mesh import shard_batch_to_mesh

    mesh = make_mesh()
    contrib = np.full(
        topo.local_device_count, float(topo.process_index + 1), np.float32
    )
    x = shard_batch_to_mesh({"v": contrib}, mesh)["v"]
    total = jax.jit(lambda a: a.sum(), out_shardings=NamedSharding(mesh, P()))(x)
    result["global_sum"] = float(total)

    # -- data loading: disjoint shard coverage (2...py:249-250) ------------
    from dss_ml_at_scale_tpu.data import DeltaTable
    from dss_ml_at_scale_tpu.data.reader import ParquetShardReader

    table = DeltaTable(args.data)
    ids: list[int] = []
    with ParquetShardReader(
        table.file_uris(),
        batch_size=4,
        cur_shard=topo.process_index,
        shard_count=topo.process_count,
        num_epochs=1,
        shuffle_row_groups=False,
        drop_last=False,
        columns=["id"],
    ) as reader:
        for batch in reader:
            ids.extend(int(v) for v in batch["id"])
    result["ids"] = sorted(ids)

    # -- control plane: HostTrials sweep driven by process 0 against a
    # worker served by EVERY other process (N-1 workers at N>2) --------
    done_file = workdir / "sweep_done"
    if topo.process_index > 0:
        from dss_ml_at_scale_tpu.parallel.trials import serve_trial_worker

        server = serve_trial_worker("127.0.0.1:0", block=False)
        host, port = server.address
        (workdir / f"worker_addr_{topo.process_index}").write_text(
            f"{host}:{port}"
        )
        _wait_for(done_file)
    else:
        addrs = []
        for i in range(1, topo.process_count):
            f = workdir / f"worker_addr_{i}"
            _wait_for(f)
            addrs.append(f.read_text())
        from dss_ml_at_scale_tpu.hpo import fmin, hp
        from dss_ml_at_scale_tpu.parallel import HostTrials

        trials = HostTrials(addrs, parallelism=len(addrs))
        best = fmin(
            "dss_ml_at_scale_tpu.hpo.objectives:quadratic",
            {"x": hp.uniform("x", -5.0, 5.0)},
            max_evals=2 * len(addrs) + 2,
            trials=trials,
            rstate=np.random.default_rng(0),
        )
        result["hpo_best_x"] = float(best["x"])
        result["hpo_ok_trials"] = sum(
            1 for t in trials.trials if t["result"]["status"] == "ok"
        )
        done_file.write_text("done")

    # -- real multi-host DP training through the train CLI ----------------
    # Both processes run the same `dsst train` command; the trainer
    # builds a global 2-device mesh, each process decodes its own reader
    # shard, and `shard_batch_to_mesh` assembles per-process rows into
    # the global batch (the reference's 4x4 TorchDistributor shape,
    # 2...py:460-470, at N=2 on localhost).
    if args.train_data:
        import contextlib
        import io

        from dss_ml_at_scale_tpu.config.cli import main as cli_main

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cli_main([
                "train", "--data", args.train_data, "--model", "tiny",
                "--num-classes", "4", "--crop", "64", "--batch-size", "8",
                "--epochs", "1", "--learning-rate", "0.01",
            ])
        result["train_rc"] = rc
        if rc == 0:
            summary = json.loads(buf.getvalue().strip().splitlines()[-1])
            result["train_steps"] = summary["steps"]
            result["train_loss"] = summary["train_loss"]
        else:
            # Surface the CLI's own output instead of dying on a parse of
            # an empty buffer (which would also drop the earlier results).
            result["train_output"] = buf.getvalue()[-2000:]

    # -- write result; filesystem barrier so neither process exits while
    #    the other still needs the jax.distributed service ----------------
    (workdir / f"result_{topo.process_index}.json").write_text(
        json.dumps(result)
    )
    for i in range(topo.process_count):
        _wait_for(workdir / f"result_{i}.json")


if __name__ == "__main__":
    main()
