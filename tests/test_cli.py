"""CLI subcommands + pipeline DAG runner (the RUNME-equivalent surface)."""

import json
import subprocess
import sys

import numpy as np
import pytest

from dss_ml_at_scale_tpu.config.cli import build_parser, main
from dss_ml_at_scale_tpu.config.pipeline import _topo_order


def test_parser_registers_all_subcommands():
    parser = build_parser()
    text = parser.format_help()
    for cmd in ("info", "datagen", "forecast", "train", "hpo", "pipeline"):
        assert cmd in text


def test_datagen_demand_and_bom(tmp_path, capsys):
    demand = tmp_path / "demand"
    assert main([
        "datagen", "demand", "--out", str(demand),
        "--skus-per-product", "1", "--years", "1",
    ]) == 0
    assert (demand / "_delta_log").is_dir()
    assert main([
        "datagen", "bom", "--demand", str(demand),
        "--out", str(tmp_path / "bom"),
        "--mapper-out", str(tmp_path / "mapper"),
    ]) == 0
    out = capsys.readouterr().out
    assert "5 SKUs" in out  # 5 products × 1 SKU
    assert "sku mappings" in out


def test_datagen_regression_and_hpo_shared_fs(tmp_path, capsys):
    npz = tmp_path / "reg.npz"
    assert main([
        "datagen", "regression", "--bytes", "200000", "--out", str(npz),
    ]) == 0
    assert npz.exists()
    assert main([
        "hpo", "--data", str(npz), "--parallelism", "2", "--max-evals", "2",
    ]) == 0
    assert "shared-fs" in capsys.readouterr().out


def test_hpo_closure_mode(tmp_path, monkeypatch, capsys):
    # Default autologging: with no tracking flags at all, every trial
    # must land in ./dsst_runs (the SparkTrials-under-MLflow default,
    # reference hyperopt/1. hyperopt.py:130-136).
    monkeypatch.chdir(tmp_path)
    assert main(["hpo", "--bytes", "100000", "--max-evals", "2"]) == 0
    assert "closure" in capsys.readouterr().out
    runs = list((tmp_path / "dsst_runs" / "hpo").iterdir())
    assert len(runs) == 1
    params = json.loads((runs[0] / "params.json").read_text())
    assert "trial_0" in params and "trial_1" in params
    metrics = [
        json.loads(line)
        for line in (runs[0] / "metrics.jsonl").read_text().splitlines()
    ]
    assert sum(1 for m in metrics if m["name"] == "loss") >= 2


def test_crashed_command_closes_run_as_failed(tmp_path, monkeypatch, capsys):
    # With tracking default-on, a command that raises AFTER its run is
    # opened must not leave the run in RUNNING state (phantom runs).
    from dss_ml_at_scale_tpu.datagen.images import write_image_delta

    monkeypatch.chdir(tmp_path)
    table = tmp_path / "imgs"
    write_image_delta(table, 32, classes=4, size=32)
    with pytest.raises(FileNotFoundError):
        main([
            "train", "--data", str(table), "--val-data", "/nonexistent/val",
            "--model", "tiny", "--num-classes", "4", "--crop", "32",
            "--batch-size", "8", "--epochs", "1",
        ])
    capsys.readouterr()
    metas = list((tmp_path / "dsst_runs" / "imagenet").glob("*/meta.json"))
    assert len(metas) == 1
    assert json.loads(metas[0].read_text())["status"] == "FAILED"


def test_hpo_no_tracking_opt_out(tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    assert main([
        "hpo", "--bytes", "100000", "--max-evals", "2", "--no-tracking",
    ]) == 0
    capsys.readouterr()
    assert not (tmp_path / "dsst_runs").exists()


@pytest.mark.slow
def test_forecast_end_to_end(tmp_path, capsys, devices8):
    demand = tmp_path / "demand"
    main([
        "datagen", "demand", "--out", str(demand),
        "--skus-per-product", "1", "--years", "1",
    ])
    out_table = tmp_path / "forecast"
    assert main([
        "forecast", "--data", str(demand), "--out", str(out_table),
        "--max-evals", "2", "--horizon", "12",
        "--max-p", "2", "--max-d", "1", "--max-q", "2", "--max-iter", "40",
        "--tracking-root", str(tmp_path / "runs"),
    ]) == 0
    assert (out_table / "_delta_log").is_dir()
    # Forecast rows match input rows; tracking run landed.
    from dss_ml_at_scale_tpu.config.commands import _read_delta_pandas

    fc = _read_delta_pandas(out_table)
    assert set(fc.columns) == {"Product", "SKU", "Date", "Demand", "Demand_Fitted"}
    assert np.isfinite(fc["Demand_Fitted"]).all()
    assert list((tmp_path / "runs" / "forecasting").iterdir())
    assert "groups" in capsys.readouterr().out


@pytest.mark.slow
@pytest.mark.parametrize("image_dtype", ["float32", "uint8"])
def test_train_cli_tiny(tmp_path, capsys, devices8, image_dtype):
    # Reuse the end-to-end fixture recipe: tiny JPEG Delta table.
    # Covers both device-transfer modes: host-normalized float32 (default)
    # and raw uint8 bytes normalized inside the jitted step.
    from test_end_to_end import _jpeg
    import pyarrow as pa

    from dss_ml_at_scale_tpu.data import write_delta

    rng = np.random.default_rng(0)
    labels = rng.integers(0, 4, 64)
    table = pa.table({
        "content": pa.array([_jpeg(rng, l) for l in labels], type=pa.binary()),
        "label_index": pa.array(labels.astype(np.int64)),
    })
    data = tmp_path / "images"
    write_delta(table, data, max_rows_per_file=16)

    assert main([
        "train", "--data", str(data), "--model", "tiny",
        "--num-classes", "4", "--crop", "64", "--batch-size", "16",
        "--epochs", "1", "--learning-rate", "0.01",
        "--image-dtype", image_dtype,
    ]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["steps"] == 4  # 64 rows // 16
    assert summary["images_per_sec"] > 0


@pytest.mark.slow
def test_train_cli_pallas_fused(tmp_path, capsys, devices8):
    """`--pallas-fused` trains the prologue-fused bottleneck program
    end to end through the CLI (interpret-mode kernels on CPU) and the
    checkpoint scores through the standard predict path (which maps
    fused_bn='pallas' back to the math-identical HLO fused model)."""
    from test_end_to_end import _jpeg
    import pyarrow as pa

    from dss_ml_at_scale_tpu.data import write_delta

    rng = np.random.default_rng(0)
    labels = rng.integers(0, 4, 32)
    table = pa.table({
        "content": pa.array([_jpeg(rng, l) for l in labels], type=pa.binary()),
        "label_index": pa.array(labels.astype(np.int64)),
    })
    data = tmp_path / "images"
    write_delta(table, data, max_rows_per_file=16)
    ckpt = tmp_path / "ckpt"

    assert main([
        "train", "--data", str(data), "--model", "tiny-bottleneck",
        "--pallas-fused", "--num-classes", "4", "--crop", "32",
        "--batch-size", "16", "--epochs", "1",
        "--learning-rate", "0.01", "--checkpoint-dir", str(ckpt),
    ]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["steps"] == 2  # 32 rows // 16
    assert np.isfinite(summary["train_loss"])
    meta = json.loads((ckpt / "dsst_model.json").read_text())
    assert meta["fused_bn"] == "pallas"

    out = tmp_path / "preds"
    assert main([
        "predict", "--data", str(data), "--checkpoint-dir", str(ckpt),
        "--out", str(out),
    ]) == 0
    # Misconfigurations are loud, not silent: --no-fused-bn conflicts,
    # ViT has no BN (flag would be inert), basic blocks have no 1x1
    # site (would raise a deep flax traceback otherwise).
    for bad in (["--model", "tiny-bottleneck", "--no-fused-bn"],
                ["--model", "vit-tiny"],
                ["--model", "tiny"]):
        assert main([
            "train", "--data", str(data), "--pallas-fused",
            "--num-classes", "4", "--crop", "32", "--batch-size", "16",
            "--epochs", "1", *bad,
        ]) == 1


@pytest.mark.slow
def test_train_cli_pretrained(tmp_path, capsys, devices8):
    # Fine-tune from a synthetic torchvision-layout state dict
    # (reference 2...py:150 fine-tunes IMAGENET1K_V2).
    from test_end_to_end import _jpeg
    from test_pretrained import tiny_torch_state
    import pyarrow as pa

    from dss_ml_at_scale_tpu.data import write_delta

    rng = np.random.default_rng(0)
    labels = rng.integers(0, 4, 32)
    table = pa.table({
        "content": pa.array([_jpeg(rng, l) for l in labels], type=pa.binary()),
        "label_index": pa.array(labels.astype(np.int64)),
    })
    data = tmp_path / "images"
    write_delta(table, data, max_rows_per_file=16)
    weights = tmp_path / "weights.npz"
    np.savez(weights, **tiny_torch_state(num_classes=4))

    ckpt = tmp_path / "ckpt"
    assert main([
        "train", "--data", str(data), "--model", "tiny",
        "--pretrained", str(weights), "--checkpoint-dir", str(ckpt),
        "--num-classes", "4", "--crop", "64", "--batch-size", "16",
        "--epochs", "1", "--learning-rate", "0.01",
    ]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["steps"] == 2  # 32 rows // 16
    assert summary["train_loss"] is not None
    # The architecture choice is persisted for flag-less resumes.
    meta = json.loads((ckpt / "dsst_model.json").read_text())
    assert meta["torch_padding"] is True


def test_topo_order_and_cycles():
    tasks = [
        {"task_key": "c", "argv": [], "depends_on": ["a", "b"]},
        {"task_key": "a", "argv": []},
        {"task_key": "b", "argv": [], "depends_on": ["a"]},
    ]
    assert [t["task_key"] for t in _topo_order(tasks)] == ["a", "b", "c"]
    with pytest.raises(ValueError, match="cycle"):
        _topo_order([
            {"task_key": "x", "argv": [], "depends_on": ["y"]},
            {"task_key": "y", "argv": [], "depends_on": ["x"]},
        ])
    with pytest.raises(ValueError, match="unknown"):
        _topo_order([{"task_key": "x", "argv": [], "depends_on": ["nope"]}])


def test_pipeline_dry_run(tmp_path, capsys):
    spec = {
        "name": "t",
        "tasks": [
            {"task_key": "gen", "argv": ["datagen", "demand", "--out", "{workdir}/d"]},
            {"task_key": "next", "argv": ["info"], "depends_on": ["gen"]},
        ],
    }
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(json.dumps(spec))
    assert main([
        "pipeline", "--spec", str(spec_path), "--workdir", str(tmp_path),
        "--dry-run",
    ]) == 0
    out = capsys.readouterr().out
    assert str(tmp_path / "d") in out
    assert out.index("gen") < out.index("next")


def test_pipeline_runs_tasks_and_skips_dependents_on_failure(tmp_path, capsys):
    # Real subprocess execution: jax-free tasks only (datagen).
    spec = {
        "name": "t",
        "timeout_seconds": 120,
        "tasks": [
            {"task_key": "gen",
             "argv": ["datagen", "demand", "--out", "{workdir}/demand",
                      "--skus-per-product", "1", "--years", "1"]},
            {"task_key": "bad",
             "argv": ["datagen", "bom", "--demand", "{workdir}/missing",
                      "--out", "{workdir}/bom", "--mapper-out", "{workdir}/m"],
             "depends_on": ["gen"]},
            {"task_key": "downstream",
             "argv": ["datagen", "regression", "--bytes", "1e5",
                      "--out", "{workdir}/r.npz"],
             "depends_on": ["bad"]},
        ],
    }
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(json.dumps(spec))
    assert main([
        "pipeline", "--spec", str(spec_path), "--workdir", str(tmp_path),
    ]) == 1
    out = capsys.readouterr().out
    assert (tmp_path / "demand" / "_delta_log").is_dir()
    assert "[bad] FAILED" in out
    assert "[downstream] SKIPPED" in out
    assert not (tmp_path / "r.npz").exists()


def test_example_pipeline_spec_is_valid():
    import pathlib

    spec = json.loads(
        (pathlib.Path(__file__).parent.parent / "pipelines"
         / "demand_forecasting.json").read_text()
    )
    order = [t["task_key"] for t in _topo_order(spec["tasks"])]
    assert order[0] == "generate_demand"
    assert set(order) == {
        "generate_demand", "generate_bom", "fine_grained_forecasting",
    }


def test_pipeline_summary_separates_failed_from_skipped(tmp_path, capsys):
    spec = {
        "tasks": [
            {"task_key": "bad",
             "argv": ["datagen", "bom", "--demand", "{workdir}/missing",
                      "--out", "{workdir}/b", "--mapper-out", "{workdir}/m"]},
            {"task_key": "down", "argv": ["info"], "depends_on": ["bad"]},
        ],
    }
    spec_path = tmp_path / "s.json"
    spec_path.write_text(json.dumps(spec))
    assert main([
        "pipeline", "--spec", str(spec_path), "--workdir", str(tmp_path),
    ]) == 1
    out = capsys.readouterr().out
    assert "pipeline failed: bad (skipped: down)" in out


@pytest.mark.slow
def test_eda_cli(tmp_path, monkeypatch, capsys, devices8):
    monkeypatch.chdir(tmp_path)
    demand = tmp_path / "demand"
    main([
        "datagen", "demand", "--out", str(demand), "--skus-per-product", "1",
    ])
    assert main([
        "eda", "--data", str(demand), "--horizon", "20",
        "--seasonal-periods", "26", "--max-evals", "2", "--parallelism", "2",
        "--max-iter", "40",
    ]) == 0
    out = capsys.readouterr().out
    assert "hw_add" in out and "sarimax_exog" in out
    assert "best SARIMAX order" in out
    # TPE trials autolog by default, one metrics line per trial.
    runs = list((tmp_path / "dsst_runs" / "eda").iterdir())
    assert len(runs) == 1
    params = json.loads((runs[0] / "params.json").read_text())
    assert "trial_0" in params and "sku" in params


def test_ingest_cli(tmp_path, capsys):
    from test_end_to_end import _jpeg

    root = tmp_path / "raw" / "Data"
    root.mkdir(parents=True)
    rng = np.random.default_rng(0)
    for i in range(6):
        (root / f"n0000000{i % 2}_{i}.JPEG").write_bytes(_jpeg(rng, i % 4))
    assert main([
        "ingest", "--data-root", str(tmp_path / "raw"), "--out",
        str(tmp_path / "table"), "--rows-per-fragment", "4",
    ]) == 0
    assert "ingested 6 rows" in capsys.readouterr().out


@pytest.mark.slow
def test_pipeline_retries_until_success(tmp_path, capsys):
    # Task succeeds only once a marker file exists; first attempt creates
    # it via a failing-then-passing wrapper is overkill — instead verify
    # retry accounting on a task that always fails with max_retries=2.
    spec = {
        "tasks": [
            {"task_key": "flaky",
             "argv": ["datagen", "bom", "--demand", "{workdir}/missing",
                      "--out", "{workdir}/b", "--mapper-out", "{workdir}/m"],
             "max_retries": 2},
        ],
    }
    spec_path = tmp_path / "s.json"
    spec_path.write_text(json.dumps(spec))
    assert main([
        "pipeline", "--spec", str(spec_path), "--workdir", str(tmp_path),
    ]) == 1
    out = capsys.readouterr().out
    assert "attempt 1/3" in out and "attempt 3/3" in out


@pytest.mark.slow
def test_hpo_remote_workers_cli(tmp_path, capsys):
    npz = tmp_path / "reg.npz"
    main(["datagen", "regression", "--bytes", "200000", "--out", str(npz)])
    capsys.readouterr()
    proc = subprocess.Popen(
        [sys.executable, "-m", "dss_ml_at_scale_tpu.config.cli",
         "trial-worker", "--bind", "127.0.0.1:0"],
        stdout=subprocess.PIPE, text=True,
    )
    try:
        addr = proc.stdout.readline().strip().rsplit(" ", 1)[-1]
        assert main([
            "hpo", "--workers", addr, "--data", str(npz),
            "--max-evals", "3", "--parallelism", "1",
        ]) == 0
        out = capsys.readouterr().out
        assert "remote, 1 workers" in out and "3/3 trials ok" in out
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_hpo_remote_workers_requires_data(capsys):
    assert main(["hpo", "--workers", "127.0.0.1:1"]) == 2
    assert "requires --data" in capsys.readouterr().out


@pytest.mark.slow
@pytest.mark.parametrize("ffn", ["dense", "moe"])
def test_lm_cli_tiny(capsys, devices8, ffn):
    # Beyond-parity LM track through the CLI: a tiny transformer on the
    # Markov stream must reach a val loss well under uniform log(V)
    # within a few hundred steps (the entropy floor is far lower).
    assert main([
        "lm", "--vocab", "16", "--dim", "32", "--heads", "2",
        "--layers", "1", "--seq", "32", "--batch-size", "8",
        "--epochs", "2", "--steps-per-epoch", "60",
        "--learning-rate", "0.01", "--attention", "reference",
        # 8 experts over the 8 simulated devices: divisible, so the CLI
        # enables expert sharding (EP) on the moe variant.
        "--ffn", ffn, "--num-experts", "8",
        "--concentration", "0.05",
    ]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["steps"] == 120
    assert summary["val_loss"] < 0.8 * np.log(16), summary
    assert summary["entropy_floor_nats"] < summary["val_loss"]


def test_info_probe_reports_instead_of_hanging(capsys, monkeypatch):
    # --probe runs the device query in a subprocess with a timeout; a
    # query that does not return surfaces as TimeoutExpired. Simulate it
    # deterministically and check the diagnostic path: report + exit 3,
    # no blocking.
    def fake_run(*a, **kw):
        raise subprocess.TimeoutExpired(cmd=a[0], timeout=kw["timeout"])

    monkeypatch.setattr(subprocess, "run", fake_run)
    rc = main(["info", "--probe", "0.5"])
    out = capsys.readouterr().out
    assert rc == 3 and "unreachable" in out and "0.5s" in out


@pytest.mark.slow
def test_predict_cli_round_trip(tmp_path, capsys, devices8):
    # train -> checkpoint -> predict: the full use loop. The quadrant
    # task is learnable, so predictions should beat chance on the
    # training table itself.
    from test_end_to_end import _jpeg
    import pyarrow as pa

    from dss_ml_at_scale_tpu.data import write_delta
    from dss_ml_at_scale_tpu.config.commands import _read_delta_pandas

    rng = np.random.default_rng(0)
    labels = rng.integers(0, 4, 64)
    table = pa.table({
        "content": pa.array([_jpeg(rng, l) for l in labels], type=pa.binary()),
        "label_index": pa.array(labels.astype(np.int64)),
    })
    data = tmp_path / "images"
    write_delta(table, data, max_rows_per_file=16)

    ckpt = tmp_path / "ckpt"
    assert main([
        "train", "--data", str(data), "--model", "tiny",
        "--num-classes", "4", "--crop", "64", "--batch-size", "16",
        # lr 3e-3: at 1e-2 this run sits on a collapse-to-one-class
        # cliff where float rounding (e.g. a different fusion order)
        # picks the attractor; the gentler rate converges reliably.
        "--epochs", "8", "--learning-rate", "0.003",
        # Single reader worker: deterministic batch order, so the
        # accuracy assertion can't flake on thread scheduling.
        "--workers", "1",
        "--checkpoint-dir", str(ckpt),
        "--val-data", str(data),
    ]) == 0
    capsys.readouterr()

    out = tmp_path / "preds"
    assert main([
        "predict", "--data", str(data), "--checkpoint-dir", str(ckpt),
        "--out", str(out), "--batch-size", "24",  # exercises drop_last=False
    ]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["rows"] == 64
    # Above chance (0.25) with margin; training on 64 images for a few
    # epochs is deliberately small, so don't demand a solved task.
    assert summary["accuracy_vs_label_index"] > 0.4

    preds = _read_delta_pandas(out)
    assert len(preds) == 64
    assert set(preds.columns) == {"row", "label_index", "pred_index", "pred_prob"}
    assert preds["pred_prob"].between(0, 1).all()
    # The "row" index is a positional key into the table's CANONICAL read
    # order (file_uris order — what any reader of the same table sees),
    # which single-worker unshuffled streaming preserves. Note this is
    # not the pre-write in-memory row order: write_delta names fragments
    # by uuid and listings sort by filename.
    canonical = _read_delta_pandas(data)["label_index"].to_numpy()
    np.testing.assert_array_equal(
        preds.sort_values("row")["label_index"].to_numpy(), canonical
    )


def test_datagen_images(tmp_path, capsys):
    out = tmp_path / "imgs"
    assert main([
        "datagen", "images", "--out", str(out), "--n", "32",
        "--classes", "4", "--size", "32",
    ]) == 0
    assert (out / "_delta_log").is_dir()
    from dss_ml_at_scale_tpu.config.commands import _read_delta_pandas

    df = _read_delta_pandas(out)
    assert len(df) == 32
    assert set(df["label_index"]) <= {0, 1, 2, 3}
    assert "32 JPEGs" in capsys.readouterr().out


def test_datagen_images_label_noise(tmp_path):
    # Same seed, with and without noise: images identical, a fraction of
    # stored labels flipped — a pinned accuracy ceiling of
    # (1-p) + p/classes.
    from dss_ml_at_scale_tpu.config.commands import _read_delta_pandas

    clean, noisy = tmp_path / "clean", tmp_path / "noisy"
    assert main(["datagen", "images", "--out", str(clean), "--n", "256",
                 "--classes", "4", "--size", "16"]) == 0
    assert main(["datagen", "images", "--out", str(noisy), "--n", "256",
                 "--classes", "4", "--size", "16",
                 "--label-noise", "0.5"]) == 0
    df_c = _read_delta_pandas(clean).sort_values("content", ignore_index=True)
    df_n = _read_delta_pandas(noisy).sort_values("content", ignore_index=True)
    # Images come from the TRUE labels — byte-identical across runs.
    assert (df_c["content"] == df_n["content"]).all()
    flipped = (df_c["label_index"] != df_n["label_index"]).mean()
    # p=0.5 with uniform redraw over 4 classes changes ~0.5*3/4 = 0.375.
    assert 0.25 < flipped < 0.5


def test_datagen_photos_and_ingest_label_index(tmp_path, capsys):
    # Real-photograph bytes (sklearn's CC-BY sample photos) through the
    # ingest path: deterministic crops, filename-prefix labels, and the
    # new first-encounter label_index vocabulary persisted as labels.json.
    from dss_ml_at_scale_tpu.config.commands import _read_delta_pandas

    assert main([
        "datagen", "photos", "--out", str(tmp_path / "raw"),
        "--n", "12", "--size", "48",
    ]) == 0
    files = sorted((tmp_path / "raw" / "Data").glob("*.JPEG"))
    assert len(files) == 12
    from PIL import Image

    with Image.open(files[0]) as im:
        assert im.size == (48, 48) and im.format == "JPEG"
    # Same seed → byte-identical tree (ingest ids stay stable).
    assert main([
        "datagen", "photos", "--out", str(tmp_path / "raw2"),
        "--n", "12", "--size", "48",
    ]) == 0
    assert files[0].read_bytes() == (
        tmp_path / "raw2" / "Data" / files[0].name
    ).read_bytes()

    assert main([
        "ingest", "--data-root", str(tmp_path / "raw"),
        "--out", str(tmp_path / "table"),
    ]) == 0
    df = _read_delta_pandas(tmp_path / "table")
    assert set(df["object_id"]) == {"china", "flower"}
    vocab = json.loads((tmp_path / "table" / "labels.json").read_text())
    assert sorted(vocab) == ["china", "flower"]
    for _, row in df.iterrows():
        assert row["label_index"] == vocab[row["object_id"]]

    # predict maps indices back through the ingested vocabulary.
    # (batch sizes must divide the simulated 8-device mesh's data axis)
    assert main([
        "train", "--data", str(tmp_path / "table"),
        "--model", "tiny", "--num-classes", "2", "--crop", "32",
        "--batch-size", "8", "--epochs", "1",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ]) == 0
    assert main([
        "predict", "--data", str(tmp_path / "table"),
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--out", str(tmp_path / "preds"), "--batch-size", "8",
    ]) == 0
    # The vocabulary rides the CHECKPOINT (dsst_model.json), not the
    # scoring table — a differently-ordered table must not mislabel.
    meta = json.loads((tmp_path / "ckpt" / "dsst_model.json").read_text())
    names = meta["label_names"]
    assert sorted(names) == ["china", "flower"]
    preds = _read_delta_pandas(tmp_path / "preds")
    assert set(preds["pred_label"]) <= {"china", "flower"}
    for _, row in preds.iterrows():
        assert row["pred_label"] == names[row["pred_index"]]
    capsys.readouterr()


def _run_pipeline_spec(spec: str, tmp_path, timeout: float = 900) -> str:
    """Run a shipped pipeline spec as a real subprocess DAG on the
    simulated CPU slice (tasks must not claim an accelerator in CI);
    returns stdout after asserting success + predictions."""
    import os

    env = dict(os.environ)
    rc = subprocess.run(
        [sys.executable, "-m", "dss_ml_at_scale_tpu.config.cli",
         "pipeline", "--spec", spec,
         "--workdir", str(tmp_path), "--task-platform", "cpu"],
        env={**env,
             "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")},
        capture_output=True, text=True, timeout=timeout,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert rc.returncode == 0, rc.stdout[-2000:] + rc.stderr[-2000:]
    assert (tmp_path / "predictions" / "_delta_log").is_dir()
    return rc.stdout


@pytest.mark.slow
def test_real_photos_train_pipeline_spec(tmp_path):
    # One pipeline DAG over real photographs — real
    # JPEG bytes through datagen photos -> ingest -> train -> predict.
    out = _run_pipeline_spec("pipelines/real_photos_train.json", tmp_path)
    # The trained classifier must beat chance on the real photos.
    acc = json.loads(
        [l for l in out.splitlines() if "accuracy_vs_label_index" in l][-1]
    )["accuracy_vs_label_index"]
    assert acc > 0.6


@pytest.mark.slow
def test_imagenet_train_pipeline_spec(tmp_path):
    # The track-A RUNME analogue: datagen images -> train -> predict as a
    # real subprocess DAG over the shipped spec.
    _run_pipeline_spec("pipelines/imagenet_train.json", tmp_path)


@pytest.mark.slow
def test_lm_cli_resume(tmp_path, capsys, devices8):
    # LM checkpoints resume through the same Orbax machinery as train.
    common = [
        "lm", "--vocab", "16", "--dim", "16", "--heads", "2",
        "--layers", "1", "--seq", "16", "--batch-size", "8",
        "--steps-per-epoch", "10", "--attention", "reference",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ]
    assert main(common + ["--epochs", "1"]) == 0
    s1 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert s1["steps"] == 10
    assert main(common + ["--epochs", "2", "--resume"]) == 0
    s2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert s2["steps"] == 20  # resumed from 10, ran one more epoch


def test_predict_without_model_meta_fails_cleanly(tmp_path, capsys):
    (tmp_path / "ckpt").mkdir()
    rc = main([
        "predict", "--data", str(tmp_path / "d"),
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--out", str(tmp_path / "o"),
    ])
    assert rc == 1
    assert "dsst_model.json" in capsys.readouterr().out


def test_train_cli_cosine_schedule(tmp_path, capsys, devices8):
    # The cosine schedule trains end to end and the loss still improves;
    # resume restores cleanly (the schedule's count lives in opt_state).
    from dss_ml_at_scale_tpu.datagen.images import write_image_delta

    table = tmp_path / "imgs"
    write_image_delta(table, 64, classes=4, size=32)
    common = [
        "train", "--data", str(table), "--model", "tiny",
        "--num-classes", "4", "--crop", "32", "--batch-size", "16",
        "--learning-rate", "0.01", "--lr-schedule", "cosine",
        "--warmup-steps", "2", "--workers", "1",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ]
    assert main(common + ["--epochs", "2"]) == 0
    s1 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert s1["steps"] == 8
    assert np.isfinite(s1["train_loss"])
    # The FULL trajectory persists (not just the schedule kind): a
    # flag-less resume must land the restored step count on the same
    # warmup/decay curve, not a reshaped one.
    meta = json.loads((tmp_path / "ckpt" / "dsst_model.json").read_text())
    assert meta["lr_schedule"] == "cosine"
    assert meta["warmup_steps"] == 2 and meta["decay_steps"] == 8

    # Flag-less resume: the persisted lr_schedule must rebuild the
    # schedule-shaped optimizer or the Orbax restore structure-fails.
    flagless = [a for a in common if a not in ("--lr-schedule", "cosine")]
    assert main(flagless + ["--epochs", "3", "--resume"]) == 0
    s2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert s2["steps"] == 12  # resumed from 8, one more epoch
    meta2 = json.loads((tmp_path / "ckpt" / "dsst_model.json").read_text())
    assert meta2["warmup_steps"] == 2 and meta2["decay_steps"] == 8

    # predict must load a cosine-trained checkpoint (schedule-shaped
    # opt_state template) without a structure mismatch.
    assert main([
        "predict", "--data", str(table),
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--out", str(tmp_path / "preds"), "--batch-size", "16",
    ]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["rows"] == 64


@pytest.mark.slow
def test_lm_cli_cosine_schedule_resume(tmp_path, capsys, devices8):
    # Same structure discipline as train: the cosine choice persists in
    # dsst_lm.json so a flag-less --resume rebuilds the schedule-shaped
    # optimizer instead of structure-mismatching the Orbax restore.
    common = [
        "lm", "--vocab", "16", "--dim", "16", "--heads", "2",
        "--layers", "1", "--seq", "16", "--batch-size", "8",
        "--steps-per-epoch", "10", "--attention", "reference",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ]
    assert main(common + ["--epochs", "1", "--lr-schedule", "cosine",
                          "--warmup-steps", "2"]) == 0
    s1 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert s1["steps"] == 10
    meta = json.loads((tmp_path / "ckpt" / "dsst_lm.json").read_text())
    assert meta["lr_schedule"] == "cosine"
    assert main(common + ["--epochs", "2", "--resume"]) == 0
    s2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert s2["steps"] == 20


def test_resolve_lr_schedule_precedence():
    # Pure-logic unit test of the shared resolution: explicit flag
    # redefines the trajectory; omitted flag reuses the persisted one
    # bit-for-bit; constant clears persisted trajectory keys.
    import argparse

    from dss_ml_at_scale_tpu.config.commands import _resolve_lr_schedule

    def ns(schedule=None, warmup=None, lr=0.01):
        return argparse.Namespace(
            lr_schedule=schedule, warmup_steps=warmup, learning_rate=lr
        )

    # Fresh explicit cosine: trajectory derived from this run.
    meta = {}
    lr = _resolve_lr_schedule(ns("cosine"), meta, total_steps=100)
    assert callable(lr)
    assert meta == {"lr_schedule": "cosine", "warmup_steps": 5,
                    "decay_steps": 100}

    # Flag-less resume with a DIFFERENT run length: persisted trajectory
    # wins (the restored step count sits on the original curve).
    meta2 = dict(meta)
    lr2 = _resolve_lr_schedule(ns(None), meta2, total_steps=999)
    assert callable(lr2)
    assert meta2["decay_steps"] == 100 and meta2["warmup_steps"] == 5
    # Same curve numerically, not just same keys.
    assert float(lr(50)) == pytest.approx(float(lr2(50)))

    # Explicit re-declaration redefines from the new run length.
    meta3 = dict(meta)
    _resolve_lr_schedule(ns("cosine"), meta3, total_steps=200)
    assert meta3["decay_steps"] == 200 and meta3["warmup_steps"] == 10

    # Explicit warmup override on a persisted trajectory keeps decay.
    meta4 = dict(meta)
    _resolve_lr_schedule(ns(None, warmup=1), meta4, total_steps=999)
    assert meta4 == {"lr_schedule": "cosine", "warmup_steps": 1,
                     "decay_steps": 100}

    # constant (default with no persisted state) returns the float and
    # clears any stale trajectory keys.
    meta5 = dict(meta)
    lr5 = _resolve_lr_schedule(ns("constant"), meta5, total_steps=50)
    assert lr5 == 0.01
    assert meta5 == {"lr_schedule": "constant"}


def test_train_cli_eval_topk(tmp_path, capsys, devices8):
    """--eval-topk 2 lands val_top2_acc in the training summary's
    underlying history (surface check via a val split)."""
    from test_end_to_end import _jpeg
    import pyarrow as pa

    from dss_ml_at_scale_tpu.data import write_delta

    rng = np.random.default_rng(0)
    labels = rng.integers(0, 4, 64)
    table = pa.table({
        "content": pa.array([_jpeg(rng, l) for l in labels], type=pa.binary()),
        "label_index": pa.array(labels.astype(np.int64)),
    })
    data = tmp_path / "images"
    write_delta(table, data, max_rows_per_file=16)
    assert main([
        "train", "--data", str(data), "--val-data", str(data),
        "--model", "tiny",
        "--num-classes", "4", "--crop", "64", "--batch-size", "16",
        "--epochs", "1", "--eval-topk", "2",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["val_top2_acc"] is not None
    assert summary["val_top2_acc"] >= summary["val_acc"]
    # Invalid k fails before any training runs.
    with pytest.raises(SystemExit, match="eval-topk"):
        main([
            "train", "--data", str(data), "--model", "tiny",
            "--num-classes", "4", "--crop", "64", "--batch-size", "16",
            "--epochs", "1", "--eval-topk", "9",
            "--checkpoint-dir", str(tmp_path / "ckpt2"),
        ])


def test_lm_cli_sample(capsys, devices8, tmp_path, monkeypatch):
    """dsst lm --sample N: trained-model greedy generation scored
    against the true chain lands in the summary."""
    monkeypatch.chdir(tmp_path)
    assert main([
        "lm", "--vocab", "16", "--dim", "32", "--heads", "4",
        "--layers", "1", "--seq", "24", "--batch-size", "8",
        "--epochs", "1", "--steps-per-epoch", "10",
        "--learning-rate", "0.003", "--concentration", "0.02",
        "--sample", "8", "--no-tracking",
    ]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(summary["sample_tokens"]) == 12  # 4 prompt + 8 generated
    assert 0.0 <= summary["sample_mean_true_prob"] <= 1.0
    assert summary["sample_chance_prob"] == round(1 / 16, 4)


@pytest.mark.slow
def test_full_stack_pipeline_spec(tmp_path):
    """The showcase DAG: all three tracks in one run — demand ->
    forecast, images -> train(+top-k) -> predict + export, lm train +
    sample — as real subprocesses."""
    # 7 serial tasks; give the harness budget room above the spec's own
    # per-task ceilings on a loaded CI host.
    out = _run_pipeline_spec("pipelines/full_stack.json", tmp_path,
                             timeout=2400)
    assert (tmp_path / "forecasts" / "_delta_log").is_dir()
    assert (tmp_path / "weights.npz").exists()
    lm_line = [l for l in out.splitlines() if "sample_mean_true_prob" in l][-1]
    assert json.loads(lm_line)["sample_mean_true_prob"] >= 0.0
    train_line = [l for l in out.splitlines() if "val_top2_acc" in l][-1]
    assert json.loads(train_line)["val_top2_acc"] is not None
