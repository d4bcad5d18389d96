"""bench.py as the driver sees it: the parent and its children.

The parent never imports jax and runs one child at a time; a child that
finds no ``tpu`` device exits non-zero and prints no metric, and so does
the parent. ``DSST_BENCH_FORCE_CPU=1`` asks for a CPU harness check by
name; its line never carries the chip metric's name. The heavyweight
train child is covered by the slow suites; lm and vit are slow-marked.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(env_extra: dict, timeout: float):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("DSST_BENCH_")}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=timeout, cwd=REPO,
    )


def _run_child(mode: str, timeout: float):
    proc = _bench({"DSST_BENCH_CHILD": "1", "DSST_BENCH_MODE": mode,
                   "DSST_BENCH_FORCE_CPU": "1"}, timeout)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert lines, "child printed nothing"
    return json.loads(lines[-1])


def test_parent_without_a_tpu_exits_nonzero_and_prints_no_metric():
    proc = _bench({"JAX_PLATFORMS": "cpu"}, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a tpu device" in proc.stderr
    assert "DSST_BENCH_FORCE_CPU" in proc.stderr  # the named way out


@pytest.mark.parametrize("mode", ["train", "group", "lm", "vit"])
def test_child_without_a_tpu_exits_nonzero_and_prints_no_metric(mode):
    proc = _bench({"JAX_PLATFORMS": "cpu", "DSST_BENCH_CHILD": "1",
                   "DSST_BENCH_MODE": mode}, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a tpu device" in proc.stderr


def test_parent_never_imports_jax():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import bench\n"
        "assert bench.parent_main() != 0\n"  # no tpu here: fails fast
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
        "assert not bad, bad\n"
        "print('PARENT_OFF_JAX')\n" % REPO
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=REPO,
        env={k: v for k, v in dict(os.environ, JAX_PLATFORMS="cpu").items()
             if not k.startswith("DSST_BENCH_")},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PARENT_OFF_JAX" in proc.stdout


def test_failed_child_fails_the_parent_without_retry(monkeypatch, capsys):
    import bench

    calls = []

    def fake_run(cmd, **kw):
        calls.append(kw["env"]["DSST_BENCH_MODE"])
        return subprocess.CompletedProcess(cmd, 1, "", "boom\n")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    assert bench.parent_main() == 1
    assert calls == ["train"]  # one attempt, nothing after the failure
    out = capsys.readouterr()
    assert out.out == "" and "boom" in out.err


def test_cpu_harness_check_never_carries_the_chip_metric_name(monkeypatch,
                                                              capsys):
    """The parent prints what the children measured; the train child
    names a forced-CPU line ``cpu_harness_check``."""
    import bench

    def fake_run(cmd, **kw):
        mode = kw["env"]["DSST_BENCH_MODE"]
        rec = ({"metric": bench.CPU_METRIC, "value": 1.0, "platform": "cpu"}
               if mode == "train" else {"platform": "cpu"})
        return subprocess.CompletedProcess(cmd, 0, json.dumps(rec) + "\n", "")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setenv("DSST_BENCH_FORCE_CPU", "1")
    assert bench.parent_main() == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "cpu_harness_check" != bench.CHIP_METRIC
    assert "vs_baseline" not in line
    assert set(line) >= {"group", "lm"}


@pytest.mark.slow
def test_lm_child_measures_tokens_per_sec():
    out = _run_child("lm", timeout=420)
    assert out["platform"] == "cpu"
    assert out["tokens_per_sec"] > 0
    # CPU harness-check shape: reference attention, shrunk geometry.
    assert out["attention"] == "reference"
    assert out["seq_len"] == 256


@pytest.mark.slow
def test_train_child_cpu_harness_check():
    out = _run_child("train", timeout=600)
    assert out["metric"] == "cpu_harness_check"
    assert out["platform"] == "cpu" and out["value"] > 0
    assert "vs_baseline" not in out
    assert any("images_per_sec" in p for p in out["sweep"])
    assert "pipeline" in out
    # cpu traces carry no TPU events, so the category list is empty.
    assert out["profile"] == {"top_hlo_categories": []}


@pytest.mark.slow
def test_vit_child_measures_images_per_sec():
    out = _run_child("vit", timeout=420)
    assert out["platform"] == "cpu"
    assert out["model"] == "vit_micro"
    assert out["images_per_sec"] > 0
