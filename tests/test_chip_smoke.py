"""Rehearsals of ``chip_smoke.py`` that cost no chip time.

1. End to end on the CPU the script must FAIL: the platform is not
   ``tpu``, so no ``"ok": true`` line may appear.
2. With the expected platform passed as an ARGUMENT of the script's own
   phase functions (the command line has no such flag, the environment
   no such variable), each phase's control flow runs to its end at a
   tiny size — Pallas kernels in interpret mode, the data-parallel path
   on 4 of the suite's virtual CPU devices.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (imports nothing of jax)

TINY_DP4 = chip_smoke.Dp4Sizes(model="tiny", global_batch=8, image=32,
                               classes=10)


def _run_script(args, env_extra=None, cwd=REPO, script=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("args", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_script_fails_on_cpu_and_prints_no_ok(args):
    proc = _run_script(args)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "expected platform 'tpu'" in proc.stderr


def test_script_fails_alone_in_a_directory(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {"PYTHONPATH": ""}
    proc = _run_script([], env_extra=env, cwd=str(tmp_path),
                       script=str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_parent_never_imports_jax(tmp_path):
    """The parent's whole life — argument parsing, spawning a phase
    child, reading its failure — with ``jax`` never in ``sys.modules``
    (a parent that has touched jax holds the chip)."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke\n"
        "rc = chip_smoke.main([])\n"
        "assert rc != 0\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
        "assert not bad, bad\n"
        "print('PARENT_OFF_JAX')\n" % REPO
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PARENT_OFF_JAX" in proc.stdout


def test_phase_train_control_flow(tmp_path):
    facts = chip_smoke.phase_train(
        tmp_path, "cpu",
        chip_smoke.TrainSizes(model="tiny-bottleneck", batch=8, crop=32,
                              classes=10, image_size=48, steps=4,
                              pair_steps=2),
    )
    assert facts["steps"] == 4 and len(facts["losses"]) == 4
    assert facts["param_platforms"] == ["cpu"]
    assert facts["pair"]["steps"] == 2 and facts["pair"]["rel_diff"] <= 1e-5
    assert facts["decode_backend"] in ("native", "pil")
    json.dumps(facts)  # the phase line must be JSON


def test_phase_kernels_control_flow(tmp_path):
    facts = chip_smoke.phase_kernels(
        tmp_path, "cpu",
        chip_smoke.KernelSizes(flash=(1, 2, 128, 32), bn_batch=2,
                               bn_stages=((4, 16, 32),), dtype="float32",
                               flash_tol=1e-4, bn_tol=1e-4),
    )
    kinds = [(c["kernel"], c.get("causal"), c.get("residual"))
             for c in facts["checks"]]
    assert kinds == [("flash_attention", True, None),
                     ("flash_attention", False, None),
                     ("bn_relu_matmul", None, False),
                     ("bn_relu_matmul", None, True)]
    assert facts["interpret"] is True  # the CPU rehearsal only


def test_phase_kernels_catches_a_wrong_kernel(tmp_path, monkeypatch):
    """A kernel that disagrees with the reference fails the phase."""
    import importlib

    # (`ops.flash_attention` the attribute is the function; the module
    # is reached by name.)
    fa = importlib.import_module("dss_ml_at_scale_tpu.ops.flash_attention")
    real = fa.attention_reference
    monkeypatch.setattr(
        fa, "attention_reference",
        lambda q, k, v, **kw: real(q, k, v, **kw) * 1.5,
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="attention_reference"):
        chip_smoke.phase_kernels(
            tmp_path, "cpu",
            chip_smoke.KernelSizes(flash=(1, 2, 128, 32), bn_batch=2,
                                   bn_stages=(), dtype="float32",
                                   flash_tol=1e-4),
        )


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_phase_serve_lm_control_flow(tmp_path, attention):
    sizes = chip_smoke.ServeSizes(
        vocab=64, dim=32, heads=2, layers=1, max_len=64, slots=4,
        buckets=(8, 16), attention=attention, requests=4, prompt_lo=5,
        prompt_hi=14, new_tokens=8,
    )
    facts = chip_smoke.phase_serve_lm(tmp_path, "cpu", sizes)
    assert facts["decoder"] == "TransformerDecoder"
    assert facts["device"]["platform"] == "cpu"
    assert facts["streams_completed"] == 4 and facts["drained_exit"] == 0


@pytest.mark.parametrize("groups,want", [(None, 5), (4, 4)],
                         ids=["every-sku", "kept-4"])
def test_phase_forecast_control_flow(tmp_path, groups, want):
    facts = chip_smoke.phase_forecast(
        tmp_path, "cpu",
        chip_smoke.ForecastSizes(
            skus_per_product=1, years=1, groups=groups,
            extra_args=("--max-p", "1", "--max-d", "0", "--max-q", "0",
                        "--max-iter", "10", "--horizon", "8"),
        ),
    )
    assert facts["G"] == want and facts["rows"] == want * facts["weeks"]
    assert facts["launches"] == 1 and facts["groups_per_launch"] == want


def test_phase_refuses_the_wrong_platform(tmp_path):
    with pytest.raises(chip_smoke.SmokeFailure, match="expected platform"):
        chip_smoke.phase_kernels(tmp_path, "tpu", chip_smoke.KernelSizes())


def test_dp4_on_four_virtual_devices(tmp_path, devices8):
    facts = chip_smoke.phase_dp4(tmp_path, "cpu", TINY_DP4)
    assert len(facts["batch_shard_devices"]) == 4
    assert facts["all_reduce_in_compiled_step"] is True
    assert facts["loss_rel_diff"] <= 1e-3
    assert facts["param_worst_update_rel_err"] <= 5e-2


def test_dp4_fails_when_everything_sits_on_device_0(tmp_path, devices8):
    import jax

    def all_on_device_0(batch, mesh):
        return jax.device_put(batch, jax.devices()[0])

    with pytest.raises(chip_smoke.SmokeFailure,
                       match="not on 4 distinct devices"):
        chip_smoke.phase_dp4(tmp_path, "cpu", TINY_DP4,
                             place=all_on_device_0)


def test_parent_reports_count_and_last_line(tmp_path, monkeypatch, capsys):
    """The parent's bookkeeping, with the phase children faked: the last
    line is the contract's and carries the device a child reported."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    def fake_run(cmd, **kw):
        name = cmd[cmd.index("--phase") + 1]
        return subprocess.CompletedProcess(
            cmd, 0, json.dumps({"phase": name, "device": dev}) + "\n", "")

    monkeypatch.setattr(chip_smoke.subprocess, "run", fake_run)
    assert chip_smoke.run_parent(1) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": dev}
    assert [json.loads(x)["phase"] for x in lines[:4]] == list(
        chip_smoke.ONE_CHIP_PHASES)
    # Four chips asked for, one seen: no ok line.
    assert chip_smoke.run_parent(4) != 0
    assert '"ok"' not in capsys.readouterr().out
