"""The `nemotron_h_lm` family's side of the benchmark: the configuration
as one chip's share of a stated deployment, its FLOP and byte functions
against hand counts at the published widths, the two SSD readers on a
made-up trace that also holds a flash call and a Mamba-1 scan (which
neither may count), and a tiny cell through ``drivers/serve.py`` on the
CPU, where ``correct`` comes out false under the fp8 control."""

import importlib
import json
import time
from pathlib import Path

import pytest

import bytes_nemotron_h_lm as nbytes
import flops
import flops_nemotron_h_lm as count
import harness
import trace as tracemod

HERE = Path(__file__).resolve().parent
BENCH_FILE = HERE / "rehearsal_nemotron_h" / "BENCHMARK.json"
CELL = "nemotron_h_tiny_serve_agent"
CFG = json.loads((HERE.parent / "configs"
                  / "nemotron3_super_120b_ep8.json").read_text())

# -- counts from shapes, by hand ----------------------------------------------

D, DI, H, P, N, G = 4096, 8192, 128, 64, 128, 8
CONV, K, L = 10240, 4, 128
LAT, F, FS, RW, E, V = 1024, 2688, 5376, 512, 64, 16384
MAMBA = D * (DI + CONV + H) + DI * D                          # 109,576,192
# beside its matrices: conv kernel and bias, dt_bias, A_log, D, the norm
MAMBA_SMALL = K * CONV + CONV + 3 * H + DI                    # 59,776
ATTENTION = 2 * D * 32 * 128 + 2 * D * 2 * 128                # 35,651,584
MOE_DENSE = D * RW + 2 * D * LAT + 2 * D * FS                 # 54,525,952
EXPERT = 2 * LAT * F                                          # 5,505,024


def _prod(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def test_the_configuration_is_one_chips_share_of_eight():
    assert CFG["family"] == "nemotron_h_lm"
    assert CFG["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert not any("hidden" in key for key in CFG["reduced"])
    assert CFG["published"] == {"num_hidden_layers": 88,
                                "n_routed_experts": 512,
                                "vocab_size": 131072}
    assert CFG["num_hidden_layers"] == 88 and CFG["router_width"] == 512
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    assert CFG["n_routed_experts"] * 8 == CFG["router_width"]
    assert "64 chips" in CFG["deployment"]
    for key in ("router", "attention", "mtp", "state", "weight_map"):
        assert key in CFG["assumed"], key
    assert count.kinds(CFG) == "MEMEMEM*EME"
    assert count.layer_counts(CFG) == (5, 5, 1)
    reference = importlib.import_module("references.nemotron_h_lm")
    held = sum(_prod(s) for s in reference.param_shapes(CFG).values())
    assert held == (5 * (MAMBA + MAMBA_SMALL + D)
                    + 5 * (MOE_DENSE + RW + D + E * EXPERT)
                    + ATTENTION + D + 2 * V * D + D) == 2_752_338_304


def test_flop_counts_against_hand_counts():
    assert count.mamba_params(CFG) == MAMBA
    assert count.attention_params(CFG) == ATTENTION
    assert count.moe_dense_params(CFG) == MOE_DENSE
    assert count.expert_params(CFG) == EXPERT
    assert count.held_experts_per_token(CFG) == 22 * 64 / 512 == 2.75
    macs = (5 * (MAMBA + K * CONV) + ATTENTION
            + 5 * (MOE_DENSE + 2.75 * EXPERT))
    assert count.matrix_macs_per_token(CFG) == macs
    recurrence = 5 * DI * N + 3 * DI
    ssd = 2 * L * N * G + H * (2 * P * L + 4 * P * N)
    assert count.ssd_flops_per_position(CFG) == ssd == 6_553_600
    scores = 32 * 2 * 2 * 128
    head = 2 * D * V
    assert count.decode_flops(CFG, 1000) == (
        2 * macs + scores * 1000 + 5 * recurrence + head)
    n = 4096
    assert count.prefill_flops(CFG, n) == (
        2 * n * macs + scores * n * (n + 1) // 2 + 5 * n * ssd + head)
    call = count.ssd_scan_call(CFG, 1024)
    assert call["flops"] == 1024 * ssd
    assert call["bytes"] == 4 * (2 * 1024 * DI + 3 * 1024 * H
                                 + 2 * 1024 * G * N + 2 * DI * N + H)


def test_byte_counts_against_hand_counts():
    wide = (5 * (MAMBA_SMALL + D) + 5 * (D + D * RW + RW) + D + D)
    narrow = (5 * MAMBA + ATTENTION + 5 * (MOE_DENSE - D * RW) + V * D)
    assert nbytes.float32_params(CFG) == wide
    assert nbytes.step_weight_bytes(CFG) == 2 * narrow + 4 * wide
    assert 1.8e9 < nbytes.step_weight_bytes(CFG) < 1.9e9
    state = 5 * (DI * N + 3 * CONV) * 4
    assert nbytes.state_bytes_per_sequence(CFG) == state == 21_585_920
    assert nbytes.cache_bytes_per_token(CFG) == 2 * 2 * 128 * 2 == 1024
    got = nbytes.decode_step_bytes(CFG, context_tokens=120_000, active=32,
                                   experts_touched=240)
    assert got == (2 * narrow + 4 * wide + 240 * 2 * EXPERT + 32 * D * 2
                   + 32 * 2 * state + 120_000 * 1024 + 32 * V * 4)
    # weights, 48 experts touched a layer, the state of 32 slots
    assert 5.9e9 < got < 6.1e9


# -- the readers on a made-up trace -------------------------------------------


def window_with(ops, modules):
    cell = harness.load_cell("nemotron3_super_120b_ep8_serve_agent")
    kind = next(iter(json.loads((HERE.parent / "peaks.json").read_text())
                     ["by_device_kind"]))
    return harness.Window(
        cell=cell, t0=0.0, t1=4.0, wall0=0.0, spans=[],
        counters0={"metrics": []}, counters1={"metrics": []}, stats={},
        device_kind=kind, traced=(0.0, 4.0),
        tables=tracemod.Tables(devices={0: {
            "modules": modules, "ops": ops, "async": []}}, host=[]))


SSD_OP = ("%ssd_scan.{k} = (f32[128,64,1024]{{2,1,0:T(8,128)}}, "
          "f32[128,64,128]{{2,1,0:T(8,128)}}) custom-call(%x, %dt), "
          'custom_call_target="tpu_custom_call"')
SSM_OP = ("%ssm_scan.4 = (f32[1024,5120]{1,0:T(8,128)}, "
          "f32[16,5120]{1,0:T(8,128)}) custom-call(%u), "
          'custom_call_target="tpu_custom_call"')
FLASH_OP = ("%flash_attention_fwd.3 = bf16[32,1024,128]{2,1,0} "
            'custom-call(%q, %k, %v), custom_call_target="tpu_custom_call"')
MS = 1_000_000


def made_up():
    """Two prefill executions of 100 ms, each with two SSD calls of 1 ms,
    a Mamba-1 scan of 3 ms and a flash call of 5 ms; a decode execution
    with an SSD-named op outside any prefill, which is not counted."""
    modules = [("jit_prefill_bucket(1)", 0, 100 * MS),
               ("jit_prefill_bucket(1)", 200 * MS, 100 * MS),
               ("jit_slot_decode(2)", 400 * MS, 20 * MS)]
    ops = []
    for base in (0, 200 * MS):
        ops += [(SSD_OP.format(k=1), base + 10 * MS, 1 * MS),
                (SSD_OP.format(k=2), base + 30 * MS, 1 * MS),
                (SSM_OP, base + 40 * MS, 3 * MS),
                (FLASH_OP, base + 50 * MS, 5 * MS)]
    ops.append((SSD_OP.format(k=9), 405 * MS, 1 * MS))
    return ops, modules


def test_the_ssd_roofline_reads_the_ssd_calls_only():
    ops, modules = made_up()
    window = window_with(ops, modules)
    got = harness.read_layer_metrics(window)
    peak = flops.peaks(window.device_kind)
    call = count.ssd_scan_call(CFG, 1024)
    least, bound = flops.roofline_seconds(call["flops"], call["bytes"], peak)
    assert bound == "memory"
    assert got["ssd_scan_roofline"]["value"] == pytest.approx(
        100 * 4 * least / (4 * 0.001))
    # the union of the four 1 ms calls over the two 100 ms executions
    assert got["ssd_scan_prefill_share"]["value"] == pytest.approx(2.0)


def test_a_program_without_the_ssd_kernel_is_not_read():
    ops, modules = made_up()
    ops = [op for op in ops if "ssd_scan" not in op[0]]
    got = harness.read_layer_metrics(window_with(ops, modules))
    assert "ssd_scan_roofline" not in got
    assert "ssd_scan_prefill_share" not in got


# -- the tiny cell on the CPU -------------------------------------------------


def drive(seed=3_900_000_019, seconds=1.5, **kw):
    return harness.run_cell(CELL, seed, seconds, False,
                            t_start=time.perf_counter(), require_chip=False,
                            bench_file=BENCH_FILE, **kw)


def test_the_tiny_cell_is_correct_and_the_control_is_not():
    """The model with the benchmark's seeded weights (the weight map
    applied), through the engine's chunked prefill and the HTTP front
    end; the SSD and flash kernels interpreted. The driver holds the
    program's mean gap to a limit beside its largest, and the control
    fails by the mean (in bfloat16 the tiny model's largest gap, one
    routed expert changed for one token, reaches the control's)."""
    result = drive(variants=("control_fp8",))
    assert set(result["compared"]) == {"logit_gap_max", "logit_gap_mean",
                                       "failed_requests"}
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    stood = result["readings"]["control_fp8"]
    assert not stood["correct"]
    assert "logit_gap_mean" in [k for k, row in stood["compared"].items()
                                if not row["value"] <= row["limit"]]


def test_the_mean_gap_driver_adds_the_programs_mean_only(monkeypatch):
    """``drivers/serve_gap_mean.py`` gives ``harness.compare`` the mean of
    the program's gaps where its numbers lack one, leaves numbers that
    carry their own (the control's) as they are, and puts ``serve`` back
    as it found it."""
    import harness as real
    from drivers import serve, serve_gap_mean

    calls = []

    def fake_run(cell, **kwargs):
        served, _ = serve._gaps([], 1, {}, None, None)
        numbers = {"logit_gap_max": max(served), "failed_requests": 0.0}
        ok, table = serve.harness.compare(
            numbers, {"logit_gap_mean": 0.5, "logit_gap_max": 9.0})
        stood = {**numbers, "logit_gap_mean": 0.75}
        bad, _ = serve.harness.compare(stood, {"logit_gap_mean": 0.5},
                                       quiet=True)
        calls.append((ok, table, bad, stood))
        return "result"

    monkeypatch.setattr(serve, "_gaps", lambda *a: ([0.2, 0.4], []))
    monkeypatch.setattr(serve, "run", fake_run)
    gaps = serve._gaps
    assert serve_gap_mean.run("cell", seed=1) == "result"
    ok, table, bad, stood = calls[0]
    assert ok and table["logit_gap_mean"]["value"] == pytest.approx(0.3)
    assert not bad and stood["logit_gap_mean"] == 0.75
    assert serve._gaps is gaps and serve.harness is real
