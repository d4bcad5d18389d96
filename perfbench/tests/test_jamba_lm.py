"""The `jamba_lm` family's side of the benchmark: its FLOP and byte
functions against hand counts at the published widths, the two scan
readers on a made-up trace that also holds a flash call (which neither may
count), and a tiny cell through ``drivers/serve.py`` on the CPU, where
``correct`` comes out false under the fp8 control."""

import importlib
import json
import time
from pathlib import Path

import pytest

import bytes_jamba_lm as nbytes
import flops
import flops_jamba_lm as count
import harness
import trace as tracemod

HERE = Path(__file__).resolve().parent
BENCH_FILE = HERE / "rehearsal_jamba" / "BENCHMARK.json"
CELL = "jamba_tiny_serve_reason"
CFG = json.loads((HERE.parent / "configs" / "jamba2_3b.json").read_text())

# -- counts from shapes, by hand ----------------------------------------------

D, DI, N, R, F, V = 2560, 5120, 16, 160, 8192, 65536
MAMBA = D * 2 * DI + DI * (R + 2 * N) + R * DI + DI * D      # 41,123,840
ATTENTION = 2 * D * D + 2 * D * 128                           # 13,762,560
MLP = 3 * D * F                                               # 62,914,560
# what the mixer holds beside its matrices: conv kernel and bias, dt bias,
# A_log, D, the three inner norms
MAMBA_SMALL = 4 * DI + DI + DI + DI * N + DI + (R + 2 * N)


def test_the_configuration_is_the_whole_model():
    assert CFG["reduced"] == [] and CFG["family"] == "jamba_lm"
    reference = importlib.import_module("references.jamba_lm")
    shapes = reference.param_shapes(CFG)
    held = sum(_prod(s) for s in shapes.values())
    assert MAMBA + MAMBA_SMALL == 41_241_792
    assert held == (26 * (MAMBA + MAMBA_SMALL + MLP + 2 * D)
                    + 2 * (ATTENTION + MLP + 2 * D) + V * D + D) \
        == 3_029_337_472
    kinds = [reference.kind_of(CFG, i) for i in range(28)]
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [7, 21]


def _prod(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def test_flop_counts_against_hand_counts():
    assert count.layer_counts(CFG) == (26, 2)
    assert count.mamba_params(CFG) == MAMBA
    assert count.attention_params(CFG) == ATTENTION
    assert count.mlp_params(CFG) == MLP
    macs = 26 * (MAMBA + 4 * DI) + 2 * ATTENTION + 28 * MLP
    assert count.matrix_macs_per_token(CFG) == macs
    scan = DI * (6 * N + 3)
    head = 2 * D * V
    # decode: one token over 1,000 positions in each attention layer
    assert count.decode_flops(CFG, 1000) == (
        2 * macs + 2 * 2 * 20 * 2 * 128 * 1000 + 26 * scan + head)
    n = 2048
    assert count.prefill_flops(CFG, n) == (
        2 * n * macs + 2 * 2 * 20 * 2 * 128 * n * (n + 1) // 2
        + 26 * n * scan + head)
    assert 11.7e12 < count.prefill_flops(CFG, n) < 11.9e12
    call = count.ssm_scan_call(CFG, n)
    assert call["flops"] == n * scan
    assert call["bytes"] == 4 * (3 * n * DI + 2 * n * N + 2 * N * DI + DI)


def test_byte_counts_against_hand_counts():
    matrices = 26 * MAMBA + 2 * ATTENTION + 28 * MLP + V * D
    wide = 26 * MAMBA_SMALL + 28 * 2 * D + D
    assert matrices + wide == 3_029_337_472
    assert nbytes.weight_bytes(CFG) == 2 * matrices + 4 * wide
    assert 6.0e9 < nbytes.weight_bytes(CFG) < 6.1e9
    state = 26 * (N + 3) * DI * 4
    assert nbytes.state_bytes_per_sequence(CFG) == state == 10_117_120
    assert nbytes.cache_bytes_per_token(CFG) == 2 * 2 * 128 * 2 == 1024
    got = nbytes.decode_step_bytes(CFG, context_tokens=64_000, active=32)
    assert got == (2 * matrices + 4 * wide + 32 * 2 * state
                   + 64_000 * 1024 + 32 * V * 4)
    assert 6.7e9 < got < 6.9e9


# -- the readers on a made-up trace -------------------------------------------


def window_with(ops, modules):
    cell = harness.load_cell("jamba2_3b_serve_reason")
    kind = next(iter(json.loads((HERE.parent / "peaks.json").read_text())
                     ["by_device_kind"]))
    return harness.Window(
        cell=cell, t0=0.0, t1=4.0, wall0=0.0, spans=[],
        counters0={"metrics": []}, counters1={"metrics": []}, stats={},
        device_kind=kind, traced=(0.0, 4.0),
        tables=tracemod.Tables(devices={0: {
            "modules": modules, "ops": ops, "async": []}}, host=[]))


SCAN_OP = ("%ssm_scan.{k} = (f32[2048,5120]{{1,0:T(8,128)}}, "
           "f32[16,5120]{{1,0:T(8,128)}}) custom-call(%bitcast, %u.1), "
           'custom_call_target="tpu_custom_call"')
FLASH_OP = ("%flash_attention_fwd.3 = bf16[20,2048,128]{2,1,0} "
            'custom-call(%q, %k, %v), custom_call_target="tpu_custom_call"')
MS = 1_000_000


def made_up():
    """Two prefill executions of 100 ms, each with two scan calls of 2 ms
    and one flash call of 5 ms; a decode execution with a scan-named op
    outside any prefill, which is not counted."""
    modules = [("jit_prefill_bucket(1)", 0, 100 * MS),
               ("jit_prefill_bucket(1)", 200 * MS, 100 * MS),
               ("jit_slot_decode(2)", 400 * MS, 20 * MS)]
    ops = []
    for base in (0, 200 * MS):
        ops += [(SCAN_OP.format(k=1), base + 10 * MS, 2 * MS),
                (SCAN_OP.format(k=2), base + 30 * MS, 2 * MS),
                (FLASH_OP, base + 50 * MS, 5 * MS)]
    ops.append((SCAN_OP.format(k=9), 405 * MS, 2 * MS))
    return ops, modules


def test_the_scan_roofline_reads_the_scan_calls_only():
    ops, modules = made_up()
    window = window_with(ops, modules)
    got = harness.read_layer_metrics(window)
    peak = flops.peaks(window.device_kind)
    call = count.ssm_scan_call(CFG, 2048)
    least, bound = flops.roofline_seconds(call["flops"], call["bytes"], peak)
    assert bound == "memory"
    assert got["ssm_scan_roofline"]["value"] == pytest.approx(
        100 * 4 * least / (4 * 0.002))
    # the union of the four 2 ms calls over the two 100 ms executions
    assert got["ssm_scan_prefill_share"]["value"] == pytest.approx(4.0)


def test_a_program_without_the_scan_is_not_read():
    ops, modules = made_up()
    ops = [op for op in ops if "ssm_scan" not in op[0]]
    got = harness.read_layer_metrics(window_with(ops, modules))
    assert "ssm_scan_roofline" not in got
    assert "ssm_scan_prefill_share" not in got


# -- the tiny cell on the CPU -------------------------------------------------


def drive(seed=3_900_000_019, seconds=1.5, **kw):
    return harness.run_cell(CELL, seed, seconds, False,
                            t_start=time.perf_counter(), require_chip=False,
                            bench_file=BENCH_FILE, **kw)


def test_the_tiny_cell_is_correct_and_the_control_is_not():
    """The model with the benchmark's seeded weights, through the engine
    and the HTTP front end; the scan kernel interpreted."""
    result = drive(variants=("control_fp8",))
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    stood = result["readings"]["control_fp8"]
    assert not stood["correct"]
    assert [k for k, row in stood["compared"].items()
            if not row["value"] <= row["limit"]] == ["logit_gap_max"]
