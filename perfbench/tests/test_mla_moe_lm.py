"""The `mla_moe_lm` family's side of the benchmark: its FLOP and byte
functions against hand counts at the published widths, a tiny cell through
``drivers/serve.py`` on the CPU with the three per-layer metrics the family
brings read off the window, and ``correct`` coming out false under the fp8
control and under an altered token."""

import json
import time
from pathlib import Path

import pytest

import bytes_mla_moe_lm as nbytes
import flops_mla_moe_lm as count
import harness
import trace as tracemod

HERE = Path(__file__).resolve().parent
BENCH_FILE = HERE / "rehearsal_mla_moe" / "BENCHMARK.json"
CELL = "mla_moe_tiny_serve_doc"
CFG = json.loads(
    (HERE.parent / "configs" / "mistral_small_4_119b_ep8.json").read_text())


# -- counts from shapes, by hand ----------------------------------------------

ATTENTION = (4096 * 1024 + 1024 * 32 * 128 + 4096 * 320
             + 256 * 32 * 192 + 32 * 128 * 4096)          # 28,049,408
EXPERT = 3 * 4096 * 2048                                  # 25,165,824
ROUTER = 4096 * 128


def test_the_configuration_is_the_issues_cut():
    assert CFG["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert (CFG["num_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (6, 16, 16384)
    assert CFG["published"] == {"num_hidden_layers": 36,
                                "n_routed_experts": 128,
                                "vocab_size": 131072}
    assert CFG["router_width"] == 128 and CFG["expert_offset"] == 0
    # parameters held: 6 x (53.75 M + 16 x 25.17 M) + embedding and head
    import importlib

    reference = importlib.import_module("references.mla_moe_lm")
    held = sum(_prod(s) for s in reference.param_shapes(CFG).values())
    gains = 2 * 4096 + 1024 + 256
    assert held == (6 * (ATTENTION + EXPERT + ROUTER + gains + 16 * EXPERT)
                    + 2 * 16384 * 4096 + 4096) == 2_872_634_880


def _prod(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def test_flop_counts_against_hand_counts():
    assert count.attention_params(CFG) == ATTENTION == 28_049_408
    assert count.expert_params(CFG) == EXPERT
    assert count.held_experts_per_token(CFG) == 0.5       # 4 x 16 / 128
    layer = ATTENTION + ROUTER + EXPERT + 0.5 * EXPERT
    assert count.layer_macs_per_token(CFG) == layer
    head = 4096 * 16384
    # decode: one token over 1,000 latent rows; scores 320 and values 256
    # a row a head
    assert count.decode_flops(CFG, 1000) == 2 * (
        6 * layer + 6 * 32 * 1000 * (320 + 256) + head)
    # prefill of 8,192: every token through the layers, the causal half of
    # the 128 + 128 score and value products, one row of the head
    n = 8192
    assert count.prefill_flops(CFG, n) == 2 * (
        n * 6 * layer + 6 * 32 * 256 * n * (n + 1) // 2 + head)
    assert 9.7e12 < count.prefill_flops(CFG, n) < 9.9e12
    call = count.flash_prefill_call(CFG, n)
    assert call["flops"] == 2 * 2 * 4096 * n * (n + 1) // 2
    assert call["bytes"] == 4 * n * 4096 * 2


def test_byte_counts_against_hand_counts():
    assert nbytes.expert_bytes(CFG) == 2 * EXPERT == 50_331_648
    gains = 2 * 4096 + 1024 + 256
    layer = 2 * (ATTENTION + EXPERT) + 4 * (ROUTER + gains)
    always = 6 * layer + 4 * 4096 + 2 * 4096 * 16384
    assert nbytes.step_weight_bytes(CFG) == always
    assert 0.78e9 < always < 0.80e9            # 0.65 GB of layers, 0.13 head
    assert nbytes.cache_bytes_per_token(CFG) == 6 * 320 * 2 == 3840
    got = nbytes.decode_step_bytes(CFG, context_tokens=160_000, active=32,
                                   experts_touched=60)
    assert got == (always + 60 * 50_331_648 + 32 * 4096 * 2
                   + 160_000 * 3840 + 32 * 16384 * 4)


# -- the tiny cell on the CPU -------------------------------------------------


def drive(seed=77, seconds=1.0, **kw):
    seen = {}
    finish = harness.finish

    def keep(cell, **kwargs):
        seen["window"] = kwargs["window"]
        return finish(cell, **kwargs)

    harness.finish = keep
    try:
        result = harness.run_cell(CELL, seed, seconds, False,
                                  t_start=time.perf_counter(),
                                  require_chip=False, bench_file=BENCH_FILE,
                                  **kw)
    finally:
        harness.finish = finish
    return result, seen["window"]


def failed(result):
    return [k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]]


@pytest.fixture(scope="module")
def sound():
    return drive(variants=("control_fp8",))


def test_the_tiny_cell_is_correct_and_the_control_is_not(sound):
    result, _ = sound
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    stood = result["readings"]["control_fp8"]
    assert not stood["correct"] and failed(stood) == ["logit_gap_max"]


def test_the_window_gives_the_three_new_metrics(sound):
    _, window = sound
    # no device trace on the CPU: the counters and spans alone
    got = harness.read_layer_metrics(window)
    assert set(got) == {"moe_expert_load_peak", "latent_cache_live_share"}
    assert 1.0 <= got["moe_expert_load_peak"]["value"] <= 4.0   # 4 held
    assert 0 < got["latent_cache_live_share"]["value"] < 100
    held = window.counter_delta("lm_moe_assignments_total", where="held")
    absent = window.counter_delta("lm_moe_assignments_total", where="absent")
    per = sum(window.counter_delta("lm_moe_expert_assignments_total",
                                   expert=str(e)) or 0 for e in range(4))
    assert held == per > 0 and absent > 0
    # the roofline reader on a made-up trace: 3 decode executions, each
    # busy for 2 ms, in a traced part that holds every step of the window
    window.traced = (window.t0, window.t1)
    window.tables = tracemod.Tables(devices={0: {
        "modules": [(f"jit_slot_decode({k})", k * 10_000_000, 2_000_000)
                    for k in range(3)],
        "ops": [("%fusion.1 = f32[4]{0} fusion()", k * 10_000_000, 2_000_000)
                for k in range(3)],
        "async": []}}, host=[])
    import flops

    window.device_kind = next(iter(
        json.loads((HERE.parent / "peaks.json").read_text())
        ["by_device_kind"]))
    got = harness.read_layer_metrics(window)["moe_decode_hbm_roofline"]
    steps = [e["args"] for e in window.spans if e["name"] == "lm.step"]
    touched = window.counter_delta("lm_moe_experts_touched_total",
                                   program="decode")
    n_steps = sum(window.counter_delta("lm_decode_steps_total", mode=m) or 0
                  for m in ("ahead", "lockstep"))
    least = nbytes.decode_step_bytes(
        window.cell.config,
        sum(a["context_tokens"] for a in steps) / len(steps),
        sum(a["active"] for a in steps) / len(steps), touched / n_steps)
    peak = flops.peaks(window.device_kind)["hbm_bytes_per_s"]
    assert got["value"] == pytest.approx(100 * least / peak / 0.002)
    assert 0 < touched / n_steps <= 4 * 2      # held experts x layers


def test_a_program_without_the_counters_is_not_read(sound):
    _, window = sound
    bare = harness.Window(
        cell=window.cell, t0=window.t0, t1=window.t1, wall0=window.wall0,
        spans=window.spans, counters0={"metrics": []},
        counters1={"metrics": []}, stats=window.stats,
        device_kind=window.device_kind, tables=window.tables,
        traced=window.traced)
    assert harness.read_layer_metrics(bare) == {}


def test_a_token_altered_where_it_is_produced_is_caught():
    # greedy ids are picked on the device: the host samples a request's
    # first token only, so every one is altered and every checked request
    # holds one
    result, _ = drive(faults={"alter_token_every": 1})
    assert not result["correct"]
    assert "logit_gap_max" in failed(result)
