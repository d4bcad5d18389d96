"""``moe_expert_kernel_roofline``: the grouped-expert kernel's reader on
a made-up trace of the Nemotron-H agent cell, where the decode step's
``moe_experts`` calls are counted and the chunk's calls, its flash call
and the parent's ``while`` loops are not; and its entry in
``BENCHMARK.json``."""

import json
from pathlib import Path

import pytest

import bytes_nemotron_h_lm as nbytes
import flops
import harness
import trace as tracemod

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
NAME = "moe_expert_kernel_roofline"
AGENT = "nemotron3_super_120b_ep8_serve_agent"
CFG = json.loads((HERE.parent / "configs"
                  / "nemotron3_super_120b_ep8.json").read_text())
EXPERT = 2 * 1024 * 2688      # an expert's up and down, in parameters
MS = 1_000_000

EXPERT_OP = ("%moe_experts.{k} = f32[4,8,1024]{{2,1,0:T(8,128)}} "
             'custom-call(%ids, %u, %up, %down), '
             'custom_call_target="tpu_custom_call"')
WHILE_OP = "%while.19 = (s32[], f32[32,1024]{1,0}) while(%tuple.3)"
FLASH_OP = ("%flash_attention_fwd.3 = bf16[32,1024,128]{2,1,0} "
            'custom-call(%q, %k, %v), custom_call_target="tpu_custom_call"')


def window_with(ops, modules, counters=()):
    kind = next(iter(json.loads((HERE.parent / "peaks.json").read_text())
                     ["by_device_kind"]))
    return harness.Window(
        cell=harness.load_cell(AGENT), t0=0.0, t1=4.0, wall0=0.0, spans=[],
        counters0={"metrics": []}, counters1={"metrics": list(counters)},
        stats={}, device_kind=kind, traced=(0.0, 4.0),
        tables=tracemod.Tables(devices={0: {
            "modules": modules, "ops": ops, "async": []}}, host=[]))


def expert_counters(touched=1800.0, steps=(45.0, 5.0)):
    """``touched`` held experts with a token over ``steps`` decode steps
    (ahead, lockstep), beside a chunk count the reader must not read."""
    counters = [{"name": "lm_moe_experts_touched_total",
                 "labels": {"program": "decode"}, "value": touched},
                {"name": "lm_moe_experts_touched_total",
                 "labels": {"program": "prefill"}, "value": 9999.0}]
    return counters + [{"name": "lm_decode_steps_total",
                        "labels": {"mode": m}, "value": n}
                       for m, n in zip(("ahead", "lockstep"), steps)]


def expert_trace(with_kernel=True):
    """Two decode executions of 10 ms, each with five expert-layer calls
    of 1 ms beside a 2 ms ``while`` loop; a chunk execution of 30 ms with
    an expert-layer call of 4 ms and a flash call, neither counted."""
    modules = [("jit_slot_decode(2)", 0, 10 * MS),
               ("jit_slot_decode(2)", 20 * MS, 10 * MS),
               ("jit_prefill_bucket(1)", 40 * MS, 30 * MS)]
    ops = []
    for base in (0, 20 * MS):
        if with_kernel:
            ops += [(EXPERT_OP.format(k=j), base + j * MS, 1 * MS)
                    for j in range(5)]
        ops.append((WHILE_OP, base + 6 * MS, 2 * MS))
    ops += [(EXPERT_OP.format(k=7), 41 * MS, 4 * MS),
            (FLASH_OP, 50 * MS, 5 * MS)]
    return ops, modules


def test_the_entry_in_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "expert layer",
                     "moves": "serve_tokens_per_s", "workloads": [AGENT]}


def test_the_expert_kernel_roofline_reads_the_decode_steps_calls():
    """36 touched experts a step (1,800 over 50 steps, the chunks' count
    not read) at 11.0 MB each, over the HBM peak, over the 5 ms of
    ``moe_experts`` calls per decode execution."""
    window = window_with(*expert_trace(), counters=expert_counters())
    got = harness.read_layer_metrics(window)[NAME]
    peak = flops.peaks(window.device_kind)["hbm_bytes_per_s"]
    assert nbytes.expert_bytes(CFG) == 2 * EXPERT
    want = 100 * (36 * 2 * EXPERT / peak) / 0.005
    assert got["value"] == pytest.approx(want)
    assert got["unit"] == "%" and 0 < got["value"] < 100


def test_a_decode_step_without_the_expert_kernel_is_not_read():
    """The parent's step runs its experts in ``while`` loops: nothing is
    named ``moe_experts`` there, and the metric is left out; so it is
    where the window has no expert counters."""
    ops, modules = expert_trace(with_kernel=False)
    got = harness.read_layer_metrics(
        window_with(ops, modules, counters=expert_counters()))
    assert NAME not in got
    got = harness.read_layer_metrics(window_with(*expert_trace()))
    assert NAME not in got
