"""``prefill_turn_share``: the reader on windows made by hand, its entry
in ``BENCHMARK.json`` (found by name), and the tiny latent-attention
serving cell on the CPU, whose prompts the engine prefills in chunks."""

import json
import time
from pathlib import Path

import pytest

import harness
from layer_metrics import prefill_turn_share

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
NAME = "prefill_turn_share"
CHAT = "cerebras_gpt_1p3b_serve_chat"
DOC = "mistral_small_4_119b_ep8_serve_doc"
REASON = "jamba2_3b_serve_reason"


def window(c0=(), c1=()):
    return harness.Window(
        cell=harness.load_cell(DOC), t0=100.0, t1=110.0, wall0=5000.0,
        spans=[], counters0={"metrics": list(c0)},
        counters1={"metrics": list(c1)}, stats={}, device_kind="TPU v5 lite")


def launches(kind, value):
    return {"name": "lm_prefill_launches_total", "labels": {"kind": kind},
            "value": value}


def steps(mode, value):
    return {"name": "lm_decode_steps_total", "labels": {"mode": mode},
            "value": value}


def test_the_entry_in_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "http and engine",
        "moves": "itl_p95_ms", "workloads": [CHAT, DOC, REASON]}
    for cell in (CHAT, DOC, REASON):
        assert NAME in harness.load_cell(cell).per_layer


@pytest.mark.parametrize("c0, c1, want", [
    # warm-up's four chunk programs, then 300 steps ahead and 100 chunks
    ([launches("whole", 0), launches("chunk", 4), steps("ahead", 2),
      steps("lockstep", 1)],
     [launches("whole", 0), launches("chunk", 104), steps("ahead", 302),
      steps("lockstep", 1)], 100.0 * 100 / 300),
    # a whole-prompt program: one launch in seven steps
    ([], [launches("whole", 10), launches("chunk", 0), steps("ahead", 60),
          steps("lockstep", 10)], 100.0 / 7),
    # launches of both kinds count alike
    ([], [launches("whole", 3), launches("chunk", 5), steps("ahead", 40)],
     20.0),
    # no decode step in the window
    ([launches("chunk", 4), steps("ahead", 2)],
     [launches("chunk", 9), steps("ahead", 2)], None),
    # a program without the launch counter (the parent of this metric)
    ([steps("ahead", 2)], [steps("ahead", 302)], None),
])
def test_by_hand(c0, c1, want):
    got = prefill_turn_share.read(window(c0, c1))
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_tiny_latent_cell_prefills_in_chunks():
    """The tiny cell's buckets are 16 and 32: its model prefills a chunk
    at a start position, so every launch is a chunk of 16 rows, and a
    prompt of 17-32 tokens takes two of them."""
    seen = {}
    finish = harness.finish

    def keep(cell, **kwargs):
        seen["window"] = kwargs["window"]
        return finish(cell, **kwargs)

    harness.finish = keep
    try:
        result = harness.run_cell(
            "mla_moe_tiny_serve_doc", 40, 1.0, False,
            t_start=time.perf_counter(), require_chip=False,
            bench_file=HERE / "rehearsal_mla_moe" / "BENCHMARK.json")
    finally:
        harness.finish = finish
    assert result["correct"], result["compared"]
    w = seen["window"]
    chunk = w.counter_delta("lm_prefill_launches_total", kind="chunk")
    whole = w.counter_delta("lm_prefill_launches_total", kind="whole")
    padded = w.counter_delta("lm_prefill_tokens_total", kind="padded")
    assert chunk > 0 and whole == 0
    assert padded == 16 * chunk
    got = prefill_turn_share.read(w)
    assert 0 < got < 100
