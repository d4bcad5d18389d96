"""``decode_cache_read_share`` (PR 32): the reader on windows made by hand,
and in ``BENCHMARK.json`` beside the decode program's other metrics."""

import json
from pathlib import Path

import pytest

import harness
from layer_metrics import decode_cache_read_share

ROOT = Path(__file__).resolve().parents[2]
NAME = "decode_cache_read_share"
COUNTER = "lm_decode_cache_rows_total"
CHAT = "cerebras_gpt_1p3b_serve_chat"


def window(c0=(), c1=()):
    return harness.Window(
        cell=harness.load_cell(CHAT), t0=100.0, t1=110.0, wall0=5000.0,
        spans=[], counters0={"metrics": list(c0)},
        counters1={"metrics": list(c1)}, stats={}, device_kind="TPU v5 lite")


def series(kind, value):
    return {"name": COUNTER, "labels": {"kind": kind}, "value": value}


def test_the_entry_in_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "decode program",
        "moves": "serve_tokens_per_s", "workloads": [CHAT]}
    assert NAME in harness.load_cell(CHAT).per_layer


@pytest.mark.parametrize("c0, c1, want", [
    # warm-up's two steps read one block a slot; the window's 100 steps read
    # 34 blocks of 256 each, of an arena of 16 x 2,048
    ([series("read", 2 * 16 * 256), series("arena", 2 * 32768)],
     [series("read", 2 * 16 * 256 + 100 * 34 * 256),
      series("arena", 102 * 32768)], 100.0 * 34 * 256 / 32768),
    # the op took its einsum form: every row, every step
    ([series("read", 65536), series("arena", 65536)],
     [series("read", 10 * 65536), series("arena", 10 * 65536)], 100.0),
    # counted from the first step of the window
    ([], [series("read", 5120), series("arena", 32768)], 15.625),
    # no step in the window
    ([series("read", 512), series("arena", 4096)],
     [series("read", 512), series("arena", 4096)], None),
    # a program without the counter (the parent of PR 32)
    ([], [], None),
])
def test_by_hand(c0, c1, want):
    got = decode_cache_read_share.read(window(c0, c1))
    assert got == (pytest.approx(want) if want is not None else None)
