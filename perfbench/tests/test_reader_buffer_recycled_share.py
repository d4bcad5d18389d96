"""``reader_buffer_recycled_share`` (PR 25): the reader on windows made by
hand, and in ``BENCHMARK.json`` beside the table reader's other metrics."""

import json
from pathlib import Path

import pytest

import harness
from layer_metrics import reader_buffer_recycled_share

ROOT = Path(__file__).resolve().parents[2]
NAME = "reader_buffer_recycled_share"
COUNTER = "reader_batch_buffers_total"
PARQUET = "resnet50_train_parquet"


def window(c0=(), c1=()):
    return harness.Window(
        cell=harness.load_cell(PARQUET), t0=100.0, t1=110.0, wall0=5000.0,
        spans=[], counters0={"metrics": list(c0)},
        counters1={"metrics": list(c1)}, stats={}, device_kind="TPU v5 lite")


def series(source, value):
    return {"name": COUNTER, "labels": {"source": source}, "value": value}


def test_the_entry_in_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "table reader and decode",
        "moves": "train_samples_per_s.table", "workloads": [PARQUET]}
    assert NAME in harness.load_cell(PARQUET).per_layer


@pytest.mark.parametrize("c0, c1, want", [
    # 6 fresh in warm-up, none in the window; views count in neither term
    ([series("fresh", 6), series("recycled", 10), series("view", 3)],
     [series("fresh", 6), series("recycled", 210), series("view", 43)], 100.0),
    # a consumer that holds batches: 50 of the window's 200 copies were new
    ([series("fresh", 6), series("recycled", 10)],
     [series("fresh", 56), series("recycled", 160)], 75.0),
    # nothing ever came free
    ([series("fresh", 6)], [series("fresh", 206), series("view", 40)], 0.0),
    # the first recycled batch fell inside the window
    ([series("fresh", 2)], [series("fresh", 2), series("recycled", 8)], 100.0),
    # every batch a slice of one row group: no copy, nothing to share out
    ([series("view", 5)], [series("view", 50)], None),
    # a program without the counter (the parent of PR 25)
    ([], [], None),
])
def test_by_hand(c0, c1, want):
    got = reader_buffer_recycled_share.read(window(c0, c1))
    assert got == (pytest.approx(want) if want is not None else None)
