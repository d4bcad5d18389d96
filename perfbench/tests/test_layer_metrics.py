"""Each per-layer reader on a window made by hand: the arithmetic, and
that a reader with nothing to read returns nothing (never 0)."""

import importlib
import json
from pathlib import Path

import pytest

import flops
import flops_resnet
import flops_transformer_lm as flops_lm
import harness
from trace import Tables

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MS = 1_000_000


def window(cell, *, tables=None, spans=(), stats=None, traced=(100.0, 102.0),
           c0=None, c1=None):
    return harness.Window(
        cell=harness.load_cell(cell), t0=100.0, t1=110.0, wall0=5000.0,
        spans=list(spans), counters0=c0 or {"metrics": []},
        counters1=c1 or {"metrics": []}, stats=stats or {},
        device_kind="TPU v5 lite", tables=tables,
        traced=traced if tables is not None else None)


def reader(name):
    return importlib.import_module(
        "layer_metrics." + name.replace(".", "_")).read


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]]
                         + ["allreduce_exposed_ms"])
def test_nothing_to_read_gives_nothing(metric):
    cells = next((m["workloads"] for m in BENCH["per_layer"]
                  if m["name"] == metric), ["resnet50_train_predecoded"])
    assert reader(metric)(window(cells[0])) is None


def train_tables():
    ops = [("%fusion.1 = f32[8] fusion()", 0, 90 * MS),
           ("%fusion.1 = f32[8] fusion()", 100 * MS, 90 * MS)]
    mods = [("jit_train_step(1)", 0, 95 * MS),
            ("jit_train_step(1)", 100 * MS, 95 * MS)]
    return Tables({0: {"modules": mods, "ops": ops, "async": []}}, [])


def test_train_readers():
    w = window("resnet50_train_predecoded", tables=train_tables(),
               stats={"batch": 212, "chips": 1},
               spans=[{"name": "feeder.place", "ts": 5001.0, "dur": 0.002},
                      {"name": "feeder.place", "ts": 5002.0, "dur": 0.004},
                      {"name": "reader.next", "ts": 5001.0, "dur": 0.150}],
               c0={"metrics": [{"name": "feeder_stall_seconds_total",
                                "labels": {"feeder": "train"}, "value": 1.0}]},
               c1={"metrics": [{"name": "feeder_stall_seconds_total",
                                "labels": {"feeder": "train"}, "value": 3.5}]})
    assert reader("feeder_place_ms")(w) == pytest.approx(3.0)
    assert reader("reader_next_ms")(w) == pytest.approx(150.0)
    assert reader("data_wait_share.train")(w) == pytest.approx(25.0)
    assert reader("step_device_ms.train")(w) == pytest.approx(90.0)
    assert reader("device_idle_share.train")(w) == pytest.approx(91.0)
    # two executions 100 ms apart: one step period, on the trace's clock
    per_sample = flops_resnet.train_flops_per_sample(w.cell.config)
    assert reader("mfu.train")(w) == pytest.approx(
        100 * per_sample * (212 / 0.1) / 197e12)


def test_the_table_cell_reads_through_the_same_readers():
    names = [m["name"] for m in BENCH["per_layer"]
             if m["moves"] == "train_samples_per_s.table"]
    assert len(names) == 6
    w = window("resnet50_train_parquet", tables=train_tables(),
               stats={"batch": 212, "chips": 1})
    assert w.cell.per_layer == names
    assert w.cell.end_to_end == ["train_samples_per_s.table", "setup_s"]
    for name in names:
        twin = name.replace(".table", ".train")
        if twin not in [m["name"] for m in BENCH["per_layer"]]:
            twin = name.replace(".table", "")
        assert reader(name) is reader(twin), name
    assert reader("mfu.table")(w) == pytest.approx(
        100 * flops_resnet.train_flops_per_sample(w.cell.config)
        * (212 / 0.1) / 197e12)


def test_serve_readers():
    kernel = ('%block_0.1 = bf16[16,512,128]{2,1,0} custom-call(bf16[16,512,128]'
              '{2,1,0} %q, bf16[16,512,128]{2,1,0} %k, bf16[16,512,128]{2,1,0} '
              '%v), custom_call_target="tpu_custom_call"')
    ops = [(kernel, 1 * MS, 1 * MS), ("%fusion.9 = f32[8] fusion()", 2 * MS, 9 * MS),
           ("%fusion.2 = f32[8] fusion()", 20 * MS, 18 * MS),
           (kernel, 50 * MS, 1 * MS)]          # outside any prefill program
    mods = [("jit_prefill_bucket(7)", 0, 12 * MS),
            ("jit_write_slot(8)", 12 * MS, 1 * MS),
            ("jit_slot_decode(9)", 20 * MS, 19 * MS)]
    tables = Tables({0: {"modules": mods, "ops": ops, "async": []}}, [])
    spans = [{"name": "lm.step", "ts": 5000.5, "dur": 0.024,
              "args": {"active": 16}},
             {"name": "lm.step", "ts": 5005.0, "dur": 0.026,
              "args": {"active": 16}},     # outside the traced two seconds
             {"name": "lm.prefill", "ts": 5000.2, "dur": 0.03,
              "args": {"bucket": 512, "prompt_tokens": 300}}]
    w = window("cerebras_gpt_1p3b_serve_chat", tables=tables, spans=spans,
               stats={"chips": 1, "ttft_s": [0.01 * i for i in range(101)]})
    cfg = w.cell.config
    assert reader("ttft_p95_ms")(w) == pytest.approx(950.0)
    assert reader("engine_step_host_ms")(w) == pytest.approx(25.0)
    assert reader("decode_step_device_ms")(w) == pytest.approx(18.0)
    assert reader("prefill_device_ms")(w) == pytest.approx(10.0)
    call = flops_lm.flash_prefill_call(cfg, 512)
    least, _ = flops.roofline_seconds(call["flops"], call["bytes"],
                                      flops.peaks("TPU v5 lite"))
    assert reader("flash_prefill_roofline")(w) == pytest.approx(
        100 * least / 1e-3)
    total = 16 * flops_lm.decode_flops(cfg, 1) + flops_lm.prefill_flops(cfg, 300)
    assert reader("mfu.serve")(w) == pytest.approx(100 * total / (2 * 197e12))
    assert reader("device_idle_share.serve")(w) == pytest.approx(
        100 * (1 - 0.029 / 2))


def test_compare_holds_only_what_has_a_limit():
    ok, table = harness.compare({"a": 0.1, "b": 5.0}, {"a": 0.2})
    assert ok and table == {"a": {"value": 0.1, "limit": 0.2}}
    ok, _ = harness.compare({"a": 0.3}, {"a": 0.2})
    assert not ok
    ok, _ = harness.compare({"a": float("nan")}, {"a": 0.2})
    assert not ok
    with pytest.raises(KeyError):
        harness.compare({"b": 1.0}, {"a": 0.2})
