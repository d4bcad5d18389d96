"""The per-layer metrics that read the spans and counters inside the
reader's threads and the engine's loop (PR 24): each reader on a window
made by hand, the least bytes of a decode step against a hand count, the
clock check on made-up tables and on the recorded chip trace, and every
new span and counter metric read off a CPU rehearsal of the tiny cells."""

import importlib
import json
import math
import time
from pathlib import Path

import pytest

import bytes_transformer_lm as bytes_lm
import clockcheck
import flops
import harness
from trace import Tables

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
LAYERS_FILE = HERE / "rehearsal" / "BENCHMARK_layers.json"
GPT = json.loads((HERE.parent / "configs" / "cerebras_gpt_1p3b.json").read_text())
MS = 1_000_000
PARQUET, SERVE = "resnet50_train_parquet", "cerebras_gpt_1p3b_serve_chat"
NEW = [m["name"] for m in json.loads(LAYERS_FILE.read_text())["per_layer"]]


def reader(name):
    return importlib.import_module(
        "layer_metrics." + name.replace(".", "_")).read


def window(cell, *, tables=None, spans=(), stats=None, c0=(), c1=()):
    return harness.Window(
        cell=harness.load_cell(cell), t0=100.0, t1=110.0, wall0=5000.0,
        spans=list(spans), counters0={"metrics": list(c0)},
        counters1={"metrics": list(c1)}, stats=stats or {},
        device_kind="TPU v5 lite", tables=tables,
        traced=(100.0, 102.0) if tables is not None else None)


def series(name, value, **labels):
    return {"name": name, "labels": labels, "value": value}


def test_every_new_metric_is_in_the_benchmark_with_its_reader():
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    assert len(NEW) == 11 and set(NEW) <= set(listed)
    for name in NEW:
        assert listed[name]["workloads"], name
        assert reader(name)(window(listed[name]["workloads"][0])) is None


def test_reader_stage_metrics_by_hand():
    stage = "reader_stage_seconds_total"
    w = window(
        PARQUET,
        c0=[series(stage, 10.0, stage="read"), series(stage, 100.0, stage="decode"),
            series("reader_rows_total", 1000.0),
            series("reader_stall_seconds_total", 2.0),
            series("reader_workers", 2.0)],
        c1=[series(stage, 10.5, stage="read"), series(stage, 113.0, stage="decode"),
            series("reader_rows_total", 11000.0),
            series("reader_stall_seconds_total", 8.5),
            series("reader_workers", 2.0)],
        spans=[{"name": "reader.assemble", "ts": 5001.0, "dur": 0.030},
               {"name": "reader.assemble", "ts": 5002.0, "dur": 0.050},
               {"name": "reader.next", "ts": 5001.0, "dur": 0.150}])
    assert reader("reader_read_us_per_row")(w) == pytest.approx(50.0)
    assert reader("reader_decode_us_per_row")(w) == pytest.approx(1300.0)
    # 13.5 s of loading on 2 threads over a window of 10 s
    assert reader("reader_worker_busy_share")(w) == pytest.approx(67.5)
    assert reader("reader_wait_share")(w) == pytest.approx(65.0)
    assert reader("reader_assemble_ms")(w) == pytest.approx(40.0)


def test_a_parent_without_the_stage_counters_reads_only_the_wait():
    # reader_stall_seconds_total is older than its reader
    w = window(PARQUET, c0=[series("reader_stall_seconds_total", 1.0)],
               c1=[series("reader_stall_seconds_total", 2.0)])
    assert reader("reader_wait_share")(w) == pytest.approx(10.0)
    for name in ("reader_read_us_per_row", "reader_decode_us_per_row",
                 "reader_worker_busy_share", "reader_assemble_ms"):
        assert reader(name)(w) is None


def engine_spans():
    def step(ts, context, active=16):
        return {"name": "lm.step", "ts": ts, "dur": 0.024,
                "args": {"active": active, "context_tokens": context}}

    spans = [step(5000.500, 6000), step(5000.530, 6016),
             {"name": "lm.prefill", "ts": 5000.556, "dur": 0.02,
              "args": {"bucket": 512, "prompt_tokens": 300}},
             step(5000.580, 6800), step(5000.612, 6816),
             step(5005.000, 100, 2)]          # outside the traced two seconds
    for e in list(spans):
        if e["name"] == "lm.step":
            spans.append({"name": "lm.fetch", "ts": e["ts"] + 0.021,
                          "dur": 0.003})
            spans.append({"name": "lm.sample", "ts": e["ts"] + 0.024,
                          "dur": 0.002})
    return spans


def test_engine_metrics_by_hand():
    w = window(SERVE, spans=engine_spans(),
               c0=[series("lm_prefill_tokens_total", 1000.0, kind="real"),
                   series("lm_prefill_tokens_total", 2000.0, kind="padded")],
               c1=[series("lm_prefill_tokens_total", 1700.0, kind="real"),
                   series("lm_prefill_tokens_total", 3000.0, kind="padded")])
    assert reader("logits_fetch_ms")(w) == pytest.approx(3.0)
    assert reader("sampler_host_ms")(w) == pytest.approx(2.0)
    # pairs (1,2) and (3,4): 6 and 8 ms; (2,3) holds a prefill; (4,5) is a
    # pair like any other: 4,364 ms
    assert reader("engine_gap_ms")(w) == pytest.approx((6 + 8 + 4364) / 3)
    assert reader("prefill_padding_share")(w) == pytest.approx(30.0)


def test_the_gap_is_not_read_from_a_program_without_the_sampler_span():
    spans = [e for e in engine_spans() if e["name"] != "lm.sample"]
    assert reader("engine_gap_ms")(window(SERVE, spans=spans)) is None


def test_decode_step_bytes_by_hand_at_cerebras_gpt_1p3b():
    layer = (4 * 2048 * 2048                      # qkv and proj, no bias
             + 2048 * 8192 + 8192 + 8192 * 2048 + 2048   # feed-forward, biases
             + 2 * 2048)                          # two norm gains
    weights = 24 * layer + 2048 + 2048 * 50257    # final norm, untied head
    assert weights == 1_311_232_000
    assert bytes_lm.step_weights(GPT) == weights
    assert bytes_lm.cache_bytes_per_token(GPT) == 196_608
    # 16 slots at a mean context of 400
    assert bytes_lm.decode_step_bytes(GPT, 6400, 16) == (
        2 * weights + 16 * 2 * 2048 * 2 + 6400 * 196_608 + 16 * 50257 * 4)
    assert bytes_lm.decode_step_bytes(GPT, 6400, 16) == 3_884_102_720


def test_step_weights_are_the_references_variables_less_the_embeddings():
    from references import transformer_lm as reference

    shapes = reference.param_shapes(GPT)
    rest = sum(math.prod(s) for k, s in shapes.items() if "embed" not in k)
    assert bytes_lm.step_weights(GPT) == rest


def test_decode_hbm_roofline_by_hand():
    ops = [("%fusion.2 = f32[8] fusion()", 20 * MS, 18 * MS),
           ("%fusion.2 = f32[8] fusion()", 50 * MS, 22 * MS)]
    mods = [("jit_slot_decode(9)", 20 * MS, 19 * MS),
            ("jit_slot_decode(9)", 50 * MS, 23 * MS)]
    tables = Tables({0: {"modules": mods, "ops": ops, "async": []}}, [])
    w = window(SERVE, tables=tables, spans=engine_spans())
    # the four steps that began in the traced two seconds
    least = sum(bytes_lm.decode_step_bytes(GPT, c, 16)
                for c in (6000, 6016, 6800, 6816)) / 4
    peak = flops.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    value = reader("decode_hbm_roofline")(w)
    assert value == pytest.approx(100 * (least / peak) / 0.020)
    assert 20 < value < 30
    # spans of a program that does not say what each step attended over
    old = [{**e, "args": {"active": 16}} for e in engine_spans()
           if e["name"] == "lm.step"]
    assert reader("decode_hbm_roofline")(
        window(SERVE, tables=tables, spans=old)) is None


# -- the clock check -----------------------------------------------------------

def serve_tables(shift_ns=0):
    """Two decode steps: dispatch 1 ms, the device starts 0.3 ms into it
    and runs 20 ms, the wait ends 0.1 ms after the device."""
    mods, host = [], []
    for t in (0, 30 * MS):
        host += [("lm.step", t, 25 * MS), ("lm.dispatch", t, 1 * MS),
                 ("lm.wait", t + 1 * MS, int(19.4 * MS)),
                 ("lm.fetch", t + int(20.4 * MS), 3 * MS)]
        mods.append(("jit_slot_decode(9)", t + int(0.3 * MS) + shift_ns, 20 * MS))
    return Tables({0: {"modules": mods, "ops": [], "async": []}}, host)


def test_clock_check_of_a_serving_trace():
    good = clockcheck.serve(serve_tables())
    assert good["executions"] == 2 and good["worst_violation_ms"] == 0
    assert good["median_slack_ms"] == pytest.approx(0.3)
    assert good["median_end_slack_ms"] == pytest.approx(0.1)
    # the device's clock half a millisecond ahead: the execution ends
    # after its wait
    late = clockcheck.serve(serve_tables(shift_ns=MS // 2))
    assert late["worst_violation_ms"] == pytest.approx(0.4)
    assert late["least_end_slack_ms"] == pytest.approx(-0.4)
    # ... or behind: it begins before its dispatch, and is still paired
    # with it
    early = clockcheck.serve(serve_tables(shift_ns=-MS // 2))
    assert early["worst_violation_ms"] == pytest.approx(0.2)
    assert early["least_slack_ms"] == pytest.approx(-0.2)
    assert clockcheck.serve(Tables({0: {"modules": [], "ops": [],
                                        "async": []}}, [])) is None


def test_clock_check_of_the_recorded_training_trace():
    # perfbench/tests/data: five steps of the tiny bottleneck model on the
    # chip (PR 23), spans from the host tracer of that recording
    t = Tables.read(str(HERE / "data" / "tiny_bottleneck_train_v5e.json.gz"))
    execs = sorted(s for n, s, _ in t.devices[0]["modules"])
    spans = sorted(s for n, s, _ in t.host if n == "train_step")
    got = clockcheck.train(t)
    assert got["executions"] == 5 == len(execs) == len(spans)
    slack = [e - s for e, s in zip(execs, spans)]
    assert got["worst_violation_ms"] == pytest.approx(
        max(0, -min(slack)) / MS)
    assert got["median_slack_ms"] == pytest.approx(sorted(slack)[2] / MS)


# -- the rehearsal: the tiny cells print every new span and counter metric ------

def rehearse(cell, seconds):
    """One CPU run of a tiny cell under the file that lists the new
    metrics; the window as the per-layer readers get it."""
    seen = {}
    finish = harness.finish

    def keep(cell, **kw):
        seen["window"] = kw["window"]
        return finish(cell, **kw)

    harness.finish = keep
    try:
        result = harness.run_cell(cell, 77, seconds, False,
                                  t_start=time.perf_counter(),
                                  require_chip=False, bench_file=LAYERS_FILE)
    finally:
        harness.finish = finish
    assert result["correct"], result["compared"]
    return harness.read_layer_metrics(seen["window"])


def test_the_tiny_parquet_cell_prints_the_readers_metrics():
    got = rehearse("resnet_tiny_train_parquet", 0.5)
    assert set(got) == {"reader_read_us_per_row", "reader_decode_us_per_row",
                        "reader_worker_busy_share", "reader_wait_share",
                        "reader_assemble_ms"}
    assert all(v["value"] > 0 for v in got.values())
    assert got["reader_worker_busy_share"]["value"] <= 100.0


def test_the_tiny_serving_cell_prints_the_engines_metrics():
    # decode_hbm_roofline needs a device trace: a chip
    got = rehearse("lm_tiny_serve_chat", 1.0)
    assert set(got) == {"logits_fetch_ms", "sampler_host_ms",
                        "engine_gap_ms", "prefill_padding_share"}
    assert got["engine_gap_ms"]["value"] >= got["sampler_host_ms"]["value"]
    assert 0 <= got["prefill_padding_share"]["value"] < 100
