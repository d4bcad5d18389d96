"""The spans and counters inside the reader's threads and the LM engine's
loop (PR 24): that they appear where the work happens, that their sums
stay inside what the clock allows, and that the engine thread's time
between two decode steps is cut into named intervals that do not overlap,
with one decode step in flight and in lock-step (PR 30).
"""

from __future__ import annotations

import functools
import time
from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from dss_ml_at_scale_tpu import telemetry
from dss_ml_at_scale_tpu.analysis import lint_text, run_lint
from dss_ml_at_scale_tpu.analysis.checkers.span_discipline import (
    SpanDisciplineChecker,
)
from dss_ml_at_scale_tpu.analysis.checkers.telemetry_registry import (
    TelemetryRegistryChecker,
)
from dss_ml_at_scale_tpu.data import ParquetShardReader, TransformSpec
from dss_ml_at_scale_tpu.data.transform import Field
from dss_ml_at_scale_tpu.serving.lm import LMConfig, LMEngine, StubLMDecoder
from dss_ml_at_scale_tpu.telemetry import catalog, flightrec
from dss_ml_at_scale_tpu.telemetry.spans import SpanLog

PACKAGE = Path(telemetry.__file__).resolve().parents[2]
READER_SPANS = ("reader.read", "reader.decode", "reader.assemble")
ENGINE_SPANS = ("lm.dispatch", "lm.wait", "lm.fetch", "lm.sample", "lm.admit")
NEW_METRICS = {
    "reader_stage_seconds_total": "counter", "reader_rows_total": "counter",
    "reader_workers": "gauge", "lm_prefill_tokens_total": "counter",
    "lm_decode_steps_total": "counter",
    "lm_decode_cache_rows_total": "counter",
}
REMOVED_METRICS = ("trace_spans_total", "lm_decode_step_seconds",
                   "lm_prefill_seconds")


def series(name, **labels):
    for m in telemetry.snapshot()["metrics"]:
        if m["name"] == name and m["labels"] == labels:
            return m["value"]
    return None


# -- the reader ---------------------------------------------------------------


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """4 files x 3 row groups x 20 rows = 240 rows."""
    root = tmp_path_factory.mktemp("traced_table")
    for f in range(4):
        ids = np.arange(f * 60, (f + 1) * 60)
        pq.write_table(pa.table({"id": pa.array(ids)}),
                       root / f"part-{f}.parquet", row_group_size=20)
    return sorted(str(p) for p in root.glob("*.parquet"))


def slow_double(cols):
    time.sleep(0.002)
    return {"id": cols["id"] * 2}


def doubling_spec():
    return TransformSpec(func=slow_double, backend="test",
                         fields=[Field("id", np.dtype(np.int64), ())])


@pytest.fixture
def two_worker_read(table):
    telemetry.reset()
    spec = doubling_spec()
    reader = ParquetShardReader(table, batch_size=48, num_epochs=1,
                                workers_count=2, transform_spec=spec,
                                shuffle_row_groups=False)
    t0 = time.perf_counter()
    it = iter(reader)
    first = next(it)
    workers_mid_read = series("reader_workers")
    batches = [first] + list(it)
    elapsed = time.perf_counter() - t0
    return {"batches": batches, "elapsed": elapsed,
            "workers": workers_mid_read,
            "events": telemetry.get_span_log().events()}


@pytest.mark.parametrize("name", READER_SPANS)
def test_reader_spans_appear_where_the_work_happens(two_worker_read, name):
    events = [e for e in two_worker_read["events"] if e["name"] == name]
    if name == "reader.assemble":
        # one a batch, on the thread that iterates (this one)
        assert len(events) == len(two_worker_read["batches"]) == 5
        assert {e["thread"] for e in events} == {"MainThread"}
        assert [e["args"]["rows"] for e in events] == [48] * 5
        assert all(1 <= e["args"]["groups"] <= 4 for e in events)
    else:
        # one a row group, on the loading threads
        assert len(events) == 12
        assert {e["thread"] for e in events} <= {"reader-worker-0",
                                                  "reader-worker-1"}
        assert all(e["args"]["rows"] == 20 for e in events)
        if name == "reader.decode":
            assert {e["args"]["backend"] for e in events} == {"test"}


def test_reader_stage_seconds_fit_inside_the_workers_time(two_worker_read):
    read = series("reader_stage_seconds_total", stage="read")
    decode = series("reader_stage_seconds_total", stage="decode")
    assert read > 0 and decode >= 12 * 0.002
    assert two_worker_read["workers"] == 2
    assert read + decode <= 2 * two_worker_read["elapsed"]
    # the counters are taken at the spans' boundaries
    for stage, total in (("read", read), ("decode", decode)):
        spans = sum(e["dur"] for e in two_worker_read["events"]
                    if e["name"] == f"reader.{stage}")
        assert spans <= total <= spans + 12 * 0.001


def test_reader_rows_total_is_the_rows_delivered(two_worker_read):
    delivered = sum(len(b["id"]) for b in two_worker_read["batches"])
    assert delivered == 240 == series("reader_rows_total")
    assert series("reader_workers") == 0      # the read is over


def test_the_inline_pool_records_the_same_on_the_callers_thread(table):
    telemetry.reset()
    reader = ParquetShardReader(table[:1], batch_size=20, num_epochs=1,
                                reader_pool_type="dummy",
                                transform_spec=doubling_spec())
    assert sum(len(b["id"]) for b in reader) == 60
    events = telemetry.get_span_log().events()
    for name in READER_SPANS:
        found = [e for e in events if e["name"] == name]
        assert len(found) == 3 and {e["thread"] for e in found} == {
            "MainThread"}
    assert series("reader_rows_total") == 60


# -- the engine's loop ---------------------------------------------------------


class RecordingStub(StubLMDecoder):
    """The stub, remembering the positions it was stepped with."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.stepped: list = []

    def dispatch(self, override, pos):
        self.stepped.append(np.array(pos))
        return super().dispatch(override, pos)


PROMPTS = [[(3 * i + j) % 97 for j in range(2 + i % 7)] for i in range(8)]
DEPTHS = ["ahead", "lockstep"]


@pytest.fixture(scope="module", params=DEPTHS)
def engine_run(request):
    """8 greedy streams over 3 slots, to the end; the span log
    afterwards. At depth ``lockstep`` a request that samples on the host
    holds a fourth slot from before the first to after the last, so the
    engine collects every step in the turn that dispatched it."""
    telemetry.reset()
    parked = request.param == "lockstep"
    slots = 3 + parked
    decoder = RecordingStub(vocab_size=97, step_ms=8.0, slots=slots,
                            max_len=512, buckets=(8, 16))
    engine = LMEngine(decoder, LMConfig(slots=slots, max_len=512,
                                        prefill_buckets=(8, 16),
                                        queue_depth=16)).start()
    try:
        sampler = None
        if parked:
            sampler = engine.submit([1], 500, temperature=1.0, seed=5)
            assert sampler.next_event(timeout=30.0)[0] == "token"
        gens = [engine.submit(p, 6, seed=i) for i, p in enumerate(PROMPTS)]
        for gen in gens:
            while gen.next_event(timeout=30.0)[0] == "token":
                pass
        if parked:
            assert not sampler.is_settled()
            sampler.cancel()
            while sampler.next_event(timeout=30.0)[0] == "token":
                pass
    finally:
        engine.drain(5.0)
    return {"depth": request.param, "decoder": decoder,
            "requests": len(PROMPTS) + parked,
            "events": telemetry.get_span_log().events(),
            "ahead": series("lm_decode_steps_total", mode="ahead"),
            "lockstep": series("lm_decode_steps_total", mode="lockstep"),
            "real": series("lm_prefill_tokens_total", kind="real"),
            "padded": series("lm_prefill_tokens_total", kind="padded")}


def intervals(events, names):
    return sorted((e["ts"], e["ts"] + e["dur"], e["name"]) for e in events
                  if e["name"] in names and e["thread"] == "lm-decode")


def test_engine_intervals_do_not_overlap_and_cover_the_loop(engine_run):
    events = engine_run["events"]
    steps = intervals(events, {"lm.step"})
    assert len(steps) == len(engine_run["decoder"].stepped) > 5
    lo, hi = steps[0][0], steps[-1][0]
    parts = intervals(events, set(ENGINE_SPANS) | {"lm.prefill"})
    assert {name for _, _, name in parts} == set(ENGINE_SPANS) | {
        "lm.prefill"}
    # two clocks are read once an interval: allow their jitter
    for (_, end, a), (start, _, b) in zip(parts, parts[1:]):
        assert start >= end - 50e-6, (a, b, end - start)
    covered = sum(min(e, hi) - max(s, lo) for s, e, _ in parts
                  if e > lo and s < hi)
    assert covered >= 0.95 * (hi - lo), covered / (hi - lo)


def held_by_steps(events):
    """Per ``lm.step`` span, the engine thread's records that were made
    while it was open, and those made under no span. A span is logged
    when it closes and a record when it is made, both by the engine's
    thread, so the log's own order says which span held what: no clock
    is compared. (The records' begins are ``perf_counter`` marks put on
    the epoch by one reading of both clocks each, the span's begin is
    ``time.time()``: a thread preempted between two such reads, as
    under six ``xdist`` workers, shifts a record against its span by
    the length of the preemption.)"""
    names = {"lm.dispatch", "lm.wait", "lm.fetch"}
    held, bare, open_records = [], [], []
    closed_before = False
    for e in events:
        if e["thread"] != "lm-decode":
            continue
        if e["name"] == "lm.sample":
            # the turn is over: what was recorded since the last span
            # closed was recorded under none
            bare.extend(open_records)
            open_records = []
        elif e["name"] in names:
            open_records.append(e)
        elif e["name"] == "lm.step":
            held.append((e, open_records))
            open_records = []
    return held, bare + open_records


def test_every_step_holds_its_dispatch_and_the_collection_of_one(engine_run):
    """One ``lm.step`` a dispatched decode step, its ``lm.dispatch``
    inside it; ``lm.wait`` then ``lm.fetch`` then ``lm.sample`` once a
    collected step. In lock-step the step collected is the span's own;
    a step ahead it is the one before, so a run of steps begins with a
    span that collects nothing and ends with a collection under no
    span. Order is the log's; containment is held on one clock: what a
    span held lasts no longer than the span."""
    events = engine_run["events"]
    held, bare = held_by_steps(events)
    assert len(held) == len(engine_run["decoder"].stepped)
    collected = [e["name"] for e in events if e["thread"] == "lm-decode"
                 and e["name"] in ("lm.wait", "lm.fetch", "lm.sample")]
    assert collected == ["lm.wait", "lm.fetch", "lm.sample"] * (
        len(collected) // 3)
    # every dispatched step is collected, and none twice
    assert collected.count("lm.wait") == len(held)
    for step, records in held:
        names = [r["name"] for r in records]
        assert names in (["lm.dispatch"],
                         ["lm.dispatch", "lm.wait", "lm.fetch"]), names
        # durations are differences of one clock (perf_counter)
        assert sum(r["dur"] for r in records) <= step["dur"] + 1e-6
    assert [r["name"] for r in bare] == ["lm.wait", "lm.fetch"] * (
        len(bare) // 2)
    collecting = [len(records) == 3 for _, records in held]
    waits = [e["dur"] for e in events if e["name"] == "lm.wait"]
    if engine_run["depth"] == "lockstep":
        assert all(collecting) and not bare
        # the stub's sleep stands for the device: from its dispatch, a
        # step is ready 8 ms later, and the span is open until then
        assert all(step["dur"] >= 0.008 for step, _ in held)
        assert all(w > 0 for w in waits)
    else:
        # the k-th collection lies in the span that dispatched step k+1,
        # or, where the engine had nothing more to dispatch, in none: a
        # run of steps begins with a span that collects nothing
        runs = sum(1 for _, records in held if len(records) == 1)
        assert runs == len(bare) // 2
        assert 1 <= runs == engine_run["lockstep"] < len(held) / 3
        # the device's time, less what the host spent since the dispatch
        assert sum(waits) >= 0.004 * len(waits)


def test_decode_steps_are_counted_by_how_they_were_dispatched(engine_run):
    steps = len(engine_run["decoder"].stepped)
    assert engine_run["ahead"] + engine_run["lockstep"] == steps
    if engine_run["depth"] == "lockstep":
        assert engine_run["ahead"] == 0
    else:
        assert engine_run["ahead"] > 2 * engine_run["lockstep"] > 0


def test_context_tokens_is_the_sum_of_the_positions_stepped(engine_run):
    steps = sorted((e for e in engine_run["events"]
                    if e["name"] == "lm.step"), key=lambda e: e["ts"])
    slots = engine_run["decoder"].slots
    for event, pos in zip(steps, engine_run["decoder"].stepped):
        # idle slots are stepped at position 0
        assert event["args"]["context_tokens"] == int(pos.sum())
        assert 1 <= event["args"]["active"] <= slots
        # a slot whose last token is in flight is left out of the step
        assert event["args"]["active"] == int((pos > 0).sum())


def test_the_sampler_interval_counts_what_it_retired(engine_run):
    samples = [e["args"] for e in engine_run["events"]
               if e["name"] == "lm.sample"]
    assert sum(a["retired"] for a in samples) == engine_run["requests"]
    assert all(a["retired"] <= a["active"] <= engine_run["decoder"].slots
               for a in samples)
    admits = [e["args"] for e in engine_run["events"]
              if e["name"] == "lm.admit"]
    assert sum(a["admitted"] for a in admits) == engine_run["requests"]


def test_prefill_tokens_real_and_padded_match_the_prompts(engine_run):
    parked = engine_run["requests"] - len(PROMPTS)   # its prompt: 1 token
    assert engine_run["real"] == sum(len(p) for p in PROMPTS) + parked
    # prompts of 1..8 tokens all pad to the bucket of 8
    assert engine_run["padded"] == 8 * engine_run["requests"]
    prefills = [e["args"] for e in engine_run["events"]
                if e["name"] == "lm.prefill"]
    assert sorted(a["prompt_tokens"] for a in prefills) == sorted(
        [len(p) for p in PROMPTS] + [1] * parked)


# -- what a span costs, and what went ------------------------------------------


def test_an_unarmed_span_reaches_no_flight_recorder(tmp_path):
    log = SpanLog()
    recorder = flightrec.get_recorder()
    assert not flightrec.armed()
    before = len(recorder.tail(10_000))
    with log.span("train_step", step=1):
        pass
    log.record("train_step", time.time(), 0.001)
    assert len(recorder.tail(10_000)) == before
    assert [e["name"] for e in log.events()] == ["train_step"] * 2
    tail = tmp_path / "tail.jsonl"
    flightrec.enable(tail)
    try:
        with log.span("train_step", step=2):
            pass
    finally:
        flightrec.disable(tail)
    phases = [e["ph"] for e in flightrec.read_events(tail)]
    assert phases == ["M", "B", "E"]


@pytest.mark.parametrize("name", REMOVED_METRICS)
def test_series_nothing_read_are_gone(name, engine_run):
    assert name not in catalog.KNOWN_METRICS
    assert name not in {m["name"] for m in telemetry.snapshot()["metrics"]}


# -- the catalog and the lint, both ways ---------------------------------------


@functools.lru_cache(maxsize=1)
def registry_findings():
    return run_lint(["span-discipline", "telemetry-registry"]).findings


@pytest.mark.parametrize("name", READER_SPANS + ENGINE_SPANS)
def test_new_span_is_declared_and_has_its_call_site(name):
    assert name in catalog.KNOWN_SPANS
    assert not [f for f in registry_findings() if repr(name) in f.message]
    # the other way: the call site without the declaration is a finding
    where = "data/reader.py" if name in READER_SPANS else (
        "serving/lm/engine.py")
    known = {k: v for k, v in catalog.KNOWN_SPANS.items() if k != name}
    source = (PACKAGE / "dss_ml_at_scale_tpu" / where).read_text()
    found = lint_text(SpanDisciplineChecker(known=known), source,
                      filename=f"dss_ml_at_scale_tpu/{where}")
    if name in ("reader.read", "reader.decode", "reader.assemble"):
        assert any(repr(name) in f.message for f in found)
    else:
        # recorded at close (suppressed raw records): the declaration
        # still has to find its call site
        checker = SpanDisciplineChecker(known={name: ""})
        lint_text(checker, source, filename=f"dss_ml_at_scale_tpu/{where}")
        assert name in checker.used


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_is_declared_with_its_kind_and_used(name):
    assert catalog.KNOWN_METRICS[name] == NEW_METRICS[name]
    assert not [f for f in registry_findings() if repr(name) in f.message]
    where = ("data/reader.py" if name.startswith("reader_")
             else "serving/lm/engine.py")
    known = {k: v for k, v in catalog.KNOWN_METRICS.items() if k != name}
    source = (PACKAGE / "dss_ml_at_scale_tpu" / where).read_text()
    found = lint_text(TelemetryRegistryChecker(known=known), source,
                      filename=f"dss_ml_at_scale_tpu/{where}")
    assert any(repr(name) in f.message for f in found)


def test_nested_spans_are_left_out_of_a_steps_attribution():
    assert catalog.SPAN_NESTED <= set(catalog.KNOWN_SPANS)
    assert not catalog.SPAN_NESTED & set(catalog.SPAN_ATTRIBUTION)
    assert "reader.assemble" in catalog.SPAN_NESTED
