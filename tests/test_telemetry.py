"""Unified telemetry subsystem (dss_ml_at_scale_tpu/telemetry/).

Registry math and concurrency, Prometheus/JSON renderers, span log +
Perfetto export, device monitor degradation on CPU, compile tracking,
Trainer wiring, the serving `/metrics` scrape, run archival, the
`dsst telemetry` CLI, and the <50 µs/step instrumentation budget.
"""

import http.client
import json
import threading
import time

import numpy as np
import pytest

from dss_ml_at_scale_tpu import telemetry
from dss_ml_at_scale_tpu.telemetry import (
    CompileTracker,
    DeviceMonitor,
    MetricsRegistry,
    SpanLog,
    export_perfetto,
    log_buckets,
    to_perfetto,
)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Zero the process-default registry and span log around each test so
    cross-test counts never leak into assertions."""
    telemetry.reset()
    yield
    telemetry.reset()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    r = MetricsRegistry()
    c = r.counter("requests", "total requests")
    c.inc()
    c.inc(4)
    g = r.gauge("depth")
    g.set(7)
    g.inc(3)
    g.dec(5)
    snap = {m["name"]: m for m in r.snapshot()["metrics"]}
    assert snap["requests"]["value"] == 5.0
    assert snap["depth"]["value"] == 5.0
    with pytest.raises(ValueError):
        c.inc(-1)  # counters only go up


def test_get_or_create_identity_and_kind_mismatch():
    r = MetricsRegistry()
    assert r.counter("x") is r.counter("x")
    with pytest.raises(ValueError):
        r.gauge("x")  # same name, different kind
    r.counter("labeled", labels=("a",))
    with pytest.raises(ValueError):
        r.counter("labeled", labels=("b",))  # label-schema fork
    r.histogram("h", buckets=(0.1, 1.0))
    with pytest.raises(ValueError):
        r.histogram("h", buckets=(5.0, 50.0))  # bucket-schema fork


def test_log_bucket_edges():
    edges = log_buckets(1e-6, 100.0, per_decade=3)
    assert edges[0] == 1e-6 and edges[-1] == 100.0
    assert len(edges) == 25  # 8 decades x 3 + 1
    assert all(a < b for a, b in zip(edges, edges[1:]))  # strictly rising
    # Log spacing: constant ratio between consecutive edges.
    ratios = [b / a for a, b in zip(edges, edges[1:])]
    assert max(ratios) / min(ratios) < 1.01


def test_histogram_bucket_edges_le_semantics():
    r = MetricsRegistry()
    h = r.histogram("lat", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.001, 0.005, 0.05, 5.0):
        h.observe(v)
    (m,) = r.snapshot()["metrics"]
    assert m["count"] == 5
    assert m["sum"] == pytest.approx(5.0565)
    # Cumulative le counts: 0.001 catches 0.0005 AND the exact edge.
    assert m["buckets"] == [
        ["0.001", 2], ["0.01", 3], ["0.1", 4], ["+Inf", 5],
    ]


def test_counter_concurrency_under_threads():
    r = MetricsRegistry()
    c = r.counter("hits")
    h = r.histogram("obs", buckets=(1.0,))
    n_threads, per_thread = 8, 10_000

    def work():
        for _ in range(per_thread):
            c.inc()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = {m["name"]: m for m in r.snapshot()["metrics"]}
    assert snap["hits"]["value"] == n_threads * per_thread
    assert snap["obs"]["count"] == n_threads * per_thread


def test_prometheus_rendering_types_and_escaping():
    r = MetricsRegistry()
    r.counter("total", "all of\nthem").inc(2)
    r.histogram("lat", "latency", labels=("path",), buckets=(0.1, 1.0)) \
        .labels(path='/a"b\\c\nd').observe(0.05)
    text = r.render_prometheus()
    assert "# TYPE total counter" in text
    assert "# HELP total all of\\nthem" in text
    assert "# TYPE lat histogram" in text
    # Label escaping: quote, backslash, newline.
    assert 'path="/a\\"b\\\\c\\nd"' in text
    assert 'lat_bucket{path="/a\\"b\\\\c\\nd",le="0.1"} 1' in text
    assert 'lat_bucket{path="/a\\"b\\\\c\\nd",le="+Inf"} 1' in text
    assert "lat_count" in text and "lat_sum" in text
    assert "total 2" in text


def test_registry_reset_keeps_registrations():
    r = MetricsRegistry()
    c = r.counter("n")
    c.inc(3)
    r.reset()
    snap = {m["name"]: m for m in r.snapshot()["metrics"]}
    assert snap["n"]["value"] == 0.0
    c.inc()  # same family object still live
    assert r.snapshot()["metrics"][0]["value"] == 1.0


# ---------------------------------------------------------------------------
# spans / Perfetto
# ---------------------------------------------------------------------------

def test_span_log_and_perfetto_roundtrip(tmp_path):
    log = SpanLog()
    with log.span("outer", epoch=0):
        with log.span("inner"):
            time.sleep(0.002)
    events = log.events()
    assert [e["name"] for e in events] == ["inner", "outer"]  # close order
    assert events[1]["dur"] >= events[0]["dur"] >= 0.002
    assert "args" not in events[0]  # no-arg spans stay lean
    assert events[1]["args"] == {"epoch": 0}

    # JSONL -> Chrome trace_event file round trip.
    jsonl = tmp_path / "spans.jsonl"
    assert log.dump_jsonl(jsonl) == 2
    out = tmp_path / "trace.json"
    assert export_perfetto(jsonl, out) == 2
    trace = json.loads(out.read_text())  # valid JSON by construction
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    # ph "M" metadata labels the lanes (process + this thread's name);
    # the spans themselves are ph "X" complete events.
    meta = [e for e in evs if e["ph"] == "M"]
    assert {e["name"] for e in meta} == {"process_name", "thread_name"}
    assert any(
        e["name"] == "thread_name" and e["args"]["name"] for e in meta
    )
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        assert e["dur"] >= 0
    # Monotonic microsecond timestamps (metadata first at ts 0).
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)


def test_span_log_capacity_bounded():
    log = SpanLog(capacity=10)
    for i in range(50):
        log.record(f"e{i}", float(i), 0.1)
    events = log.events()
    assert len(events) == 10
    assert events[0]["name"] == "e40"  # oldest evicted


def test_to_perfetto_sorts_unordered_events():
    events = [
        {"name": "b", "ts": 2.0, "dur": 0.1},
        {"name": "a", "ts": 1.0, "dur": 0.1},
    ]
    trace = to_perfetto(events)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["a", "b"]


# ---------------------------------------------------------------------------
# device telemetry
# ---------------------------------------------------------------------------

def test_device_monitor_degrades_on_cpu(devices8):
    r = MetricsRegistry()
    mon = DeviceMonitor(r, devices=devices8)
    mon.sample()  # must not raise: CPU memory_stats may be None
    snap = {
        (m["name"], m["labels"].get("device")): m
        for m in r.snapshot()["metrics"]
    }
    # Every device reported its supportedness; samples counted.
    supported = [
        m for (name, _), m in snap.items()
        if name == "device_memory_stats_supported"
    ]
    assert len(supported) == 8
    assert snap[("device_monitor_samples_total", None)]["value"] == 1.0
    # Background thread start/stop is clean.
    mon.interval_s = 0.01
    mon.start()
    time.sleep(0.05)
    mon.stop()


def test_compile_tracker_counts_retraces():
    import jax
    import jax.numpy as jnp

    r = MetricsRegistry()
    counter = r.counter("compiles")
    fn = jax.jit(lambda x: x * 2)
    tracker = CompileTracker(fn, counter)
    fn(1.0)
    assert tracker.update() == 1  # first call compiled
    fn(2.0)
    assert tracker.update() == 0  # cache hit
    fn(jnp.zeros((4,)))
    assert tracker.update() == 1  # new shape -> retrace
    assert r.snapshot()["metrics"][0]["value"] == 2.0


# ---------------------------------------------------------------------------
# trainer wiring
# ---------------------------------------------------------------------------

def test_trainer_fit_records_metric_series_and_spans(devices8):
    import optax

    from test_models import tiny_resnet
    from test_trainer import synthetic_batches

    from dss_ml_at_scale_tpu.parallel import (
        ClassifierTask,
        Trainer,
        TrainerConfig,
    )
    from dss_ml_at_scale_tpu.runtime import make_mesh

    task = ClassifierTask(model=tiny_resnet(num_classes=4),
                          tx=optax.adam(1e-2))
    trainer = Trainer(
        TrainerConfig(max_epochs=1, steps_per_epoch=8,
                      log_every_steps=1000),
        mesh=make_mesh(),
    )
    result = trainer.fit(task, iter(synthetic_batches(8)))
    assert len(result.history) == 1

    snap = {m["name"]: m for m in telemetry.snapshot()["metrics"]}
    # >= 4 distinct series: step time, data wait, throughput, compiles.
    # The per-step histograms are SAMPLED 1-in-4 (exact totals ride the
    # feeder counters): 8 ticks -> 7 intervals, compile skipped -> 6
    # recorded -> 1 sampled; 8 waits -> 2 sampled.
    assert snap["train_step_seconds"]["count"] == 6 // 4
    assert snap["train_data_wait_seconds"]["count"] == 8 // 4
    assert snap["train_throughput_rows_per_sec"]["value"] > 0
    assert snap["train_compile_events_total"]["value"] >= 1
    # The feeder staged + sharded every batch on its own thread, with
    # exact batch/stall accounting and occupancy/depth gauges.
    train_feeder = {
        m["name"]: m
        for m in telemetry.snapshot()["metrics"]
        if (m.get("labels") or {}).get("feeder") == "train"
    }
    assert train_feeder["feeder_stage_seconds"]["count"] == 8
    assert train_feeder["feeder_batches_total"]["value"] == 8
    assert train_feeder["feeder_depth"]["value"] >= 1
    assert "feeder_occupancy" in train_feeder
    assert "feeder_stall_seconds_total" in train_feeder

    # Span log covers the epoch and exports to valid Chrome JSON.
    events = telemetry.get_span_log().events()
    assert any(e["name"] == "train_epoch" for e in events)
    trace = json.loads(json.dumps(to_perfetto(events)))
    ts = [e["ts"] for e in trace["traceEvents"]]
    assert ts == sorted(ts) and len(ts) >= 1


def test_step_timer_observer_skips_compile_interval():
    from dss_ml_at_scale_tpu.utils import StepTimer

    seen = []
    t = StepTimer(capacity=2, observer=seen.append)
    for _ in range(5):
        t.tick()
    # 4 intervals ticked; the compile one dropped; ring holds last 2 but
    # the observer saw every recorded interval.
    assert len(seen) == 3
    assert len(t.intervals) == 2
    assert t.intervals == seen[-2:]


# ---------------------------------------------------------------------------
# serving /metrics
# ---------------------------------------------------------------------------

class _StubPredictor:
    """Predictor-shaped stub: make_server only needs meta/step/crop and
    predict() — no checkpoint or compile required for scrape tests."""

    meta = {"model": "stub"}
    step = 7
    crop = 8

    def predict(self, jpegs):
        return [{"pred_index": 0, "pred_prob": 1.0} for _ in jpegs]


@pytest.fixture()
def stub_server():
    from dss_ml_at_scale_tpu.workloads.serving import serve_in_thread

    handle = serve_in_thread(_StubPredictor())
    yield handle.port
    handle.close()


def _request(port, method, path, body=None, content_type=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    headers = {"Content-Type": content_type} if content_type else {}
    conn.request(method, path, body=body, headers=headers)
    resp = conn.getresponse()
    payload = resp.read()
    ctype = resp.getheader("Content-Type", "")
    conn.close()
    return resp.status, payload, ctype


def test_metrics_endpoint_scrape(stub_server):
    port = stub_server
    # Generate one successful predict and one 404.
    status, _, _ = _request(port, "POST", "/predict", body=b"rawbytes",
                            content_type="image/jpeg")
    assert status == 200
    status, _, _ = _request(port, "GET", "/nope")
    assert status == 404

    status, body, ctype = _request(port, "GET", "/metrics")
    assert status == 200
    assert ctype.startswith("text/plain")
    text = body.decode()
    # Prometheus exposition with the request-latency histogram.
    assert "# TYPE serving_request_seconds histogram" in text
    assert 'serving_request_seconds_bucket{path="/predict",le="+Inf"} 1' \
        in text
    assert 'serving_request_seconds_count{path="/predict"} 1' in text
    assert "# TYPE serving_errors_total counter" in text
    assert 'serving_errors_total{code="404"} 1' in text


def test_metrics_endpoint_on_fresh_server_declares_series(stub_server):
    status, body, _ = _request(stub_server, "GET", "/metrics")
    assert status == 200
    text = body.decode()
    # No traffic yet (beyond this scrape) — the families still declare
    # themselves so scrapers see stable series types.
    assert "# TYPE serving_request_seconds histogram" in text
    assert "# TYPE serving_errors_total counter" in text


def test_remote_snapshot_pull_over_rpc():
    """The multi-host discipline: a coordinator pulls a worker host's
    snapshot over the runtime/rpc control plane (the handlers every
    `dsst trial-worker` serves)."""
    from dss_ml_at_scale_tpu.parallel.trials import serve_trial_worker
    from dss_ml_at_scale_tpu.telemetry import collect_remote_snapshots

    telemetry.counter("worker_side_things").inc(5)
    server = serve_trial_worker(block=False)
    try:
        addr = f"{server.address[0]}:{server.address[1]}"
        snaps = collect_remote_snapshots([addr, "127.0.0.1:1"], timeout=5)
        names = {m["name"]: m for m in snaps[addr]["metrics"]}
        assert names["worker_side_things"]["value"] == 5.0
        # Unreachable workers degrade to an error entry, not a raise.
        assert "error" in snaps["127.0.0.1:1"]
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# run archival + CLI
# ---------------------------------------------------------------------------

def test_run_store_context_manager_and_telemetry_archive(tmp_path):
    from dss_ml_at_scale_tpu.tracking import RunStore

    telemetry.counter("archived_things").inc(3)
    with RunStore(tmp_path, "exp", run_name="ctx") as store:
        store.log_metrics({"loss": 1.0}, step=1)
        assert store.metrics()[0]["value"] == 1.0  # read-back while open
        store.log_telemetry()
    meta = json.loads((store.path / "meta.json").read_text())
    assert meta["status"] == "FINISHED"
    snap = json.loads((store.path / "telemetry.json").read_text())
    names = {m["name"]: m for m in snap["metrics"]}
    assert names["archived_things"]["value"] == 3.0
    # finish() is idempotent: the crash handler double-close is a no-op.
    store.finish("FAILED")
    assert json.loads(
        (store.path / "meta.json").read_text()
    )["status"] == "FINISHED"


def test_run_store_context_manager_marks_failed(tmp_path):
    from dss_ml_at_scale_tpu.tracking import RunStore

    with pytest.raises(RuntimeError):
        with RunStore(tmp_path, "exp") as store:
            raise RuntimeError("boom")
    meta = json.loads((store.path / "meta.json").read_text())
    assert meta["status"] == "FAILED"


def test_telemetry_cli_table_json_and_perfetto(tmp_path, capsys):
    from dss_ml_at_scale_tpu.config.cli import main

    run_dir = tmp_path / "root" / "exp" / "run1"
    (run_dir / "artifacts").mkdir(parents=True)
    (run_dir / "telemetry.json").write_text(json.dumps({
        "ts": 1.0,
        "metrics": [
            {"name": "steps", "type": "counter", "labels": {}, "value": 8},
            {"name": "lat", "type": "histogram", "labels": {"p": "/x"},
             "count": 2, "sum": 0.5,
             "buckets": [["0.1", 1], ["+Inf", 2]]},
        ],
    }))
    (run_dir / "artifacts" / "spans.jsonl").write_text(
        json.dumps({"name": "epoch", "ts": 2.0, "dur": 1.0}) + "\n"
        + json.dumps({"name": "eval", "ts": 1.0, "dur": 0.5}) + "\n"
    )

    assert main(["telemetry", "--run", str(run_dir)]) == 0
    out = capsys.readouterr().out
    assert "steps" in out and "lat{p=/x}" in out and "count=2" in out

    assert main(["telemetry", "--run", str(run_dir), "--json"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["metrics"][0]["name"] == "steps"

    trace_out = tmp_path / "trace.json"
    assert main([
        "telemetry", "--run", str(run_dir),
        "--export-perfetto", str(trace_out),
    ]) == 0
    capsys.readouterr()
    trace = json.loads(trace_out.read_text())
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    assert names == ["eval", "epoch"]  # sorted by ts

    # Usage errors are loud, not tracebacks.
    assert main(["telemetry"]) == 2
    assert main(["telemetry", "--run", str(tmp_path / "missing")]) == 1
    capsys.readouterr()

    # A run with NO archived span log still prints its snapshot before
    # the export reports the miss.
    bare = tmp_path / "root" / "exp" / "run2"
    bare.mkdir(parents=True)
    bare.joinpath("telemetry.json").write_text(
        json.dumps({"ts": 1.0, "metrics": []})
    )
    assert main([
        "telemetry", "--run", str(bare),
        "--export-perfetto", str(tmp_path / "t2.json"),
    ]) == 1
    out = capsys.readouterr().out
    assert "(empty snapshot)" in out and "no span log" in out


# ---------------------------------------------------------------------------
# overhead budget
# ---------------------------------------------------------------------------

def test_per_step_instrumentation_under_50us():
    """The Trainer's per-step registry work (two histogram observes, a
    counter probe path, a gauge set) must stay under 50 µs on CPU."""
    from dss_ml_at_scale_tpu.analysis.sanitize import is_armed

    if is_armed():
        # A DSST_SANITIZE=1 session wraps every lock acquire with
        # bookkeeping — the budget below is the PRODUCTION (disarmed)
        # contract; the armed overhead is `dsst bench`'s
        # sanitizer_overhead scenario.
        pytest.skip("sanitizer armed: per-op budget is a disarmed contract")
    r = MetricsRegistry()
    step_hist = r.histogram("step_s")
    wait_hist = r.histogram("wait_s")
    compiles = r.counter("compiles")
    depth = r.gauge("depth")

    n = 5_000
    t0 = time.perf_counter()
    for _ in range(n):
        wait_hist.observe(1e-4)
        step_hist.observe(1e-3)
        compiles.inc(0)
        depth.set(2)
    per_step = (time.perf_counter() - t0) / n
    assert per_step < 50e-6, f"registry ops cost {per_step * 1e6:.1f} µs/step"
