"""Fleet observability plane: trace propagation wire codec, sketch /
registry / SLO-source federation, the aggregator's straggler
resilience, and the fleet CLI over real replica processes.

The live tests spawn REAL stub-scorer serving subprocesses
(``bench.loadgen.spawn_stub_server``), so the cross-process claims
(one trace id across client → server → response header; fleet-merged
p99 vs pooled offline quantile) are exercised over actual sockets and
actual process boundaries, not in-process simulations.
"""

import json
import random
import socket
import time

import http.client

import pytest

from dss_ml_at_scale_tpu.telemetry import federation, slo, windows
from dss_ml_at_scale_tpu.telemetry.registry import MetricsRegistry
from dss_ml_at_scale_tpu.telemetry.tracecontext import (
    Handoff,
    TraceContext,
    new_trace_id,
)
from dss_ml_at_scale_tpu.telemetry.windows import (
    SlidingQuantile,
    WindowedCounter,
    quantile,
)

# One sketch bucket's width (9 per decade, + float slack): the
# documented value-error bound every merged-quantile assertion uses —
# the same constant tests/test_windows.py pins for the local sketch.
BUCKET_RATIO = 10 ** (1 / 9) + 0.01


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


# -- Handoff wire codec -------------------------------------------------------


def test_handoff_header_roundtrip():
    h = Handoff.root("request")
    header = h.to_header()
    assert header.startswith("dsst1-")
    back = Handoff.from_header(header)
    assert back.ctx == h.ctx
    # Every declared kind round-trips, not just "request".
    for kind in ("request", "step", "trial", "run"):
        ctx = TraceContext(new_trace_id(), "ab12cd34", kind)
        assert Handoff(ctx).to_header() is not None
        assert Handoff.from_header(Handoff(ctx).to_header()).ctx == ctx


def test_handoff_empty_to_header():
    assert Handoff(None).to_header() is None
    assert Handoff.capture().to_header() is None  # no active trace here


def test_handoff_from_header_hostile_inputs():
    good = Handoff.root("request").to_header()
    hostile = [
        None,
        "",
        123,
        b"dsst1-0000000000000000-00000000-request",
        "x" * 1000,                      # oversized
        good + "-extra",                 # wrong field count
        good.rsplit("-", 1)[0],          # missing kind
        "dsst2-" + good.split("-", 1)[1],  # unknown version
        good.upper(),                    # hex must be lowercase
        "dsst1-zzzzzzzzzzzzzzzz-00000000-request",  # bad hex
        "dsst1-0000000000000000-0000000g-request",  # bad hex (span)
        "dsst1-0000000000000000-00000000-Re quest",  # bad kind chars
        "dsst1-0000000000000000-00000000-" + "k" * 40,  # kind too long
        "dsst1-00000000000000-00000000-request",    # trace too short
    ]
    for value in hostile:
        h = Handoff.from_header(value)  # must NEVER raise
        assert h.ctx is None, value


# -- window wire codec --------------------------------------------------------


def test_windowed_counter_wire_merge():
    clock = FakeClock()
    a = WindowedCounter(30.0, clock=clock)
    b = WindowedCounter(30.0, clock=clock)
    a.add(3.0)
    b.add(4.0)
    b.merge_wire(a.to_wire())
    assert b.total() == pytest.approx(7.0)
    # Merging an empty counter is a no-op, not an error.
    b.merge_wire(WindowedCounter(30.0, clock=clock).to_wire())
    assert b.total() == pytest.approx(7.0)


def test_windowed_counter_wire_geometry_checked():
    clock = FakeClock()
    c = WindowedCounter(30.0, clock=clock)
    other = WindowedCounter(60.0, clock=clock)
    other.add(1.0)
    with pytest.raises(ValueError, match="geometry"):
        c.merge_wire(other.to_wire())
    wire = WindowedCounter(30.0, clock=clock).to_wire()
    with pytest.raises(ValueError, match="version"):
        c.merge_wire({**wire, "v": 99})
    with pytest.raises(ValueError, match="kind"):
        c.merge_wire({**wire, "kind": "sliding_quantile"})
    with pytest.raises(ValueError):
        c.merge_wire("not a dict")


def test_sliding_quantile_wire_merge_property():
    """Fleet-merged quantiles match the pooled-sample definition within
    one bucket width — the federation invariant every fleet p99 claim
    rests on."""
    rng = random.Random(7)
    clock = FakeClock()
    samples = [rng.lognormvariate(-3.0, 1.0) for _ in range(3000)]
    shards = [samples[i::3] for i in range(3)]
    sketches = []
    for shard in shards:
        sk = SlidingQuantile(window_s=60.0, clock=clock)
        for v in shard:
            sk.observe(v)
        sketches.append(sk)
    fleet = SlidingQuantile(window_s=60.0, clock=clock)
    for sk in sketches:
        fleet.merge_wire(sk.to_wire())
    assert fleet.count() == len(samples)
    pooled = sorted(samples)
    for q in (0.5, 0.9, 0.99):
        est = fleet.quantile(q)
        exact = quantile(pooled, q)
        assert 1 / BUCKET_RATIO <= est / exact <= BUCKET_RATIO, (
            q, est, exact,
        )
    snap = fleet.snapshot()
    assert snap["min"] == pytest.approx(min(samples))
    assert snap["max"] == pytest.approx(max(samples))
    assert snap["sum"] == pytest.approx(sum(samples), rel=1e-6)


def test_sliding_quantile_wire_carries_worst_trace():
    clock = FakeClock()
    a = SlidingQuantile(window_s=60.0, clock=clock)
    b = SlidingQuantile(window_s=60.0, clock=clock)
    a.observe(0.010, trace="aaaa")
    b.observe(5.000, trace="the-worst")
    a.merge_wire(b.to_wire())
    assert a.worst_trace() == "the-worst"


def test_sliding_quantile_wire_geometry_checked():
    clock = FakeClock()
    sk = SlidingQuantile(window_s=60.0, clock=clock)
    other = SlidingQuantile(window_s=30.0, clock=clock)
    other.observe(1.0)
    with pytest.raises(ValueError, match="geometry"):
        sk.merge_wire(other.to_wire())
    wire = other.to_wire()
    with pytest.raises(ValueError, match="version"):
        sk.merge_wire({**wire, "v": 2})
    # Edges are part of the geometry: same window, different buckets
    # must refuse (silently misaligned counts would corrupt quantiles).
    custom = SlidingQuantile(window_s=60.0, edges=(0.1, 1.0, 10.0),
                             clock=clock)
    custom.observe(0.5)
    with pytest.raises(ValueError):
        sk.merge_wire(custom.to_wire())


def test_quantile_of_wire():
    clock = FakeClock()
    sk = SlidingQuantile(window_s=60.0, clock=clock)
    vals = [0.001 * (i + 1) for i in range(100)]
    for v in vals:
        sk.observe(v)
    est = windows.quantile_of_wire(sk.to_wire(), 0.99)
    exact = quantile(sorted(vals), 0.99)
    assert 1 / BUCKET_RATIO <= est / exact <= BUCKET_RATIO
    empty = SlidingQuantile(window_s=60.0, clock=clock)
    assert windows.quantile_of_wire(empty.to_wire(), 0.99) is None


# -- registry federation ------------------------------------------------------


def test_registry_wire_snapshot_merges_all_kinds():
    src = MetricsRegistry()
    dst = MetricsRegistry()
    src.counter("c_total").inc(3)
    src.gauge("g").set(2.5)
    src.counter("lc_total", labels=("k",)).labels(k="a").inc(2)
    h = src.histogram("h_seconds")
    for v in (1e-4, 1e-3, 0.5):
        h.observe(v)
    w = src.window("w_seconds")
    for i in range(100):
        w.observe(0.001 * (i + 1))
    # Merge TWICE (two replicas with identical series): everything
    # must be additive.
    snap = src.wire_snapshot()
    assert dst.merge_wire_snapshot(snap) == 5
    assert dst.merge_wire_snapshot(json.loads(json.dumps(snap))) == 5

    assert dst.counter("c_total")._children[()].value == 6
    assert dst.gauge("g")._children[()].value == 5.0  # gauges sum
    assert dst.counter(
        "lc_total", labels=("k",)
    ).labels(k="a").value == 4
    hd = dst.histogram("h_seconds")._children[()]
    assert hd.count == 6
    assert hd.sum == pytest.approx(2 * (1e-4 + 1e-3 + 0.5))
    assert dst.window("w_seconds")._children[()]._sketch.count() == 200


def test_registry_wire_snapshot_geometry_checked():
    src = MetricsRegistry()
    src.histogram("h", buckets=(0.1, 1.0)).observe(0.5)
    dst = MetricsRegistry()
    dst.histogram("h", buckets=(0.1, 1.0, 10.0))
    with pytest.raises(ValueError, match="buckets"):
        dst.merge_wire_snapshot(src.wire_snapshot())
    with pytest.raises(ValueError, match="version"):
        dst.merge_wire_snapshot({"version": 99, "metrics": []})
    # Kind mismatch through the get-or-create path fails loudly too.
    src2 = MetricsRegistry()
    src2.counter("x").inc()
    dst2 = MetricsRegistry()
    dst2.gauge("x")
    with pytest.raises(ValueError, match="registered"):
        dst2.merge_wire_snapshot(src2.wire_snapshot())


# -- SLO source federation ----------------------------------------------------


def test_slo_wire_sources_merge_pools_windows():
    clock = FakeClock()
    a = slo.SloEngine(clock=clock)
    b = slo.SloEngine(clock=clock)
    fleet = slo.SloEngine(clock=clock)
    for _ in range(30):
        a.note_request(0.010, 200)
    for _ in range(30):
        b.note_request(0.010, 503)
    fleet.merge_wire_sources(a.wire_sources())
    fleet.merge_wire_sources(b.wire_sources())
    _, report = fleet._evaluate()
    err = report["serving_error_rate"]["m"]
    assert err["samples"] == 60
    assert err["value"] == pytest.approx(0.5)
    # 50% bad over a 1% budget: both windows burn way past threshold.
    assert err["burn_fast"] >= 6.0 and err["burn_slow"] >= 6.0


def test_slo_wire_sources_version_and_unknown_names():
    clock = FakeClock()
    e = slo.SloEngine(clock=clock)
    doc = e.wire_sources()
    with pytest.raises(ValueError, match="version"):
        e.merge_wire_sources({**doc, "version": 99})
    # An unknown objective from a newer replica is skipped, not fatal:
    # every declared objective merges, the foreign name contributes 0.
    extra = dict(doc["sources"])
    extra["future_objective"] = {"kind": "events"}
    assert e.merge_wire_sources({**doc, "sources": extra}) == len(doc["sources"])
    # A known name with the wrong kind payload fails loudly.
    bad = dict(doc["sources"])
    bad["serving_error_rate"] = bad["feeder_stall_fraction"]
    with pytest.raises(ValueError, match="kind"):
        e.merge_wire_sources({**doc, "sources": bad})


def test_slo_reset_sources_keeps_judgment_state():
    clock = FakeClock()
    e = slo.SloEngine(clock=clock)
    e.set_latency_budget(0.5)
    e.set_target("train_step_p95", 0.25)
    for _ in range(30):
        e.note_request(0.010, 200)
    e.reset_sources()
    # Windows gone, configuration kept.
    _, report = e._evaluate()
    assert report["serving_error_rate"]["m"]["samples"] == 0
    assert e.latency_budget == 0.5
    assert report["train_step_p95"]["m"]["budget"] == 0.25
    # The fleet adopts the strictest budget seen, never a laxer one.
    peer = slo.SloEngine(clock=clock)
    peer.set_latency_budget(2.0)
    e.merge_wire_sources(peer.wire_sources())
    assert e.latency_budget == 0.5
    peer.set_latency_budget(0.1)
    e.merge_wire_sources(peer.wire_sources())
    assert e.latency_budget == 0.1


def test_federation_burning_helper():
    doc = {
        "firing": ["a"],
        "objectives": [
            {"name": "a", "burn_fast": 0, "burn_slow": 0,
             "burn_threshold": 6.0},
            {"name": "b", "burn_fast": 50.0, "burn_slow": 50.0,
             "burn_threshold": 6.0},
            {"name": "c", "burn_fast": 50.0, "burn_slow": 0.0,
             "burn_threshold": 6.0},  # fast alone is not a burn
        ],
    }
    assert federation.burning(doc) == ["a", "b"]
    assert federation.burning({"firing": [], "objectives": []}) == []


def test_read_fleet_journal_tolerates_torn_tail(tmp_path):
    p = tmp_path / "fleet.jsonl"
    rows = [
        json.dumps({"kind": "fleet_scrape", "ts": 1.0, "up": 2}),
        json.dumps({"kind": "other", "ts": 2.0}),
        '{"kind": "fleet_scrape", "ts": 3.0, "up',  # torn append
    ]
    p.write_text("\n".join(rows) + "\n")
    out = federation.read_fleet_journal(p)
    assert len(out) == 1 and out[0]["up"] == 2
    assert federation.read_fleet_journal(tmp_path / "missing.jsonl") == []


# -- live fleet over real replica processes -----------------------------------


@pytest.fixture(scope="module")
def stub_fleet(tmp_path_factory):
    """TWO stub-scorer serving subprocesses with access logs and
    flight recorders armed, plus a shot of real propagated-trace load
    at each — the fleet every live test below judges."""
    from dss_ml_at_scale_tpu.bench.loadgen import (
        run_load,
        spawn_stub_server,
    )

    td = tmp_path_factory.mktemp("fleet")
    procs, replicas = [], []
    try:
        for i in range(2):
            access = td / f"access{i}.jsonl"
            rec = td / f"flightrec{i}.jsonl"
            proc, port = spawn_stub_server(
                score_ms=1.0, batch_window_ms=1.0,
                access_log=access, flightrec=rec,
            )
            procs.append(proc)
            report = run_load("127.0.0.1", port, b"0", threads=2,
                              duration_s=1.0)
            assert report["requests"] > 0
            # EVERY request's injected trace id came back: the server
            # adopted rather than minted, across a real process hop.
            assert report["trace_propagated"] == report["requests"]
            replicas.append({
                "endpoint": f"127.0.0.1:{port}",
                "port": port,
                "access": access,
                "flightrec": rec,
                "report": report,
            })
        yield replicas
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            proc.wait(15)


def _request(port, method, path, body=None, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request(method, path, body=body, headers=headers or {})
    resp = conn.getresponse()
    payload = resp.read()
    trace = resp.getheader("X-DSST-Trace")
    conn.close()
    return resp.status, payload, trace


def _access_rows(path):
    return [
        json.loads(line)
        for line in path.read_text().splitlines() if line.strip()
    ]


def test_preminted_trace_is_one_trace_end_to_end(stub_fleet):
    """ONE pre-minted trace id across client → both replicas → response
    headers, journaled as inherited — the cross-process propagation
    acceptance path."""
    pre = new_trace_id()
    header = Handoff(TraceContext(pre, "00000001", "request")).to_header()
    for r in stub_fleet:
        status, _, echoed = _request(
            r["port"], "POST", "/predict", body=b"0",
            headers={"Content-Type": "image/jpeg",
                     "X-DSST-Trace": header},
        )
        assert status == 200
        assert echoed == pre  # adopted, not minted
    # A minted (headerless) request still works and is journaled as
    # NOT inherited.
    status, _, minted = _request(
        stub_fleet[0]["port"], "POST", "/predict", body=b"0",
        headers={"Content-Type": "image/jpeg"},
    )
    assert status == 200 and minted and minted != pre
    time.sleep(0.3)  # let the access writer flush
    for r in stub_fleet:
        rows = _access_rows(r["access"])
        inherited = [x for x in rows if x["request_id"] == pre]
        assert len(inherited) == 1
        assert inherited[0]["trace_inherited"] is True
        # The load fixture's requests all carried headers too.
        assert all(
            x["trace_inherited"] is True
            for x in rows if x["request_id"] != minted
        )
    minted_rows = [
        x for x in _access_rows(stub_fleet[0]["access"])
        if x["request_id"] == minted
    ]
    assert minted_rows and minted_rows[0]["trace_inherited"] is False


def test_trace_export_merge_renders_both_replicas(stub_fleet, tmp_path,
                                                  capsys):
    """`trace export --merge` of two replicas' recorders: both process
    lanes labeled, and a pre-minted trace id served by BOTH replicas
    draws flow arrows ACROSS the files."""
    from dss_ml_at_scale_tpu.config.cli import main
    from dss_ml_at_scale_tpu.telemetry.spans import REPLICA_PID_STRIDE

    # One trace id through both replicas (self-sufficient: no ordering
    # dependence on the propagation test above).
    shared = Handoff.root("request")
    for r in stub_fleet:
        status, _, _ = _request(
            r["port"], "POST", "/predict", body=b"0",
            headers={"Content-Type": "image/jpeg",
                     "X-DSST-Trace": shared.to_header()},
        )
        assert status == 200
    time.sleep(0.3)  # let both recorders write through

    out = tmp_path / "merged.json"
    rc = main([
        "trace", "export",
        "--merge", str(stub_fleet[0]["flightrec"]),
        str(stub_fleet[1]["flightrec"]),
        "--out", str(out),
    ])
    capsys.readouterr()
    assert rc == 0
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    proc_names = {
        e["pid"]: e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    bands = {pid // REPLICA_PID_STRIDE for pid in proc_names}
    assert bands == {0, 1}
    names = sorted(proc_names.values())
    assert any("replica 0" in n for n in names)
    assert any("replica 1" in n for n in names)
    # Cross-file flows: at least one trace id's flow arrows touch BOTH
    # pid bands (the pre-minted trace served by both replicas).
    flow_bands: dict[str, set] = {}
    for e in events:
        if e.get("ph") in ("s", "f"):
            flow_bands.setdefault(e["name"], set()).add(
                e["pid"] // REPLICA_PID_STRIDE
            )
    assert any(b == {0, 1} for b in flow_bands.values()), flow_bands


def test_fleet_aggregator_merges_live_replicas(stub_fleet, tmp_path):
    """Merged fleet p99 within sketch error of the POOLED offline
    quantile over both replicas' journaled per-request latencies."""
    journal = tmp_path / "fleet.jsonl"
    agg = federation.FleetAggregator(
        [r["endpoint"] for r in stub_fleet], journal_path=journal,
    )
    view = agg.scrape()
    assert view.up == 2
    assert all(r.outcome == "ok" for r in view.replicas)
    assert view.merged_series > 0

    pooled = sorted(
        row["latency_ms"] / 1000.0
        for r in stub_fleet
        for row in _access_rows(r["access"])
        if row["status"] == 200
    )
    fam = view.registry.window("serving_request_window_seconds")
    merged_p99 = fam.quantile(0.99)
    exact = quantile(pooled, 0.99)
    assert merged_p99 is not None
    assert 1 / BUCKET_RATIO <= merged_p99 / exact <= BUCKET_RATIO, (
        merged_p99, exact,
    )
    # The merged 60s window saw every pooled request — counts federate
    # exactly, not approximately.
    assert fam._children[()]._sketch.count() == len(pooled)
    lat = [o for o in view.slo["objectives"]
           if o["name"] == "serving_latency_p99"][0]
    assert lat["samples"] > 0
    assert view.slo["ok"] is True
    # The cycle journaled crash-durably.
    cycles = federation.read_fleet_journal(journal)
    assert cycles and cycles[-1]["up"] == 2
    assert cycles[-1]["ok"] is True


def test_fleet_survives_dead_and_hung_endpoints(stub_fleet):
    """One live + one dead + one hung replica: partial view inside the
    timeout budget, fleet_replicas_up reflecting it."""
    import dss_ml_at_scale_tpu.telemetry as telemetry

    # A socket that accepts (kernel backlog) but never responds: the
    # hung-replica case, distinct from connection-refused (dead).
    hung = socket.socket()
    hung.bind(("127.0.0.1", 0))
    hung.listen(1)
    hung_port = hung.getsockname()[1]
    try:
        agg = federation.FleetAggregator(
            [
                stub_fleet[0]["endpoint"],
                "127.0.0.1:9",        # discard port: refused (dead)
                f"127.0.0.1:{hung_port}",
            ],
            timeout_s=0.5,
        )
        t0 = time.monotonic()
        view = agg.scrape()
        elapsed = time.monotonic() - t0
        # Budget: timeout_s + join grace + merge/judge slack. The hung
        # endpoint must never stretch the cycle to its 30s socket
        # default.
        assert elapsed < 3.0, elapsed
        assert view.up == 1
        by_ep = {r.endpoint: r for r in view.replicas}
        assert by_ep[stub_fleet[0]["endpoint"]].outcome == "ok"
        assert by_ep["127.0.0.1:9"].up is False
        assert by_ep[f"127.0.0.1:{hung_port}"].up is False
        # The partial view still carries the live replica's data.
        assert view.registry.window(
            "serving_request_window_seconds"
        ).quantile(0.5) is not None
        # Self-metering on the default registry.
        fam = telemetry.get_registry().gauge("fleet_replicas_up")
        assert fam._children[()].value == 1.0
        up_stale = telemetry.get_registry().gauge(
            "fleet_scrape_staleness_seconds", labels=("endpoint",)
        ).labels(endpoint=stub_fleet[0]["endpoint"])
        assert up_stale.value == pytest.approx(0.0, abs=5.0)
    finally:
        hung.close()


def test_fleet_cli_check_and_top(stub_fleet, tmp_path, capsys):
    from dss_ml_at_scale_tpu.config.cli import main

    journal = tmp_path / "cli_fleet.jsonl"
    endpoints = [r["endpoint"] for r in stub_fleet]
    rc = main(["slo", "check", "--fleet", *endpoints,
               "--fleet-journal", str(journal), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["ok"] is True and doc["up"] == 2
    assert len(doc["replicas"]) == 2
    assert federation.read_fleet_journal(journal)

    rc = main(["top", "--fleet", *endpoints, "--once"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "REPLICA" in out and "2 up" in out
    assert "serving_request_window_seconds" in out  # merged windows

    rc = main(["slo", "status", "--fleet", *endpoints])
    out = capsys.readouterr().out
    assert rc == 0
    assert "serving_latency_p99" in out

    # No replica answering is an unusable source: exit 2, like a dead
    # --url, not a silent green check.
    rc = main(["slo", "check", "--fleet", "127.0.0.1:9"])
    capsys.readouterr()
    assert rc == 2


def test_fleet_check_exits_1_when_one_replica_burns(stub_fleet, capsys):
    """A 1 ms deadline against a 30 ms scorer turns one replica into a
    pure-503 error source; the FLEET check must refuse (exit 1) even
    though the other replica is healthy."""
    from dss_ml_at_scale_tpu.bench.loadgen import (
        run_load,
        spawn_stub_server,
    )
    from dss_ml_at_scale_tpu.config.cli import main

    proc, port = spawn_stub_server(score_ms=30.0, batch_window_ms=1.0,
                                   deadline_ms=1.0)
    try:
        report = run_load("127.0.0.1", port, b"0", threads=4,
                          duration_s=2.0)
        assert report["statuses"].get("503", 0) >= 20  # min_samples
        rc = main([
            "slo", "check", "--fleet",
            stub_fleet[0]["endpoint"], f"127.0.0.1:{port}", "--json",
        ])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert "serving_error_rate" in doc["failing"]
    finally:
        proc.terminate()
        proc.wait(15)
