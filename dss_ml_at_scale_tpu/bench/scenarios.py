"""The registered scenarios of ``dsst bench``, a CPU regression gate at
toy sizes.

Each scenario isolates one seam of the system — decode, reader, device
feeding, the compiled step, the group fit, the observability layers'
own overhead. Where a scenario executes a compiled program it builds it
through the **audit entrypoint registry** (the same builders ``dsst
audit`` certifies), so the measured program and the pinned cost budget
describe identical XLA.

Declarations here are reconciled against
``telemetry.catalog.KNOWN_BENCH_METRICS`` in both directions by the
``bench-registry`` lint rule: scenario/metric names must be literal.

The ``feeder_e2e`` scenario self-verifies: its measured wall time is
cross-checked against the flight-recorder attribution buckets (the
SAME ``telemetry.catalog.SPAN_ATTRIBUTION`` mapping ``dsst trace
attribution`` uses), and an unexplained gap fails the scenario — a
harness whose own spans stop covering its loop must say so, not emit
numbers nobody can attribute.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

from .core import Metric, Scenario, register_scenario

# Geometry shared by the decode/reader stages: tiny sources so tier-1
# children finish in seconds; throughput at this size is a *relative*
# gate (same work every run), not an absolute claim.
_SRC_SIZE = 32
_CROP = 32
_N_IMAGES = 96
_BATCH = 16


def _tiny_jpegs(n: int, size: int, seed: int = 0) -> list[bytes]:
    """Blocky low-frequency JPEGs: realistic decode entropy (pure noise
    inflates decode cost; flat color deflates it)."""
    import io

    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        blocks = rng.uniform(0, 255, (8, 8, 3))
        img = np.kron(blocks, np.ones((size // 8, size // 8, 1)))
        buf = io.BytesIO()
        Image.fromarray(img.astype(np.uint8)).save(
            buf, format="JPEG", quality=85
        )
        out.append(buf.getvalue())
    return out


def _transform_spec():
    from ..data.transform import imagenet_transform_spec

    return imagenet_transform_spec(
        resize=_CROP + _CROP // 8, crop=_CROP, output_dtype="uint8"
    )


# -- decode -------------------------------------------------------------------


def _decode_setup():
    jpegs = _tiny_jpegs(_N_IMAGES, _SRC_SIZE)
    spec = _transform_spec()
    probe = {
        "content": jpegs,
        "label_index": [0] * len(jpegs),
    }
    spec(dict(probe))  # warm the decode path (thread pool, caches)
    return {"spec": spec, "probe": probe}


def _decode_measure(ctx) -> dict:
    t0 = time.perf_counter()
    ctx["spec"](dict(ctx["probe"]))
    dt = time.perf_counter() - t0
    return {"decode_images_per_sec": len(ctx["probe"]["content"]) / dt}


register_scenario(Scenario(
    name="decode",
    description="JPEG decode + transform throughput, raw bytes in, "
    "host batch out (no reader, no device)",
    tier="tier1",
    metrics=(
        Metric("decode_images_per_sec", "images/sec", "higher",
               floor=0.6),
    ),
    setup=_decode_setup,
    measure=_decode_measure,
    repetitions=5,
    timeout_s=120.0,
))


# -- reader -------------------------------------------------------------------


def _reader_setup():
    import pyarrow as pa

    from ..data import write_delta

    tmpdir = tempfile.mkdtemp(prefix="dsst_bench_reader_")
    jpegs = _tiny_jpegs(_N_IMAGES, _SRC_SIZE)
    table = pa.table({
        "content": pa.array(jpegs, type=pa.binary()),
        "label_index": pa.array([i % 7 for i in range(len(jpegs))],
                                type=pa.int64()),
    })
    path = os.path.join(tmpdir, "bench_imagenet")
    write_delta(table, path, max_rows_per_file=max(16, len(jpegs) // 4))
    return {"tmpdir": tmpdir, "path": path, "spec": _transform_spec()}


def _reader_measure(ctx) -> dict:
    from ..data import batch_loader

    n_batches = 4
    with batch_loader(
        ctx["path"],
        batch_size=_BATCH,
        num_epochs=None,
        workers_count=2,
        results_queue_size=8,
        transform_spec=ctx["spec"],
    ) as reader:
        it = iter(reader)
        next(it)  # warm: open files, fill the pool
        t0 = time.perf_counter()
        for _ in range(n_batches):
            next(it)
        dt = time.perf_counter() - t0
    return {"reader_images_per_sec": _BATCH * n_batches / dt}


register_scenario(Scenario(
    name="reader",
    description="Delta table -> sharded reader -> decode pool -> host "
    "batches (no device)",
    tier="tier1",
    metrics=(
        Metric("reader_images_per_sec", "images/sec", "higher",
               floor=0.6),
    ),
    setup=_reader_setup,
    teardown=lambda ctx: shutil.rmtree(ctx["tmpdir"], ignore_errors=True),
    repetitions=3,
    measure=_reader_measure,
    timeout_s=240.0,
))


# -- compute (the audited classifier train step) ------------------------------


def _audited_train_step(mesh=None):
    """(compiled, state, batch): the EXACT program ``dsst audit`` pins
    for ``train_step.classifier``, built through the audit registry's
    builder on the same 8-device abstract mesh and AOT-compiled — the
    ONE builder both the compute and feeder_e2e scenarios share, so
    they can never measure different programs while citing one pin."""
    from ..analysis.audit.core import default_audit_mesh
    from ..analysis.audit.entrypoints import train_step_classifier

    spec = train_step_classifier(
        default_audit_mesh() if mesh is None else mesh
    )
    state, batch = spec.args
    compiled = spec.jitted.lower(*spec.args).compile()
    return compiled, state, batch


def _compute_setup():
    compiled, state, batch = _audited_train_step()
    return {"compiled": compiled, "state": state, "batch": batch}


def _compute_measure(ctx) -> dict:
    steps = 10
    state, batch = ctx["state"], ctx["batch"]
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = ctx["compiled"](state, batch)
    float(metrics["train_loss"])
    dt = time.perf_counter() - t0
    ctx["state"] = state
    sps = steps / dt
    return {
        "compute_steps_per_sec": sps,
        "compute_images_per_sec": sps * batch["image"].shape[0],
    }


register_scenario(Scenario(
    name="compute",
    description="audited train_step.classifier program (8-device "
    "abstract mesh) steps/sec — prices the audit-pinned FLOPs budget "
    "into the achieved-FLOPs/s gauges",
    tier="tier1",
    metrics=(
        Metric("compute_steps_per_sec", "steps/sec", "higher",
               floor=0.6),
        Metric("compute_images_per_sec", "images/sec", "higher",
               gate=False),
    ),
    setup=_compute_setup,
    measure=_compute_measure,
    repetitions=3,
    timeout_s=420.0,
    needs_mesh=True,
    entrypoint="train_step.classifier",
    steps_metric="compute_steps_per_sec",
))


# -- feeder e2e (traced, self-verifying) --------------------------------------


def _feeder_setup():
    from ..analysis.audit.core import default_audit_mesh

    mesh = default_audit_mesh()
    compiled, state, batch = _audited_train_step(mesh)
    # One throwaway call so the first measured repetition starts from a
    # warm executable (the warmup repetition then covers feeder spin-up).
    state, metrics = compiled(state, batch)
    float(metrics["train_loss"])
    return {
        "mesh": mesh,
        "compiled": compiled,
        "state": state,
        "tmpdir": tempfile.mkdtemp(prefix="dsst_bench_feeder_"),
        "rep": 0,
    }


# Rows per synthetic host batch — MUST match the audited
# train_step.classifier batch shape (the compiled program is
# shape-specialized); also the numerator of e2e_images_per_sec.
_E2E_ROWS = 16


def _host_batches(n: int):
    import numpy as np

    for _ in range(n):
        yield {
            "image": np.zeros((_E2E_ROWS, 16, 16, 3), np.float32),
            "label": np.zeros((_E2E_ROWS,), np.int32),
        }


def _attribution_buckets(tail_path, since: float) -> dict[str, float]:
    """Seconds per attribution bucket over the tail's step-kind spans
    opened after ``since`` — the same SPAN_ATTRIBUTION mapping ``dsst
    trace attribution`` reads, so this cross-check and the CLI tool
    cannot drift apart."""
    from ..telemetry import flightrec
    from ..telemetry.catalog import SPAN_ATTRIBUTION, SPAN_NESTED

    complete, _opens = flightrec.reconstruct(
        flightrec.read_events(tail_path)
    )
    buckets = {"data_wait": 0.0, "transfer": 0.0, "compute": 0.0,
               "host": 0.0}
    for e in complete:
        if (e.get("kind") != "step" or e.get("ts", 0.0) < since
                or e.get("name") in SPAN_NESTED):
            continue
        buckets[SPAN_ATTRIBUTION.get(e.get("name"), "host")] += e.get(
            "dur", 0.0
        )
    return buckets


def _feeder_measure(ctx) -> dict:
    from .. import telemetry
    from ..data.prefetch import MeshFeeder
    from ..telemetry import flightrec

    steps = 8
    ctx["rep"] += 1
    state = ctx["state"]
    # Record onto the recorder's existing tail when one is live (a
    # tracked run, or `dsst bench profile` merging this very trace);
    # otherwise scope a private tail for the cross-check. The `since`
    # mark keeps the bucket read to THIS repetition either way.
    rec = flightrec.get_recorder()
    own_tail = None
    tail = rec.path
    if tail is None:
        own_tail = os.path.join(ctx["tmpdir"], f"tail{ctx['rep']}.jsonl")
        tail = flightrec.enable(own_tail)
    since = time.time()
    try:
        feeder = MeshFeeder(
            _host_batches(steps), ctx["mesh"], depth=3, name="bench-e2e"
        )
        try:
            stall = 0.0
            t0 = time.perf_counter()
            for _ in range(steps):
                s0 = time.perf_counter()
                batch, _prov = next(feeder)
                stall += time.perf_counter() - s0
                with feeder.last_handoff.activate(), \
                        telemetry.span("train_step"):
                    state, metrics = ctx["compiled"](state, batch)
            float(metrics["train_loss"])
            wall = time.perf_counter() - t0
        finally:
            feeder.close()
    finally:
        if own_tail is not None:
            flightrec.disable(own_tail)
    ctx["state"] = state

    buckets = _attribution_buckets(tail, since)
    traced = sum(buckets.values())
    unexplained = max(0.0, wall - traced) / wall if wall > 0 else 0.0
    if unexplained > 0.5:
        # The harness's self-verification: if the spans the attribution
        # tool buckets stop covering this loop (a renamed span, a broken
        # handoff), the number is unattributable — fail loudly instead
        # of shipping it.
        raise RuntimeError(
            f"e2e wall time unexplained by trace attribution: "
            f"{unexplained:.0%} of {wall*1e3:.1f}ms has no span "
            f"(buckets: { {k: round(v*1e3, 1) for k, v in buckets.items()} } "
            "ms) — feeder/step spans or the step handoff broke"
        )
    return {
        "e2e_images_per_sec": _E2E_ROWS * steps / wall,
        "e2e_steps_per_sec": steps / wall,
        "feeder_stall_fraction": stall / wall if wall > 0 else 0.0,
        "e2e_unexplained_fraction": unexplained,
    }


register_scenario(Scenario(
    name="feeder_e2e",
    description="traced MeshFeeder -> audited train step loop; wall "
    "time cross-checked against flight-recorder attribution buckets "
    "(fails on unexplained gap)",
    tier="slow",
    metrics=(
        Metric("e2e_images_per_sec", "images/sec", "higher",
               floor=0.6),
        Metric("e2e_steps_per_sec", "steps/sec", "higher", gate=False),
        Metric("feeder_stall_fraction", "fraction", "lower", gate=False),
        Metric("e2e_unexplained_fraction", "fraction", "lower",
               gate=False),
    ),
    setup=_feeder_setup,
    teardown=lambda ctx: shutil.rmtree(ctx["tmpdir"], ignore_errors=True),
    measure=_feeder_measure,
    repetitions=3,
    timeout_s=420.0,
    needs_mesh=True,
    entrypoint="train_step.classifier",
))


# -- group fit (grid-fused SARIMAX panel) -------------------------------------


def _group_panel(n_sku: int, weeks: int, seed: int = 0):
    """Synthetic demand panel (level + damped random walk + noise,
    weekly dates), built
    vectorized so 10k-SKU setup is numpy-bound, not loop-bound."""
    import numpy as np
    import pandas as pd

    from ..workloads.forecasting import add_exo_variables

    rng = np.random.default_rng(seed)
    level = rng.uniform(20, 80, (n_sku, 1))
    walk = np.cumsum(rng.normal(0, 1.0, (n_sku, weeks)), axis=1) * 0.5
    noise = rng.normal(0, 3.0, (n_sku, weeks))
    demand = np.maximum(level + walk + noise, 0.0)
    dates = pd.date_range("2020-01-06", periods=weeks, freq="W-MON")
    skus = np.array([f"P{g % 5}_{g:05d}" for g in range(n_sku)])
    frame = pd.DataFrame({
        "Product": np.repeat([f"P{g % 5}" for g in range(n_sku)], weeks),
        "SKU": np.repeat(skus, weeks),
        "Date": np.tile(dates, n_sku),
        "Demand": demand.ravel(),
    })
    return add_exo_variables(frame)


def _group_mesh():
    """The operator mesh for the group-fit launches: every REAL device
    the box has — the shape ``dsst forecast`` runs (unmeasured on the
    chip). On an 8-chip box this is exactly the audited
    ``sarimax.batched_fit`` topology; on a CPU host the harness's 8-way
    multiplexed view exists for structural audits, not silicon —
    partitioning the vectorized fit plane across fake devices only
    fragments it, so the launch runs single-device there. The per-SKU
    math (and so the audit FLOPs pin pricing the launches) is identical
    either way.
    """
    import jax

    from ..runtime.mesh import make_mesh

    devices = list(jax.devices())
    if devices[0].platform == "cpu":
        devices = devices[:1]
    return make_mesh({"data": len(devices)}, devices=devices)


def _group_fit_setup():
    from ..workloads.forecasting import (
        GROUP_FIT_BENCH_GROUPS,
        GROUP_FIT_BENCH_WEEKS,
    )

    return {
        "mesh": _group_mesh(),
        "panel": _group_panel(GROUP_FIT_BENCH_GROUPS,
                              GROUP_FIT_BENCH_WEEKS),
    }


def _group_fit_measure(ctx) -> dict:
    import numpy as np

    from ..ops.sarimax import grid_orders
    from ..workloads.forecasting import (
        GROUP_FIT_BENCH_CFG,
        GROUP_FIT_BENCH_GROUPS,
        GROUP_FIT_BENCH_HORIZON,
        tune_and_forecast_panel,
    )

    g = GROUP_FIT_BENCH_GROUPS
    t0 = time.perf_counter()
    out = tune_and_forecast_panel(
        ctx["panel"],
        forecast_horizon=GROUP_FIT_BENCH_HORIZON,
        mesh=ctx["mesh"],
        cfg=GROUP_FIT_BENCH_CFG,
        search="grid",
        chunk_size=g,
    )
    wall = time.perf_counter() - t0
    if not np.isfinite(out["Demand_Fitted"]).all():
        raise RuntimeError("group_fit produced non-finite forecasts")
    # The REAL launch count, reported by the grid driver itself: one
    # chunk at this geometry. Anything else means the fused launch
    # family broke apart — fail, don't mis-price the MFU gauge.
    chunks = out.attrs["grid_chunks"]
    if chunks != 1:
        raise RuntimeError(
            f"group_fit expected ONE fused launch, driver reports "
            f"{chunks}"
        )
    k = len(grid_orders(GROUP_FIT_BENCH_CFG))
    return {
        "group_fit_skus_per_sec": g / wall,
        "group_fit_fits_per_sec": g * k / wall,
        "group_fit_launches_per_sec": chunks / wall,
    }


register_scenario(Scenario(
    name="group_fit",
    description="grid-fused SARIMAX group-fit panel (32 SKUs x 40 "
    "weeks x the full 8-order grid of the reduced bench bounds) "
    "through tune_and_forecast_panel on the operator mesh — ONE "
    "launch fits and tunes every SKU via the sarimax.batched_fit "
    "program family, so the audit FLOPs pin prices skus/sec",
    tier="tier1",
    metrics=(
        Metric("group_fit_skus_per_sec", "skus/sec", "higher",
               floor=0.6),
        Metric("group_fit_fits_per_sec", "fits/sec", "higher",
               gate=False),
        Metric("group_fit_launches_per_sec", "launches/sec", "higher",
               gate=False),
    ),
    setup=_group_fit_setup,
    measure=_group_fit_measure,
    repetitions=3,
    timeout_s=420.0,
    entrypoint="sarimax.batched_fit",
    steps_metric="group_fit_launches_per_sec",
))


# 10k-SKU scale smoke: the ROADMAP item 3 target shape ("10k+ SKUs per
# launch family"). A liveness-scale fit config (shorter NM chains than
# the tier-1 gate) keeps the slow-tier wall in minutes on a CPU host;
# the scenario's claim is CHUNKED completion — bounded launches, no
# host-loop fallback — with throughput recorded for trend, not gated.
_10K_SKUS = 10_000
_10K_CHUNK = 1024


def _group_fit_10k_setup():
    import dataclasses

    from ..workloads.forecasting import (
        GROUP_FIT_BENCH_CFG,
        GROUP_FIT_BENCH_WEEKS,
    )

    return {
        "mesh": _group_mesh(),
        "panel": _group_panel(_10K_SKUS, GROUP_FIT_BENCH_WEEKS),
        "cfg": dataclasses.replace(GROUP_FIT_BENCH_CFG, max_iter=16),
    }


def _group_fit_10k_measure(ctx) -> dict:
    import numpy as np

    from ..workloads.forecasting import (
        GROUP_FIT_BENCH_HORIZON,
        tune_and_forecast_panel,
    )

    t0 = time.perf_counter()
    out = tune_and_forecast_panel(
        ctx["panel"],
        forecast_horizon=GROUP_FIT_BENCH_HORIZON,
        mesh=ctx["mesh"],
        cfg=ctx["cfg"],
        search="grid",
        chunk_size=_10K_CHUNK,
    )
    wall = time.perf_counter() - t0
    if not np.isfinite(out["Demand_Fitted"]).all():
        raise RuntimeError("group_fit_10k produced non-finite forecasts")
    groups = out.groupby(["Product", "SKU"]).ngroups
    if groups != _10K_SKUS:
        raise RuntimeError(
            f"group_fit_10k fitted {groups} groups, wanted {_10K_SKUS}"
        )
    # Measured, not assumed: the driver's own launch count — a host
    # loop or a broken chunk bound would show up right here.
    return {
        "group_fit_10k_skus_per_sec": _10K_SKUS / wall,
        "group_fit_10k_chunks": out.attrs["grid_chunks"],
    }


register_scenario(Scenario(
    name="group_fit_10k",
    description="10k-SKU grid-fused panel through the bounded chunked "
    "launch family (1024 groups/launch, liveness fit config) — proves "
    "ROADMAP item 3 scale completes with no host-loop fallback",
    tier="slow",
    metrics=(
        Metric("group_fit_10k_skus_per_sec", "skus/sec", "higher",
               gate=False),
        Metric("group_fit_10k_chunks", "launches", "lower", gate=False),
    ),
    setup=_group_fit_10k_setup,
    measure=_group_fit_10k_measure,
    repetitions=1,
    warmup=0,
    timeout_s=1800.0,
))


# -- recorder overhead --------------------------------------------------------

_EMIT_EVENTS = 1500


def _recorder_setup():
    return {"tmpdir": tempfile.mkdtemp(prefix="dsst_bench_rec_"), "rep": 0}


def _recorder_measure(ctx) -> dict:
    from ..telemetry import flightrec

    rec = flightrec.get_recorder()
    ctx["rep"] += 1
    # The scenario must OWN the recorder target for both halves of the
    # comparison: a live recorder (a tracked run, `dsst bench profile`)
    # would otherwise absorb the ring loop's synthetic events into its
    # tail — measuring tail cost where ring cost was claimed — and the
    # scoped disable below would silently switch that recorder off.
    # Park the previous target and restore it on the way out.
    prev = rec.path
    if prev is not None:
        flightrec.disable(prev)

    def _event(i: int) -> dict:
        return {
            "ph": "X", "name": "train_step", "ts": time.time(),
            "dur": 0.001, "pid": os.getpid(), "tid": 1,
            "thread": "bench", "span": f"{i:08x}",
        }

    tail = os.path.join(ctx["tmpdir"], f"tail{ctx['rep']}.jsonl")
    try:
        t0 = time.perf_counter()
        for i in range(_EMIT_EVENTS):
            rec.emit(_event(i))
        ring_dt = time.perf_counter() - t0

        flightrec.enable(tail)
        try:
            t0 = time.perf_counter()
            for i in range(_EMIT_EVENTS):
                rec.emit(_event(i))
            tail_dt = time.perf_counter() - t0
        finally:
            flightrec.disable(tail)
    finally:
        if prev is not None:
            flightrec.enable(prev)
    tail_bytes = os.path.getsize(tail)
    return {
        "recorder_emit_ring_us": ring_dt / _EMIT_EVENTS * 1e6,
        "recorder_emit_tail_us": tail_dt / _EMIT_EVENTS * 1e6,
        "recorder_tail_bytes_per_event": tail_bytes / _EMIT_EVENTS,
    }


register_scenario(Scenario(
    name="recorder_overhead",
    description="flight-recorder emit cost: in-memory ring vs "
    "write-through JSONL tail, plus bytes per event",
    tier="tier1",
    metrics=(
        Metric("recorder_emit_ring_us", "us/event", "lower", gate=False),
        Metric("recorder_emit_tail_us", "us/event", "lower", gate=False),
        # Bytes/event is deterministic for a fixed event shape — the one
        # recorder metric a shared CI box can gate tightly: it catches
        # event-payload bloat before every tail on every run grows.
        Metric("recorder_tail_bytes_per_event", "bytes", "lower",
               floor=0.25),
    ),
    setup=_recorder_setup,
    teardown=lambda ctx: shutil.rmtree(ctx["tmpdir"], ignore_errors=True),
    measure=_recorder_measure,
    repetitions=5,
    timeout_s=120.0,
))


# -- sanitizer overhead -------------------------------------------------------

_ACQUIRES = 20_000


def _lock_loop(lock, n: int) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        with lock:
            pass
    return time.perf_counter() - t0


def _sanitizer_measure(_ctx) -> dict:
    import threading

    from ..analysis.sanitize import sanitize_scope

    plain_dt = _lock_loop(threading.Lock(), _ACQUIRES)
    with sanitize_scope():
        # Constructed INSIDE the armed scope: instrumentation covers
        # locks created while armed (the dsst sanitize model).
        armed_dt = _lock_loop(threading.Lock(), _ACQUIRES)
    return {
        "sanitizer_plain_acquire_us": plain_dt / _ACQUIRES * 1e6,
        "sanitizer_armed_acquire_us": armed_dt / _ACQUIRES * 1e6,
        "sanitizer_overhead_ratio": (
            armed_dt / plain_dt if plain_dt > 0 else 0.0
        ),
    }


register_scenario(Scenario(
    name="sanitizer_overhead",
    description="dsst sanitize interposition cost per uncontended lock "
    "acquire, armed vs plain",
    tier="tier1",
    metrics=(
        Metric("sanitizer_plain_acquire_us", "us/acquire", "lower",
               gate=False),
        Metric("sanitizer_armed_acquire_us", "us/acquire", "lower",
               gate=False),
        # The ratio cancels host speed; floor 1.5 tolerates scheduler
        # noise while catching an interposition cost blow-up.
        Metric("sanitizer_overhead_ratio", "x", "lower", floor=1.5),
    ),
    measure=_sanitizer_measure,
    repetitions=5,
    timeout_s=120.0,
))


# -- slo overhead -------------------------------------------------------------

_SLO_OBS = 20_000


def _slo_values(n: int) -> list[float]:
    """Deterministic observation values spanning the sketch's decades
    (a single constant would hit one bucket's cache line forever and
    understate the bisect cost)."""
    return [10.0 ** (-5 + (i % 83) / 11.0) for i in range(n)]


def _slo_overhead_measure(_ctx) -> dict:
    from ..telemetry.registry import MetricsRegistry
    from ..telemetry.windows import SlidingQuantile

    reg = MetricsRegistry()
    # dsst: ignore[telemetry-registry] private throwaway registry: a bench probe series, never rendered on /metrics
    hist = reg.histogram("slo_overhead_probe_hist")
    sketch = SlidingQuantile()
    vals = _slo_values(_SLO_OBS)
    # Warm both paths (allocate the first digest, touch the buckets).
    for v in vals[:64]:
        hist.observe(v)
        sketch.observe(v)
    t0 = time.perf_counter()
    for v in vals:
        hist.observe(v)
    hist_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    for v in vals:
        sketch.observe(v)
    sketch_dt = time.perf_counter() - t0
    sketch_us = sketch_dt / _SLO_OBS * 1e6
    # The acceptance bound, self-verified like feeder_e2e's attribution
    # cross-check: one windowed emit must cost under 1% of a 1 ms step
    # budget (i.e. <10 µs) — a sketch that got expensive must fail the
    # scenario loudly, not ship a quietly slower hot path.
    frac = sketch_us / 1000.0
    if frac >= 0.01:
        raise RuntimeError(
            f"windowed-sketch emit costs {sketch_us:.2f}us — "
            f"{frac:.1%} of a 1ms step budget (>=1%); the sliding "
            "window stopped being histogram-cheap"
        )
    return {
        "slo_sketch_observe_us": sketch_us,
        "slo_hist_observe_us": hist_dt / _SLO_OBS * 1e6,
        "slo_overhead_ratio": (
            sketch_dt / hist_dt if hist_dt > 0 else 0.0
        ),
        "slo_emit_step_fraction": frac,
    }


register_scenario(Scenario(
    name="slo_overhead",
    description="windowed-sketch emit cost vs plain histogram observe "
    "(the live SLO plane's hot-path tax); self-verifies the sketch "
    "emit stays under 1% of a 1ms step budget",
    tier="tier1",
    metrics=(
        Metric("slo_sketch_observe_us", "us/observe", "lower",
               gate=False),
        Metric("slo_hist_observe_us", "us/observe", "lower", gate=False),
        # The ratio cancels host speed (the sanitizer_overhead idiom);
        # floor 1.5 tolerates scheduler noise while catching a sketch
        # cost blow-up vs the histogram it rides next to.
        Metric("slo_overhead_ratio", "x", "lower", floor=1.5),
        Metric("slo_emit_step_fraction", "fraction", "lower",
               gate=False),
    ),
    measure=_slo_overhead_measure,
    repetitions=5,
    timeout_s=120.0,
))
