"""Driver of the serving cells: the real engine behind the real HTTP
front end in this process, the load generator in a process of its own.

The window is the load generator's: it stamps every streamed line on its
own clock, which is the machine's monotonic clock and so the same as
this process's.  This process only marks the window in the program's
counters and spans, takes the trace, and, once the window has closed and
the server's state is freed, runs the plain reference over a sample of
the requests that the window finished.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import harness
import trace as tracemod
import weights


def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        time.sleep(delay)


def _gaps(sample, seed, cfg, reference, quant):
    """Per served token, how far its logit lies below the reference's
    best; with ``quant`` also the gap of the token a lower precision puts
    first at the same positions."""
    import numpy as np

    served, control = [], []
    for req in sample:
        prompt, tokens = req["prompt"], req["tokens"]
        seq = prompt + tokens[:-1]
        rows = slice(len(prompt) - 1, len(seq))
        ref = np.asarray(reference.logits(seq, seed, cfg))[rows]
        best = ref.max(axis=-1)
        served.extend((best - ref[np.arange(len(tokens)), tokens]).tolist())
        if quant is not None:
            low = np.asarray(reference.logits(seq, seed, cfg, quant))[rows]
            first = low.argmax(axis=-1)
            control.extend((best - ref[np.arange(len(tokens)), first]).tolist())
    return served, control


def run(cell, *, seed, seconds, trace, t_start, require_chip, faults,
        variants=()):
    import jax

    devices, device = harness.find_devices(cell.chips, require_chip)
    compiles = harness.CompileCounter()
    from dss_ml_at_scale_tpu.runtime import enable_compile_cache

    cache_dir = enable_compile_cache()
    cfg, tr = cell.config, cell.traffic
    server = tr["server"]
    adapter = importlib.import_module(f"adapters.{cfg['family']}")
    reference = importlib.import_module(f"references.{cfg['family']}")
    lowprec = importlib.import_module("references.lowprec")

    model = adapter.build_model(cfg, server)
    shapes = reference.param_shapes(
        {**cfg, "max_position_embeddings": server["max_len"]})
    if shapes != adapter.variable_shapes(model, server["prefill_buckets"][0]):
        raise RuntimeError("the reference and the program disagree on the "
                           "model's variables")
    ref_cfg = {**cfg, "max_position_embeddings": server["max_len"]}
    variables = weights.nest(weights.make(shapes, seed))
    jax.block_until_ready(variables)
    engine, handle = adapter.start_server(model, variables, server)
    if faults.get("alter_token_every"):
        from dss_ml_at_scale_tpu.serving.lm import engine as engine_mod

        every, calls = faults["alter_token_every"], [0]
        sample_fn = engine_mod.Generation.sample

        def altered(self, row):
            calls[0] += 1
            tok = sample_fn(self, row)
            return (tok + 1) % len(row) if calls[0] % every == 0 else tok

        engine_mod.Generation.sample = altered
    setup_done = time.perf_counter()

    child = subprocess.Popen(
        [sys.executable, os.path.join(harness.BENCH_DIR, "loadgen.py"),
         "--port", str(handle.port), "--traffic", cell.traffic_path,
         "--seed", str(seed), "--seconds", str(seconds),
         "--vocab", str(cfg["vocab_size"])],
        stdout=subprocess.PIPE, text=True,
        env={k: v for k, v in os.environ.items() if k != "BENCH_RUN"})
    marks: dict = {}
    trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_") if trace else None
    try:
        plan = json.loads(child.stdout.readline())
        t0, t1 = plan["t0"], plan["t1"]

        def mark():
            _sleep_until(t0)
            marks["wall0"] = time.time()
            marks["c0"] = harness.program_counters()
            _sleep_until(t1)
            marks["c1"] = harness.program_counters()

        marker = threading.Thread(target=mark, daemon=True)
        marker.start()
        traced = None
        if trace:
            _sleep_until(t0 + tr["trace_offset_seconds"])
            tracemod.start(trace_dir)
            a = time.perf_counter()
            _sleep_until(a + min(tr["trace_seconds"], max(0.5, t1 - a)))
            b = time.perf_counter()
            tracemod.stop()
            traced = (a, b)
        out = child.stdout.readline()
        child.wait(timeout=30)
        marker.join(timeout=5)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        if faults.get("alter_token_every"):
            engine_mod.Generation.sample = sample_fn
    if not out:
        raise RuntimeError("the load generator printed no result")
    res = json.loads(out)
    in_window = compiles.inside(t0, t1)
    peak = harness.memory_peak_bytes(devices)
    spans = harness.program_spans(marks["wall0"],
                                  marks["wall0"] + (t1 - t0))
    handle.close(tr.get("drain_seconds", 60))
    del engine, handle, variables, model
    gc.collect()

    # -- the reference over a sample of what the window finished
    t_ref = time.perf_counter()
    want_control = "control_fp8" in variants
    served, control = _gaps(res["sample"], seed, ref_cfg, reference,
                            lowprec.fp8 if want_control else None)
    numbers = {
        "logit_gap_max": max(served) if served else float("inf"),
        "failed_requests": float(res["failed"]),
    }
    ref_seconds = time.perf_counter() - t_ref
    correct, compared = harness.compare(numbers, tr["limits"])
    readings = {}
    if want_control:
        # The control is held to the cell's limits by the same comparison.
        stood = {**numbers,
                 "logit_gap_max": max(control) if control else float("inf"),
                 "logit_gap_mean": sum(control) / max(len(control), 1)}
        ok, table = harness.compare(stood, tr["limits"], quiet=True)
        readings["control_fp8"] = {"correct": ok, "compared": table,
                                   "numbers": stood}

    finite = [x for x in res["ttft_s"] if x != float("inf")]
    window = harness.Window(
        cell=cell, t0=t0, t1=t1, wall0=marks["wall0"], spans=spans,
        counters0=marks["c0"], counters1=marks["c1"],
        stats={"tokens": res["tokens_in_window"],
               "requests": res["attempted"], "chips": cell.chips,
               "ttft_s": res["ttft_s"],
               "clients": tr.get("clients")},
        device_kind=device["kind"], traced=traced)
    tables = None
    if trace:
        tables = tracemod.load_xplane(tracemod.find_xplane(trace_dir),
                                      spans=spans)
        window.tables = tables
        shutil.rmtree(trace_dir, ignore_errors=True)
    end_to_end = {
        "serve_tokens_per_s": res["tokens_in_window"] / (t1 - t0),
        "itl_p95_ms": 1e3 * harness.percentile(res["gap_s"], 95)
        if res["gap_s"] else float("inf"),
        "setup_s": harness.process_age(t_start)
        - (time.perf_counter() - t0),
    }
    device["memory_peak_bytes"] = peak
    harness.log(json.dumps({
        "cell": cell.name, "seed": seed, "window_s": t1 - t0,
        "requests_started": res["attempted"], "failed": res["failed"],
        "errors": res["errors"], "requests_total": res["requests_total"],
        "tokens_in_window": res["tokens_in_window"],
        "ttft_p50_ms": 1e3 * harness.percentile(finite, 50) if finite else None,
        "ttft_p95_ms": 1e3 * harness.percentile(res["ttft_s"], 95)
        if res["ttft_s"] else None,
        "itl_p50_ms": 1e3 * harness.percentile(res["gap_s"], 50)
        if res["gap_s"] else None,
        "generator_lateness_s": res["lateness_s"],
        "ready_s": setup_done - t_start,
        "compiles_in_window": in_window,
        "compile_cache": harness.cache_report(cache_dir),
        "compile_cache_events": compiles.events,
        "compile_seconds": round(sum(s for _, s in compiles.compiles), 2),
        "memory_peak_bytes": peak, "host_cores": os.cpu_count(),
        "checked_requests": len(res["sample"]), "checked_tokens": len(served),
        "logit_gap_mean": sum(served) / len(served) if served else None,
        "reference_seconds": round(ref_seconds, 2),
    }))
    if in_window:
        raise RuntimeError(f"{in_window} compilations inside the window")
    return harness.finish(
        cell, trace=trace, correct=correct, compared=compared,
        attempted=res["attempted"], failed=res["failed"],
        end_to_end=end_to_end, window=window, device=device, tables=tables,
        busy_window=(traced[1] - traced[0]) if traced else None,
        extra={"readings": readings} if readings else None)
