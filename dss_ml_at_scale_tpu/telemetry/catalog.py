"""The metric- and span-name catalogs: every series and every span name
the package may emit.

The registry is get-or-create by design (call sites never coordinate),
which means a typo'd name silently forks a series and a renamed metric
silently orphans its dashboard. This catalog is the single place metric
names are *declared*; the ``telemetry-registry`` lint rule
(``dsst lint``) holds call sites to it in both directions — every
literal name used with ``counter()``/``gauge()``/``histogram()`` in the
package must appear here with the matching kind, and every entry here
must still have a call site. Mirrors ``resilience.faults.KNOWN_SITES``
(the ``fault-sites`` rule) exactly.

:data:`KNOWN_SPANS` is the same gate for span names (the
``span-discipline`` rule): trace tooling groups and attributes by span
name (``dsst trace attribution`` buckets ``reader.next`` as data wait,
``train_step`` as compute), so a typo'd span name silently falls out of
every breakdown. Every literal name at a ``span()`` call site must be
declared here, and every declared name must still have a call site.

Adding a metric or span: add the call site AND the entry here (the lint
fails on either alone). Removing one: remove both.
"""

from __future__ import annotations

# name -> kind ("counter" | "gauge" | "histogram" | "window")
KNOWN_METRICS: dict[str, str] = {
    # -- analysis ----------------------------------------------------------
    "audit_entrypoints_total": "counter",
    "audit_findings_total": "counter",
    # -- bench / utilization ----------------------------------------------
    "bench_regressions_total": "counter",
    "bench_scenarios_total": "counter",
    "entrypoint_achieved_flops_per_sec": "gauge",
    "entrypoint_flops_utilization": "gauge",
    # -- checkpointing / resilience ---------------------------------------
    "auto_resume_total": "counter",
    "checkpoint_fallback_total": "counter",
    "faults_injected_total": "counter",
    "fsync_seconds_total": "counter",
    "health_rollbacks_total": "counter",
    "loss_spikes_total": "counter",
    "nonfinite_steps_total": "counter",
    "preemption_signals_total": "counter",
    "quarantined_batches_total": "counter",
    "retry_total": "counter",
    "runs_interrupted_total": "counter",
    "worker_readmitted_total": "counter",
    # -- tracing / flight recorder ----------------------------------------
    "flight_recorder_bytes_total": "counter",
    # -- device / compile --------------------------------------------------
    "device_hbm_bytes_in_use": "gauge",
    "device_hbm_bytes_limit": "gauge",
    "device_hbm_bytes_peak": "gauge",
    "device_live_buffers": "gauge",
    "device_memory_stats_supported": "gauge",
    "device_monitor_samples_total": "counter",
    "jit_compile_events_total": "counter",
    # -- input pipeline ----------------------------------------------------
    "corrupt_samples_total": "counter",
    "feeder_batches_total": "counter",
    "feeder_depth": "gauge",
    "feeder_occupancy": "gauge",
    "feeder_stage_seconds": "histogram",
    "feeder_stall_seconds_total": "counter",
    "ingest_bytes_total": "counter",
    "ingest_rows_total": "counter",
    "reader_batch_buffers_total": "counter",
    "reader_queue_depth": "gauge",
    "reader_rows_total": "counter",
    "reader_stage_seconds_total": "counter",
    "reader_stall_seconds_total": "counter",
    "reader_workers": "gauge",
    # -- training / HPO ----------------------------------------------------
    "hpo_trials_total": "counter",
    "skus_fitted_total": "counter",
    "pipeline_utilization": "gauge",
    "train_compile_events_total": "counter",
    "train_data_wait_seconds": "histogram",
    "train_step_seconds": "histogram",
    "train_throughput_rows_per_sec": "gauge",
    # -- live SLO plane ----------------------------------------------------
    "admission_est_queue_wait_ms": "gauge",
    "admission_service_rate_ewma": "gauge",
    "feeder_stall_window_seconds": "window",
    "fleet_replicas_up": "gauge",
    "fleet_scrape_staleness_seconds": "gauge",
    "fleet_scrape_total": "counter",
    "serving_request_window_seconds": "window",
    "slo_alert_transitions_total": "counter",
    "slo_alerts_firing": "gauge",
    "train_step_window_seconds": "window",
    # -- LM token serving --------------------------------------------------
    "lm_cache_bytes": "gauge",
    "lm_decode_cache_rows_total": "counter",
    "lm_decode_steps_total": "counter",
    "lm_inter_token_window_seconds": "window",
    "lm_moe_assignments_total": "counter",
    "lm_moe_expert_assignments_total": "counter",
    "lm_moe_experts_touched_total": "counter",
    "lm_prefill_tokens_total": "counter",
    "lm_queue_depth": "gauge",
    "lm_retired_total": "counter",
    "lm_slots_active": "gauge",
    "lm_tokens_total": "counter",
    "lm_ttft_window_seconds": "window",
    "lm_weights_bytes": "gauge",
    # -- serving -----------------------------------------------------------
    "predict_batch_seconds": "histogram",
    "predict_errors_total": "counter",
    "predict_images_total": "counter",
    "scoring_nonfinite_total": "counter",
    "serving_admission_rejected_total": "counter",
    "serving_batch_fill": "histogram",
    "serving_batches_total": "counter",
    "serving_deadline_expired_total": "counter",
    "serving_errors_total": "counter",
    "serving_queue_depth": "gauge",
    "serving_ready": "gauge",
    "serving_request_seconds": "histogram",
    "serving_time_in_queue_seconds": "histogram",
}

# Span name -> what the span covers. The ``span-discipline`` lint rule
# (``dsst lint``) reconciles ``span()`` call sites against this in both
# directions; ``dsst trace attribution`` buckets step spans by these
# names (:data:`SPAN_ATTRIBUTION` below — the one bucket mapping it
# shares with the bench harness's e2e cross-check).
KNOWN_SPANS: dict[str, str] = {
    # -- training ----------------------------------------------------------
    "fit": "one Trainer.fit call, open for the whole run",
    "train_epoch": "one epoch's committed-step loop",
    "train_step": "one train-step dispatch (+ verdict fetch when "
                  "health-supervised)",
    "eval": "one epoch's validation pass",
    "checkpoint": "orbax save dispatch for one step",
    "checkpoint.finalize": "manifest finalizer: async-save wait + "
                           "hash + journal commit",
    "health_rollback": "restore-from-checkpoint on a health rollback",
    # -- input pipeline ----------------------------------------------------
    "reader.next": "feeder thread pulling one host batch from the reader",
    "reader.read": "a loading thread reading one row group and turning "
                   "its columns into numpy",
    "reader.decode": "a loading thread running the transform (JPEG "
                     "decode, resize, crop, normalise) over one row group",
    "reader.assemble": "the consumer's thread copying buffered row "
                       "groups into one batch (inside reader.next)",
    "feeder.place": "feeder thread staging + sharding one batch onto "
                    "devices",
    "mesh.plan": "MeshBatchPlacer building a placement plan for a new "
                 "batch structure (cache miss)",
    # -- serving -----------------------------------------------------------
    "serve.request": "one HTTP /predict request, admission to response",
    "serve.decode": "decode pool turning one request's payloads into "
                    "arrays",
    "serve.score": "one request's share of a scored micro-batch",
    "serve.generate": "one HTTP /generate request, admission to the "
                      "final streamed chunk",
    "lm.prefill": "one bucket-padded prompt prefill + arena scatter "
                  "(admission into a free slot)",
    "lm.step": "one turn of the decode loop that dispatches a "
               "slot_decode step (args active, context_tokens: that "
               "step's) and collects the step dispatched a turn "
               "earlier, or its own while a request samples on the host",
    "lm.dispatch": "inside lm.step: host-to-device copies of the token "
                   "overrides and positions and the jitted call returning",
    "lm.wait": "until the step being collected (one dispatched a turn "
               "earlier, while steps run ahead) is done on the device; "
               "inside lm.step unless nothing was dispatched that turn",
    "lm.fetch": "after lm.wait: the copy of that step's [slots] ids, and "
                "of its logits while a request samples on the host, to "
                "host arrays",
    "lm.sample": "after lm.step: for the step collected, per-slot token "
                 "(the device's id, or sampled from the logits row), "
                 "streaming, windows, SLO notes and retirement",
    "lm.admit": "the admission scan over the waiting list and its "
                "settlements, when there was anything to scan",
    # -- HPO ---------------------------------------------------------------
    "trial": "one HPO trial evaluation",
    "trial.submit": "driver-side proposal/submission of one trial",
    # -- group fit ---------------------------------------------------------
    "panel.build": "pad_groups stacking a long frame into the (G, L) "
                   "panel (vectorized scatter, host-side)",
    "grid.chunk": "one grid-fused group-fit launch: place one chunk, "
                  "fit the full order grid, device argmin",
    # -- ingest ------------------------------------------------------------
    "ingest": "one ingest run over a raw image tree",
    # -- SLO ---------------------------------------------------------------
    "slo.alert": "one burn-rate alert state transition (recorded under "
                 "the worst offender's trace id, so the Perfetto export "
                 "draws a flow arrow to the offending request/step)",
}

# SLO objective name -> what the objective covers. The ``slo-registry``
# lint rule (``dsst lint``) reconciles the ``Objective(name=...)``
# declarations in ``telemetry/slo.py`` (and every literal objective
# name at ``set_target(...)`` call sites) against this in both
# directions — a typo'd objective would otherwise silently declare a
# NEW budget nobody alerts on, exactly the series-forking failure mode
# KNOWN_METRICS guards against.
KNOWN_SLOS: dict[str, str] = {
    "serving_latency_p99": "admitted requests settle inside the latency "
                           "budget (the configured deadline)",
    "serving_error_rate": "requests answered without 429/503/5xx",
    "feeder_stall_fraction": "step-loop wall time blocked on the feeder "
                             "queue stays under 1%",
    "train_step_p95": "windowed p95 train-step seconds vs the armed "
                      "step budget",
    "ttft_p99": "windowed p99 time-to-first-token (admit -> first "
                "streamed chunk) vs the armed TTFT budget",
    "inter_token_p99": "windowed p99 gap between consecutive streamed "
                       "tokens vs the armed per-token budget",
}

# Span name -> attribution bucket: where a step's wall time went. The
# ONE definition shared by ``dsst trace attribution`` and the bench
# harness's e2e cross-check (``bench/scenarios.py``) — both used to be
# free to drift from KNOWN_SPANS independently; sourcing the mapping
# here means a renamed span breaks the span-discipline lint, not the
# attribution silently. Spans not listed bucket as "host".
SPAN_ATTRIBUTION: dict[str, str] = {
    "reader.next": "data_wait",
    "feeder.place": "transfer",
    "mesh.plan": "transfer",
    "train_step": "compute",
    "panel.build": "host",
    "grid.chunk": "compute",
    "lm.prefill": "compute",
    "lm.step": "compute",
}

# Spans that lie inside another span of the same thread and trace:
# reader.assemble runs on the feeder thread inside reader.next, the
# three parts of a decode step inside lm.step (the wait and the fetch of
# a run's last step, which dispatches nothing, lie outside one and are
# left out all the same). The two consumers of
# SPAN_ATTRIBUTION sum durations a trace, so they leave these out: the
# enclosing span already holds their wall time. (reader.read and
# reader.decode run on the reader's own threads under no step's trace,
# and so never reach a step's buckets.)
SPAN_NESTED: frozenset[str] = frozenset({
    "reader.assemble", "lm.dispatch", "lm.wait", "lm.fetch",
})

# Scenario name -> the exact metric keys its schema may emit
# (``dsst bench``). The ``bench-registry`` lint rule reconciles the
# ``Scenario(...)`` declarations in ``bench/scenarios.py`` against this
# in both directions, exactly as ``telemetry-registry`` holds metric
# call sites to KNOWN_METRICS: a typo'd metric key would otherwise
# silently fork a baseline series and dodge its regression gate.
KNOWN_BENCH_METRICS: dict[str, tuple[str, ...]] = {
    "compute": (
        "compute_steps_per_sec",
        "compute_images_per_sec",
    ),
    "decode": (
        "decode_images_per_sec",
    ),
    "feeder_e2e": (
        "e2e_images_per_sec",
        "e2e_steps_per_sec",
        "feeder_stall_fraction",
        "e2e_unexplained_fraction",
    ),
    "group_fit": (
        "group_fit_skus_per_sec",
        "group_fit_fits_per_sec",
        "group_fit_launches_per_sec",
    ),
    "group_fit_10k": (
        "group_fit_10k_skus_per_sec",
        "group_fit_10k_chunks",
    ),
    "reader": (
        "reader_images_per_sec",
    ),
    "recorder_overhead": (
        "recorder_emit_ring_us",
        "recorder_emit_tail_us",
        "recorder_tail_bytes_per_event",
    ),
    "sanitizer_overhead": (
        "sanitizer_plain_acquire_us",
        "sanitizer_armed_acquire_us",
        "sanitizer_overhead_ratio",
    ),
    "slo_overhead": (
        "slo_sketch_observe_us",
        "slo_hist_observe_us",
        "slo_overhead_ratio",
        "slo_emit_step_fraction",
    ),
}
