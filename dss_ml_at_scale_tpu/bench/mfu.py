"""Achieved-FLOPs/s gauges priced by the audit-pinned cost budgets.

``dsst audit`` already commits a FLOPs budget for every production
entrypoint (``AUDIT_BASELINE.json``, ``programs[name].flops`` — the
XLA-counted cost of the exact compiled program). Multiplying that pin
by a *measured* steps/sec gives an achieved-FLOPs/s figure — and,
divided by the device's public peak, an MFU-style utilization — with
**no new tracing**: the steps/sec comes from measurements the runtime
already makes (a bench scenario's timed repetitions, or the flight
recorder's ``train_step`` spans).

The gauges land on the process-default registry, so any process that
serves ``GET /metrics`` (``dsst serve``) exposes them after publishing.

Honesty contract: the pin prices ONE program. Publish only for
steps/sec measured on the same entrypoint the pin names — the bench
scenarios that opt in (``Scenario.entrypoint``) run the audited
program itself via its registry builder, so the budget and the
measurement describe identical XLA.
"""

from __future__ import annotations

import json
from pathlib import Path

# Public per-chip peaks (Google Cloud documentation, "TPU v5e": 197
# TFLOP/s bf16, 819 GB/s HBM), keyed by the exact ``device_kind`` string
# JAX reports on that chip.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}
PEAK_HBM_BYTES = {"TPU v5 lite": 819e9}


class UnknownDeviceKind(LookupError):
    pass


def peak_for(table: dict, device_kind: str | None) -> float | None:
    """The peak of ``device_kind`` from ``table``; None for the CPU (a
    CPU run has no utilization to report). Any other kind missing from
    the table raises — another chip's peak is never assumed, and
    utilization is never dropped in silence."""
    kind = device_kind or ""
    if kind in table:
        return table[kind]
    if kind == "cpu":
        return None
    raise UnknownDeviceKind(
        f"unknown device kind {kind!r}: no peak in bench/mfu.py "
        f"(known: {', '.join(sorted(table))})"
    )


def pinned_flops(entrypoint: str,
                 baseline_path: Path | None = None) -> float | None:
    """The audit-committed FLOPs budget of ``entrypoint``, or None when
    the entrypoint is unpinned (or the budget was recorded cost-less)."""
    from ..analysis.audit.core import DEFAULT_AUDIT_BASELINE

    path = DEFAULT_AUDIT_BASELINE if baseline_path is None else baseline_path
    try:
        data = json.loads(Path(path).read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError):
        return None
    prog = data.get("programs", {}).get(entrypoint)
    if not isinstance(prog, dict):
        return None
    flops = prog.get("flops")
    return float(flops) if flops else None


def publish_achieved(entrypoint: str, steps_per_sec: float, *,
                     device_kind: str | None = None,
                     baseline_path: Path | None = None) -> dict | None:
    """Set the achieved-FLOPs/s and utilization gauges for
    ``entrypoint``; returns the published block or None when the
    entrypoint has no pinned budget. On the CPU ``utilization`` is None;
    for any other device kind without a peak in the table the block
    names the gap (``"peak": "unknown device kind <k>"``)."""
    from .. import telemetry

    flops = pinned_flops(entrypoint, baseline_path)
    if flops is None or steps_per_sec <= 0:
        return None
    achieved = flops * steps_per_sec
    telemetry.gauge(
        "entrypoint_achieved_flops_per_sec",
        "measured steps/sec times the audit-pinned FLOPs budget",
        labels=("entrypoint",),
    ).labels(entrypoint=entrypoint).set(achieved)
    block = {
        "entrypoint": entrypoint,
        "steps_per_sec": round(steps_per_sec, 4),
        "flops_per_step": flops,
        "achieved_flops_per_sec": achieved,
        "utilization": None,
    }
    try:
        peak = peak_for(PEAK_BF16_FLOPS, device_kind)
    except UnknownDeviceKind:
        peak = None
        block["peak"] = f"unknown device kind {device_kind}"
    if peak:
        util = achieved / peak
        telemetry.gauge(
            "entrypoint_flops_utilization",
            "achieved FLOPs/s over the device's public peak (MFU-style)",
            labels=("entrypoint",),
        ).labels(entrypoint=entrypoint).set(util)
        block["utilization"] = util
    return block


def publish_from_trace(tail_path, entrypoint: str, *,
                       span_name: str = "train_step",
                       device_kind: str | None = None,
                       baseline_path: Path | None = None) -> dict | None:
    """Price an existing flight-recorder tail: ``span_name`` arrival
    rate → steps/sec → :func:`publish_achieved`. No new tracing — the
    recorder was already on.

    Steps/sec is spans over the WALL window (first open to last close),
    not ``1/mean(duration)``: inter-step gaps (data wait — exactly what
    a stalled run has) must depress achieved FLOPs/s, or the
    utilization gauge would read *inflated* on the runs it exists to
    diagnose. A single span has no window and falls back to its own
    duration.
    """
    from ..telemetry import flightrec

    complete, _opens = flightrec.reconstruct(
        flightrec.read_events(tail_path)
    )
    spans = sorted(
        (e for e in complete
         if e.get("name") == span_name and e.get("dur", 0.0) > 0),
        key=lambda e: e.get("ts", 0.0),
    )
    if not spans:
        return None
    window = (spans[-1].get("ts", 0.0) + spans[-1].get("dur", 0.0)
              - spans[0].get("ts", 0.0))
    if window <= 0:
        window = spans[0]["dur"]
    return publish_achieved(
        entrypoint, len(spans) / window, device_kind=device_kind,
        baseline_path=baseline_path,
    )
