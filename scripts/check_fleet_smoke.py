#!/usr/bin/env python
"""Two-replica fleet observability smoke (CI preflight).

Spawns TWO stub-scorer serving subprocesses
(``bench.loadgen.spawn_stub_server``), drives a little real traffic
with propagated trace headers at each, then judges the FLEET through
the real CLI:

    dsst slo check --fleet 127.0.0.1:P1 127.0.0.1:P2

Exit 0 means the whole plane held together end to end: both replicas
served ``/telemetry``, the aggregator merged their registries and SLO
windows inside its timeout budget, and no fleet-level objective is
burning. Any crash, straggler-blocked scrape, or merged burn fails the
preflight — exactly the multi-replica claim the TPU artifact pipeline
wants gated before it publishes serving numbers.

A third replica exercises the LM tier: one stub ``serve-lm`` process
(``bench.loadgen.spawn_stub_lm_server``), streamed generations with a
propagated trace header per request, then two judgments — ``dsst slo
check --strict`` against the replica alone (TTFT and inter-token
objectives armed and not even pending), and the LM replica MERGED into
the ``--fleet`` view with the two image replicas, so the LM windowed
sketches federate through the same wire forms before any LM serving
claim ships.
"""

from __future__ import annotations

import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> int:
    from dss_ml_at_scale_tpu.bench.loadgen import (
        run_lm_load,
        run_load,
        spawn_stub_lm_server,
        spawn_stub_server,
    )
    from dss_ml_at_scale_tpu.config.cli import main as dsst_main
    from dss_ml_at_scale_tpu.telemetry import federation

    procs = []
    try:
        endpoints = []
        for _ in range(2):
            proc, port = spawn_stub_server(score_ms=1.0,
                                           batch_window_ms=1.0)
            procs.append(proc)
            endpoints.append(f"127.0.0.1:{port}")
            report = run_load("127.0.0.1", port, b"0", threads=2,
                              duration_s=1.0)
            if report["requests"] == 0:
                print(f"fleet smoke: no requests served by {port}",
                      file=sys.stderr)
                return 1
            if report["trace_propagated"] != report["requests"]:
                print(
                    "fleet smoke: trace propagation broken "
                    f"({report['trace_propagated']}/{report['requests']} "
                    "echoed the injected trace id)",
                    file=sys.stderr,
                )
                return 1

        # -- LM tier: one streaming replica joins the fleet -----------
        proc, lm_port = spawn_stub_lm_server(
            step_ms=2.0, deadline_ms=2000.0, inter_token_budget_ms=250.0,
        )
        procs.append(proc)
        report = run_lm_load("127.0.0.1", lm_port, prompt=[1, 2, 3],
                             max_new_tokens=8, streams=4, duration_s=1.0)
        if report["requests"] == 0:
            print(f"fleet smoke: no generations served by {lm_port}",
                  file=sys.stderr)
            return 1
        if report["trace_propagated"] != report["requests"]:
            print(
                "fleet smoke: LM trace propagation broken "
                f"({report['trace_propagated']}/{report['requests']} "
                "done-lines echoed the injected trace id)",
                file=sys.stderr,
            )
            return 1
        # Strict solo gate first: TTFT/inter-token armed and not even
        # pending on the replica that actually decoded.
        rc = dsst_main([
            "slo", "check", "--strict",
            "--url", f"http://127.0.0.1:{lm_port}",
        ])
        if rc != 0:
            print(f"fleet smoke: LM slo check --strict exited {rc}",
                  file=sys.stderr)
            return 1
        endpoints.append(f"127.0.0.1:{lm_port}")

        with tempfile.TemporaryDirectory() as td:
            journal = Path(td) / "fleet.jsonl"
            rc = dsst_main([
                "slo", "check",
                "--fleet", *endpoints,
                "--fleet-journal", str(journal),
            ])
            if rc != 0:
                print(f"fleet smoke: slo check --fleet exited {rc}",
                      file=sys.stderr)
                return 1
            cycles = federation.read_fleet_journal(journal)
            if not cycles or cycles[-1]["up"] != 3:
                print(f"fleet smoke: journal shows {cycles!r}",
                      file=sys.stderr)
                return 1
        print("fleet smoke: 2 image replicas + 1 LM replica scraped, "
              "merged, and judged OK; LM streams propagated traces and "
              "passed the strict SLO gate")
        return 0
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            proc.wait(15)


if __name__ == "__main__":
    sys.exit(main())
