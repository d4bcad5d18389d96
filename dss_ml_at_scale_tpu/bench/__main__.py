"""Isolated scenario child: ``python -m dss_ml_at_scale_tpu.bench``.

One scenario per process — a hung backend, an OOM, or a watchdog kill
takes down this child, never the harness. Protocol: exactly one JSON
line on stdout (``{"scenario", "samples", "extra", "completed"}`` on success,
``{"scenario", "failed": true, "error"}`` on failure), per-repetition
durable partials at ``--partial`` for parent-side salvage, exit 0
either way — the parent judges the JSON, not the return code.

The environment fingerprint is not computed in a scenario child (its
scenario may never pay for a jax import). The isolating parent stays off
JAX too — it would hold the chip its children need — so it takes the
fingerprint from ``--fingerprint``, a child that prints
``environment_fingerprint()`` as one JSON line and exits before the
first scenario child starts.
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

from .core import environment_fingerprint, get_scenario, measure_scenario


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="dss_ml_at_scale_tpu.bench")
    ap.add_argument("--scenario")
    ap.add_argument("--fingerprint", action="store_true")
    ap.add_argument("--partial", default=None)
    ap.add_argument("--repetitions", type=int, default=None)
    args = ap.parse_args(argv)
    if args.fingerprint:
        # dsst: ignore[no-print] the one-JSON-line child protocol
        print(json.dumps(environment_fingerprint()))
        return 0
    if not args.scenario:
        ap.error("one of --scenario / --fingerprint is required")
    from ..runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        sc = get_scenario(args.scenario)
        record = measure_scenario(
            sc, repetitions=args.repetitions, partial_path=args.partial,
            env={},
        )
    except BaseException:  # noqa: BLE001 - the JSON line IS the report
        record = {
            "scenario": args.scenario,
            "failed": True,
            "error": traceback.format_exc(limit=8),
        }
    # dsst: ignore[no-print] the one-JSON-line child protocol: stdout is the parent's only channel
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
