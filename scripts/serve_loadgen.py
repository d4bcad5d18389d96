#!/usr/bin/env python
"""Closed-loop load generator for the serving scheduler (CLI shim over
``dss_ml_at_scale_tpu.bench.loadgen``):

    python scripts/serve_loadgen.py --selftest --threads 16 --duration 3
    python scripts/serve_loadgen.py --url http://127.0.0.1:8008 --image cat.jpg

Against ``--selftest`` it loads a stub scorer whose step is a sleep: a
check of the scheduler's mechanics, not a speed.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from dss_ml_at_scale_tpu.bench.loadgen import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
