"""The table of peaks, the roofline, and the way to a family's counts.

Operations and bytes are counted from shapes, one module a family:
``flops_<family>.py`` (``family`` as the configuration's file names it).
Model FLOPs are what the algorithm needs (a multiply-add is 2 FLOPs;
backward = 2 x forward; recomputation and padding are not counted).
Nothing is read from XLA's cost analysis.  A training family gives
``train_flops_per_sample(cfg)``; a served one ``prefill_flops(cfg, n)``,
``decode_flops(cfg, context)`` and, per kernel, ``<kernel>_call``.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

_PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    table = json.loads(_PEAKS.read_text())["by_device_kind"]
    if device_kind not in table:
        raise LookupError(
            f"no peak for device kind {device_kind!r} in {_PEAKS.name} "
            f"(known: {', '.join(sorted(table))})")
    return table[device_kind]


def of_family(family: str):
    """The module that counts this family's operations and bytes."""
    return importlib.import_module(f"flops_{family}")


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time the chip could take, and which bound sets it."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
