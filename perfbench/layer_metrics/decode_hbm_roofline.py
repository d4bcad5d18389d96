"""The decode step's share of its HBM roofline: the least bytes a decode
step must move (``bytes_<family>.decode_step_bytes``: from shapes alone,
whatever implements the step), mean over the ``lm.step`` spans that began
in the traced part, over the chip's HBM peak, divided by the device-busy
time of one ``slot_decode`` execution in the same traced part.  The
context each step attends over is the span's ``context_tokens``; a
program whose spans lack it is not read."""

import importlib

import flops
import trace as tracemod


def read(window):
    if window.tables is None:
        return None
    cfg = window.cell.config
    steps = [e["args"] for e in window.spans_in_trace("lm.step")
             if "context_tokens" in e.get("args", {})]
    seconds = tracemod.busy_per_execution(window.tables, "jit_slot_decode")
    if not steps or not seconds:
        return None
    count = importlib.import_module(f"bytes_{cfg['family']}")
    least = sum(count.decode_step_bytes(cfg, a["context_tokens"], a["active"])
                for a in steps) / len(steps)
    peak = flops.peaks(window.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (least / peak) / seconds
