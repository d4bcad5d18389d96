"""An expert model's decode step's share of its HBM roofline: the least
bytes a step must move given its routing (``bytes_<family>.
decode_step_bytes``: the weights every token passes and the head once, the
held experts that got a token, the latent rows attended over, the logits),
over the chip's HBM peak, divided by the device-busy time of one
``slot_decode`` execution in the traced part.

The experts touched a step are ``lm_moe_experts_touched_total{program=
"decode"}`` over ``lm_decode_steps_total`` across the window; the context
and the active slots the means of the ``lm.step`` spans that began in the
traced part.  A program without those counters is not read."""

import importlib

import flops
import trace as tracemod


def read(window):
    if window.tables is None:
        return None
    cfg = window.cell.config
    touched = window.counter_delta("lm_moe_experts_touched_total",
                                   program="decode")
    steps = sum(window.counter_delta("lm_decode_steps_total", mode=m) or 0.0
                for m in ("ahead", "lockstep"))
    spans = [e["args"] for e in window.spans_in_trace("lm.step")
             if "context_tokens" in e.get("args", {})]
    seconds = tracemod.busy_per_execution(window.tables, "jit_slot_decode")
    if touched is None or not steps or not spans or not seconds:
        return None
    count = importlib.import_module(f"bytes_{cfg['family']}")
    least = count.decode_step_bytes(
        cfg, sum(a["context_tokens"] for a in spans) / len(spans),
        sum(a["active"] for a in spans) / len(spans), touched / steps)
    peak = flops.peaks(window.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (least / peak) / seconds
