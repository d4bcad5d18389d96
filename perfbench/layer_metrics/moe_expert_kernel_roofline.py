"""The grouped-expert kernel's share of its HBM roofline in the decode
step: the least bytes of the step's expert work (each held expert that
got a token read once: ``lm_moe_experts_touched_total{program="decode"}``
over ``lm_decode_steps_total`` across the window, times
``bytes_<family>.expert_bytes``), over the chip's HBM peak, over the
device time of the operations named ``moe_experts`` per ``slot_decode``
execution in the traced part.  The kernel is found by its name, never by
``tpu_custom_call`` alone (the step holds no other kernel today, but a
chunk holds the flash and SSD kernels).  A program without the kernel,
or a family whose byte count has no ``expert_bytes``, is not read."""

import importlib
import re

import flops
import trace as tracemod

KERNEL = re.compile(r"moe_experts(\.\d+)?$")
PROGRAM = "jit_slot_decode"


def kernel_seconds(tables):
    """Device seconds of the ``moe_experts`` operations that began inside
    an execution of the decode program on device 0, per execution; None
    where the program never ran or holds no such operation."""
    dev = tables.devices[0]
    runs = [(s, s + d) for n, s, d in dev["modules"]
            if tracemod.program_name(n) == PROGRAM]
    spans = tracemod.union(runs)
    spent = sum(d for n, s, d in dev["ops"]
                if KERNEL.match(tracemod.op_name(n))
                and any(lo <= s < hi for lo, hi in spans))
    if not runs or spent <= 0:
        return None
    return spent / 1e9 / len(runs)


def read(window):
    if window.tables is None:
        return None
    count = importlib.import_module(f"bytes_{window.cell.config['family']}")
    touched = window.counter_delta("lm_moe_experts_touched_total",
                                   program="decode")
    steps = sum(window.counter_delta("lm_decode_steps_total", mode=m) or 0.0
                for m in ("ahead", "lockstep"))
    seconds = kernel_seconds(window.tables)
    if (not hasattr(count, "expert_bytes") or touched is None or not steps
            or seconds is None):
        return None
    least = touched / steps * count.expert_bytes(window.cell.config)
    peak = flops.peaks(window.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (least / peak) / seconds
