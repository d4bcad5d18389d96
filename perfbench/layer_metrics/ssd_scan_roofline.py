"""The SSD kernel's share of its roofline in the prefill program: the
least time the chip could take for each ``ssd_scan`` call (the larger of
its FLOPs over the peak and its bytes over the peak bandwidth, both from
the call's shapes: ``flops_<family>.ssd_scan_call``) over the kernel's
device time in the trace.  The kernel is found by its name, never by
``tpu_custom_call`` alone (the prefill also holds the flash kernel, and
a Mamba-1 model's ``ssm_scan``); the call's positions are read from its
first output's shape in the operation's HLO text,
``f32[heads,head_dim,positions]``.  A program without the kernel is not
read."""

import re

import flops
import harness
import trace as tracemod

KERNEL = re.compile(r"ssd_scan(\.\d+)?$")
OUTPUT = re.compile(r"f32\[(\d+),(\d+),(\d+)\]")


def scan_ops(window):
    """``(name, start, duration)`` of every ``ssd_scan`` operation that
    began inside an execution of the prefill program on device 0, and
    those executions' intervals."""
    dev = window.tables.devices[0]
    spans = tracemod.union((s, s + d) for n, s, d in dev["modules"]
                           if n.startswith("jit_prefill_bucket"))
    return [(n, s, d) for n, s, d in dev["ops"]
            if KERNEL.match(tracemod.op_name(n))
            and any(lo <= s < hi for lo, hi in spans)], spans


def read(window):
    if window.tables is None:
        return None
    ops, _ = scan_ops(window)
    peak = flops.peaks(window.device_kind)
    count = flops.of_family(window.cell.config["family"])
    least = spent = 0.0
    bounds = {}
    for name, _, d in ops:
        m = OUTPUT.search(name)
        if not m:
            continue
        call = count.ssd_scan_call(window.cell.config, int(m.group(3)))
        t, bound = flops.roofline_seconds(call["flops"], call["bytes"], peak)
        least += t
        spent += d / 1e9
        bounds[bound] = bounds.get(bound, 0) + 1
    if spent <= 0:
        return None
    harness.log(f"ssd_scan_roofline: {len(ops)} ssd_scan calls, bound by "
                f"{bounds}, {spent:.4f} s")
    return 100.0 * least / spent
