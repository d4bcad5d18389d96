"""The flash-attention kernel's share of its roofline in the prefill
program: the least time the chip could take for each call (the larger
of its FLOPs over the peak and its bytes over the peak bandwidth, both
from the call's shapes, the causal half counted once) over the kernel's
device time in the trace.  The call's sequence length is read from the
operand shapes in the operation's HLO text."""

import re

import flops
import harness
import trace as tracemod

# As the chip's trace names it (PR 23): '%block_0.1 = bf16[16,128,128]
# custom-call(bf16[16,128,128] q, k, v), custom_call_target="tpu_custom_call"':
# [heads, sequence, head size].
KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
OPERAND = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")


def read(window):
    if window.tables is None:
        return None
    dev = window.tables.devices[0]
    spans = tracemod.union((s, s + d) for n, s, d in dev["modules"]
                           if n.startswith("jit_prefill_bucket"))
    peak = flops.peaks(window.device_kind)
    count = flops.of_family(window.cell.config["family"])
    least = spent = 0.0
    bounds = {}
    for name, s, d in dev["ops"]:
        if not KERNEL.search(name):
            continue
        if not any(lo <= s < hi for lo, hi in spans):
            continue
        m = OPERAND.search(name)
        if not m:
            continue
        call = count.flash_prefill_call(window.cell.config, int(m.group(2)))
        t, bound = flops.roofline_seconds(call["flops"], call["bytes"], peak)
        least += t
        spent += d / 1e9
        bounds[bound] = bounds.get(bound, 0) + 1
    if spent <= 0:
        return None
    harness.log(f"flash_prefill_roofline: bound by {bounds}")
    return 100.0 * least / spent
