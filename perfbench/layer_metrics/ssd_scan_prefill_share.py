"""The SSD kernel's share of the prefill program: the union of the
``ssd_scan`` operations' intervals over the union of the
``jit_prefill_bucket`` executions, on device 0.  It says whether the scan
or the rest of the prefill (its projections, experts and attention) binds
an admission.  A program without the kernel is not read."""

import trace as tracemod

from layer_metrics.ssd_scan_roofline import scan_ops


def read(window):
    if window.tables is None:
        return None
    ops, spans = scan_ops(window)
    if not ops or not spans:
        return None
    scan = tracemod.union((s, s + d) for _, s, d in ops)
    return 100.0 * tracemod.measure(scan) / tracemod.measure(spans)
