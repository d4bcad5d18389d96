"""Device-busy time of one admission in the trace: the bucketed prefill
and the scatter into the slot, mean over the admissions traced."""

import trace as tracemod


def read(window):
    if window.tables is None:
        return None
    progs = tracemod.programs(window.tables)
    prefill = progs.get("jit_prefill_bucket")
    if not prefill or not prefill["count"]:
        return None
    scatter = progs.get("jit_write_slot", {"busy_s": 0.0})
    return 1e3 * (prefill["busy_s"] + scatter["busy_s"]) / prefill["count"]
