"""Device-busy time of one execution of ``slot_decode`` in the trace."""

import trace as tracemod


def read(window):
    if window.tables is None:
        return None
    seconds = tracemod.busy_per_execution(window.tables, "jit_slot_decode")
    return None if seconds is None else 1e3 * seconds
