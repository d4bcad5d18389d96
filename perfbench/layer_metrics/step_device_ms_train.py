"""Device-busy time of one execution of the jitted train step: the union
of the step program's operations in the trace, per execution."""

import trace as tracemod


def read(window):
    if window.tables is None:
        return None
    seconds = tracemod.busy_per_execution(window.tables, "jit_train_step")
    return None if seconds is None else 1e3 * seconds
