"""Share of the window that the step loop spent waiting on the feeder's
queue: the program's ``feeder_stall_seconds_total`` over the window."""


def read(window):
    waited = window.counter_delta("feeder_stall_seconds_total",
                                  feeder="train")
    if waited is None:
        return None
    return 100.0 * waited / window.seconds
