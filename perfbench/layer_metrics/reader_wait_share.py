"""Share of the window that the reader's consumer (the feeder thread,
inside ``reader.next``) waited on the queue of loaded row groups: the
program's ``reader_stall_seconds_total`` over the window."""


def read(window):
    waited = window.counter_delta("reader_stall_seconds_total")
    if waited is None:
        return None
    return 100.0 * waited / window.seconds
