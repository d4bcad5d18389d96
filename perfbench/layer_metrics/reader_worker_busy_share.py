"""Share of the loading threads' time spent loading: both stages' seconds
(``reader_stage_seconds_total``, summed over the threads) over the
threads' number times the window.  The number of threads is the program's
own gauge ``reader_workers`` at the window's end, not the traffic file's."""


def gauge(snapshot, name):
    for m in snapshot["metrics"]:
        if m["name"] == name:
            return m.get("value")
    return None


def read(window):
    stages = [window.counter_delta("reader_stage_seconds_total", stage=s)
              for s in ("read", "decode")]
    workers = gauge(window.counters1, "reader_workers")
    if None in stages or not workers:
        return None
    return 100.0 * sum(stages) / (workers * window.seconds)
