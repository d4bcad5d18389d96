"""Share of the batches assembled by a copy whose memory the reader had
used before: recycled / (recycled + fresh) of the program's
``reader_batch_buffers_total{source}`` over the window.  A batch that is a
slice of one row group (``view``) is copied nowhere and counts in neither
term."""


def read(window):
    recycled = window.counter_delta("reader_batch_buffers_total",
                                    source="recycled")
    fresh = window.counter_delta("reader_batch_buffers_total", source="fresh")
    recycled, fresh = recycled or 0.0, fresh or 0.0
    if not recycled + fresh:          # no such counter, or no batch copied
        return None
    return 100.0 * recycled / (recycled + fresh)
