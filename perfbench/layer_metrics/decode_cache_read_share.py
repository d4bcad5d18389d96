"""The share of the slot arena's rows that the window's decode steps
fetched: read / arena of the program's ``lm_decode_cache_rows_total{kind}``
over the window.  ``read`` counts, a layer, the rows in the blocks a step's
attention fetches for each slot's ``pos``; ``arena`` counts ``slots x
max_len`` a step.  100% is a step that reads every row whatever the slots
hold.  A program without the counter (one whose decode reads the whole
arena and says nothing of it) is not read."""


def read(window):
    rows = window.counter_delta("lm_decode_cache_rows_total", kind="read")
    arena = window.counter_delta("lm_decode_cache_rows_total", kind="arena")
    if not arena:                     # no such counter, or no step at all
        return None
    return 100.0 * (rows or 0.0) / arena
