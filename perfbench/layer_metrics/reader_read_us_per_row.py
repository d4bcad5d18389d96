"""Time a loading thread of the table reader spends reading one row group
and turning its columns into numpy, a row: the program's
``reader_stage_seconds_total{stage="read"}`` over ``reader_rows_total``,
both counted where the work is done, over the window."""


def per_row_us(window, stage):
    seconds = window.counter_delta("reader_stage_seconds_total", stage=stage)
    rows = window.counter_delta("reader_rows_total")
    if seconds is None or not rows:
        return None
    return 1e6 * seconds / rows


def read(window):
    return per_row_us(window, "read")
