"""Time a loading thread of the table reader spends in the transform (JPEG
decode, resize, crop, normalise), a row:
``reader_stage_seconds_total{stage="decode"}`` over ``reader_rows_total``."""

from layer_metrics.reader_read_us_per_row import per_row_us


def read(window):
    return per_row_us(window, "decode")
