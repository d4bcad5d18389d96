"""``mfu_train`` in a cell fed from a table: the same reading, moving
``train_samples_per_s.table`` (PERF.md, section 2: one bound a metric)."""

from layer_metrics.mfu_train import read  # noqa: F401
