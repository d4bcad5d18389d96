"""``device_idle_share_train`` in a cell fed from a table: the same reading, moving
``train_samples_per_s.table`` (PERF.md, section 2: one bound a metric)."""

from layer_metrics.device_idle_share_train import read  # noqa: F401
