"""Share of the traced part of the window in which no operation ran on
the device (the mean over the devices used)."""

from layer_metrics.device_idle_share_train import read  # noqa: F401
