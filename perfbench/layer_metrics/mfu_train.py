"""The whole train step's share of the chips' peak: model FLOPs a sample
(forward and backward from the configuration's shapes, counted by the
family's ``flops_<family>.train_flops_per_sample``; recomputation not
counted) times the samples a second of the traced part of the window,
over chips times the peak bf16 FLOP/s of the device kind.  The rate is
the trace's own: the executions of the step program on one device, from
the start of the first to the start of the last, so a whole number of
step periods with whatever idle time lies between the steps."""

import flops
import trace as tracemod

PROGRAM = "jit_train_step"


def read(window):
    if window.tables is None:
        return None
    starts = sorted(s for name, s, _ in window.tables.devices[0]["modules"]
                    if tracemod.program_name(name) == PROGRAM)
    if len(starts) < 2 or starts[-1] <= starts[0]:
        return None
    per_sample = flops.of_family(
        window.cell.config["family"]).train_flops_per_sample(window.cell.config)
    seconds = (starts[-1] - starts[0]) / 1e9
    rate = (len(starts) - 1) * window.stats["batch"] / seconds
    peak = flops.peaks(window.device_kind)["bf16_flops_per_s"]
    return 100.0 * per_sample * rate / (window.stats["chips"] * peak)
