"""Mean duration of the program's ``reader.assemble`` spans in the window:
the serial copy of loaded row groups into one batch, on the feeder
thread."""


def read(window):
    durs = window.span_durations("reader.assemble")
    return 1e3 * sum(durs) / len(durs) if durs else None
