"""Mean duration of the program's ``feeder.place`` spans in the window:
staging one host batch and its host-to-device copy."""


def read(window):
    durs = window.span_durations("feeder.place")
    return 1e3 * sum(durs) / len(durs) if durs else None
