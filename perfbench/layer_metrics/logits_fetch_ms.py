"""Mean duration of the program's ``lm.fetch`` intervals in the window:
the copy of one decode step's logits from the device to a host array,
after the device has finished."""


def read(window):
    durs = window.span_durations("lm.fetch")
    return 1e3 * sum(durs) / len(durs) if durs else None
