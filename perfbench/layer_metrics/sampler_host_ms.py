"""Mean duration of the program's ``lm.sample`` intervals in the window:
per decode step, the host's sampling, streaming, windows, SLO notes and
retirement over the active slots."""


def read(window):
    durs = window.span_durations("lm.sample")
    return 1e3 * sum(durs) / len(durs) if durs else None
