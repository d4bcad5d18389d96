"""Mean duration of the program's ``lm.step`` spans in the window: one
decode step as the engine's thread sees it (dispatch, device, logits to
the host)."""


def read(window):
    durs = window.span_durations("lm.step")
    return 1e3 * sum(durs) / len(durs) if durs else None
