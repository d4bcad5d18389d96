"""Mean duration of the program's ``reader.next`` spans in the window:
what the feeder thread waited for the table reader and decode, a batch."""


def read(window):
    durs = window.span_durations("reader.next")
    return 1e3 * sum(durs) / len(durs) if durs else None
