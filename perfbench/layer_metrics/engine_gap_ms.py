"""What the engine's thread spends between two decode steps when it
admits nothing: the mean, over consecutive ``lm.step`` spans of the
window with no ``lm.prefill`` between them, of the next span's start less
this span's end.  It holds ``sampler_host_ms``.  Only a program that
records ``lm.sample`` is read (the spans' clock is then known to be the
one the intervals were cut on)."""

import bisect


def read(window):
    if not window.span_durations("lm.sample"):
        return None
    steps = sorted((e["ts"], e["ts"] + e["dur"]) for e in window.spans
                   if e["name"] == "lm.step")
    prefills = sorted(e["ts"] for e in window.spans
                      if e["name"] == "lm.prefill")
    gaps = []
    for (_, end), (start, _) in zip(steps, steps[1:]):
        if bisect.bisect_left(prefills, end) == bisect.bisect_left(
                prefills, start):
            gaps.append(start - end)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
