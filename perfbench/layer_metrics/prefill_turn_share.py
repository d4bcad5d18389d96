"""Prefill launches per decode step of the window: the launches of both
kinds, ``whole`` (a prompt in one program) and ``chunk`` (one chunk of a
prompt, before a turn's decode step), of the program's
``lm_prefill_launches_total{kind}`` over the decode steps dispatched,
ahead + lockstep of ``lm_decode_steps_total{mode}``, as a share.  It says
how often the gap between two tokens carries a launch, so whether the
95th percentile of those gaps falls inside such a turn.  A program
without the launch counter is not read."""


def read(window):
    whole = window.counter_delta("lm_prefill_launches_total", kind="whole")
    chunk = window.counter_delta("lm_prefill_launches_total", kind="chunk")
    if whole is None and chunk is None:   # no such counter
        return None
    steps = sum(window.counter_delta("lm_decode_steps_total", mode=mode) or 0.0
                for mode in ("ahead", "lockstep"))
    if not steps:
        return None
    return 100.0 * ((whole or 0.0) + (chunk or 0.0)) / steps
