"""Share of the decode steps of the window that the engine dispatched
ahead: while the step before them was still uncollected, their tokens
that step's ids on the device.  ahead / (ahead + lockstep) of the
program's ``lm_decode_steps_total{mode}`` over the window.  A program
without the counter (one whose loop is strictly lock-step) is not read."""


def read(window):
    ahead = window.counter_delta("lm_decode_steps_total", mode="ahead")
    lockstep = window.counter_delta("lm_decode_steps_total", mode="lockstep")
    ahead, lockstep = ahead or 0.0, lockstep or 0.0
    if not ahead + lockstep:          # no such counter, or no step at all
        return None
    return 100.0 * ahead / (ahead + lockstep)
