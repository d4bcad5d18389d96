"""Runtime thread sanitizer — the third analysis tier (``dsst sanitize``).

Two static tiers already guard this runtime's concurrency: ``dsst
lint`` checks ``with self._lock`` blocks syntactically and ``dsst
audit`` pins the compiled programs. Neither can see what actually
happens when the six thread families (feeder, serving batcher + decode
pool, HPO workers, journal writer, async checkpoint finalizer) run
together — both real races shipped so far were found by hand, after
the fact. This package closes the loop TSan-style, in process:

- **Lock interposition** (:mod:`.runtime`): while armed,
  ``threading.Lock/RLock/Condition/Thread`` *creation from this
  package's own modules* returns instrumented objects. Per-thread
  held-lock sets feed a global lock-acquisition-order graph; cycles are
  reported as potential deadlocks with the acquisition stacks of every
  edge — even when no deadlock fires in the run.
- **Dynamic guarded-by enforcement**: classes declaring
  ``_guarded_by_lock`` (the same contract the lint rule checks
  statically) get their guarded attributes checked on every read/write
  — an access off the declaring lock while another live thread is (or
  has been) inside that lock is a finding carrying the offending stack
  and the lock's current holder.
- **Scope-exit checks**: threads created inside a sanitize scope that
  are still alive at its end (unjoined), and instrumented locks still
  held (leaked), are findings.

Disarmed, nothing is patched: the declaring classes get plain
``threading`` objects and guarded attributes stay ordinary slots/dict
entries — zero overhead on the hot path. Armed overhead is what the
``sanitizer_overhead`` scenario of ``dsst bench`` gates.

Findings render through the same text/JSON + mandatory-reason
suppression + content-addressed baseline idioms as ``dsst lint``
(:data:`DEFAULT_SANITIZE_BASELINE` → ``SANITIZE_BASELINE.json``);
suppressions are ordinary ``# dsst: ignore[rule] reason`` comments on
the offending source line (resolved from the finding's stack at report
time, so one comment idiom serves the static and dynamic tiers).
"""

from __future__ import annotations

from .report import (  # noqa: F401
    DEFAULT_SANITIZE_BASELINE,
    RULES,
    SanitizeResult,
    SanitizeUsageError,
    build_result,
)
from .runtime import (  # noqa: F401
    SanitizeScope,
    is_armed,
    sanitize_scope,
)
from .workloads import (  # noqa: F401
    run_workloads,
    workload_catalog,
    workload_names,
)

_OBSERVATION: tuple | None = None


def arm_observation_mode() -> None:
    """``DSST_SANITIZE=1`` on any dsst invocation: arm instrumentation
    for the whole process and report findings to stderr at exit.

    Observation, not a gate — the exit code is untouched, so a chaos
    soak (or a production run) can ride with the sanitizer armed
    without changing its pass/fail semantics. ``dsst sanitize`` is the
    gating face; the pytest ``DSST_SANITIZE=1`` mode gates via the
    session hook.
    """
    global _OBSERVATION
    if _OBSERVATION is not None:
        return
    import atexit

    cm = sanitize_scope()
    scope = cm.__enter__()
    _OBSERVATION = (cm, scope)
    atexit.register(_report_observation)


def _report_observation() -> None:
    global _OBSERVATION
    if _OBSERVATION is None:
        return
    cm, scope = _OBSERVATION
    _OBSERVATION = None
    try:
        cm.__exit__(None, None, None)
    except Exception:  # disarm must never mask the command's own exit
        return
    import sys

    res = build_result(scope, ["<env-armed process>"], full_run=False)
    if res.findings:
        sys.stderr.write(
            "dsst sanitize (DSST_SANITIZE=1 observation mode):\n"
            + res.render_text() + "\n"
        )
