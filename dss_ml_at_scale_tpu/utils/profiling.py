"""Profiling and step-timing hooks (the subsystem the reference lacks).

The reference's only observability artifacts are a wall-clock epoch print
(``deep_learning/2.distributed-data-loading-petastorm.py:184``) and debug
batch prints gated on a logging level (``:176-179,203-206``); SURVEY.md
§5.1 calls for real ``jax.profiler`` trace hooks plus per-step timing.
This module provides both:

- :func:`trace` — context manager around
  ``jax.profiler.start_trace``/``stop_trace`` producing a TensorBoard /
  XProf-loadable trace directory (XLA HLO timelines, host/device
  activity).
- :func:`annotate` — named ``TraceAnnotation`` so framework phases
  (decode, device_put, train_step) show up as labeled spans.
- :class:`StepTimer` — cheap host-side per-step wall-time recorder with
  summary statistics. It deliberately does NOT block on device results:
  steady-state dispatch intervals equal device step time once the
  dispatch queue fills, and blocking every step would serialize the very
  pipeline being measured. Call :meth:`StepTimer.summary` after a
  ``block_until_ready`` for honest totals.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Callable, Iterator

# jax is imported where it is used: telemetry imports this module, and a
# launcher that only starts children (dsst bench) must stay off jax.


@contextlib.contextmanager
def trace(logdir: str, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace for the enclosed block.

    The resulting ``logdir`` loads in TensorBoard's profile plugin /
    XProf and shows the XLA op timeline on device plus host-side Python
    activity — the diagnostic the reference's epoch print stood in for.
    """
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named trace span: ``with annotate("decode"): ...``."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class StepTimer:
    """Rolling per-step wall-time recorder.

    ``tick()`` marks a step boundary; intervals between consecutive ticks
    are recorded. With ``skip_first_interval`` (default) the first
    recorded interval after construction is discarded at record time —
    that interval spans the jit compile of the first step. Discarding at
    the recorder, not in :meth:`summary`, keeps the stats honest after
    ring-buffer eviction and across per-epoch :meth:`reset` calls (epochs
    ≥ 2 have no compile step, so ``reset`` does not re-arm the skip
    unless asked).
    """

    def __init__(self, capacity: int = 4096, skip_first_interval: bool = True,
                 observer: Callable[[float], None] | None = None):
        self.capacity = capacity
        # deque(maxlen=...) evicts in O(1); list.pop(0) was O(n) per step
        # once at capacity — a growing per-step tax on long runs.
        self._times: collections.deque[float] = collections.deque(
            maxlen=capacity
        )
        self._last: float | None = None
        self._skip_next = skip_first_interval
        # Called once per RECORDED interval (compile-skipped intervals are
        # not observed) — the telemetry histogram hook, kept out of the
        # eviction-bounded ring so exported stats cover the whole run.
        self._observer = observer

    def reset(self, *, skip_next_interval: bool = False) -> None:
        self._times.clear()
        self._last = None
        self._skip_next = skip_next_interval

    def tick(self) -> None:
        now = time.perf_counter()
        if self._last is not None:
            if self._skip_next:
                self._skip_next = False
            else:
                dt = now - self._last
                self._times.append(dt)
                if self._observer is not None:
                    self._observer(dt)
        self._last = now

    @property
    def intervals(self) -> list[float]:
        return list(self._times)

    def summary(self) -> dict[str, float]:
        """Mean / p50 / p90 / max step seconds and steps/sec."""
        xs = self._times
        if not xs:
            return {}
        xs_sorted = sorted(xs)
        n = len(xs_sorted)
        mean = sum(xs_sorted) / n
        return {
            "step_time_mean_s": mean,
            "step_time_p50_s": xs_sorted[n // 2],
            "step_time_p90_s": xs_sorted[min(n - 1, (9 * n) // 10)],
            "step_time_max_s": xs_sorted[-1],
            "steps_per_sec": 1.0 / mean if mean > 0 else float("inf"),
        }
