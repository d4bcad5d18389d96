"""Device telemetry: HBM usage, live buffers, and compile events.

TPU HBM is the scarcest resource in the system and the one the reference
stack never shows (SURVEY §5.1); ``Device.memory_stats()`` exposes the
allocator's view (``bytes_in_use``, ``peak_bytes_in_use``, ...) on TPU
and GPU backends. CPU devices typically return ``None`` — every probe
here degrades to "no sample" instead of raising, so the same
instrumented code runs in CI's simulated 8-device CPU mesh.

Compile events are the other silent cost: an unexpected retrace
mid-training (a shape drift, a weak-type flip) turns a 10 ms step into a
30 s one. Rather than wrapping jit lowering (private API churn),
:class:`CompileTracker` watches a jitted callable's executable-cache
size — growth after a call IS a compile — which is exact, costs one
attribute read per step, and needs no device sync.
"""

from __future__ import annotations

import threading
from typing import Sequence

# jax is imported where it is used: a launcher that only starts children
# (dsst bench) imports telemetry and must stay off jax.


def device_memory_stats(device) -> dict:
    """``device.memory_stats()`` or ``{}`` when unsupported (CPU)."""
    try:
        stats = device.memory_stats()
    except Exception:
        return {}
    return dict(stats) if stats else {}


def device_label(device) -> str:
    return f"{device.platform}:{device.id}"


class DeviceMonitor:
    """Background sampler of per-device memory gauges.

    ``sample()`` takes one sample synchronously (what the thread calls
    every ``interval_s``); ``start()``/``stop()`` manage the daemon
    thread. Gauges written (all labeled ``device="tpu:0"`` style):

    - ``device_hbm_bytes_in_use`` / ``device_hbm_bytes_peak`` /
      ``device_hbm_bytes_limit`` — from ``memory_stats()`` when present.
    - ``device_live_buffers`` — live on-device buffer count when the
      runtime exposes it.
    - ``device_memory_stats_supported`` — 1/0 per device, so dashboards
      can tell "no data" from "zero bytes".
    """

    # Lint contract (dsst lint, lock-discipline rule; enforced at
    # runtime by dsst sanitize): start()/stop() race from embedding
    # code and the serve/train teardown paths — the sampler-thread
    # handle only under _lock.
    _guarded_by_lock = ("_thread",)

    def __init__(self, registry=None, *, interval_s: float = 1.0,
                 devices: Sequence | None = None):
        if registry is None:
            from . import get_registry

            registry = get_registry()
        self.registry = registry
        self.interval_s = interval_s
        import jax

        self.devices = (
            list(devices) if devices is not None else jax.local_devices()
        )
        self._in_use = registry.gauge(
            "device_hbm_bytes_in_use", "allocator bytes in use",
            labels=("device",))
        self._peak = registry.gauge(
            "device_hbm_bytes_peak", "allocator peak bytes in use",
            labels=("device",))
        self._limit = registry.gauge(
            "device_hbm_bytes_limit", "allocator byte limit",
            labels=("device",))
        self._live = registry.gauge(
            "device_live_buffers", "live on-device buffers",
            labels=("device",))
        self._supported = registry.gauge(
            "device_memory_stats_supported",
            "1 when memory_stats() reports on this device",
            labels=("device",))
        self._samples = registry.counter(
            "device_monitor_samples_total", "DeviceMonitor sampling passes")
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None

    @staticmethod
    def _live_counts() -> dict:
        """Live jax.Array count per device (one pass over live arrays —
        cheap at sampling cadence; {} when the runtime can't say)."""
        import jax

        counts: dict = {}
        try:
            for a in jax.live_arrays():
                for dev in a.devices():
                    counts[dev] = counts.get(dev, 0) + 1
        except Exception:
            return {}
        return counts

    def sample(self) -> None:
        """One sampling pass over every device. Never raises on an
        unsupported backend — CPU devices just report supported=0."""
        live = self._live_counts()
        for d in self.devices:
            label = device_label(d)
            stats = device_memory_stats(d)
            self._supported.labels(device=label).set(1.0 if stats else 0.0)
            if stats:
                if "bytes_in_use" in stats:
                    self._in_use.labels(device=label).set(
                        stats["bytes_in_use"])
                if "peak_bytes_in_use" in stats:
                    self._peak.labels(device=label).set(
                        stats["peak_bytes_in_use"])
                if "bytes_limit" in stats:
                    self._limit.labels(device=label).set(
                        stats["bytes_limit"])
            self._live.labels(device=label).set(live.get(d, 0))
        self._samples.inc()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.sample()
            # dsst: ignore[bare-except] sampler thread: a flaky backend must not kill it
            except Exception:
                pass

    def start(self) -> "DeviceMonitor":
        # The whole check-then-spawn under _lock: two concurrent
        # start() calls used to both see no live thread and spawn two
        # sampler loops (and a stop() racing a start() could join a
        # thread the start was about to replace) — the check-then-act
        # shape the lock-discipline/sanitizer tier exists to catch.
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop.clear()
            self.sample()  # one immediate sample so gauges exist right away
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="device-monitor")
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        # The event is set INSIDE the lock: set-before-lock left a
        # window where a racing start() could observe the dead thread,
        # clear the event, and spawn a sampler this stop() then joined
        # without ever signalling — a loop running forever with
        # _thread=None. Ordered under the lock, every sampler swapped
        # out below has seen its stop signal.
        with self._lock:
            self._stop.set()
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=timeout)

    def __enter__(self) -> "DeviceMonitor":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False


class CompileTracker:
    """Count executable compiles of a jitted callable via its cache size.

    ``update()`` after each call: if the jit cache grew, that call
    compiled — increment the counter by the growth. Exact for shape/dtype
    retraces, free of device syncs, and cheap enough for the hot loop
    (one method call + int compare). Degrades to a no-op on callables
    without a ``_cache_size`` probe.
    """

    def __init__(self, fn, counter=None):
        if counter is None:
            from . import get_registry

            counter = get_registry().counter(
                "jit_compile_events_total", "jit executable compiles")
        self._fn = fn
        self._counter = counter
        self._last = self._size()

    def _size(self) -> int | None:
        probe = getattr(self._fn, "_cache_size", None)
        if probe is None:
            return None
        try:
            return int(probe())
        except Exception:
            return None

    def update(self) -> int:
        """Record (and return) the number of compiles since last update."""
        size = self._size()
        if size is None:
            return 0
        if self._last is None or size < self._last:
            # First successful probe, or a cache clear: re-anchor.
            self._last = size
            return 0
        delta = size - self._last
        if delta > 0:
            self._counter.inc(delta)
            self._last = size
        return delta
