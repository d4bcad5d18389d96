"""Background feeder pipeline: host→device input work off the step loop.

The last hop of the input pipeline. The reference gets reader/compute
overlap for free from torch DataLoader + CUDA streams; the first JAX
port approximated it with *pull-driven* double buffering
(``prefetch_to_mesh``): the training thread itself still sharded and
enqueued every batch, so that host work — layout staging, sharding
validation, ``device_put`` dispatch — serialized with step dispatch
(its share of step time is unmeasured on the chip).

The fix is the tf.data shape (Murray et al., VLDB 2021): a dedicated
**feeder thread per consumer**. The feeder pulls host batches from the
reader, pops the row-provenance side channel (host metadata that must
never reach ``device_put``), places the batch on the mesh through a
cached-sharding batched-transfer placer
(:class:`~dss_ml_at_scale_tpu.runtime.mesh.MeshBatchPlacer`), and hands
finished on-device batches through a bounded queue. The step loop's
per-batch cost collapses to one ``queue.get`` — shard+enqueue time
overlaps step dispatch instead of adding to it, and the bounded queue
gives backpressure (at most ``depth`` batches of HBM in flight).

Telemetry (``/metrics``): ``feeder_depth`` / ``feeder_occupancy``
gauges, ``feeder_stall_seconds_total`` / ``feeder_batches_total``
counters (all labeled by feeder name), and a ``feeder_stage_seconds``
histogram of the feeder-thread cost per batch. Occupancy near ``depth``
means the input side keeps ahead of compute; occupancy pinned at zero
with stall time accruing means training is input-bound.

``prefetch_to_mesh`` / ``prefetch_to_devices`` remain as thin
generator wrappers over a feeder, preserving the old pull-driven API.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Mapping

import jax
from jax.sharding import Mesh

from .. import telemetry
from ..resilience.rollback import PROVENANCE_KEY
from ..runtime.mesh import get_batch_placer
from ..telemetry import tracecontext

_SENTINEL = object()


class _FeederFailure:
    """Wraps an exception raised in the feeder thread for re-raise in
    the consumer (same cross-thread discipline as the reader pool)."""

    __slots__ = ("error",)

    def __init__(self, error: BaseException):
        self.error = error


def split_provenance(batch) -> tuple[Any, Any]:
    """Pop the reader's row-provenance side channel off a batch.

    Provenance is host metadata (a list of RowRanges) — it must never
    reach ``device_put``. Returns ``(batch_without_provenance, prov)``;
    ``prov`` is None for batches without it (in-memory iterables,
    provenance-disabled readers).
    """
    if isinstance(batch, Mapping) and PROVENANCE_KEY in batch:
        prov = batch[PROVENANCE_KEY]
        return {k: v for k, v in batch.items() if k != PROVENANCE_KEY}, prov
    return batch, None


# dsst: ignore[lock-discipline] no lock-guarded state: every producer/consumer crossing rides the bounded Queue or the stop Event; _done/_last_handoff are single-consumer-thread by the iterator contract
class Feeder:
    """Background feeder thread feeding one consumer through a bounded queue.

    Iterating yields ``(device_batch, provenance)`` pairs in source
    order — provenance rides the queue WITH its batch, so consumer-side
    row accounting (the PR 4 health/quarantine machinery) keeps exact
    parity by construction instead of by a separate FIFO.

    Lifecycle: the thread starts at construction and exits when the
    source is exhausted, the source raises (the exception is re-raised
    from the consumer's ``next()``), or :meth:`close` is called.
    ``close`` is idempotent, unblocks a producer stuck on a full queue,
    and joins the thread — callers should close from a ``finally`` (or
    use the context manager) so no feeder thread outlives its loop.
    """

    def __init__(
        self,
        source: Iterable,
        place: Callable[[Any], Any],
        *,
        depth: int = 2,
        name: str = "feeder",
        wait_observer: Callable[[float], None] | None = None,
    ):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._source = iter(source)
        self._place = place
        self.depth = depth
        self.name = name
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._done = False
        self._last_handoff = tracecontext.Handoff(None)
        # Bound on the instance so close() still works from a generator
        # finalizer during interpreter shutdown (module globals may be
        # torn down by then — same discipline as the reader pool).
        self._empty_exc = queue.Empty
        self._full_exc = queue.Full
        self._wait_observer = wait_observer
        # Handles bound once; the per-batch cost on both sides is plain
        # method calls on pre-resolved children.
        self._depth_gauge = telemetry.gauge(
            "feeder_depth",
            "configured bound of the feeder's on-device batch queue",
            labels=("feeder",),
        ).labels(feeder=name)
        self._depth_gauge.set(depth)
        self._occupancy = telemetry.gauge(
            "feeder_occupancy",
            "on-device batches queued at last consumer read",
            labels=("feeder",),
        ).labels(feeder=name)
        self._stall_total = telemetry.counter(
            "feeder_stall_seconds_total",
            "cumulative consumer wait on the feeder queue",
            labels=("feeder",),
        ).labels(feeder=name)
        self._batches_total = telemetry.counter(
            "feeder_batches_total",
            "batches staged, sharded, and enqueued by the feeder thread",
            labels=("feeder",),
        ).labels(feeder=name)
        self._stage_hist = telemetry.histogram(
            "feeder_stage_seconds",
            "feeder-thread time to stage + shard + enqueue one batch",
            labels=("feeder",),
        ).labels(feeder=name)
        # The live half of the stall story: windowed waits (per feeder
        # on /metrics) plus the SLO engine's aggregate stall-fraction
        # objective — "are we input-bound NOW", not "were we ever".
        self._stall_window = telemetry.window(
            "feeder_stall_window_seconds",
            "windowed consumer waits on the feeder queue",
            labels=("feeder",),
        ).labels(feeder=name)
        from ..telemetry import slo as _slo

        self._slo_note_wait = _slo.get_engine().note_feeder_wait
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"feeder-{name}"
        )
        self._thread.start()

    # -- producer (feeder thread) -----------------------------------------

    # dsst: hotpath — feeder-thread stage cost is what overlaps step dispatch
    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                # One step trace per batch, born HERE: the feeder is the
                # first thread to touch a step's data, so the step_id
                # covers reader pull → staging/sharding → (via the
                # handoff riding the queue) the consumer's step dispatch.
                with tracecontext.trace(kind="step") as tctx:
                    with telemetry.span("reader.next", feeder=self.name):
                        raw = next(self._source, _SENTINEL)
                    if raw is _SENTINEL:
                        break
                    t0 = time.perf_counter()
                    batch, prov = split_provenance(raw)
                    with telemetry.span("feeder.place", feeder=self.name):
                        device_batch = self._place(batch)
                    self._stage_hist.observe(time.perf_counter() - t0)
                if not self._put(
                    ((device_batch, prov), tracecontext.Handoff(tctx))
                ):
                    return  # closed while blocked on a full queue
                self._batches_total.inc()
        except BaseException as e:
            self._put(_FeederFailure(e))
        finally:
            self._put(_SENTINEL)

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except self._full_exc:
                continue
        return False

    # -- consumer ----------------------------------------------------------

    @property
    def occupancy(self) -> int:
        """On-device batches currently queued (approximate, lock-free)."""
        return self._queue.qsize()

    @property
    def last_handoff(self) -> tracecontext.Handoff:
        """The step-trace handoff of the batch the last ``next()``
        returned — the consumer activates it around its step dispatch so
        the step's spans join the batch's causal timeline. Read it
        before the next ``next()`` (single-consumer, like the iterator
        itself)."""
        return self._last_handoff

    def __iter__(self) -> Iterator[tuple[Any, Any]]:
        return self

    # dsst: hotpath — the consumer's entire per-batch cost: one queue.get
    def __next__(self) -> tuple[Any, Any]:
        if self._done:
            raise StopIteration
        t0 = time.perf_counter()
        while True:
            try:
                item = self._queue.get(timeout=0.1)
                break
            except self._empty_exc:
                if self._stop.is_set():
                    # Closed under the consumer (abort path); a clean
                    # StopIteration lets an in-flight loop wind down.
                    self._done = True
                    raise StopIteration from None
        wait = time.perf_counter() - t0
        self._stall_total.inc(wait)
        self._stall_window.observe(wait)
        self._slo_note_wait(wait)
        if self._wait_observer is not None:
            self._wait_observer(wait)
        self._occupancy.set(self._queue.qsize())
        if item is _SENTINEL:
            self._done = True
            self._thread.join(timeout=5)
            raise StopIteration
        if isinstance(item, _FeederFailure):
            self._done = True
            self._thread.join(timeout=5)
            raise item.error
        pair, self._last_handoff = item
        return pair

    def close(self) -> None:
        """Stop the feeder thread and join it. Idempotent; safe to call
        from ``finally`` on every exit path (exhaustion, exception,
        abort, preemption) — no daemon thread outlives the loop."""
        self._done = True
        self._stop.set()
        # Drain so a producer blocked on a full queue observes the stop.
        try:
            while True:
                self._queue.get_nowait()
        except self._empty_exc:
            pass
        self._thread.join(timeout=5)
        # Release queued device batches (HBM) and the source promptly.
        try:
            while True:
                self._queue.get_nowait()
        except self._empty_exc:
            pass

    def __enter__(self) -> "Feeder":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class MeshFeeder(Feeder):
    """Feeder that places batches on a mesh, batch-sharded.

    The placer is shared per (mesh, axis, specs) — cached
    ``NamedSharding`` objects and one batched ``device_put`` per batch
    (:func:`~dss_ml_at_scale_tpu.runtime.mesh.get_batch_placer`).
    """

    def __init__(
        self,
        source: Iterable,
        mesh: Mesh,
        *,
        axis: str = "data",
        depth: int = 2,
        specs=None,
        name: str = "feeder",
        wait_observer: Callable[[float], None] | None = None,
    ):
        super().__init__(
            source,
            get_batch_placer(mesh, axis=axis, specs=specs),
            depth=depth,
            name=name,
            wait_observer=wait_observer,
        )


class DeviceFeeder(Feeder):
    """Single-device feeder: plain async ``device_put`` staging."""

    def __init__(
        self,
        source: Iterable,
        *,
        depth: int = 2,
        name: str = "feeder",
        wait_observer: Callable[[float], None] | None = None,
    ):
        super().__init__(
            source, jax.device_put, depth=depth, name=name,
            wait_observer=wait_observer,
        )


def prefetch_to_mesh(
    it: Iterable,
    mesh: Mesh,
    *,
    axis: str = "data",
    depth: int = 2,
    specs=None,
) -> Iterator:
    """Yield batches placed on ``mesh`` (batch-sharded), ``depth`` ahead.

    Compatibility wrapper over :class:`MeshFeeder` — the sharding and
    enqueue now happen on a background feeder thread instead of the
    calling thread. Provenance-tagged batches are stripped (the side
    channel is dropped); callers that need it consume the feeder's
    ``(batch, provenance)`` pairs directly.
    """
    feeder = MeshFeeder(
        it, mesh, axis=axis, depth=depth, specs=specs, name="prefetch"
    )
    try:
        for batch, _prov in feeder:
            yield batch
    finally:
        feeder.close()


def prefetch_to_devices(it: Iterable, *, depth: int = 2) -> Iterator:
    """Single-device variant: feeder-threaded device_put pipelining."""
    feeder = DeviceFeeder(it, depth=depth, name="prefetch")
    try:
        for batch, _prov in feeder:
            yield batch
    finally:
        feeder.close()
