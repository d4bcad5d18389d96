"""Sharded streaming Parquet reader with a host decode pool.

Capability target: Petastorm's reader as the reference drives it
(``deep_learning/2.distributed-data-loading-petastorm.py:246-259``):

    make_batch_reader(parquet_files, transform_spec=..., cur_shard=rank,
                      shard_count=world, workers_count=2,
                      reader_pool_type="thread", results_queue_size=20,
                      num_epochs=None)

Semantics preserved:

- ``num_epochs=None`` streams forever; epoch boundaries are the *trainer's*
  job via steps-per-epoch accounting (the reference's central workaround
  for sharded readers of unequal length, prose ``:218-220``).
- ``workers_count`` decode workers feed a results queue bounded at
  ``results_queue_size`` row groups — backpressure bounds host RAM by
  workers × queue × rows-per-rowgroup × rowsize, the documented OOM
  formula (``:338``), exposed here as :meth:`ParquetShardReader.memory_estimate`.
- ``cur_shard``/``shard_count`` give disjoint epoch-reshuffled coverage
  (see :mod:`.sharding`).
- Reader lifecycle is context-managed; re-entering per epoch is allowed
  but unnecessary (the reference must rebuild its loader every epoch to
  dodge Petastorm reader-reuse errors, ``:261-280`` — this reader is
  re-iterable and a single instance serves the whole run).

TPU-first notes: output batches are fixed-shape numpy dicts, so the jitted
train step compiles once; partial trailing batches are dropped by default
(``drop_last``) rather than triggering a recompile.

A batch that spans row groups is copied into a host buffer the reader has
used before (:class:`_BufferRing`): a fresh array of a training batch's size
costs more in first-touch page faults than the copy itself. **A batch may be
overwritten once every reference to it is dropped**: a consumer that wants
to keep one keeps a reference to it (the array, the dict, or any view of
it), as it would to keep any object alive; a raw pointer is not a reference.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import queue
import sys
import threading
import time
from typing import Any, Callable, Iterator, NamedTuple, Sequence

import numpy as np
import pyarrow.parquet as pq

from ..resilience.faults import fault_fires, maybe_fail
from ..resilience.retry import RetryPolicy, call_with_retry
from ..resilience.rollback import (
    PROVENANCE_KEY,
    QuarantineList,
    compress_rows,
)
from .sharding import RowGroupUnit, list_row_groups, shard_units
from .transform import TransformSpec

log = logging.getLogger(__name__)

_SENTINEL = object()

# Transient-read retry shape: two quick retries cover an NFS/object-store
# blip without meaningfully delaying a genuinely failed epoch.
_READ_RETRY = RetryPolicy(max_retries=2, base_delay=0.05, max_delay=0.5)

# Most buffers a column's ring grows to. Reckoned from who holds a host
# batch at once behind ``Trainer.fit``: the feeder's queue of placed batches
# at its default depth (2; JAX may keep the host array for as long as the
# placed one lives), the one in the feeder thread's hands, the one under the
# trainer's step, the trainer's peek at the first batch (held for the whole
# fit), and the one being filled. A consumer that holds more gets fresh
# arrays beyond it, as before there was a ring.
_RING_BOUND = 6


class _ReaderTelemetry(NamedTuple):
    """The reader's spans and series, bound once a reader (the import of
    telemetry is lazy: see ``_telemetry_handles``)."""

    span: Callable
    queue_depth: Any
    stall_total: Any
    read_seconds: Any
    decode_seconds: Any
    rows_total: Any
    workers: Any
    batch_buffers: Any


class _WorkerError:
    """Wraps an exception raised in a decode worker for cross-thread rethrow."""

    def __init__(self, error: BaseException):
        self.error = error


# dsst: ignore[lock-discipline] no lock-guarded state: worker results cross threads only via the bounded results Queue and stop Event; _threads/_results are consumer-thread-only (a second concurrent iteration raises), per-worker file handles are thread-local
class ParquetShardReader:
    """Background-threaded, sharded, optionally-infinite batch reader."""

    def __init__(
        self,
        paths: Sequence[str],
        *,
        batch_size: int,
        cur_shard: int = 0,
        shard_count: int = 1,
        workers_count: int = 2,
        results_queue_size: int = 20,
        num_epochs: int | None = None,
        transform_spec: TransformSpec | None = None,
        columns: Sequence[str] | None = None,
        shuffle_row_groups: bool = True,
        seed: int = 0,
        reader_pool_type: str = "thread",
        drop_last: bool = True,
        quarantine: "QuarantineList | str | None" = None,
        emit_provenance: bool = False,
        on_corrupt: str = "raise",
    ):
        """``quarantine``: a poison-row blocklist (path or QuarantineList)
        consulted at every iteration start — blocklisted rows are dropped
        at load time, before decode, so a replay/resume never feeds them
        again. ``emit_provenance``: tag each batch with the RowRanges
        that built it (under ``_provenance``) so a training-health
        supervisor can quarantine the exact rows behind a bad step.
        ``on_corrupt="quarantine"``: a row whose decode/transform raises
        is isolated (per-row retry of the failed group), counted on
        ``corrupt_samples_total``, quarantined (when a list is
        configured), and skipped — instead of killing the reader thread;
        the default ``"raise"`` preserves fail-fast semantics."""
        if reader_pool_type not in ("thread", "dummy"):
            raise ValueError(
                f"reader_pool_type must be 'thread' or 'dummy' (inline), "
                f"got {reader_pool_type!r}"
            )
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if on_corrupt not in ("raise", "quarantine"):
            raise ValueError(
                f"on_corrupt must be 'raise' or 'quarantine', "
                f"got {on_corrupt!r}"
            )
        self._units = list_row_groups(list(paths))
        if len(self._units) < shard_count:
            raise ValueError(
                f"{len(self._units)} row groups cannot feed {shard_count} shards; "
                f"write the dataset with smaller row groups or fewer shards"
            )
        self.batch_size = batch_size
        self.cur_shard = cur_shard
        self.shard_count = shard_count
        self.workers_count = max(1, workers_count)
        self.results_queue_size = results_queue_size
        self.num_epochs = num_epochs
        self.transform_spec = transform_spec
        self.columns = list(columns) if columns is not None else None
        self.shuffle_row_groups = shuffle_row_groups
        self.seed = seed
        self.reader_pool_type = reader_pool_type
        self.drop_last = drop_last
        self.emit_provenance = emit_provenance
        self.on_corrupt = on_corrupt
        self.quarantine = (
            QuarantineList(quarantine)
            if isinstance(quarantine, (str, bytes)) or hasattr(
                quarantine, "__fspath__"
            )
            else quarantine
        )
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._results: queue.Queue | None = None
        # Bound on the instance so stop() still works when invoked from a
        # generator finalizer during interpreter shutdown (module globals
        # like `queue` may already be torn down by then).
        self._empty_exc = queue.Empty
        self._local = threading.local()
        self._ring = _BufferRing(batch_size, _RING_BOUND)

    # -- diagnostics ------------------------------------------------------

    @property
    def queue_occupancy(self) -> int:
        """Decoded row groups currently waiting in the results queue."""
        results = self._results
        return results.qsize() if results is not None else 0

    def _telemetry_handles(self):
        """Decode-pipeline gauge/counter children, bound ONCE per reader.

        The import stays lazy (telemetry pulls jax via its device
        module; jax-free paths — datagen subprocesses, pure Delta IO —
        must not touch the device runtime), but re-iterating the reader
        no longer pays a registry lookup per epoch, and the consumer
        loop's per-row-group cost is two pre-bound method calls.
        """
        handles = getattr(self, "_telemetry", None)
        if handles is None:
            from .. import telemetry

            stage = telemetry.counter(
                "reader_stage_seconds_total",
                "cumulative time inside one stage of loading a row "
                "group, summed over the threads that load",
                labels=("stage",),
            )
            handles = self._telemetry = _ReaderTelemetry(
                span=telemetry.span,
                queue_depth=telemetry.gauge(
                    "reader_queue_depth",
                    "decoded row groups waiting in the results queue at "
                    "last consumer read",
                ),
                stall_total=telemetry.counter(
                    "reader_stall_seconds_total",
                    "cumulative consumer wait on the decode queue",
                ),
                read_seconds=stage.labels(stage="read"),
                decode_seconds=stage.labels(stage="decode"),
                rows_total=telemetry.counter(
                    "reader_rows_total",
                    "rows read from row groups (before any are dropped "
                    "as quarantined or corrupt)",
                ),
                workers=telemetry.gauge(
                    "reader_workers",
                    "threads loading row groups for the iteration in "
                    "progress (1 for the inline pool)",
                ),
                batch_buffers=telemetry.counter(
                    "reader_batch_buffers_total",
                    "batches by the memory they were assembled into: a "
                    "buffer used before (recycled), a new allocation "
                    "(fresh), or none, the batch being a slice of one row "
                    "group (view)",
                    labels=("source",),
                ),
            )
        return handles

    def memory_estimate(self, row_size_bytes: int) -> int:
        """Worst-case host RAM of the decode pipeline, in bytes.

        The reference documents this as
        workers × queue × rows-per-rowgroup × rowsize (``2...py:338``);
        beside it stand the buffers batches are assembled into, at most
        ``_RING_BOUND`` of ``batch_size`` rows (a ring grows only as far
        as its consumer holds batches).
        """
        rows_per_group = max(u.num_rows for u in self._units)
        return (
            (self.workers_count + self.results_queue_size) * rows_per_group
            + _RING_BOUND * self.batch_size
        ) * row_size_bytes

    # -- work generation --------------------------------------------------

    def _unit_stream(self) -> Iterator[RowGroupUnit]:
        epochs = itertools.count() if self.num_epochs is None else range(self.num_epochs)
        for epoch in epochs:
            yield from shard_units(
                self._units,
                self.cur_shard,
                self.shard_count,
                epoch=epoch,
                shuffle=self.shuffle_row_groups,
                seed=self.seed,
            )

    def _load_unit(
        self, unit: RowGroupUnit
    ) -> tuple[dict[str, np.ndarray], np.ndarray]:
        """Load + transform one row group → ``(cols, orig_rows)``.

        ``orig_rows`` maps each surviving output row back to its
        original row index within the group — the provenance spine.
        Quarantined rows are dropped BEFORE decode (no cycles spent on
        known-poison bytes); under ``on_corrupt="quarantine"`` a failing
        transform is retried row-by-row to isolate, count, and
        quarantine exactly the corrupt samples.
        """
        # Fault-injection site: a transient failure here (or a real NFS
        # blip / truncated read below) is retried by the worker before it
        # gives up and fails the epoch — see _load_unit_with_retry.
        maybe_fail("reader.next")
        # One ParquetFile handle per (worker thread, path): footers parse
        # once per worker instead of once per row group, and handles are
        # never shared across threads (ParquetFile reads aren't
        # guaranteed thread-safe).
        tel = self._telemetry_handles()
        t_read = time.perf_counter()
        with tel.span("reader.read", rows=unit.num_rows):
            cache = self._local.__dict__.setdefault("files", {})
            pf = cache.get(unit.path)
            if pf is None:
                pf = cache[unit.path] = pq.ParquetFile(unit.path)
            table = pf.read_row_group(unit.row_group, columns=self.columns)
            cols = {
                name: _column_to_numpy(table.column(i))
                for i, name in enumerate(table.column_names)
            }
        tel.read_seconds.inc(time.perf_counter() - t_read)
        num_rows = len(next(iter(cols.values()))) if cols else 0
        tel.rows_total.inc(num_rows)
        orig_rows = np.arange(num_rows, dtype=np.int64)
        if self.quarantine is not None:
            mask = self.quarantine.keep_mask(
                unit.path, unit.row_group, num_rows
            )
            if mask is not None:
                cols = {k: v[mask] for k, v in cols.items()}
                orig_rows = orig_rows[mask]
        if fault_fires("sample.corrupt"):
            cols = _corrupt_first_sample(cols)
        if self.transform_spec is not None and len(orig_rows):
            t_decode = time.perf_counter()
            try:
                cols, orig_rows = self._transform(tel, unit, cols, orig_rows)
            finally:
                tel.decode_seconds.inc(time.perf_counter() - t_decode)
        return cols, orig_rows

    def _transform(
        self, tel: _ReaderTelemetry, unit: RowGroupUnit, cols, orig_rows
    ) -> tuple[dict[str, np.ndarray], np.ndarray]:
        """The decode stage of one row group: the transform over all
        its rows, or row by row where that is how corrupt ones are
        isolated."""
        try:
            with tel.span(
                "reader.decode", rows=len(orig_rows),
                backend=getattr(self.transform_spec, "backend", None),
            ):
                cols = self.transform_spec(cols)
        except Exception:
            if self.on_corrupt != "quarantine":
                raise
            return self._isolate_corrupt_rows(unit, cols, orig_rows)
        n_out = len(next(iter(cols.values()))) if cols else 0
        if n_out != len(orig_rows):
            if self.emit_provenance or self.quarantine is not None:
                # Row-level provenance (and therefore quarantine
                # exclusion) is only meaningful for row-preserving
                # transforms; a filtering transform would silently
                # misattribute rows.
                raise ValueError(
                    f"transform changed the row count "
                    f"({len(orig_rows)} -> {n_out}) in {unit.path}"
                    f"[rg={unit.row_group}]; provenance/quarantine "
                    "require a row-preserving transform"
                )
            orig_rows = np.arange(n_out, dtype=np.int64)
        return cols, orig_rows

    def _isolate_corrupt_rows(
        self, unit: RowGroupUnit, cols, orig_rows
    ) -> tuple[dict[str, np.ndarray], np.ndarray]:
        """Per-row transform of a failed group: good rows survive, each
        corrupt row is counted, quarantined, and dropped — the reader
        thread outlives isolated data corruption."""
        from .. import telemetry

        corrupt_counter = telemetry.counter(
            "corrupt_samples_total",
            "undecodable samples skipped (and quarantined) by the reader",
        )
        good: list[dict[str, np.ndarray]] = []
        good_rows: list[int] = []
        bad_rows: list[int] = []
        last_error = "?"
        for i in range(len(orig_rows)):
            row = {k: v[i:i + 1] for k, v in cols.items()}
            try:
                good.append(self.transform_spec(row))
                good_rows.append(int(orig_rows[i]))
            except Exception as e:
                bad_rows.append(int(orig_rows[i]))
                last_error = f"{type(e).__name__}: {e}"
        corrupt_counter.inc(len(bad_rows))
        log.warning(
            "reader: %d corrupt sample(s) in %s[rg=%d] skipped (last "
            "error: %s)", len(bad_rows), unit.path, unit.row_group,
            last_error,
        )
        if self.quarantine is not None and bad_rows:
            self.quarantine.add(
                compress_rows(unit.path, unit.row_group, bad_rows),
                reason=f"undecodable sample ({last_error})",
            )
        if not good:
            return {}, np.empty(0, np.int64)
        out = {
            k: np.concatenate([g[k] for g in good]) for k in good[0]
        }
        return out, np.asarray(good_rows, np.int64)

    def _load_unit_with_retry(
        self, unit: RowGroupUnit
    ) -> tuple[dict[str, np.ndarray], np.ndarray]:
        # A flaky filesystem read should cost a short backoff, not the
        # whole epoch; semantic decode errors (bad bytes, schema
        # mismatch) are deterministic and fail immediately.
        def evict_handle(attempt, exc, delay) -> None:
            # The cached ParquetFile holds an open fd + parsed footer; a
            # stale NFS handle or truncated read poisons it, and retrying
            # through the same handle would just replay the failure.
            # Close it too — dropping the reference alone leaks the fd
            # until GC.
            stale = self._local.__dict__.setdefault("files", {}).pop(
                unit.path, None
            )
            if stale is not None:
                try:
                    stale.close()
                except Exception as close_exc:
                    log.debug("closing evicted reader handle: %r", close_exc)

        return call_with_retry(
            self._load_unit, unit, policy=_READ_RETRY, site="reader.next",
            on_retry=evict_handle,
        )

    # -- thread pool ------------------------------------------------------

    def _worker(self, work: Iterator[RowGroupUnit], lock: threading.Lock, results: queue.Queue):
        def _put(item) -> None:
            while not self._stop.is_set():
                try:
                    results.put(item, timeout=0.1)
                    return
                except queue.Full:
                    continue

        try:
            while not self._stop.is_set():
                with lock:
                    unit = next(work, _SENTINEL)
                if unit is _SENTINEL:
                    break
                _put((self._load_unit_with_retry(unit), unit))
        except BaseException as e:  # propagate to the consumer, don't die silently
            _put(_WorkerError(e))
        finally:
            _put(_SENTINEL)

    def _row_groups(
        self,
    ) -> Iterator[tuple[dict[str, np.ndarray], np.ndarray]]:
        """Stream ``(cols, orig_rows)`` row groups, in arrival order."""
        tel = self._telemetry_handles()
        if self.reader_pool_type == "dummy":
            tel.workers.set(1)
            for unit in self._unit_stream():
                if self._stop.is_set():
                    return
                yield self._load_unit_with_retry(unit), unit
            return

        self._results = results = queue.Queue(maxsize=self.results_queue_size)
        work = self._unit_stream()
        lock = threading.Lock()
        # Decode-pipeline health gauges: queue depth says whether workers
        # keep ahead of the consumer; stall time is the consumer-side
        # cost when they don't (the "is training input-bound?" number).
        self._threads = [
            threading.Thread(
                target=self._worker, args=(work, lock, results), daemon=True,
                name=f"reader-worker-{i}",
            )
            for i in range(self.workers_count)
        ]
        for t in self._threads:
            t.start()
        live = len(self._threads)
        tel.workers.set(live)
        try:
            while live:
                wait_t0 = time.perf_counter()
                item = results.get()
                tel.stall_total.inc(time.perf_counter() - wait_t0)
                tel.queue_depth.set(results.qsize())
                if item is _SENTINEL:
                    live -= 1
                    tel.workers.set(live)
                    continue
                if isinstance(item, _WorkerError):
                    raise RuntimeError(
                        "reader worker failed while decoding"
                    ) from item.error
                yield item
        finally:
            # May run as a generator finalizer during interpreter shutdown,
            # where even stdlib module globals are torn down — nothing
            # raised here is actionable (workers are daemon threads).
            try:
                self.stop()
                tel.workers.set(0)
            # dsst: ignore[bare-except] generator finalizer at interpreter shutdown: nothing raised here is actionable
            except BaseException:
                pass

    # -- batch assembly ---------------------------------------------------

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        if self._threads and any(t.is_alive() for t in self._threads):
            raise RuntimeError(
                "reader is already being iterated; create a second reader "
                "for concurrent streams"
            )
        if self.quarantine is not None:
            # Replay/resume semantics: a fresh iteration always sees the
            # full blocklist, including rows quarantined by another
            # process since this reader was built.
            self.quarantine.refresh()
        self._stop.clear()
        # buf entries: (cols, unit_path, unit_row_group, orig_rows) —
        # provenance rides the buffer so _take can slice it with the rows.
        buf: list[tuple] = []
        buffered = 0
        for (group, orig_rows), unit in self._row_groups():
            if not group or len(orig_rows) == 0:
                continue  # fully quarantined / fully corrupt group
            buf.append((group, unit.path, unit.row_group, orig_rows))
            buffered += _num_rows(group)
            while buffered >= self.batch_size:
                batch, buf, buffered = self._assemble(buf, self.batch_size)
                yield batch
                # The ring takes a buffer again when nothing but the ring
                # refers to it: not this frame either.
                del batch
        if buffered and not self.drop_last:
            batch, _, _ = self._assemble(buf, buffered)
            yield batch

    def _assemble(self, buf, n):
        """One n-row batch off the buffered row groups: the serial copy
        on the consumer's thread (the feeder's, under ``reader.next``)."""
        need, groups = n, 0
        for group, *_ in buf:
            if need <= 0:
                break
            need -= _num_rows(group)
            groups += 1
        tel = self._telemetry_handles()
        with tel.span("reader.assemble", rows=n, groups=groups):
            batch, prov, rest, buffered, source = _take(buf, n, self._ring)
            tel.batch_buffers.labels(source=source).inc()
            return self._finish_batch(batch, prov), rest, buffered

    def _finish_batch(self, batch, prov) -> dict[str, np.ndarray]:
        if self.emit_provenance:
            batch[PROVENANCE_KEY] = [
                r
                for path, rg, rows in prov
                for r in compress_rows(path, rg, rows)
            ]
        return batch

    def stop(self) -> None:
        self._stop.set()
        # Drain so workers blocked on a full queue can observe the stop.
        if self._results is not None:
            try:
                while True:
                    self._results.get_nowait()
            except self._empty_exc:
                pass
        for t in self._threads:
            t.join(timeout=5)
        self._threads = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def _num_rows(group: dict[str, np.ndarray]) -> int:
    return len(next(iter(group.values())))


def _slot_refs(buffers: list, i: int) -> int:
    return sys.getrefcount(buffers[i])


# What ``_slot_refs`` reads of an object that only its list refers to,
# on this interpreter.
_SOLE_REFS = _slot_refs([object()], 0)


class _BufferRing:
    """Host buffers that multi-group batches are assembled into, a list
    a column, each ``[batch_size, ...]`` and taken again only when the
    ring alone refers to it.

    That is observed, not assumed: the owning array's reference count is
    back at the ring's own. A view a consumer keeps holds its base; JAX
    holds the host array it was given until the host-to-device copy is
    complete (on some paths and backends for as long as the device array
    lives). With every buffer still referenced and the
    bound reached, a batch gets a fresh array that the ring does not
    keep: never a wait, and nothing that anything can still see is
    written to.
    """

    def __init__(self, rows: int, bound: int):
        self._rows, self._bound = rows, bound
        self._buffers: dict[str, list[np.ndarray]] = {}

    def concatenate(
        self, name: str, parts: list[np.ndarray]
    ) -> tuple[np.ndarray, str | None]:
        """``np.concatenate(parts)`` and the memory it went into:
        ``"recycled"``, ``"fresh"``, or None for a column the ring does
        not hold (it holds the numeric ones whose parts share one dtype
        and row shape, the same from batch to batch)."""
        dtype, row_shape = parts[0].dtype, parts[0].shape[1:]
        buffers = self._buffers.get(name, ())
        if dtype.hasobject or any(
            (a.dtype, a.shape[1:]) != (dtype, row_shape)
            for a in (*parts, *buffers[:1])
        ):
            return np.concatenate(parts), None
        for i in range(len(buffers)):
            if _slot_refs(buffers, i) == _SOLE_REFS:
                out, source = buffers[i], "recycled"
                break
        else:
            if len(buffers) >= self._bound:
                return np.concatenate(parts), "fresh"
            out, source = np.empty((self._rows,) + row_shape, dtype), "fresh"
            self._buffers.setdefault(name, []).append(out)
        n = sum(len(p) for p in parts)
        if n < self._rows:
            out = out[:n]  # a short tail batch: a view, which holds its base
        return np.concatenate(parts, out=out), source


def _take(buf, n, ring: _BufferRing):
    """Split the buffered row groups into one n-row batch + remainder.

    Buffer entries are ``(cols, path, row_group, orig_rows)``; the
    returned ``prov`` mirrors the batch as ``(path, row_group,
    taken_rows)`` triples so provenance slices exactly with the data.
    A batch that lies inside one row group is a slice of it (``source``
    ``"view"``); one that spans groups is concatenated through ``ring``,
    ``"recycled"`` unless some column needed a ``"fresh"`` array.
    """
    taken: dict[str, list[np.ndarray]] = {}
    prov: list[tuple[str, int, np.ndarray]] = []
    need = n
    rest: list[tuple] = []
    for group, path, row_group, orig_rows in buf:
        if need == 0:
            rest.append((group, path, row_group, orig_rows))
            continue
        rows = _num_rows(group)
        use = min(rows, need)
        for k, v in group.items():
            taken.setdefault(k, []).append(v[:use])
        prov.append((path, row_group, orig_rows[:use]))
        if use < rows:
            rest.append((
                {k: v[use:] for k, v in group.items()},
                path, row_group, orig_rows[use:],
            ))
        need -= use
    batch, memories = {}, set()
    for k, v in taken.items():
        if len(v) == 1:
            batch[k] = v[0]
            continue
        batch[k], memory = ring.concatenate(k, v)
        memories.add(memory)
    source = next((m for m in ("fresh", "recycled") if m in memories), "view")
    return batch, prov, rest, sum(_num_rows(g) for g, *_ in rest), source


def _corrupt_first_sample(cols: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """``sample.corrupt`` fault: truncate the first byte-valued cell.

    Simulates a torn object-store read / bit-rotted record: downstream
    decode raises on the short payload, exercising the per-row
    isolation + quarantine path deterministically in tier-1. Datasets
    with no byte column get a NaN poke in the first float cell instead.
    """
    for k, v in cols.items():
        if v.dtype == object and len(v) and isinstance(
            v[0], (bytes, bytearray)
        ):
            v = v.copy()
            v[0] = bytes(v[0])[: max(1, len(v[0]) // 2)]
            return {**cols, k: v}
    for k, v in cols.items():
        if np.issubdtype(v.dtype, np.floating) and len(v):
            v = v.copy()
            v[0] = np.nan
            return {**cols, k: v}
    log.warning("sample.corrupt fired but no corruptible column found")
    return cols


def _column_to_numpy(col) -> np.ndarray:
    """Arrow column → numpy; binary/string columns become object arrays."""
    import pyarrow as pa

    combined = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
    if pa.types.is_binary(combined.type) or pa.types.is_large_binary(combined.type):
        return np.array(combined.to_pylist(), dtype=object)
    if pa.types.is_string(combined.type) or pa.types.is_large_string(combined.type):
        return np.array(combined.to_pylist(), dtype=object)
    return combined.to_numpy(zero_copy_only=False)


def make_batch_reader(paths_or_table, **kwargs) -> ParquetShardReader:
    """Factory accepting a file list, a dataset dir, or a DeltaTable.

    Mirrors petastorm's ``make_batch_reader`` entry point; a Delta table
    path resolves through the Delta log (the reference resolves file lists
    with deltalake-rs for exactly this call, ``2...py:99-112,246``).
    """
    from .delta import DeltaTable

    if isinstance(paths_or_table, DeltaTable):
        paths = paths_or_table.file_uris()
    elif isinstance(paths_or_table, (list, tuple)):
        paths = list(paths_or_table)
    else:
        from pathlib import Path

        p = Path(paths_or_table)
        if (p / "_delta_log").is_dir():
            paths = DeltaTable(p).file_uris()
        elif p.is_dir():
            paths = sorted(str(q) for q in p.glob("**/*.parquet"))
        elif p.is_file():
            paths = [str(p)]
        else:
            raise FileNotFoundError(f"no such dataset: {p}")
        if not paths:
            raise FileNotFoundError(f"no parquet files under {p}")
    return ParquetShardReader(paths, **kwargs)


@contextlib.contextmanager
def batch_loader(paths_or_table, **kwargs):
    """Context-managed reader (the create_dataloader_context analogue,
    reference ``2...py:246-259``) guaranteeing worker teardown."""
    reader = make_batch_reader(paths_or_table, **kwargs)
    try:
        yield reader
    finally:
        reader.stop()
