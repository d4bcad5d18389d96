"""Continuous-batching decode engine: token serving over slot arenas.

Generalizes the image-serving scheduler (one compiled shape, batch
ACROSS requests) to an always-running decode loop: requests are
admitted INTO an in-flight batch. One engine thread alternates

    admit waiting requests into free slots
        (bucket-padded prefill, compiled once per bucket;
         aliased scatter into the slot arena; first token = TTFT)
    dispatch one ``slot_decode`` step over ALL slots
        (every active request advances one token per step; the
         program picks each slot's greedy token on the device)
    collect the step dispatched one turn earlier
        (its ``[slots]`` ids; stream, note, retire while the device
         runs the step just dispatched)
    per-slot retirement
        (EOS / max-token / deadline / cancel — the slot frees and the
         batch keeps running; nothing stops, nothing recompiles)

One decode step is kept in flight: step n+1 takes its tokens from step
n's ids, which never visit the host, so it is dispatched before step n
is collected and the host's turn runs under the device's. A request
that samples on the host (``temperature > 0``) needs its logits row
before its next token is known; while one is active the same loop
collects each step in the turn that dispatched it (depth 0).

The HTTP layer talks to the engine through :meth:`LMEngine.submit`,
which returns a :class:`Generation` whose event queue streams tokens
to the response writer. Admission, deadline, and drain semantics are
the image tier's, reused verbatim: a full queue raises
:class:`~..admission.QueueFull` (429 + Retry-After), a draining engine
raises :class:`~..admission.NotAccepting` (503), and drain = stop
admitting, finish every in-flight slot.

A decoder whose ``chunk`` is set prefills a prompt in chunks of that
many tokens: the engine admits in arrival order, and while any slot
decodes it launches one chunk of the oldest admission a turn, just
before the turn's decode step, so no stream's next token waits for more
than one chunk; with no slot decoding the chunks run back to back. No
launch waits for the device: the turn after a prompt's last chunk went
out (its decode step already queued behind it) scatters the prompt into
its slot and takes the first token from that chunk's logits row.

Two decoder backends satisfy the same protocol
(``prefill``/``dispatch``/``fetch``/``warmup`` + ``slots``/
``vocab_size``/``waited_s``, the seconds it has been blocked until the
device finished a step or a prefill, and ``chunk`` with
``prefill_chunk``/``finish_prefill``): :class:`TransformerDecoder` runs
the real audited programs; :class:`StubLMDecoder` is the test double:
no model, deterministic streams, and a per-STEP cost that does not
depend on how many slots are active.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import queue
import threading
import time

import numpy as np

from ... import telemetry
from ...ops.flash_attention import block_plan, blocks as flash_blocks
from ..admission import AdmissionController, DeadlineExceeded, NotAccepting
from . import kvcache


# Seconds between two readings of a thread's CPU clock, by the engine
# thread and by each streaming handler thread: ``time.thread_time``
# costs 5.8 us on the v5e's host and advances there in 10 ms ticks, so a
# reading every pass or every line would cost more than it tells.
CPU_READ_EVERY_S = 0.25


class CpuClock:
    """The CPU time of the thread that made it, read at most every
    ``CPU_READ_EVERY_S``. Made and ticked on one thread."""

    def __init__(self) -> None:
        self._at = time.perf_counter()
        self._cpu = time.thread_time()

    def tick(self, now: float, force: bool = False) -> float | None:
        """The thread's CPU seconds since the last reading, ``now`` being
        a ``perf_counter`` mark; None before ``CPU_READ_EVERY_S`` have
        passed since that reading, unless ``force``."""
        if not force and now - self._at < CPU_READ_EVERY_S:
            return None
        cpu = time.thread_time()
        spent, self._at, self._cpu = cpu - self._cpu, now, cpu
        return spent


class PromptTooLong(ValueError):
    """Request exceeds the preallocated KV capacity (HTTP 400).

    The guard the tentpole issue demands: an oversized budget must be
    REJECTED before a slot is touched — never allowed to scatter past
    the arena (the same cap ``models.transformer.generate`` now derives
    from its cache shape).
    """


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """Engine knobs — ``dsst serve-lm`` flags map 1:1."""

    slots: int = 8
    max_len: int = 128
    prefill_buckets: tuple = (16, 32, 64)
    queue_depth: int = 32
    deadline_ms: float = 0.0  # admit -> last token; 0 disables
    inter_token_budget_ms: float = 0.0  # arms inter_token_p99 when > 0
    drain_timeout_s: float = 10.0

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        buckets = tuple(sorted(set(int(b) for b in self.prefill_buckets)))
        if not buckets:
            raise ValueError("at least one prefill bucket is required")
        if buckets[0] < 1 or buckets[-1] > self.max_len:
            raise ValueError(
                f"prefill buckets {buckets} must lie in [1, max_len="
                f"{self.max_len}]"
            )
        object.__setattr__(self, "prefill_buckets", buckets)


class Generation:
    """One streamed request: engine-side state + client-side queue.

    The engine thread owns the decode state (``n_past``: the cache
    position its next dispatched step writes; ``last_token`` and
    ``emitted``: what has been streamed); the HTTP thread only reads the
    event queue and may set
    ``cancelled`` (a latch, safe without the engine lock). Events are
    ``("token", token, index)`` then exactly one terminal
    ``("done", reason)`` or ``("error", exc)`` — :meth:`settle_once` is
    the latch that keeps the terminal exactly-once even when engine
    retirement and drain's leftovers sweep race to settle the same
    generation.
    """

    _guarded_by_lock = ("_settled",)
    _lock_name = "_lock"

    def __init__(self, gen_id, prompt, max_new_tokens, *, temperature,
                 top_k, eos_id, seed, trace_id, deadline):
        self.gen_id = gen_id
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        self.trace_id = trace_id
        self.deadline = deadline  # monotonic, or None
        self.queue: queue.Queue = queue.Queue()
        self.cancelled = False
        self.reason: str | None = None
        self._lock = threading.Lock()
        self._settled = False
        self.t_admit = time.monotonic()
        self.t_first: float | None = None
        self.t_last: float | None = None
        # Engine-thread-only decode state.
        self.n_past = 0
        self.last_token = 0
        self.emitted = 0
        self._rng = np.random.default_rng(seed)

    @property
    def greedy(self) -> bool:
        """The next token is the argmax of the logits, which a decode
        step picks on the device; any other request samples on the host
        from its logits row with its own generator."""
        return self.temperature <= 0.0

    def sample(self, logits_row: np.ndarray) -> int:
        if self.greedy:
            return int(np.argmax(logits_row))
        scaled = logits_row.astype(np.float64) / self.temperature
        if self.top_k is not None:
            kth = np.sort(scaled)[-self.top_k]
            scaled = np.where(scaled < kth, -np.inf, scaled)
        scaled -= scaled.max()
        p = np.exp(scaled)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    def settle_once(self) -> bool:
        """Claim the right to emit THE terminal event (first caller
        wins). Engine retirement and drain's leftovers sweep can race
        to settle the same generation; exactly one of them may emit the
        terminal and release the admission ticket."""
        with self._lock:
            if self._settled:
                return False
            self._settled = True
            return True

    def is_settled(self) -> bool:
        with self._lock:
            return self._settled

    def next_event(self, timeout: float | None = None):
        """Block for the next stream event (raises ``queue.Empty``)."""
        return self.queue.get(timeout=timeout)

    def cancel(self) -> None:
        """Client went away: retire the slot at the next step."""
        self.cancelled = True


def _epoch_offset() -> float:
    """What to add to a ``perf_counter`` mark to put it on the span log's
    clock (epoch seconds): one reading of both clocks."""
    return time.time() - time.perf_counter()


def _record_dispatched(t0: float, t1: float) -> None:
    """A decoder's dispatch, call to return, as a complete record. A
    with-span would cost a begin event and a ``TraceAnnotation`` for an
    interval of microseconds; the enclosing ``lm.step`` span is the one
    a flight recorder sees open."""
    # dsst: ignore[span-discipline] up to three records a decode step beside the lm.step span (docstring)
    telemetry.get_span_log().record(
        "lm.dispatch", _epoch_offset() + t0, t1 - t0
    )


def _record_fetched(t0: float, t1: float, t2: float) -> None:
    """A decoder's fetch as two complete records: call to the step done
    on the device, to its ids (and logits) on the host."""
    log, wall = telemetry.get_span_log(), _epoch_offset()
    # dsst: ignore[span-discipline] as lm.dispatch above
    log.record("lm.wait", wall + t0, t1 - t0)
    # dsst: ignore[span-discipline] as lm.dispatch above
    log.record("lm.fetch", wall + t1, t2 - t1)


def chunk_rows(n: int, chunk: int) -> int:
    """The rows of the whole chunks that hold ``n`` positions."""
    return -(-n // chunk) * chunk


def cache_bytes_by_kind(model, arena) -> dict:
    """Bytes of ``arena`` by the kind of state a slot holds: the model's
    own split where it keeps several kinds, else all of it under its one
    ``cache_kind``."""
    import jax

    split = getattr(model, "cache_bytes_by_kind", None)
    if split is not None:
        return split(arena)
    return {model.cache_kind: sum(
        a.nbytes for a in jax.tree_util.tree_leaves(arena))}


class TransformerDecoder:
    """The real backend: audited slot-decode/prefill/scatter programs.

    One compiled ``slot_decode`` for the life of the server (the arena
    is donated through every call — aliased, never copied), one
    ``prefill_bucket`` executable per configured bucket length, and a
    donated ``write_slot`` scatter per admission. ``warmup()`` compiles
    all of them before the server reports ready.

    The decoder is written against what a served model gives
    (:mod:`.kvcache`): its cache for ``slots`` x ``max_len``, a prefill
    of one padded prompt into a one-slot cache, a decode of all slots at
    once with a per-slot ``pos``. It knows no layout of the cache.

    The decoder holds its own tree, each leaf at the width the model
    multiplies it in (the model's ``serving_variables``: cast once here,
    not inside every program), and no reference to the wider originals:
    a caller that drops its ``variables`` frees them.

    A model whose programs return ``stats`` beside the ids (an expert
    layer's routing counts, ``[held, absent, touched, per held
    expert...]``) has them fetched with the ids and counted here, under
    ``lm_moe_*``.

    A model that prefills a chunk at a start position
    (``prefill_chunk``) is prefilled in chunks of the smallest bucket
    (``chunk``), one ``prefill_bucket`` program a start from 0 to the
    largest bucket, in place of one a bucket.
    """

    def __init__(self, model, variables, *, slots, max_len, buckets):
        import jax
        import jax.numpy as jnp

        self._jax, self._jnp = jax, jnp
        self.model = model
        self.variables = model.serving_variables(variables)
        held: dict[str, int] = {}
        for leaf in jax.tree_util.tree_leaves(self.variables):
            name = str(leaf.dtype)
            held[name] = held.get(name, 0) + leaf.nbytes
        weights_bytes = telemetry.gauge(
            "lm_weights_bytes",
            "bytes of the decoder's own weights, by the dtype held",
            labels=("dtype",),
        )
        for name, nbytes in held.items():
            weights_bytes.labels(dtype=name).set(nbytes)
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.buckets = tuple(sorted(buckets))
        self.vocab_size = model.vocab_size
        self.waited_s = 0.0
        self.chunk = (self.buckets[0] if hasattr(model, "prefill_chunk")
                      else None)
        if self.chunk is not None and chunk_rows(
                self.buckets[-1], self.chunk) > self.max_len:
            raise ValueError(
                f"chunks of {self.chunk} cover the largest bucket "
                f"{self.buckets[-1]} in "
                f"{chunk_rows(self.buckets[-1], self.chunk)} rows, more "
                f"than max_len {self.max_len}")
        # The stats of a prompt's chunks and its last chunk's logits, on
        # the device until finish_prefill fetches them.
        self._chunk_stats: list = []
        self._chunk_logits = None
        self._arena = kvcache.make_arena(model, self.slots, self.max_len)
        cache_bytes = telemetry.gauge(
            "lm_cache_bytes",
            "bytes of the slot arena, by the kind of state a slot holds",
            labels=("kind",),
        )
        for kind, nbytes in cache_bytes_by_kind(model, self._arena).items():
            cache_bytes.labels(kind=kind).set(nbytes)
        # How far a length-aware decode engages: the rows a step's
        # attention fetches a layer against the arena's, for a model that
        # says what it fetches (``decode_rows_read``).
        self._decode_rows_read = getattr(model, "decode_rows_read", None)
        cache_rows = telemetry.counter(
            "lm_decode_cache_rows_total",
            "cache rows a layer over the decode steps dispatched: read by "
            "the step's attention, and held by the arena",
            labels=("kind",),
        )
        self._cache_rows_read = cache_rows.labels(kind="read")
        self._cache_rows_arena = cache_rows.labels(kind="arena")
        # The last dispatched step's greedy ids, on the device: the next
        # step's tokens wherever the host does not override them.
        self._ids = jnp.zeros(self.slots, jnp.int32)
        # ONE prefill scratch cache, recycled: the returned (donated-in)
        # buffers become the next call's input. Stale rows past the real
        # prompt are never attended and are overwritten before the
        # position pointer reaches them, so no re-zeroing is needed; a
        # recurrent state is never read from it (the model's prefill
        # starts it from zero).
        self._scratch = kvcache.make_arena(model, 1, self.max_len)
        self._step_fn = jax.jit(
            kvcache.slot_decode, static_argnums=0, donate_argnums=(3,)
        )
        self._prefill_fn = jax.jit(
            kvcache.prefill_bucket, static_argnums=0,
            static_argnames=("start",), donate_argnums=(3,)
        )
        self._write_fn = jax.jit(kvcache.write_slot, donate_argnums=(0,))
        assignments = telemetry.counter(
            "lm_moe_assignments_total",
            "routed token-expert pairs of active slots and real prompt "
            "tokens: to an expert held here, or to an absent one",
            labels=("where",),
        )
        self._moe_held = assignments.labels(where="held")
        self._moe_absent = assignments.labels(where="absent")
        self._moe_expert = telemetry.counter(
            "lm_moe_expert_assignments_total",
            "routed token-expert pairs by held expert, summed over layers",
            labels=("expert",),
        )
        self._moe_touched = telemetry.counter(
            "lm_moe_experts_touched_total",
            "held experts that got at least one token, per step and "
            "summed over layers",
            labels=("program",),
        )

    def _count(self, stats, program: str) -> None:
        """Feed the expert layer's counters from one program's stats."""
        held, absent, touched = (int(n) for n in stats[:3])
        self._moe_held.inc(held)
        self._moe_absent.inc(absent)
        self._moe_touched.labels(program=program).inc(touched)
        for expert, n in enumerate(stats[3:]):
            if n:
                self._moe_expert.labels(expert=str(expert)).inc(int(n))

    def warmup(self) -> None:
        """Compile every production shape before serving traffic."""
        if self.chunk is None:
            for bucket in self.buckets:
                self.prefill(np.zeros((1, bucket), np.int32), 1, 0)
        else:
            for start in range(0, self.buckets[-1], self.chunk):
                self.prefill_chunk(np.zeros((1, self.chunk), np.int32),
                                   start, start + 1)
                self.finish_prefill(0)
        # Twice: the second step takes the first's ids, as every step
        # after an engine's first does.
        zeros = np.zeros(self.slots, np.int32)
        for _ in range(2):
            self.fetch(self.dispatch(zeros, zeros), logits=True)

    def prefill(self, tokens: np.ndarray, n_real: int, slot: int):
        """Prefill one bucket-padded prompt and scatter it into ``slot``.

        Returns the logits row of the last REAL prompt position (host
        numpy) — what the first sampled token comes from.
        """
        jnp = self._jnp
        logits, stats, cache = self._prefill_fn(
            self.model, self.variables,
            jnp.asarray(tokens, jnp.int32), self._scratch,
            np.int32(n_real),
        )
        self._arena = self._write_fn(self._arena, cache, jnp.int32(slot))
        self._scratch = cache
        # A model gives every row of the bucket or the last real one.
        row = logits[0] if logits.ndim == 2 else logits[0, n_real - 1]
        t0 = time.perf_counter()
        row.block_until_ready()
        self.waited_s += time.perf_counter() - t0
        row, stats = self._jax.device_get((row, stats))
        if stats is not None:
            self._count(stats, "prefill")
        return np.asarray(row, np.float32)

    def prefill_chunk(self, tokens: np.ndarray, start: int,
                      n_real: int) -> None:
        """Launch the chunk ``tokens`` ``[1, chunk]`` of a prompt of
        ``n_real`` tokens at position ``start`` into the scratch cache,
        which holds the prompt's chunks before it, and return at once:
        nothing waits for the device, and the chunk's stats (and, from
        the chunk that holds the last real token, its logits row) stay
        there until :meth:`finish_prefill`."""
        jnp = self._jnp
        if start == 0:
            self._chunk_stats = []
        logits, stats, self._scratch = self._prefill_fn(
            self.model, self.variables,
            jnp.asarray(tokens, jnp.int32), self._scratch,
            np.int32(n_real), start=start,
        )
        if stats is not None:
            self._chunk_stats.append(stats)
        self._chunk_logits = logits

    def finish_prefill(self, slot: int):
        """After a prompt's last chunk: scatter the scratch cache into
        ``slot`` and return the last real position's logits row (host
        numpy), its stats and those of the chunks before it fetched with
        it. Call it before the next prompt's first chunk, which reuses
        the scratch."""
        jnp = self._jnp
        self._arena = self._write_fn(self._arena, self._scratch,
                                     jnp.int32(slot))
        t0 = time.perf_counter()
        self._chunk_logits.block_until_ready()
        self.waited_s += time.perf_counter() - t0
        logits, stats = self._jax.device_get(
            (self._chunk_logits, self._chunk_stats))
        self._chunk_logits, self._chunk_stats = None, []
        for chunk_stats in stats:
            self._count(chunk_stats, "prefill")
        return np.asarray(logits[0], np.float32)

    def dispatch(self, override: np.ndarray, pos: np.ndarray):
        """Launch one ``slot_decode`` over every slot and return at once.

        A slot's token is ``override`` where that is not negative and
        the previous dispatched step's greedy id otherwise, merged on
        the device. Returns the step, for :meth:`fetch`. Recorded as
        ``lm.dispatch``: two small host-to-device copies and the jitted
        call returning."""
        t0 = time.perf_counter()
        logits, ids, stats, self._arena = self._step_fn(
            self.model, self.variables, self._ids, self._arena,
            np.asarray(pos, np.int32), np.asarray(override, np.int32),
        )
        self._ids = ids
        _record_dispatched(t0, time.perf_counter())
        if self._decode_rows_read is not None:
            self._cache_rows_read.inc(
                self._decode_rows_read(pos, self._arena))
            self._cache_rows_arena.inc(self.slots * self.max_len)
        return ids, logits, stats

    def fetch(self, step, *, logits: bool = False):
        """Wait for a dispatched step; its ids ``[slots]`` on the host
        and, where asked, its logits ``[slots, vocab]`` (else None).
        Recorded as ``lm.wait`` (until the device has finished the
        step) and ``lm.fetch`` (the copies to the host)."""
        ids, rows, stats = step
        t0 = time.perf_counter()
        ids.block_until_ready()
        t1 = time.perf_counter()
        self.waited_s += t1 - t0
        # The stats ride in the copy that fetches the ids.
        ids, stats = self._jax.device_get((ids, stats))
        out = ids, (np.asarray(rows, np.float32) if logits else None)
        _record_fetched(t0, t1, time.perf_counter())
        if stats is not None:
            self._count(stats, "decode")
        return out


class StubLMDecoder:
    """Model-free test double: fixed per-STEP cost.

    The next token is a pure function of (last token, position), so
    streams are deterministic. A step costs ``step_ms`` on a timeline
    of the stub's own, one step after another from the moment each is
    dispatched and no matter how many slots are active, as a device
    queue would run them; ``fetch`` sleeps until its step's end and a
    prefill sleeps ``prefill_ms``, both counted as waits on the device.
    With ``chunk`` set the stub prefills in chunks, as a model with
    ``prefill_chunk`` does: a chunk takes ``prefill_ms`` on the same
    timeline as the steps, and ``finish_prefill`` sleeps until the last
    one's end. What a test reads off it is structure (which requests
    shared a step, what was dispatched before what was fetched), never
    a speed. Logits are one-hot at the id.
    """

    def __init__(self, *, vocab_size=256, step_ms=2.0, prefill_ms=None,
                 slots=8, max_len=128, buckets=(16,), chunk=None):
        self.vocab_size = int(vocab_size)
        self.step_ms = float(step_ms)
        self.prefill_ms = float(
            step_ms if prefill_ms is None else prefill_ms
        )
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.buckets = tuple(buckets)
        self.chunk = chunk
        self._chunk_row = None  # (logits row, end on the timeline)
        self.waited_s = 0.0
        self._ids = np.zeros(self.slots, np.int32)
        self._busy_until = 0.0

    def _next(self, tok: int, pos: int) -> int:
        return (int(tok) * 1103515245 + int(pos) * 12345 + 7) % self.vocab_size

    def warmup(self) -> None:
        pass

    def prefill(self, tokens: np.ndarray, n_real: int, slot: int):
        t0 = time.perf_counter()
        time.sleep(self.prefill_ms / 1000.0)
        self.waited_s += time.perf_counter() - t0
        row = np.zeros(self.vocab_size, np.float32)
        row[self._next(tokens[0, n_real - 1], n_real - 1)] = 1.0
        return row

    def prefill_chunk(self, tokens: np.ndarray, start: int,
                      n_real: int) -> None:
        self._busy_until = (
            max(time.perf_counter(), self._busy_until)
            + self.prefill_ms / 1000.0
        )
        last = n_real - 1 - start
        if last < tokens.shape[1]:
            row = np.zeros(self.vocab_size, np.float32)
            row[self._next(tokens[0, last], n_real - 1)] = 1.0
            self._chunk_row = row, self._busy_until

    def finish_prefill(self, slot: int):
        (row, ready_at), self._chunk_row = self._chunk_row, None
        t0 = time.perf_counter()
        time.sleep(max(0.0, ready_at - t0))
        self.waited_s += time.perf_counter() - t0
        return row

    def dispatch(self, override: np.ndarray, pos: np.ndarray):
        t0 = time.perf_counter()
        tokens = np.where(override >= 0, override, self._ids)
        self._ids = np.array(
            [self._next(t, p) for t, p in zip(tokens, pos)], np.int32
        )
        self._busy_until = (
            max(t0, self._busy_until) + self.step_ms / 1000.0
        )
        _record_dispatched(t0, time.perf_counter())
        return self._ids, self._busy_until

    def fetch(self, step, *, logits: bool = False):
        ids, ready_at = step
        t0 = time.perf_counter()
        time.sleep(max(0.0, ready_at - t0))
        t1 = time.perf_counter()
        self.waited_s += t1 - t0
        rows = None
        if logits:
            rows = np.zeros((self.slots, self.vocab_size), np.float32)
            rows[np.arange(self.slots), ids] = 1.0
        _record_fetched(t0, t1, time.perf_counter())
        return ids, rows


@dataclasses.dataclass
class _Step:
    """One dispatched decode step the engine has not collected yet."""

    handle: object  # the decoder's own, for its fetch()
    active: dict  # slot -> the Generation whose token this step computes
    logits: bool  # some generation in it samples from its logits row


@dataclasses.dataclass
class _Prefill:
    """An admission whose prompt is prefilled a chunk at a time: it holds
    its slot, and the decoder's scratch cache from its first chunk to its
    last."""

    gen: Generation
    slot: int
    padded: np.ndarray  # [1, whole chunks] the prompt, zero-padded
    start: int = 0  # the next chunk's first position
    t0: float = 0.0  # perf_counter at the first chunk's launch
    queued_s: float = 0.0  # that launch less the request's submit


class LMEngine:
    """The always-running decode loop + admission front door."""

    # Lint/sanitize contract: HTTP threads submit and drain; the engine
    # thread admits, steps, and retires — the shared scheduling state
    # below only moves under _cond.
    _guarded_by_lock = ("_waiting", "_active", "_admitting", "_accepting",
                        "_stopped")
    _lock_name = "_cond"

    def __init__(self, decoder, config: LMConfig | None = None):
        self.cfg = config or LMConfig()
        self.decoder = decoder
        if getattr(decoder, "max_len", self.cfg.max_len) < self.cfg.max_len:
            raise ValueError(
                f"decoder max_len {decoder.max_len} < config max_len "
                f"{self.cfg.max_len}"
            )
        if decoder.slots < self.cfg.slots:
            raise ValueError(
                f"decoder has {decoder.slots} slots, config wants "
                f"{self.cfg.slots}"
            )
        self._alloc = kvcache.SlotAllocator(self.cfg.slots)
        self._cond = threading.Condition()
        self._waiting: list[Generation] = []
        self._active: dict[int, Generation] = {}
        # Generations pulled off _waiting but not yet in _active (their
        # prefill is running): drain must see this in-transit window or
        # it can declare the engine empty mid-admission and truncate a
        # stream it promised to finish — and its leftovers sweep must
        # settle them if the engine thread wedges, so the actual
        # Generations are tracked, not just a count.
        self._admitting: list[Generation] = []
        self._accepting = True
        self._stopped = False
        self._gen_seq = 0
        self._thread: threading.Thread | None = None
        # Engine-thread-only: the decode step dispatched and not yet
        # collected (its ids still on the device), or None.
        self._in_flight: _Step | None = None
        # Engine-thread-only, for a decoder that prefills in chunks (its
        # ``chunk``): the admissions whose prompts are being prefilled,
        # oldest first.
        self._chunk = getattr(decoder, "chunk", None)
        self._prefills: collections.deque[_Prefill] = collections.deque()
        self._slo = telemetry.slo.get_engine()
        self._admission = AdmissionController(
            self.cfg.queue_depth,
            on_depth=lambda n: self._depth_gauge.set(n),
        )
        self._depth_gauge = telemetry.gauge(
            "lm_queue_depth", "LM generations admitted and not yet retired"
        )
        self._tokens_total = telemetry.counter(
            "lm_tokens_total", "tokens streamed by the LM engine"
        )
        self._slots_gauge = telemetry.gauge(
            "lm_slots_active", "KV arena slots currently decoding"
        )
        self._retired = telemetry.counter(
            "lm_retired_total",
            "generations retired, by reason",
            labels=("reason",),
        )
        prefill_tokens = telemetry.counter(
            "lm_prefill_tokens_total",
            "prompt tokens prefilled: real, and padded to the bucket",
            labels=("kind",),
        )
        decode_steps = telemetry.counter(
            "lm_decode_steps_total",
            "decode steps dispatched: ahead of the step before them "
            "being collected, or in lock-step with it",
            labels=("mode",),
        )
        self._steps_ahead = decode_steps.labels(mode="ahead")
        self._steps_lockstep = decode_steps.labels(mode="lockstep")
        engine_seconds = telemetry.counter(
            "lm_engine_seconds_total",
            "the engine thread's time by state: its own CPU, blocked on "
            "the device, idle with no work, and stalled (ready, not running)",
            labels=("state",),
        )
        self._seconds = {
            state: engine_seconds.labels(state=state)
            for state in ("cpu", "device", "idle", "stalled")
        }
        self._prefill_real = prefill_tokens.labels(kind="real")
        self._prefill_padded = prefill_tokens.labels(kind="padded")
        launches = telemetry.counter(
            "lm_prefill_launches_total",
            "prefill programs launched: a whole prompt, or one chunk of one",
            labels=("kind",),
        )
        self._launches = {kind: launches.labels(kind=kind)
                          for kind in ("whole", "chunk")}
        # How far the flash kernel's causal block schedule engages: the
        # block pairs of one head-layer's call a launch (every layer and
        # head of the call runs the same plan), for a model whose prefill
        # attention is that kernel; its plan by (queries, keys): a
        # bucket's square, or a chunk's rows over its prefix and itself.
        model = getattr(decoder, "model", None)
        buckets = self.cfg.prefill_buckets
        shapes = ([(b, b) for b in buckets] if self._chunk is None else
                  [(self._chunk, start + self._chunk)
                   for start in range(0, buckets[-1], self._chunk)])
        self._attention_plans = {
            (sq, sk): block_plan(sq, sk, *flash_blocks(sq, sk), causal=True)
            for sq, sk in shapes
        } if getattr(model, "attention", None) == "flash" else {}
        attention_blocks = telemetry.counter(
            "lm_prefill_attention_blocks_total",
            "flash-attention block pairs of one head-layer a prefill "
            "launch: the full rectangle, those visited, and those masked",
            labels=("kind",),
        )
        self._attention_blocks = {
            kind: attention_blocks.labels(kind=kind)
            for kind in ("grid", "visited", "masked")
        }
        self._ttft_window = telemetry.window(
            "lm_ttft_window_seconds",
            "live windowed time-to-first-token (admit -> first chunk)",
        )
        self._inter_window = telemetry.window(
            "lm_inter_token_window_seconds",
            "live windowed gap between streamed tokens",
        )

    # -- front door (HTTP threads) ------------------------------------

    def submit(self, prompt, max_new_tokens: int, *, temperature=0.0,
               top_k=None, eos_id=None, seed=0, trace_id=None) -> Generation:
        """Admit one generation (or raise the HTTP-mapped refusal).

        Raises :class:`PromptTooLong` (400) when the request cannot fit
        the preallocated capacity, ``ValueError`` (400) for sampling
        params the engine thread could not survive (non-finite
        temperature, out-of-range top_k — json accepts NaN, so the door
        must not), ``QueueFull`` (429) at the admission bound,
        ``NotAccepting`` (503) while draining.
        """
        prompt = [int(t) for t in prompt]
        n_new = int(max_new_tokens)
        if not prompt:
            raise ValueError("prompt must contain at least one token")
        if n_new < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        vocab = self.decoder.vocab_size
        if any(t < 0 or t >= vocab for t in prompt):
            raise ValueError(f"prompt tokens must lie in [0, {vocab})")
        # Sampling-state validation: everything Generation.sample and
        # default_rng consume is checked HERE, before the admission
        # ticket — a bad value past this point would blow up inside the
        # shared engine thread (or leak a ticket), not in this request.
        temperature = float(temperature)
        if not math.isfinite(temperature):
            raise ValueError(f"temperature must be finite, got {temperature}")
        if top_k is not None:
            top_k = int(top_k)
            if not 1 <= top_k <= vocab:
                raise ValueError(
                    f"top_k must lie in [1, vocab_size={vocab}], "
                    f"got {top_k}"
                )
        seed = int(seed)
        if seed < 0:
            raise ValueError(f"seed must be >= 0, got {seed}")
        buckets = self.cfg.prefill_buckets
        if len(prompt) > buckets[-1]:
            raise PromptTooLong(
                f"prompt length {len(prompt)} exceeds the largest prefill "
                f"bucket {buckets[-1]}"
            )
        if len(prompt) + n_new > self.cfg.max_len:
            raise PromptTooLong(
                f"prompt + max_new_tokens = {len(prompt) + n_new} > "
                f"max_len {self.cfg.max_len} (preallocated KV slot capacity)"
            )
        deadline = None
        if self.cfg.deadline_ms > 0:
            deadline = time.monotonic() + self.cfg.deadline_ms / 1000.0
        with self._cond:
            if not self._accepting:
                raise NotAccepting("LM engine is draining")
            self._admission.admit(1)
            self._gen_seq += 1
            gen = Generation(
                self._gen_seq, prompt, n_new, temperature=temperature,
                top_k=top_k, eos_id=eos_id, seed=seed, trace_id=trace_id,
                deadline=deadline,
            )
            self._waiting.append(gen)
            self._cond.notify_all()
        return gen

    @property
    def pending(self) -> int:
        """Generations admitted and not yet retired (for drain prints)."""
        return self._admission.pending

    def start(self) -> "LMEngine":
        """Arm SLO targets, warm the decoder, start the decode thread."""
        if self.cfg.deadline_ms > 0:
            # TTFT must beat the full-request deadline; arming turns the
            # informational quantile objective into a judged one.
            self._slo.set_target("ttft_p99", self.cfg.deadline_ms / 1000.0)
        if self.cfg.inter_token_budget_ms > 0:
            self._slo.set_target(
                "inter_token_p99", self.cfg.inter_token_budget_ms / 1000.0
            )
        self.decoder.warmup()
        self._thread = threading.Thread(
            target=self._loop, name="lm-decode", daemon=True
        )
        self._thread.start()
        return self

    def drain(self, timeout_s: float | None = None) -> bool:
        """Stop admitting, finish in-flight slots, stop the loop.

        Returns True when everything retired within the budget; on
        timeout the loop is stopped anyway and survivors are settled
        with a ``("done", "drain")`` event so no client hangs forever.
        """
        budget = self.cfg.drain_timeout_s if timeout_s is None else timeout_s
        deadline = time.monotonic() + max(0.0, budget)
        with self._cond:
            self._accepting = False
            self._cond.notify_all()
            while (
                (self._waiting or self._active or self._admitting)
                and not self._stopped
            ):
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._cond.wait(min(left, 0.1))
            clean = (
                not self._waiting and not self._active
                and not self._admitting
            )
            self._stopped = True
            self._cond.notify_all()
        thread = self._thread
        alive = False
        if thread is not None:
            thread.join(5.0)
            alive = thread.is_alive()
        # Settle anything the budget abandoned — including generations
        # caught in the in-transit admission window (neither waiting
        # nor active while their prefill runs). The join may have timed
        # out with the thread wedged inside a slow decoder call; the
        # settle-once latch makes this sweep safe to race against a
        # thread that later comes back and retires the same slots.
        with self._cond:
            leftovers = (
                list(self._waiting) + list(self._active.values())
                + list(self._admitting)
            )
            self._waiting.clear()
            self._active.clear()
            self._admitting.clear()
        for gen in leftovers:
            self._settle(gen, "drain")
        return clean and not alive

    # -- engine thread ------------------------------------------------

    def _loop(self) -> None:
        try:
            self._run()
        except Exception as exc:
            # Nothing may escape the engine thread: an unguarded raise
            # here used to kill the loop silently — every in-flight
            # stream stalled and every later request hung until its
            # event timeout. Fail CLOSED instead: refuse new work (503)
            # and settle every owned generation with an error event.
            self._halt(exc)

    def _halt(self, exc: Exception) -> None:
        with self._cond:
            self._accepting = False
            self._stopped = True
            leftovers = (
                list(self._waiting) + list(self._active.values())
                + list(self._admitting)
            )
            self._waiting.clear()
            self._active.clear()
            self._admitting.clear()
            self._cond.notify_all()
        for gen in leftovers:
            self._settle(gen, "error", error=exc)

    def _run(self) -> None:
        """Pass after pass until stopped. The passes' wall time goes to
        ``lm_engine_seconds_total`` at the end of the first pass
        ``CPU_READ_EVERY_S`` after the last time it did, and of the
        last: ``device`` (blocked until a decode step or a prefill
        finished), ``idle`` (waiting with nothing to do), ``cpu`` (the
        thread's own CPU time, at most the time outside those waits) and
        ``stalled`` (the rest: ready and not running, as while other
        threads hold the interpreter lock)."""
        cpu_s, device_s, idle_s, stalled_s = (
            self._seconds[state]
            for state in ("cpu", "device", "idle", "stalled"))
        decoder = self.decoder
        clock = CpuClock()
        going, idle = True, 0.0
        t0, dev0 = time.perf_counter(), decoder.waited_s
        while going:
            going, waited = self._pass()
            idle += waited
            t1 = time.perf_counter()
            cpu = clock.tick(t1, force=not going)
            if cpu is None:
                continue
            dev1 = decoder.waited_s
            busy, device = t1 - t0 - idle, dev1 - dev0
            # Capped at the time outside the waits, so that the four add
            # up to the wall time; CPU used inside a wait (a
            # block_until_ready, the stub's sleep) counts as cpu wherever
            # the cap does not bind.
            cpu = max(0.0, min(cpu, busy - device))
            cpu_s.inc(cpu)
            device_s.inc(device)
            idle_s.inc(idle)
            stalled_s.inc(max(0.0, busy - device - cpu))
            t0, dev0, idle = t1, dev1, 0.0

    def _pass(self) -> tuple:
        """One admission scan, its admissions and one turn of the decode
        loop: (whether to go on, seconds idle)."""
        admitted, expired, cancelled = [], [], []
        idle = 0.0
        with self._cond:
            while (
                not self._stopped
                and not self._waiting
                and not self._active
                and self._in_flight is None
                and not self._prefills
            ):
                t_wait = time.perf_counter()
                self._cond.wait(0.05)
                idle += time.perf_counter() - t_wait
            if self._stopped:
                return False, idle
            t_admit = time.perf_counter()
            scanned = len(self._waiting)
            now = time.monotonic()
            still_waiting = []
            for gen in self._waiting:
                if gen.cancelled:
                    cancelled.append(gen)
                    continue
                if gen.deadline is not None and now > gen.deadline:
                    expired.append(gen)
                    continue
                slot = self._alloc.alloc()
                if slot is None:
                    still_waiting.append(gen)
                else:
                    admitted.append((gen, slot))
            self._waiting[:] = still_waiting
            self._admitting.extend(gen for gen, _ in admitted)
        for gen in cancelled:
            self._settle(gen, "cancelled")
        for gen in expired:
            self._settle(
                gen, "deadline",
                error=DeadlineExceeded(
                    "deadline passed before a slot freed"
                ),
            )
        if scanned:
            # dsst: ignore[span-discipline] recorded only when the scan had something to look at, with counts known at its end
            telemetry.get_span_log().record(
                "lm.admit", _epoch_offset() + t_admit,
                time.perf_counter() - t_admit,
                admitted=len(admitted), waiting=len(still_waiting),
            )
        if self._chunk is None:
            for gen, slot in admitted:
                try:
                    self._admit_into_slot(gen, slot)
                except Exception as exc:
                    self._admission_failed(gen, slot, exc)
            self._admitted([gen for gen, _ in admitted])
        else:
            for gen, slot in admitted:
                padded = np.zeros(
                    (1, chunk_rows(len(gen.prompt), self._chunk)), np.int32)
                padded[0, : len(gen.prompt)] = gen.prompt
                self._prefills.append(_Prefill(gen, slot, padded))
            self._prefill_chunks()
        self._step_once()
        return True, idle

    def _admission_failed(self, gen: Generation, slot: int,
                          exc: Exception) -> None:
        """A poisoned generation (sampling state the door's validation
        could not foresee) retires ITSELF, not the shared loop: free its
        slot, settle it with an error event, keep serving everyone
        else."""
        with self._cond:
            self._active.pop(slot, None)
            self._slots_gauge.set(len(self._active))
        if not gen.is_settled():
            self._alloc.free(slot)
            self._settle(gen, "error", error=exc)

    def _admitted(self, gens: list) -> None:
        """``gens`` are out of the in-transit window: active or settled."""
        if gens:
            with self._cond:
                for gen in gens:
                    if gen in self._admitting:
                        self._admitting.remove(gen)
                self._cond.notify_all()

    def _count_blocks(self, sq: int, sk: int) -> None:
        plan = self._attention_plans.get((sq, sk))
        if plan is not None:
            self._attention_blocks["grid"].inc(plan.grid)
            self._attention_blocks["visited"].inc(plan.visited)
            self._attention_blocks["masked"].inc(plan.masked)

    def _admit_into_slot(self, gen: Generation, slot: int) -> None:
        """Bucketed prefill + scatter + first token (TTFT)."""
        prompt = gen.prompt
        bucket = next(
            b for b in self.cfg.prefill_buckets if b >= len(prompt)
        )
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(prompt)] = prompt
        with telemetry.span("lm.prefill", bucket=bucket,
                            prompt_tokens=len(prompt),
                            queued_s=time.monotonic() - gen.t_admit):
            row = self.decoder.prefill(padded, len(prompt), slot)
        self._launches["whole"].inc()
        self._prefill_real.inc(len(prompt))
        self._prefill_padded.inc(bucket)
        self._count_blocks(bucket, bucket)
        self._first_token(gen, slot, row)

    def _prefill_chunks(self) -> None:
        """This turn's prefill work, oldest admission first: the landing
        of a prompt whose last chunk went out a turn ago, and one chunk
        launch ahead of the turn's decode step while any slot decodes;
        launches and landings back to back while none does."""
        launched = False
        while self._prefills:
            if launched:
                with self._cond:
                    if self._active:
                        return
            p = self._prefills[0]
            if p.start < p.padded.shape[1]:
                self._launch_chunk(p)
                launched = True
            else:
                self._land(p)

    def _launch_chunk(self, p: _Prefill) -> None:
        gen, start, c = p.gen, p.start, self._chunk
        if start == 0:
            p.t0, p.queued_s = time.perf_counter(), (
                time.monotonic() - gen.t_admit)
        try:
            with telemetry.span("lm.prefill_chunk", start=start, rows=c):
                self.decoder.prefill_chunk(p.padded[:, start:start + c],
                                           start, len(gen.prompt))
        except Exception as exc:
            self._prefills.popleft()
            self._admission_failed(gen, p.slot, exc)
            self._admitted([gen])
            return
        self._launches["chunk"].inc()
        self._prefill_padded.inc(c)
        self._count_blocks(c, start + c)
        p.start += c

    def _land(self, p: _Prefill) -> None:
        """A prompt whose chunks have all gone out: into its slot, and
        its first token. ``lm.prefill`` covers the admission, from its
        first chunk's launch to here."""
        self._prefills.popleft()
        gen = p.gen
        try:
            row = self.decoder.finish_prefill(p.slot)
            # dsst: ignore[span-discipline] an admission's chunks run across several turns of the loop, between other spans; it is recorded at its first token
            telemetry.get_span_log().record(
                "lm.prefill", _epoch_offset() + p.t0,
                time.perf_counter() - p.t0, bucket=p.start,
                prompt_tokens=len(gen.prompt), queued_s=p.queued_s,
            )
            self._prefill_real.inc(len(gen.prompt))
            self._first_token(gen, p.slot, row)
        except Exception as exc:
            self._admission_failed(gen, p.slot, exc)
        self._admitted([gen])

    def _first_token(self, gen: Generation, slot: int, row) -> None:
        """The first token from the prefill's last logits row (TTFT);
        then the slot decodes, or retires at once."""
        gen.n_past = len(gen.prompt)
        token = gen.sample(row)
        now = time.monotonic()
        ttft = now - gen.t_admit
        gen.t_first = gen.t_last = now
        self._emit(gen, token)
        self._ttft_window.observe(ttft, gen.trace_id)
        self._slo.note_ttft(ttft, trace_id=gen.trace_id)
        if self._should_retire(gen, token):
            self._retire_slot(slot, gen)
            return
        with self._cond:
            self._active[slot] = gen
            self._slots_gauge.set(len(self._active))

    def _step_once(self) -> None:
        """One turn of the decode loop: dispatch the next step, collect
        the one dispatched a turn earlier.

        Step n+1 reads its tokens from step n's ids on the device, so
        it goes out before step n is collected, and the collecting
        (streaming, windows, SLO notes, retirement) runs under it. That
        holds while every active generation is greedy. One that samples
        on the host is not stepped past: its turn collects the step it
        dispatched (depth 0 of the same loop), and a step already in
        flight when it was admitted is collected first.
        """
        with self._cond:
            active = dict(self._active)
        due = self._in_flight

        def in_flight(slot, gen) -> bool:
            return due is not None and due.active.get(slot) is gen

        lockstep = not all(gen.greedy for gen in active.values())
        stepping = {}
        if due is None or not lockstep:
            # max_new_tokens is known now: a slot whose last token the
            # step in flight computes is not stepped again. EOS, a
            # cancel and a deadline are learned at collection; such a
            # slot's row of the step after is computed and dropped.
            stepping = {
                slot: gen for slot, gen in active.items()
                if gen.emitted + in_flight(slot, gen) < gen.max_new_tokens
            }
        if not stepping:
            self._in_flight = None
            if due is not None:
                # The last step of a run, or one a sampling request
                # caught in flight: nothing goes out with it, so no
                # lm.step span is open around its lm.wait and lm.fetch.
                self._collect(due, self.decoder.fetch(
                    due.handle, logits=due.logits))
            return
        # Sized to the DECODER's arena, not cfg.slots: both backends
        # iterate/vmap over decoder.slots, and the constructor allows a
        # decoder with more slots than the config admits. An idle slot
        # decodes token 0 at position 0 into a row nobody reads.
        override = np.zeros(self.decoder.slots, np.int32)
        pos = np.zeros(self.decoder.slots, np.int32)
        for slot, gen in stepping.items():
            # The token the step in flight computes for this generation
            # stays on the device; any other the host streamed itself.
            override[slot] = -1 if in_flight(slot, gen) else gen.last_token
            pos[slot] = gen.n_past
            gen.n_past += 1
        with telemetry.span("lm.step", active=len(stepping),
                            context_tokens=int(pos.sum())):
            step = _Step(self.decoder.dispatch(override, pos), stepping,
                         logits=lockstep)
            (self._steps_lockstep if due is None else self._steps_ahead).inc()
            if due is None and lockstep:
                due = step          # depth 0: collected in its own turn
            self._in_flight = None if due is step else step
            if due is not None:
                fetched = self.decoder.fetch(due.handle, logits=due.logits)
        if due is not None:
            self._collect(due, fetched)

    def _collect(self, step: _Step, fetched) -> None:
        """Stream, note and retire for one decode step whose ids (and
        logits, for a generation that samples) are on the host."""
        ids, logits = fetched
        t_sample = time.perf_counter()
        now = time.monotonic()
        with self._cond:
            live = dict(self._active)
        retired = 0
        for slot in sorted(step.active):
            gen = step.active[slot]
            if live.get(slot) is not gen:
                # Retired (EOS, cancel, deadline, error) while this step
                # was in flight: its row was computed for nobody.
                continue
            if gen.cancelled:
                self._retire_slot(slot, gen, reason="cancelled")
                retired += 1
                continue
            if gen.deadline is not None and now > gen.deadline:
                self._retire_slot(slot, gen, reason="deadline")
                retired += 1
                continue
            try:
                token = (
                    int(ids[slot]) if gen.greedy
                    else gen.sample(logits[slot])
                )
            except Exception as exc:
                # Per-generation blast radius: a sample() failure
                # retires this slot with an error event; the step loop
                # and every other stream keep running.
                self._retire_slot(slot, gen, reason="error", error=exc)
                retired += 1
                continue
            gap = now - (gen.t_last if gen.t_last is not None else now)
            gen.t_last = now
            self._emit(gen, token)
            self._inter_window.observe(gap, gen.trace_id)
            self._slo.note_inter_token(gap, trace_id=gen.trace_id)
            if self._should_retire(gen, token):
                self._retire_slot(slot, gen)
                retired += 1
        # dsst: ignore[span-discipline] the count of retired slots is known only at close; the loop is host-only work with no device call to be cut short in
        telemetry.get_span_log().record(
            "lm.sample", _epoch_offset() + t_sample,
            time.perf_counter() - t_sample,
            active=len(step.active), retired=retired,
        )

    def _emit(self, gen: Generation, token: int) -> None:
        gen.last_token = token
        if gen.is_settled():
            # Drain's sweep already emitted the terminal event while
            # this thread was wedged: no tokens after a terminal.
            return
        gen.queue.put(("token", token, gen.emitted))
        gen.emitted += 1
        self._tokens_total.inc()

    def _should_retire(self, gen: Generation, token: int) -> bool:
        if gen.eos_id is not None and token == gen.eos_id:
            gen.reason = "eos"
            return True
        if gen.emitted >= gen.max_new_tokens:
            gen.reason = "max_tokens"
            return True
        return False

    def _retire_slot(self, slot: int, gen: Generation,
                     reason: str | None = None,
                     error: Exception | None = None) -> None:
        with self._cond:
            self._active.pop(slot, None)
            self._slots_gauge.set(len(self._active))
            self._cond.notify_all()
        self._alloc.free(slot)
        wall = time.monotonic() - gen.t_admit
        # Seconds-per-generation normalized by slot count: the cost one
        # admission adds to the shared step loop, feeding Retry-After.
        self._admission.note_service_rate(wall / max(1, self.cfg.slots))
        self._settle(gen, reason or gen.reason or "done", error=error)

    def _settle(self, gen: Generation, reason: str,
                error: Exception | None = None) -> None:
        """Terminal event + admission release, exactly once.

        Engine retirement, the drain sweep, and the halt path can race
        to settle the same generation; the per-generation latch makes
        every settlement after the first a no-op, so a client sees ONE
        terminal and the pending count can never go negative.
        """
        if not gen.settle_once():
            return
        if gen.reason is None:
            gen.reason = reason
        if error is not None:
            gen.queue.put(("error", error))
        else:
            gen.queue.put(("done", gen.reason))
        self._retired.labels(reason=gen.reason).inc()
        self._admission.release(1)
