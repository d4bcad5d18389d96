"""LM token serving (serving/lm/, `dsst serve-lm`).

The continuous-batching contract, layer by layer:

- slot arena: alloc/free/reuse churn, double-free refusal;
- engine semantics over the stub decoder: deterministic streams under
  churn, capacity AND sampling-param refusals BEFORE a slot is touched
  (a bad top_k/NaN temperature must 400 at the door, never reach the
  shared engine thread), a poisoned generation settles with an error
  event instead of killing the loop, settlement is exactly-once even
  when drain races retirement, deadline retirement (both the in-slot
  and the never-slotted flavors), drain = finish in-flight then
  refuse;
- one decode step in flight: greedy streams are token for token the
  lock-step loop's (a request that samples on the host, parked in a
  slot, holds the same engine at depth 0), a sampled request's stream
  is the one its seed gave before, dispatch n+1 goes out before step n
  is fetched, an EOS / cancel / deadline / halt / drain that catches a
  step in flight settles once and streams nothing after the terminal,
  and the device's ids are ``np.argmax`` of the logits, ties included;
- numerics: a churned engine over the real TransformerDecoder streams
  bitwise the same tokens as solo decoding and as
  ``models.transformer.generate`` — continuous batching is a
  scheduling change, not a numerics change;
- HTTP: the streamed done-line's trace id matches the access-log row
  (the cross-process observability hop), oversized requests are 400,
  the handler threads' CPU is counted a line, and no span covers a
  whole request;
- chaos: a SIGKILLed `dsst serve-lm` replica leaves no torn tracking
  state and `dsst runs doctor` classifies it INTERRUPTED.
"""

import http.client
import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from dss_ml_at_scale_tpu.serving.admission import (
    DeadlineExceeded,
    NotAccepting,
)
from dss_ml_at_scale_tpu import telemetry
from dss_ml_at_scale_tpu.serving.lm import (
    LMConfig,
    LMEngine,
    PromptTooLong,
    SlotAllocator,
    StubLMDecoder,
)


def _collect(gen, timeout=30.0):
    """Drain one generation's event stream: (tokens, terminal_event)."""
    tokens = []
    while True:
        event = gen.next_event(timeout=timeout)
        if event[0] == "token":
            tokens.append(event[1])
        else:
            return tokens, event


def _stub_expected(decoder, prompt, n_tokens):
    """The stub's closed-form greedy stream for ``prompt``."""
    out = []
    tok, pos = prompt[-1], len(prompt) - 1
    for _ in range(n_tokens):
        tok = decoder._next(tok, pos)
        out.append(tok)
        pos += 1
    return out


# -- slot arena ------------------------------------------------------------


def test_slot_allocator_churn():
    alloc = SlotAllocator(3)
    assert [alloc.alloc() for _ in range(3)] == [0, 1, 2]
    assert alloc.alloc() is None
    alloc.free(1)
    assert alloc.n_free == 1 and alloc.n_used == 2
    # Freed slot is reused, lowest-first.
    assert alloc.alloc() == 1
    alloc.free(0)
    alloc.free(2)
    with pytest.raises(ValueError):
        alloc.free(2)  # double free
    with pytest.raises(ValueError):
        alloc.free(7)  # never allocated


# -- engine over the stub decoder ------------------------------------------


@pytest.fixture
def stub_engine():
    cfg = LMConfig(slots=3, max_len=48, prefill_buckets=(8, 16),
                   queue_depth=16)
    engine = LMEngine(
        StubLMDecoder(vocab_size=97, step_ms=1.0, slots=3, max_len=48,
                      buckets=(8, 16)),
        cfg,
    ).start()
    yield engine
    engine.drain(5.0)


def _decode_steps(mode):
    """``lm_decode_steps_total{mode}`` as the registry holds it now."""
    for metric in telemetry.snapshot()["metrics"]:
        if (metric["name"] == "lm_decode_steps_total"
                and metric["labels"] == {"mode": mode}):
            return metric["value"]
    return 0.0


def _park_sampler(engine, n_tokens):
    """A request that samples on the host, admitted and streaming: while
    it holds its slot the engine collects every step in the turn that
    dispatched it (depth 0)."""
    gen = engine.submit([1], n_tokens, temperature=1.0, seed=5)
    assert gen.next_event(timeout=30.0)[0] == "token"
    return gen


DEPTHS = ["ahead", "lockstep"]


@pytest.mark.parametrize("depth", DEPTHS)
def test_streams_deterministic_under_slot_churn(depth):
    """8 generations over 3 slots: every stream matches the stub's
    closed form even though slots free and refill mid-flight, with one
    decode step in flight and, a sampling request parked in a fourth
    slot, without."""
    telemetry.reset()
    slots = 3 + (depth == "lockstep")
    engine = LMEngine(
        StubLMDecoder(vocab_size=97, step_ms=1.0, slots=slots, max_len=512,
                      buckets=(8, 16)),
        LMConfig(slots=slots, max_len=512, prefill_buckets=(8, 16),
                 queue_depth=16),
    ).start()
    try:
        parked = (_park_sampler(engine, 500) if depth == "lockstep"
                  else None)
        prompts = [[(3 * i + j) % 97 for j in range(2 + i % 7)]
                   for i in range(8)]
        gens = [engine.submit(p, 6, seed=i) for i, p in enumerate(prompts)]
        for prompt, gen in zip(prompts, gens):
            tokens, terminal = _collect(gen)
            assert terminal == ("done", "max_tokens")
            assert tokens == _stub_expected(engine.decoder, prompt, 6)
        if parked is None:
            # Only a step with nothing in flight before it (the first,
            # and one after the engine ran empty) is not ahead.
            assert _decode_steps("ahead") > _decode_steps("lockstep") >= 1
        else:
            assert not parked.is_settled()
            assert _decode_steps("ahead") == 0
            assert _decode_steps("lockstep") >= 5
            parked.cancel()
            assert _collect(parked)[1] == ("done", "cancelled")
        # Every slot returned to the arena.
        assert engine._alloc.n_used == 0
        assert engine.pending == 0
    finally:
        engine.drain(5.0)


def test_eos_retires_early(stub_engine):
    prompt = [5, 9]
    expected = _stub_expected(stub_engine.decoder, prompt, 8)
    eos = expected[3]
    gen = stub_engine.submit(prompt, 8, eos_id=eos)
    tokens, terminal = _collect(gen)
    assert terminal == ("done", "eos")
    assert tokens == expected[:4]  # eos token itself is streamed
    with pytest.raises(queue.Empty):   # and nothing after the terminal
        gen.next_event(timeout=0.1)


class _RecordingStub(StubLMDecoder):
    """The stub, remembering the order of its calls: ``("dispatch",
    override, pos)`` and ``("fetch", k)`` for the k-th dispatched step
    (from 0)."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.calls: list = []
        self._dispatched = 0

    def dispatch(self, override, pos):
        self.calls.append(("dispatch", override.copy(), pos.copy()))
        ids, ready_at = super().dispatch(override, pos)
        self._dispatched += 1
        return ids, ready_at, self._dispatched - 1

    def fetch(self, step, *, logits=False):
        self.calls.append(("fetch", step[2]))
        return super().fetch(step[:2], logits=logits)


def test_step_n_plus_1_is_dispatched_before_step_n_is_fetched():
    """Two greedy requests of 5 and 3 tokens over two slots, waiting
    when the loop starts. The prefill gives each its first token, so the
    long one needs 4 decode steps and the short one 2: the short one's
    slot is left out of the third dispatch (``max_new_tokens`` is known
    when a step goes out), and every step but the first goes out before
    the one before it is fetched."""
    decoder = _RecordingStub(vocab_size=97, step_ms=1.0, slots=2,
                             max_len=48, buckets=(8,))
    engine = LMEngine(decoder, LMConfig(slots=2, max_len=48,
                                        prefill_buckets=(8,)))
    long_prompt, short_prompt = [5, 9], [7, 3, 4]
    long = engine.submit(long_prompt, 5)
    short = engine.submit(short_prompt, 3)
    engine.start()
    try:
        assert _collect(long) == (
            _stub_expected(decoder, long_prompt, 5), ("done", "max_tokens"))
        assert _collect(short) == (
            _stub_expected(decoder, short_prompt, 3), ("done", "max_tokens"))
    finally:
        engine.drain(5.0)
    order = [c[0] if c[0] == "dispatch" else c for c in decoder.calls]
    assert order == [
        "dispatch",                      # step 0: nothing in flight
        "dispatch", ("fetch", 0),
        "dispatch", ("fetch", 1),
        "dispatch", ("fetch", 2),
        ("fetch", 3),                    # the last: nothing to dispatch
    ]
    dispatches = [c for c in decoder.calls if c[0] == "dispatch"]
    # slot 0 the long request, slot 1 the short one (lowest slot first)
    first_tokens = [_stub_expected(decoder, p, 1)[0]
                    for p in (long_prompt, short_prompt)]
    assert dispatches[0][1].tolist() == first_tokens   # the host's tokens
    assert dispatches[0][2].tolist() == [2, 3]
    assert dispatches[1][1].tolist() == [-1, -1]       # the device's ids
    assert dispatches[1][2].tolist() == [3, 4]
    # the short request's third token is step 1's: it is not stepped again
    assert dispatches[2][1].tolist() == [-1, 0]
    assert dispatches[2][2].tolist() == [4, 0]
    assert dispatches[3][2].tolist() == [5, 0]


class _ChunkStub(StubLMDecoder):
    """A stub that prefills in chunks of 8, recording its launches,
    landings, dispatches and fetches in the order the engine made them,
    and what each launch and landing waited on the device."""

    def __init__(self, **kw):
        super().__init__(chunk=8, **kw)
        self.calls = []

    def prefill_chunk(self, tokens, start, n_real):
        waited = self.waited_s
        super().prefill_chunk(tokens, start, n_real)
        self.calls.append(("chunk", n_real, start, self.waited_s - waited))

    def finish_prefill(self, slot):
        waited = self.waited_s
        row = super().finish_prefill(slot)
        self.calls.append(("finish", slot, self.waited_s - waited))
        return row

    def dispatch(self, override, pos):
        self.calls.append(("dispatch",))
        return super().dispatch(override, pos)

    def fetch(self, step, *, logits=False):
        self.calls.append(("fetch",))
        return super().fetch(step, logits=logits)


def series(name, **labels):
    for metric in telemetry.snapshot()["metrics"]:
        if metric["name"] == name and metric["labels"] == labels:
            return metric["value"]
    return None


def _chunk_engine(**kw):
    decoder = _ChunkStub(vocab_size=97, step_ms=1.0, prefill_ms=2.0,
                         slots=2, max_len=64, buckets=(8, 32), **kw)
    engine = LMEngine(decoder, LMConfig(slots=2, max_len=64,
                                        prefill_buckets=(8, 32)))
    return decoder, engine


def _launches(calls, n_real):
    """Each of one prompt's chunk launches: (its place among the calls,
    its start)."""
    return [(i, c[2]) for i, c in enumerate(calls)
            if c[0] == "chunk" and c[1] == n_real]


def test_while_a_slot_decodes_a_turn_carries_one_chunk():
    """A prompt of 30 tokens, four chunks of 8, admitted while another
    request streams: between two of its chunks the engine dispatches a
    decode step, so no stream waits for more than one chunk; the streams
    are the stub's, as one whole prefill gives them."""
    telemetry.reset()
    decoder, engine = _chunk_engine()
    engine.start()
    try:
        streaming_prompt = [1, 2, 3]
        streaming = engine.submit(streaming_prompt, 40)
        assert streaming.next_event(timeout=30.0)[0] == "token"
        doc_prompt = [(7 * i) % 97 for i in range(30)]
        doc = engine.submit(doc_prompt, 3)
        assert _collect(doc) == (_stub_expected(decoder, doc_prompt, 3),
                                 ("done", "max_tokens"))
        assert _collect(streaming) == (
            _stub_expected(decoder, streaming_prompt, 40)[1:],
            ("done", "max_tokens"))
    finally:
        engine.drain(5.0)
    launched = _launches(decoder.calls, 30)
    assert [start for _, start in launched] == [0, 8, 16, 24]
    for (a, _), (b, _) in zip(launched, launched[1:]):
        between = [c[0] for c in decoder.calls[a + 1:b]]
        assert between.count("dispatch") == 1, between
    # it lands the turn after its last chunk, behind that turn's step
    last = launched[-1][0]
    landing = next(i for i, c in enumerate(decoder.calls)
                   if i > last and c[0] == "finish")
    assert [c[0] for c in decoder.calls[last + 1:landing]].count(
        "dispatch") == 1
    # the admission's one lm.prefill and its four lm.prefill_chunk spans
    events = telemetry.get_span_log().events()
    prefill = [e["args"] for e in events if e["name"] == "lm.prefill"
               and e["args"]["prompt_tokens"] == 30]
    assert prefill == [{"bucket": 32, "prompt_tokens": 30,
                        "queued_s": prefill[0]["queued_s"]}]
    chunks = [e["args"] for e in events if e["name"] == "lm.prefill_chunk"]
    assert {"start": 24, "rows": 8} in chunks
    assert series("lm_prefill_launches_total", kind="chunk") == 1 + 4
    assert series("lm_prefill_launches_total", kind="whole") == 0
    assert series("lm_prefill_tokens_total", kind="padded") == 8 * (1 + 4)
    assert series("lm_prefill_tokens_total", kind="real") == 3 + 30


def test_with_every_slot_empty_the_chunks_run_back_to_back():
    """Two prompts waiting when the loop starts, with no slot decoding:
    the first one's chunks go out one after another, before any decode
    step; once its slot decodes, the second one's ride one a turn."""
    decoder, engine = _chunk_engine()
    first, second = [5] * 20, [6] * 17
    gens = [engine.submit(first, 4), engine.submit(second, 4)]
    engine.start()
    try:
        for gen, prompt in zip(gens, (first, second)):
            assert _collect(gen) == (_stub_expected(decoder, prompt, 4),
                                     ("done", "max_tokens"))
    finally:
        engine.drain(5.0)
    assert [c[:3] for c in decoder.calls[:3]] == [
        ("chunk", 20, 0), ("chunk", 20, 8), ("chunk", 20, 16)]
    launched = _launches(decoder.calls, 17)
    assert [start for _, start in launched] == [0, 8, 16]
    for (a, _), (b, _) in zip(launched, launched[1:]):
        assert [c[0] for c in decoder.calls[a + 1:b]].count("dispatch") == 1


def test_a_chunk_launch_waits_for_nothing():
    """No chunk's launch waits on the device, the last one's included
    (the decoder's ``waited_s`` does not move); the landing waits for
    the last chunk's logits row. With no slot decoding it comes at once,
    so it waits out all four chunks, 2 ms each."""
    decoder, engine = _chunk_engine()
    prompt = [9] * 25
    gen = engine.submit(prompt, 2)
    engine.start()
    try:
        assert _collect(gen)[1] == ("done", "max_tokens")
    finally:
        engine.drain(5.0)
    waits = [c[3] for c in decoder.calls if c[0] == "chunk"]
    assert waits == [0.0, 0.0, 0.0, 0.0]
    landings = [c for c in decoder.calls if c[0] == "finish"]
    assert len(landings) == 1 and landings[0][2] >= 0.007
    assert decoder.calls.index(landings[0]) == 4     # before any step


def _tiny_lm():
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=64, dim=32, num_heads=4,
                          num_layers=2, max_seq=1024, dtype=jnp.float32,
                          attention="reference")
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    return model, variables


def _generate_expected(model, variables, prompt, n_new):
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.models.transformer import generate

    out = generate(model, variables, jnp.asarray([prompt], jnp.int32), n_new)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


@pytest.mark.parametrize("backend", ["stub", "transformer"])
def test_eos_met_while_a_step_is_in_flight(backend, devices8):
    """One slot, two requests. The first meets its EOS at its fourth
    token, which the engine learns when it collects that step, one step
    later than it dispatched the next: that step's row is dropped, no
    token follows the terminal, and the request that takes the slot
    over streams what it streams alone (its first token reaches the
    device as an override; the dropped row's cache write lies in a row
    its prefill overwrites whole)."""
    telemetry.reset()
    first, second, n_new = [5, 9, 3], [11, 2, 7, 4], 8
    if backend == "stub":
        decoder = StubLMDecoder(vocab_size=97, step_ms=1.0, slots=1,
                                max_len=48, buckets=(8,))
        want = [_stub_expected(decoder, p, n_new) for p in (first, second)]
    else:
        from dss_ml_at_scale_tpu.serving.lm import TransformerDecoder

        model, variables = _tiny_lm()
        decoder = TransformerDecoder(model, variables, slots=1, max_len=48,
                                     buckets=(8,))
        want = [_generate_expected(model, variables, p, n_new)
                for p in (first, second)]
    eos = want[0][3]
    stop = want[0].index(eos) + 1        # the first time it shows
    engine = LMEngine(decoder, LMConfig(slots=1, max_len=48,
                                        prefill_buckets=(8,)))
    gens = [engine.submit(first, n_new, eos_id=eos),
            engine.submit(second, n_new)]
    engine.start()
    try:
        tokens, terminal = _collect(gens[0], timeout=60.0)
        assert terminal == ("done", "eos") and tokens == want[0][:stop]
        assert _collect(gens[1], timeout=60.0) == (
            want[1], ("done", "max_tokens"))
        with pytest.raises(queue.Empty):
            gens[0].next_event(timeout=0.1)
        if stop > 1:
            # stop - 1 steps streamed a token of the first request and
            # one more was in flight when its EOS was read; n_new - 1
            # for the second, whose end was known beforehand.
            assert _decode_steps("ahead") + _decode_steps("lockstep") == (
                stop + n_new - 1)
        assert engine._alloc.n_used == 0 and engine.pending == 0
    finally:
        engine.drain(5.0)


def test_a_sampled_request_streams_what_its_seed_gave_before():
    """``temperature > 0`` keeps the host's sampler and the request's
    own generator: beside a greedy neighbour the streams are the ones
    the lock-step loop gave for these seeds (read off the parent
    commit), every step a sampling request is active in is counted
    ``lockstep``, and once the last has retired the greedy one runs
    ahead again."""
    telemetry.reset()
    decoder = _RecordingStub(vocab_size=97, step_ms=1.0, slots=3,
                             max_len=48, buckets=(8, 16))
    engine = LMEngine(decoder, LMConfig(slots=3, max_len=48,
                                        prefill_buckets=(8, 16),
                                        queue_depth=16))
    warm = engine.submit([5, 9, 11], 12, temperature=0.7, seed=1234)
    topk = engine.submit([7, 3], 12, temperature=1.3, top_k=5, seed=99)
    greedy_prompt = [2, 4, 6]
    greedy = engine.submit(greedy_prompt, 20)
    engine.start()
    try:
        assert _collect(warm) == (
            [94, 38, 89, 26, 28, 8, 24, 28, 93, 26, 44, 61],
            ("done", "max_tokens"))
        assert _collect(topk) == (
            [49, 54, 49, 94, 60, 54, 28, 53, 44, 58, 90, 24],
            ("done", "max_tokens"))
        assert _collect(greedy) == (
            _stub_expected(decoder, greedy_prompt, 20),
            ("done", "max_tokens"))
    finally:
        engine.drain(5.0)
    # 11 steps with a sampling request in them, each fetched in the turn
    # that dispatched it; then the greedy request's other 8, the first
    # of which has nothing in flight before it.
    assert _decode_steps("lockstep") == 11 + 1
    assert _decode_steps("ahead") == 7
    order = [c[0] if c[0] == "dispatch" else c for c in decoder.calls]
    assert order[:22] == [x for k in range(11)
                          for x in ("dispatch", ("fetch", k))]
    assert order[22:25] == ["dispatch", "dispatch", ("fetch", 11)]


def test_streams_hold_under_random_churn_from_many_threads():
    """160 greedy requests from 8 client threads over 4 slots, a third
    with an EOS somewhere in their stream, a tenth cancelled by their
    client after a few tokens, the interpreter switching threads every
    50 microseconds: every stream is its closed form up to where it
    ended, ends once, and every slot and ticket comes back."""
    import random
    import sys

    decoder = StubLMDecoder(vocab_size=97, step_ms=0.2, prefill_ms=0.1,
                            slots=4, max_len=64, buckets=(8,))
    engine = LMEngine(decoder, LMConfig(slots=4, max_len=64,
                                        prefill_buckets=(8,),
                                        queue_depth=64)).start()
    failures: list = []

    def client(k):
        rng = random.Random(k)
        for _ in range(20):
            prompt = [rng.randrange(97) for _ in range(rng.randint(1, 8))]
            n_new = rng.randint(1, 24)
            want = _stub_expected(decoder, prompt, n_new)
            eos = rng.choice(want) if rng.random() < 0.33 else None
            cancel_after = rng.randint(1, 5) if rng.random() < 0.1 else None
            gen = engine.submit(prompt, n_new, eos_id=eos)
            tokens = []
            while True:
                event = gen.next_event(timeout=30.0)
                if event[0] != "token":
                    break
                tokens.append(event[1])
                if len(tokens) == cancel_after:
                    gen.cancel()
            if eos is not None:
                want = want[: want.index(eos) + 1]
            if event == ("done", "cancelled"):
                ok = cancel_after is not None and tokens == want[:len(tokens)]
            else:
                ok = tokens == want and event == (
                    "done", "max_tokens" if eos is None else "eos")
            try:
                gen.next_event(timeout=0.005)
                ok = False              # something after the terminal
            except queue.Empty:
                pass
            if not ok:
                failures.append((prompt, n_new, eos, cancel_after, tokens,
                                 event))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(5e-5)
    try:
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        engine.drain(5.0)
    assert not failures, failures[:3]
    assert engine._alloc.n_used == 0 and engine.pending == 0


class _FailingStub(StubLMDecoder):
    """Raises out of the third fetch: what a device error looks like."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.fetches = 0

    def fetch(self, step, *, logits=False):
        self.fetches += 1
        if self.fetches == 3:
            raise RuntimeError("device lost")
        return super().fetch(step, logits=logits)


@pytest.mark.parametrize("how", ["cancel", "deadline", "halt", "drain"])
def test_an_end_that_catches_a_step_in_flight_settles_once(how):
    """Three greedy streams run one step ahead when a cancel, a deadline,
    a decoder error or a drain with no budget ends them: each gets
    exactly one terminal event, no token after it, and its admission
    ticket back."""
    stub = _FailingStub if how == "halt" else StubLMDecoder
    engine = LMEngine(
        stub(vocab_size=97, step_ms=20.0, prefill_ms=1.0, slots=3,
             max_len=64, buckets=(8,)),
        LMConfig(slots=3, max_len=64, prefill_buckets=(8,),
                 deadline_ms=150.0 if how == "deadline" else 0.0),
    ).start()
    try:
        gens = [engine.submit([i + 1, i + 2], 50) for i in range(3)]
        if how != "halt":
            # two tokens each: the second came from a decode step, so
            # the one after it is in flight by now
            for gen in gens:
                for _ in range(2):
                    assert gen.next_event(timeout=30.0)[0] == "token"
        if how == "cancel":
            for gen in gens:
                gen.cancel()
        elif how == "drain":
            assert engine.drain(0.0) is False
        want = {"cancel": ("done", "cancelled"),
                "deadline": ("done", "deadline"),
                "drain": ("done", "drain")}.get(how)
        for gen in gens:
            tokens, terminal = _collect(gen)
            if how == "halt":
                assert terminal[0] == "error"
                assert "device lost" in str(terminal[1])
            else:
                assert terminal == want
            assert len(tokens) < 50
        # the loop's late retirement of a slot that drain's sweep settled
        # (or a step still in flight) may not add a second terminal
        time.sleep(0.1)
        for gen in gens:
            with pytest.raises(queue.Empty):
                gen.next_event(timeout=0.01)
        assert engine.pending == 0
        if how == "halt":
            with pytest.raises(NotAccepting):
                engine.submit([1], 1)
    finally:
        engine.drain(5.0)


def test_capacity_refusals_before_any_slot(stub_engine):
    with pytest.raises(PromptTooLong, match="largest prefill bucket"):
        stub_engine.submit(list(range(17)), 4)
    with pytest.raises(PromptTooLong, match="preallocated KV slot"):
        stub_engine.submit([1, 2, 3], 46)
    with pytest.raises(ValueError, match="at least one token"):
        stub_engine.submit([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        stub_engine.submit([1], 0)
    with pytest.raises(ValueError, match="lie in"):
        stub_engine.submit([97], 4)
    # Nothing was admitted by any refusal.
    assert stub_engine.pending == 0


def test_bad_sampling_params_rejected_at_the_door(stub_engine):
    """top_k > vocab / NaN temperature / negative seed used to reach
    Generation.sample (or default_rng) INSIDE the engine thread and
    kill the shared decode loop; they must 400 before admission."""
    with pytest.raises(ValueError, match="top_k"):
        stub_engine.submit([1], 4, top_k=999)  # vocab is 97
    with pytest.raises(ValueError, match="top_k"):
        stub_engine.submit([1], 4, top_k=0)
    with pytest.raises(ValueError, match="temperature"):
        stub_engine.submit([1], 4, temperature=float("nan"))
    with pytest.raises(ValueError, match="temperature"):
        stub_engine.submit([1], 4, temperature=float("inf"))
    with pytest.raises(ValueError, match="seed"):
        stub_engine.submit([1], 4, seed=-1)
    # No refusal leaked an admission ticket.
    assert stub_engine.pending == 0
    # The decode loop never saw any of it: a valid request streams.
    tokens, terminal = _collect(stub_engine.submit([1], 3))
    assert terminal == ("done", "max_tokens") and len(tokens) == 3


def test_engine_survives_poisoned_generation():
    """Defense in depth behind the door validation: a generation whose
    per-token work raises inside the engine thread settles with an
    error event and frees its slot — the loop keeps serving others."""
    cfg = LMConfig(slots=2, max_len=48, prefill_buckets=(8,))
    engine = LMEngine(
        StubLMDecoder(vocab_size=97, step_ms=1.0, slots=2, max_len=48,
                      buckets=(8,)),
        cfg,
    )
    bad = engine.submit([1, 2], 4)
    good_prompt = [3, 4]
    good = engine.submit(good_prompt, 4)

    def _boom(_row):
        raise RuntimeError("poisoned sampling state")

    bad.sample = _boom  # corrupt AFTER validation, pre-start
    engine.start()
    try:
        tokens, terminal = _collect(bad)
        assert tokens == []
        assert terminal[0] == "error"
        assert "poisoned" in str(terminal[1])
        gtokens, gterminal = _collect(good)
        assert gterminal == ("done", "max_tokens")
        assert gtokens == _stub_expected(engine.decoder, good_prompt, 4)
        # The poisoned slot was freed and its ticket released.
        assert engine._alloc.n_used == 0
        assert engine.pending == 0
    finally:
        engine.drain(5.0)


def test_settlement_is_idempotent():
    """The drain-timeout race: the sweep settles a generation a wedged
    engine thread later retires. The second settlement must be a no-op
    — one terminal event, one admission release, pending never goes
    negative."""
    cfg = LMConfig(slots=1, max_len=48, prefill_buckets=(8,))
    engine = LMEngine(
        StubLMDecoder(slots=1, max_len=48, buckets=(8,)), cfg
    )  # never started: both settlements are ours
    gen = engine.submit([1], 1)
    assert engine.pending == 1
    engine._settle(gen, "drain")
    engine._settle(gen, "done")  # the racing late retirement
    assert gen.next_event(timeout=1.0) == ("done", "drain")
    with pytest.raises(queue.Empty):
        gen.next_event(timeout=0.1)
    assert engine.pending == 0


def test_decoder_with_more_slots_than_config():
    """A decoder arena larger than cfg.slots is legal: step arrays are
    sized to the decoder, allocation to the config — this used to
    IndexError on the first step and kill the engine thread."""
    cfg = LMConfig(slots=2, max_len=48, prefill_buckets=(8,))
    engine = LMEngine(
        StubLMDecoder(vocab_size=97, step_ms=1.0, slots=4, max_len=48,
                      buckets=(8,)),
        cfg,
    ).start()
    try:
        prompts = [[i + 1, i + 2] for i in range(4)]
        gens = [engine.submit(p, 5, seed=i)
                for i, p in enumerate(prompts)]
        for prompt, gen in zip(prompts, gens):
            tokens, terminal = _collect(gen)
            assert terminal == ("done", "max_tokens")
            assert tokens == _stub_expected(engine.decoder, prompt, 5)
        assert engine._alloc.n_used == 0
    finally:
        engine.drain(5.0)


class _CountingStub(StubLMDecoder):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.steps = 0

    def dispatch(self, override, pos):
        self.steps += 1
        return super().dispatch(override, pos)


def test_requests_submitted_together_share_decode_steps():
    """8 requests of 16 tokens waiting when the loop starts ride the
    same decode steps; one after another each pays its own. Counted
    calls of ``step``, no clock."""
    def engine():
        return LMEngine(
            _CountingStub(vocab_size=97, step_ms=0, slots=8, max_len=48,
                          buckets=(8,)),
            LMConfig(slots=8, max_len=48, prefill_buckets=(8,)),
        )

    prompts = [[i + 1, i + 2, i + 3] for i in range(8)]

    together = engine()
    gens = [together.submit(p, 16, seed=i) for i, p in enumerate(prompts)]
    together.start()
    try:
        for prompt, gen in zip(prompts, gens):
            tokens, terminal = _collect(gen)
            assert terminal == ("done", "max_tokens")
            assert tokens == _stub_expected(together.decoder, prompt, 16)
    finally:
        together.drain(5.0)

    serial = engine().start()
    try:
        for i, prompt in enumerate(prompts):
            tokens, _ = _collect(serial.submit(prompt, 16, seed=i))
            assert len(tokens) == 16
    finally:
        serial.drain(5.0)

    # The prefill yields each request's first token, 15 steps the rest.
    assert 15 <= together.decoder.steps <= 16
    assert serial.decoder.steps >= 8 * 15


def test_deadline_retires_slot_and_frees_it():
    cfg = LMConfig(slots=1, max_len=64, prefill_buckets=(8,),
                   deadline_ms=150.0)
    engine = LMEngine(
        StubLMDecoder(step_ms=30.0, slots=1, max_len=64, buckets=(8,)),
        cfg,
    ).start()
    try:
        gen = engine.submit([1, 2], 60)
        tokens, terminal = _collect(gen)
        assert terminal == ("done", "deadline")
        assert 0 < len(tokens) < 60
        # The slot is free again: a request that fits the budget runs.
        gen2 = engine.submit([1, 2], 2)
        tokens2, terminal2 = _collect(gen2)
        assert terminal2 == ("done", "max_tokens")
        assert len(tokens2) == 2
        assert engine._alloc.n_used == 0
    finally:
        engine.drain(5.0)


def test_deadline_expires_while_waiting_for_a_slot():
    """A request whose deadline passes before a slot ever frees gets
    the queue-jump error event, not a truncated stream."""
    cfg = LMConfig(slots=1, max_len=64, prefill_buckets=(8,),
                   deadline_ms=120.0)
    engine = LMEngine(
        StubLMDecoder(step_ms=25.0, slots=1, max_len=64, buckets=(8,)),
        cfg,
    ).start()
    try:
        hog = engine.submit([1], 60)  # occupies the only slot past 120ms
        starved = engine.submit([2], 4)
        tokens, terminal = _collect(starved)
        assert tokens == []
        assert terminal[0] == "error"
        assert isinstance(terminal[1], DeadlineExceeded)
        _collect(hog)  # hog itself retires on ITS deadline
    finally:
        engine.drain(5.0)


def test_drain_finishes_inflight_then_refuses(stub_engine):
    gen = stub_engine.submit([1, 2, 3], 12)
    got = {}

    def _reader():
        got["tokens"], got["terminal"] = _collect(gen)

    reader = threading.Thread(target=_reader)
    reader.start()
    assert stub_engine.drain(10.0) is True
    reader.join(10.0)
    # The in-flight stream COMPLETED during drain — not truncated.
    assert got["terminal"] == ("done", "max_tokens")
    assert len(got["tokens"]) == 12
    with pytest.raises(NotAccepting):
        stub_engine.submit([1], 1)


# -- numerics: churned engine == solo == generate() ------------------------


@pytest.mark.parametrize("depth", DEPTHS)
def test_parity_churn_vs_solo_vs_generate(depth, devices8):
    """Continuous batching must be bitwise a scheduling change: tokens
    from a churned multi-slot engine == solo decoding == the model's
    own ``generate`` reference, with one decode step in flight and,
    beside a parked request that samples on the host, in lock-step."""
    from dss_ml_at_scale_tpu.serving.lm import TransformerDecoder

    model, variables = _tiny_lm()
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(1, 64, int(n))) for n in (3, 7, 11, 5, 14)]
    n_new = 6
    expected = [_generate_expected(model, variables, p, n_new)
                for p in prompts]
    # The parked request holds a slot of its own, and an arena long
    # enough for it to outlast the others.
    spare = int(depth == "lockstep")
    max_len = 1024 if spare else 48

    # Solo: one generation at a time through a 1-slot engine.
    solo = LMEngine(
        TransformerDecoder(model, variables, slots=1 + spare,
                           max_len=max_len, buckets=(8, 16)),
        LMConfig(slots=1 + spare, max_len=max_len, prefill_buckets=(8, 16)),
    ).start()
    try:
        parked = _park_sampler(solo, 1000) if spare else None
        for prompt, want in zip(prompts, expected):
            tokens, terminal = _collect(solo.submit(prompt, n_new))
            assert terminal == ("done", "max_tokens")
            assert tokens == want
        assert parked is None or not parked.is_settled()
    finally:
        solo.drain(0.0 if spare else 10.0)

    # Churned: 5 staggered generations over 3 slots — admissions land
    # BETWEEN other streams' decode steps, slots free and refill.
    telemetry.reset()
    churn = LMEngine(
        TransformerDecoder(model, variables, slots=3 + spare,
                           max_len=max_len, buckets=(8, 16)),
        LMConfig(slots=3 + spare, max_len=max_len, prefill_buckets=(8, 16)),
    ).start()
    try:
        parked = _park_sampler(churn, 1000) if spare else None
        gens = []
        for prompt in prompts:
            gens.append(churn.submit(prompt, n_new))
            time.sleep(0.02)
        for want, gen in zip(expected, gens):
            tokens, terminal = _collect(gen, timeout=60.0)
            assert terminal == ("done", "max_tokens")
            assert tokens == want
        if parked is None:
            assert _decode_steps("ahead") >= 5
        else:
            assert not parked.is_settled()
            assert _decode_steps("ahead") == 0
    finally:
        churn.drain(0.0 if spare else 10.0)


def _decode_once(model, variables, tokens, pos, override=None, arena=None):
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.serving.lm import kvcache

    if arena is None:
        rng = np.random.default_rng(3)
        arena = jax.tree_util.tree_map(
            lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
            kvcache.make_arena(model, len(tokens), 16))
    args = [jnp.asarray(tokens, jnp.int32), arena,
            jnp.asarray(pos, jnp.int32)]
    if override is not None:
        args.append(jnp.asarray(override, jnp.int32))
    logits, ids, _, arena = jax.jit(kvcache.slot_decode, static_argnums=0)(
        model, variables, *args)
    return np.asarray(logits), np.asarray(ids), arena


def test_device_ids_are_numpy_argmax_of_the_logits_ties_included(devices8):
    """The greedy choice made on the device is the one the host made:
    ``np.argmax`` of the float32 row, the first index on a tie."""
    import jax

    model, variables = _tiny_lm()
    tokens, pos = [5, 9, 33, 60], [3, 0, 7, 12]
    logits, ids, _ = _decode_once(model, variables, tokens, pos)
    assert ids.dtype == np.int32 and logits.dtype == np.float32
    assert ids.tolist() == np.argmax(logits, axis=-1).tolist()
    assert len(set(ids.tolist())) > 1

    # Tie every row's best with a lower and a higher index: give three
    # columns of the head the same weights, so their logits are one
    # number. Then with every column alike: all 64 tie, and 0 wins.
    head = variables["params"]["lm_head"]
    top = int(ids[0])
    lower, higher = (top + 20) % 64, (top + 41) % 64
    twins = sorted((lower, top, higher))

    def tied(leaf, columns):
        leaf = np.array(leaf)
        leaf[..., columns] = leaf[..., [top]]
        return leaf

    for columns in (twins, list(range(64))):
        params = {**variables["params"], "lm_head": jax.tree_util.tree_map(
            lambda leaf: tied(leaf, columns), head)}
        logits, ids, _ = _decode_once(model, {"params": params}, tokens, pos)
        assert logits[0, columns[0]] == logits[0, columns[-1]]
        assert ids.tolist() == np.argmax(logits, axis=-1).tolist()
        if len(columns) == 64:
            assert ids.tolist() == [0, 0, 0, 0]
    # the three-way tie is the best of row 0, and its first index wins
    assert ids[0] == 0 and twins[0] < twins[1]


def test_override_replaces_a_slots_token_and_leaves_the_others(devices8):
    """``where(override >= 0, override, tokens)`` on the device: a step
    given the previous ids and one override is the step given the
    merged tokens outright."""
    model, variables = _tiny_lm()
    prev, pos = [5, 9, 33, 60], [3, 0, 7, 12]
    merged, ids_m, _ = _decode_once(model, variables, [5, 17, 33, 0], pos)
    logits, ids, _ = _decode_once(model, variables, prev, pos,
                                  override=[-1, 17, -1, 0])
    assert np.array_equal(logits, merged) and np.array_equal(ids, ids_m)


def test_a_position_past_the_arena_on_a_dropped_row_harms_no_other(devices8):
    """The engine never reads the row of a slot it has retired, but the
    program still computes it. Its position is not checked on the
    device: ``dynamic_update_slice`` clamps a write at ``max_len`` or
    beyond into the slot's own last row, so the other slots' logits and
    cache rows are what they are without it."""
    import jax

    model, variables = _tiny_lm()
    tokens = [5, 9, 33, 60]
    sane, _, arena_sane = _decode_once(model, variables, tokens,
                                       [3, 0, 7, 12])
    wild, _, arena_wild = _decode_once(model, variables, tokens,
                                       [3, 16, 7, 4000])
    keep = [0, 2]
    assert np.array_equal(sane[keep], wild[keep])
    assert np.isfinite(wild).all()
    for a, b in zip(jax.tree_util.tree_leaves(arena_sane),
                    jax.tree_util.tree_leaves(arena_wild)):
        assert np.array_equal(np.asarray(a)[keep], np.asarray(b)[keep])
        # the wild rows differ from the sane ones in their own last
        # position only (and in the position the sane step wrote)
        a, b = np.asarray(a)[1], np.asarray(b)[1]
        assert np.array_equal(a[:, 1:15], b[:, 1:15])


# -- HTTP streaming --------------------------------------------------------


@pytest.fixture
def lm_server(tmp_path):
    from dss_ml_at_scale_tpu.workloads.serving import serve_lm_in_thread

    cfg = LMConfig(slots=2, max_len=48, prefill_buckets=(8,),
                   queue_depth=8)
    engine = LMEngine(
        StubLMDecoder(step_ms=1.0, slots=2, max_len=48, buckets=(8,)),
        cfg,
    ).start()
    log = tmp_path / "access.jsonl"
    handle = serve_lm_in_thread(engine, access_log=log)
    yield handle, log
    handle.close()


def _stream(port, payload, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/generate", json.dumps(payload).encode(),
                 {"Content-Type": "application/json", **(headers or {})})
    resp = conn.getresponse()
    if resp.status != 200:
        body = json.loads(resp.read())
        conn.close()
        return resp.status, resp.getheader("X-DSST-Trace"), [], body
    lines = []
    for raw in iter(resp.readline, b""):
        lines.append(json.loads(raw))
        if "done" in lines[-1]:
            break
    resp.read()
    trace = resp.getheader("X-DSST-Trace")
    conn.close()
    return resp.status, trace, lines[:-1], lines[-1]


def test_streamed_trace_matches_access_log(lm_server):
    """The cross-process observability hop: an injected trace id comes
    back on the response header AND the done-line AND the access-log
    row — one trace across client, stream, and log."""
    handle, log = lm_server
    injected = "feedc0de12345678"
    header = f"dsst1-{injected}-abcd1234-request"
    status, trace, tokens, done = _stream(
        handle.port, {"tokens": [1, 2, 3], "max_new_tokens": 4},
        headers={"X-DSST-Trace": header},
    )
    assert status == 200
    assert trace == injected
    assert done["done"] == "max_tokens"
    assert done["trace"] == injected
    assert len(tokens) == 4
    # the handler writes the row after the stream's last byte: wait for it
    deadline = time.monotonic() + 10
    row = None
    while row is None and time.monotonic() < deadline:
        rows = [json.loads(l) for l in log.read_text().splitlines()]
        row = next((r for r in rows if r["request_id"] == injected), None)
        time.sleep(0.01)
    assert row is not None
    assert row["trace_inherited"] is True
    assert row["status"] == 200
    assert row["tokens"] == 4
    assert row["reason"] == "max_tokens"
    assert row["ttft_ms"] >= 0


def _stream_cpu_seconds():
    for metric in telemetry.snapshot()["metrics"]:
        if metric["name"] == "lm_stream_cpu_seconds_total":
            return metric["value"]
    return 0.0


def test_stream_cpu_grows_with_the_lines_written(lm_server):
    """``lm_stream_cpu_seconds_total``: the handler thread's CPU time,
    added after its lines (a stream's last line before the chunk that
    ends the response)."""
    handle, _ = lm_server
    readings = [_stream_cpu_seconds()]
    for n in (4, 16):
        status, _, tokens, done = _stream(
            handle.port, {"tokens": [1, 2], "max_new_tokens": n})
        assert status == 200 and len(tokens) == n
        readings.append(_stream_cpu_seconds())
    assert readings[0] < readings[1] < readings[2]


def test_an_http_run_logs_no_span_a_request(lm_server):
    """No span covers a whole request (one would overlap every idle gap
    of the device); the wait before a prefill rides on ``lm.prefill``."""
    from dss_ml_at_scale_tpu.telemetry import catalog

    handle, _ = lm_server
    status, trace, tokens, _ = _stream(
        handle.port, {"tokens": [5, 6, 7], "max_new_tokens": 3})
    assert status == 200 and len(tokens) == 3 and trace
    events = telemetry.get_span_log().events()
    assert "serve.generate" not in catalog.KNOWN_SPANS
    assert "serve.generate" not in {e["name"] for e in events}
    prefills = [e["args"] for e in events if e["name"] == "lm.prefill"
                and e["args"]["prompt_tokens"] == 3]
    assert prefills and all(a["queued_s"] >= 0 for a in prefills)


def test_oversized_request_is_400_not_a_scatter(lm_server):
    handle, _ = lm_server
    status, _, _, body = _stream(
        handle.port, {"tokens": list(range(1, 10)), "max_new_tokens": 4})
    assert status == 400
    assert "bucket" in body["error"]
    status, _, _, body = _stream(
        handle.port, {"tokens": [1, 2], "max_new_tokens": 47})
    assert status == 400
    assert "max_len" in body["error"]
    # The server is still healthy after both refusals.
    status, _, tokens, done = _stream(
        handle.port, {"tokens": [1, 2], "max_new_tokens": 3})
    assert status == 200 and len(tokens) == 3


def test_bad_sampling_params_400_over_http(lm_server):
    """The reviewer repro: POST /generate with top_k > vocab (or NaN
    temperature, which json.loads happily parses) used to crash the
    decode thread and hang every later request. Now: 400 at the door,
    engine stays alive."""
    handle, _ = lm_server
    status, _, _, body = _stream(
        handle.port,
        {"tokens": [1, 2], "max_new_tokens": 4, "top_k": 999})
    assert status == 400
    assert "top_k" in body["error"]
    status, _, _, body = _stream(
        handle.port,
        {"tokens": [1, 2], "max_new_tokens": 4,
         "temperature": float("nan")})
    assert status == 400
    assert "temperature" in body["error"]
    # The decode loop survived both: a valid request still streams.
    status, _, tokens, done = _stream(
        handle.port, {"tokens": [1, 2], "max_new_tokens": 3})
    assert status == 200 and len(tokens) == 3
    assert done["done"] == "max_tokens"


# -- chaos: SIGKILL a replica, doctor classifies it ------------------------


def test_sigkill_replica_classified_interrupted(tmp_path, capsys):
    """One chaos cycle against `dsst serve-lm --stub`: stream mid-kill,
    then assert no torn tracking state and a doctor INTERRUPTED verdict
    — the serving face of the crash-only runtime."""
    from dss_ml_at_scale_tpu.config.cli import main

    root = tmp_path / "runs"
    proc = subprocess.Popen(
        [sys.executable, "-m", "dss_ml_at_scale_tpu.config.cli",
         "serve-lm", "--stub", "--port", "0", "--slots", "2",
         "--max-len", "32", "--prefill-buckets", "8",
         "--step-ms", "20", "--tracking-root", str(root)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        boot = json.loads(proc.stdout.readline())
        port = boot["port"]
        # A stream is mid-flight when the kill lands.
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request(
            "POST", "/generate",
            json.dumps({"tokens": [1, 2], "max_new_tokens": 30}).encode(),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        resp.readline()  # first token arrived — decode is running
    finally:
        proc.kill()
        proc.wait(timeout=30)
    conn.close()
    assert proc.returncode == -signal.SIGKILL
    # Crash-only tracking: no torn temp files stranded anywhere.
    assert list(root.rglob("*.tmp")) == []
    # Doctor flips the dead-PID RUNNING run to INTERRUPTED.
    assert main(["runs", "doctor", "--tracking-root", str(root),
                 "--json"]) == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    runs = [r for r in report["runs"] if r["experiment"] == "serve-lm"]
    assert len(runs) == 1
    assert runs[0]["effective_status"] == "INTERRUPTED"
    assert runs[0]["marked"] is True
