"""Preallocated slot-based KV cache for continuous-batching decode.

The image-serving tier batches fixed-shape requests through ONE
compiled program; token serving cannot, because every request is at a
different decode position. The classic answer (and the one the audit
donation rule can certify) is a slot arena: a fixed
``[slots, heads, max_len, head_dim]`` k/v slab per layer, allocated
once at boot, DONATED through every decode step so XLA aliases it
in-place — zero per-token cache copies, no per-request allocation, no
shape churn, one compiled program for the life of the server.

Three compiled programs live here, all registered as audited
entrypoints (donation + collective ceilings + program hashes pinned
like the other production programs):

``slot_decode``
    One token for EVERY slot at once — ``jax.vmap`` of the single-
    sequence cached decode over the slot axis with a per-slot ``pos``
    vector. Inactive slots decode garbage at position 0; the mask
    (``arange(max_len) <= pos``) never lets any slot read another
    slot's rows, and a freshly allocated slot is overwritten wholesale
    by ``write_slot`` before its first real step, so the garbage is
    provably harmless (the bitwise-parity test in
    ``tests/test_lm_serving.py`` holds the proof).

``prefill_bucket``
    The whole prompt through one causal pass into a single-sequence
    cache, compiled once per configured bucket length. The cache
    argument is donated too: the engine keeps ONE prefill scratch
    cache and recycles the returned buffers.

``write_slot``
    Scatters a prefilled single-sequence cache into one arena slot via
    ``dynamic_update_slice`` — donated, so admission costs one aliased
    scatter, not an arena copy.

Slot bookkeeping (:class:`SlotAllocator`) is deliberately host-side
and boring: a lock, a sorted free list, an in-use set.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from ...models.transformer import TransformerLM

Arena = tuple  # tuple per layer of {"k": [slots,h,max_len,d], "v": ...}


def make_arena(model: TransformerLM, slots: int, max_len: int) -> Arena:
    """Allocate the slot arena: one k/v slab per layer.

    ``max_len`` may be smaller than ``model.max_seq`` — the attention
    mask and the cache writes both derive their length from the cache's
    own shape, so a short arena is a working (cheaper) cache.
    """
    if max_len > model.max_seq:
        raise ValueError(
            f"arena max_len {max_len} > model max_seq {model.max_seq}"
        )
    head_dim = model.dim // model.num_heads
    shape = (slots, model.num_heads, max_len, head_dim)
    return tuple(
        {
            "k": jnp.zeros(shape, dtype=model.dtype),
            "v": jnp.zeros(shape, dtype=model.dtype),
        }
        for _ in range(model.num_layers)
    )


def slot_decode(model, variables, tokens, arena, pos, override=None):
    """One decode step for every slot: the audited production program.

    ``tokens`` ``[slots] int32`` (each slot's last token), ``pos``
    ``[slots] int32`` (the cache position that token occupies).
    ``override`` ``[slots] int32``, where given, replaces ``tokens``
    wherever it is not negative: the engine hands the previous step's
    ``ids`` back as ``tokens`` without ever reading them, and overrides
    the slots whose token the host knows better (one admitted since,
    whose first token came from its prefill).

    Returns ``(logits [slots, vocab], ids [slots] int32, new_arena)``:
    ``ids`` is the greedy choice of every slot, ``argmax`` of the
    float32 logits with the first index winning a tie, as
    ``np.argmax`` of the same row. The arena is aliased in-place when
    jitted with ``donate_argnums=(3,)``.

    A row's ``pos`` is not checked: ``dynamic_update_slice`` clamps a
    write at or past ``max_len`` into the slot's own last row, so a row
    the engine computes only to throw away cannot reach another slot.
    """
    if override is not None:
        tokens = jnp.where(override >= 0, override, tokens)

    def one(tok, slot_cache, p):
        cache1 = jax.tree_util.tree_map(lambda a: a[None], slot_cache)
        logits, new_cache = model.apply(
            variables, tok[None, None], cache=cache1, pos=p
        )
        return logits[0], jax.tree_util.tree_map(lambda a: a[0], new_cache)

    logits, arena = jax.vmap(one, in_axes=(0, 0, 0))(tokens, arena, pos)
    ids = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    return logits, ids, arena


def prefill_bucket(model, variables, tokens, cache):
    """Prefill one bucket-padded prompt into a single-sequence cache.

    ``tokens`` is ``[1, bucket]`` int32; compiled once per bucket
    length. Returns ``(logits, cache)`` where logits is
    ``[1, bucket, vocab]`` (or ``[1, vocab]`` for the degenerate
    1-token bucket). Positions past the real prompt hold padding k/v —
    never attended (causal mask) and overwritten by later decode steps
    before the position pointer passes them.
    """
    return model.apply(variables, tokens, cache=cache, pos=0)


def write_slot(arena, rows, slot):
    """Scatter a prefilled single-sequence cache into arena ``slot``.

    ``rows`` leaves are ``[1, heads, len, head_dim]``; ``slot`` is an
    int32 scalar. Donating ``arena`` makes this an in-place aliased
    update in the lowered program.
    """
    return jax.tree_util.tree_map(
        lambda a, r: jax.lax.dynamic_update_slice(
            a, r.astype(a.dtype), (slot, 0, 0, 0)
        ),
        arena,
        rows,
        is_leaf=lambda x: isinstance(x, jax.Array),
    )


class SlotAllocator:
    """Host-side free-list over arena slots (lowest index first).

    Lowest-first keeps allocation deterministic, which the bitwise
    parity test leans on: the same admission order always lands in the
    same slots.
    """

    _guarded_by_lock = ("_free", "_in_use")

    def __init__(self, slots: int):
        self._lock = threading.Lock()
        self._free = list(range(slots))
        self._in_use: set[int] = set()
        self.slots = slots

    def alloc(self) -> int | None:
        """Claim the lowest free slot, or None when the arena is full."""
        with self._lock:
            if not self._free:
                return None
            slot = min(self._free)
            self._free.remove(slot)
            self._in_use.add(slot)
            return slot

    def free(self, slot: int) -> None:
        with self._lock:
            if slot not in self._in_use:
                raise ValueError(f"slot {slot} is not allocated")
            self._in_use.remove(slot)
            self._free.append(slot)

    @property
    def n_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def n_used(self) -> int:
        with self._lock:
            return len(self._in_use)
