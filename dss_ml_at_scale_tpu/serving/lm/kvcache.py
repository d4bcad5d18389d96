"""Preallocated slot-based KV cache for continuous-batching decode.

The image-serving tier batches fixed-shape requests through ONE
compiled program; token serving cannot, because every request is at a
different decode position. The classic answer (and the one the audit
donation rule can certify) is a slot arena: a fixed
slab per layer whose leading axis is the slot (the model says what a
slot holds: ``[slots, heads, max_len, head_dim]`` k and v, or one
``[slots, max_len, width]`` latent row a token), allocated
once at boot, DONATED through every decode step so XLA aliases it
in-place — zero per-token cache copies, no per-request allocation, no
shape churn, one compiled program for the life of the server.

Three compiled programs live here, all registered as audited
entrypoints (donation + collective ceilings + program hashes pinned
like the other production programs):

``slot_decode``
    One token for EVERY slot at once with a per-slot ``pos`` vector
    (the model's ``decode_slots``: one batched call, each slot's new
    k/v row written at its own position). A slot's attention reads its
    own rows ``0..pos`` and nothing else: ``TransformerLM`` through
    ``ops.decode_attention``, which fetches only the blocks of the
    arena that hold those rows and masks the tail of the last one (the
    whole slab under ``arange(max_len) <= pos`` where the shapes do not
    tile), the latent model under that mask over its latent rows.
    Inactive slots decode garbage at position 0; no slot ever reads
    another slot's rows, and a freshly allocated slot is overwritten
    wholesale by ``write_slot`` before its first real step, so the
    garbage is provably harmless (the bitwise-parity test in
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

Arena = tuple  # per layer, a dict of slabs whose leading axis is the slot

# What a served model gives (``models.transformer.TransformerLM``,
# ``models.mla_moe.MlaMoeLM``): the model, not this module, says what
# one slot's cache is and how a step reads it.
#
#   init_cache(slots, max_len)           the cache for slots x max_len
#   prefill_cache(variables, tokens[1, bucket], cache, n_real)
#                                        -> (logits, stats, cache)
#   decode_slots(variables, tokens[slots], cache, pos[slots])
#                                        -> (logits, stats, cache)
#   serving_variables(variables)         each leaf at its served width
#   decode_rows_read(pos[slots], cache) -> int     (optional) the cache
#                                        rows a layer such a step fetches
#
# ``stats`` is None or one small int32 array the model's own counters
# are fed from.


def make_arena(model, slots: int, max_len: int) -> Arena:
    """Allocate the slot arena: whatever the model keeps a slot a layer."""
    return model.init_cache(slots, max_len)


def slot_decode(model, variables, tokens, arena, pos, override=None):
    """One decode step for every slot: the audited production program.

    ``tokens`` ``[slots] int32`` (each slot's last token), ``pos``
    ``[slots] int32`` (the cache position that token occupies).
    ``override`` ``[slots] int32``, where given, replaces ``tokens``
    wherever it is not negative: the engine hands the previous step's
    ``ids`` back as ``tokens`` without ever reading them, and overrides
    the slots whose token the host knows better (one admitted since,
    whose first token came from its prefill).

    Returns ``(logits [slots, vocab], ids [slots] int32, stats,
    new_arena)``: ``ids`` is the greedy choice of every slot, ``argmax``
    of the float32 logits with the first index winning a tie, as
    ``np.argmax`` of the same row; ``stats`` is the model's (None where
    it counts nothing). The arena is aliased in-place when jitted with
    ``donate_argnums=(3,)``.

    A row's ``pos`` is not checked: ``dynamic_update_slice`` clamps a
    write at or past ``max_len`` into the slot's own last row, so a row
    the engine computes only to throw away cannot reach another slot.
    """
    if override is not None:
        tokens = jnp.where(override >= 0, override, tokens)
    logits, stats, arena = model.decode_slots(variables, tokens, arena, pos)
    ids = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    return logits, ids, stats, arena


def prefill_bucket(model, variables, tokens, cache, n_real=None):
    """Prefill one bucket-padded prompt into a single-sequence cache.

    ``tokens`` is ``[1, bucket]`` int32; compiled once per bucket
    length. ``n_real`` (int32 scalar) is the count of real tokens, for
    a model that returns the last real row only. Returns ``(logits,
    stats, cache)`` where logits is the model's: every row
    ``[1, bucket, vocab]`` (``[1, vocab]`` for the degenerate 1-token
    bucket), or ``[1, vocab]`` of the last real one. Positions past the
    real prompt hold padding rows, never attended (causal mask) and
    overwritten by later decode steps before the position pointer
    passes them.
    """
    return model.prefill_cache(variables, tokens, cache, n_real)


def write_slot(arena, rows, slot):
    """Scatter a prefilled single-sequence cache into arena ``slot``.

    ``rows`` is the arena's tree with ``[1, ...]`` leaves; ``slot`` is an
    int32 scalar. Donating ``arena`` makes this an in-place aliased
    update in the lowered program.
    """
    return jax.tree_util.tree_map(
        lambda a, r: jax.lax.dynamic_update_slice(
            a, r.astype(a.dtype), (slot,) + (0,) * (a.ndim - 1)
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
