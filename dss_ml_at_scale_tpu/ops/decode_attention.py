"""Length-aware decode attention: one query a slot over its live cache rows.

A decode step scores one new token against a slot's cache.  The arena
holds ``max_len`` rows a slot and a slot has written ``pos + 1`` of them;
the rest are zeros or a retired request's rows.  The masked einsum over the
whole slab reads them all and masks them afterwards, and at one query a
slot the read is the whole cost.  This op reads, for each slot, the blocks
of ``BLOCK`` rows that hold rows ``0..pos`` and no others.

One Pallas kernel over a grid ``(slots, max_len / BLOCK)``:

- ``pos`` is a prefetched scalar operand, so the k and v block index can
  depend on it: ``min(j, pos[b] // BLOCK)``.  A grid step past a slot's
  last live block names the block already in VMEM, the pipeline issues no
  copy for it, and its compute is skipped under ``pl.when``.
- All heads of a slot ride in one block, ``(1, heads, BLOCK, head_dim)``.
- Running maximum, sum and accumulator in float32 in VMEM scratch (the
  blockwise softmax of :mod:`.flash_attention`); scores from bfloat16
  operands into float32; the float32 probabilities go to the matrix
  unit as three bfloat16 terms against the bfloat16 value rows
  (``_probs_times_values``): the products of ``probs @
  v.astype(float32)`` with no widened copy of the values, in HBM or in
  VMEM.
- Inside the last live block rows past ``pos`` are masked before the
  softmax.

A slot's result depends on its own rows ``0..pos`` and on nothing else:
not on the other slots, not on how long the slab is past its last live
block.  The serving engine's bitwise parity with a solo decode leans on
that.

Shapes decide the form, nobody chooses: where the slabs tile (``head_dim``
a multiple of 128 lanes, ``max_len`` a multiple of ``BLOCK``, one block of
all heads within ``_BLOCK_BYTES_MAX``) the kernel
runs (in interpret mode on a CPU backend, ``_pallas.resolve_interpret``);
where they do not, the same function computes the masked einsum over the
whole slab.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ._pallas import resolve_interpret

# Rows of one k (and one v) block.  Timed on a v5e at the chat cell's
# shapes (16 slots x 16 heads x 2,048 rows x 128, bfloat16): see
# CHANGES.md, PR 32.
BLOCK = 256

_NEG_INF = -1e30  # finite "minus infinity": avoids inf-inf NaNs in masking
_LANES = 128
# k and v blocks are double-buffered in 16 MiB of scoped VMEM: 4 x 2 MiB
# leaves half of it to the kernel's own values.
_BLOCK_BYTES_MAX = 2 << 20


def tiles(cache_shape, dtype) -> bool:
    """Whether slabs ``[b, heads, max_len, head_dim]`` of ``dtype`` take
    the kernel: whole lanes, whole blocks, and a block of all heads that
    fits the kernel's fast memory twice over for k and for v."""
    _, heads, max_len, head_dim = cache_shape
    block_bytes = heads * BLOCK * head_dim * np.dtype(dtype).itemsize
    return (head_dim % _LANES == 0 and max_len % BLOCK == 0
            and block_bytes <= _BLOCK_BYTES_MAX)


def rows_fetched(pos, cache_shape, dtype) -> int:
    """Cache rows one call fetches for ``pos`` (host side, for counters):
    each slot's live blocks under the kernel, the whole slab otherwise."""
    pos = np.asarray(pos)
    max_len = cache_shape[2]
    if not tiles(cache_shape, dtype):
        return int(pos.size) * max_len
    live = np.minimum(pos // BLOCK + 1, max_len // BLOCK)
    return int(live.sum()) * BLOCK


def _probs_times_values(p, v):
    """``p`` float32 ``(heads, 1, rows)`` times ``v`` bfloat16 ``(heads,
    rows, d)``, with the products of ``p @ v.astype(float32)``: ``p`` as
    three bfloat16 terms (8 + 8 + 8 bits of its 24) stacked as three rows
    of one matmul.  Each product of two bfloat16 numbers is exact in
    float32, where they are summed; the matrix unit pads one row to eight
    anyway, so the three cost what one would.  Float32 value rows are
    multiplied as they are."""
    if v.dtype == jnp.float32:
        return jnp.einsum(
            "hqk,hkd->hqd", p, v, preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype)
    rest = p - hi.astype(jnp.float32)
    mid = rest.astype(v.dtype)
    lo = (rest - mid.astype(jnp.float32)).astype(v.dtype)
    terms = jnp.einsum(
        "hqk,hkd->hqd", jnp.concatenate([hi, mid, lo], axis=1), v,
        preferred_element_type=jnp.float32,
    )
    return jnp.sum(terms, axis=1, keepdims=True)


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale):
    b = pl.program_id(0)
    j = pl.program_id(1)  # cache block (innermost, sequential)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(j * BLOCK <= pos)
    def _step():
        q = q_ref[0]  # (heads, 1, d)
        k = k_ref[0]  # (heads, BLOCK, d)
        v = v_ref[0]
        s = jnp.einsum(
            "hqd,hkd->hqk", q, k, preferred_element_type=jnp.float32
        ) * scale  # (heads, 1, BLOCK): rows on lanes
        row = j * BLOCK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(row <= pos, s, _NEG_INF)
        m_prev = m_ref[:]  # (heads, 1, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _probs_times_values(p, v)
        m_ref[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        # l is never zero: row 0 is live for every pos >= 0.
        o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def _blockwise(q, k_cache, v_cache, pos, *, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    b, heads, max_len, d = k_cache.shape

    def cache_block(i, j, pos_ref):
        return (i, 0, jnp.minimum(j, pos_ref[i] // BLOCK), 0)

    def slot_block(i, j, pos_ref):
        return (i, 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, max_len // BLOCK),
            in_specs=[
                pl.BlockSpec((1, heads, 1, d), slot_block),
                pl.BlockSpec((1, heads, BLOCK, d), cache_block),
                pl.BlockSpec((1, heads, BLOCK, d), cache_block),
            ],
            out_specs=pl.BlockSpec((1, heads, 1, d), slot_block),
            scratch_shapes=[
                pltpu.VMEM((heads, 1, d), jnp.float32),
                pltpu.VMEM((heads, 1, 1), jnp.float32),
                pltpu.VMEM((heads, 1, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, 1, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(pos, q[:, :, None, :], k_cache, v_cache)
    return out[:, :, 0, :]


def _whole_slab(q, k_cache, v_cache, pos):
    """The masked einsum over every row of the slab."""
    scores = jnp.einsum(
        "bhd,bhkd->bhk", q, k_cache, preferred_element_type=jnp.float32,
    ) / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    live = jnp.arange(k_cache.shape[2])[None, :] <= pos[:, None]
    probs = jax.nn.softmax(
        jnp.where(live[:, None, :], scores, _NEG_INF), axis=-1)
    return jnp.einsum(
        "bhk,bhkd->bhd", probs, v_cache, preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos):
    """Attention of one query a slot over its cache rows ``0..pos``.

    ``q`` ``[b, heads, head_dim]``; ``k_cache`` and ``v_cache`` ``[b,
    heads, max_len, head_dim]`` as the arena holds them; ``pos`` an int32
    scalar (every slot at one position) or ``[b]``.  Returns ``[b, heads,
    head_dim]`` in ``q``'s dtype.
    """
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), k_cache.shape[:1])
    if tiles(k_cache.shape, k_cache.dtype):
        return _blockwise(
            q, k_cache, v_cache, pos, interpret=resolve_interpret(None))
    return _whole_slab(q, k_cache, v_cache, pos)
