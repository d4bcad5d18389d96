"""Pallas TPU flash attention: blockwise online-softmax attention.

The reference has no attention anywhere (SURVEY.md §5.7) — this op exists
because the framework treats long-context as first-class: it is the
single-device fast path of the attention stack (cross-shard sequence
parallelism lives in :mod:`dss_ml_at_scale_tpu.parallel.ring`, which
shares this module's blockwise-softmax math) and the building block of
the transformer model family.

Design (pallas_guide.md patterns):

- grid ``(batch*heads, pairs)`` over the (q-block, k-block) pairs of a
  :func:`block_plan`, prefetched as scalar tables that the q/k/v index
  maps read. Causal: only the pairs holding a visible key, so no grid
  step (and no k/v copy) falls past the diagonal; a q-block's pairs are
  contiguous and in k order, so VMEM scratch (acc, running max m,
  running denominator l) persists across them and the output block is
  written once a q-block — the classic TPU flash forward, less the dead
  half of the rectangle.
- Inside a pair, the q-block goes through the online softmax in row
  sub-tiles of a quarter of the k-block's rows (:func:`_walk`): each
  sub-tile's Q·Kᵀ, mask, max, exp, sum and P·V, then its rows of acc / m
  / l, so the float32 score tile is ``sub_q x block_k``, never
  ``block_q x block_k``. The walk is a ``fori_loop`` over turns of
  ``TURN_Q`` rows; a turn's sub-tiles are independent and no branch
  splits a turn, so the scheduler overlaps one sub-tile's matrix work
  with another's vector work. The loop's body is traced once, whatever
  ``block_q`` is. A q-block of one sub-tile runs the whole-tile step.
  Every row sees the same keys in the same order as a whole-tile step
  would: the sub-tiles move no bit.
- ``_flash_forward`` is jitted, so a program that calls the kernel once
  a layer traces and lowers it once a shape: set-up does not grow with
  the layers.
- Q·Kᵀ and P·V hit the MXU via ``jnp.dot(..., preferred_element_type=f32)``;
  inputs may be bf16, statistics and accumulation are f32.
- Causal masking via ``broadcasted_iota`` global indices, on the pairs
  that straddle the diagonal only. ``block_q`` and ``block_k`` come from
  the lengths (:func:`blocks`); ``block_k`` sets the softmax's order.
- Backward is a ``custom_vjp`` that recomputes attention in q-chunks under
  ``jax.checkpoint``: peak memory is O(block_q × S) in both directions,
  never O(S²), while the recompute stays compiler-fused XLA.

On a CPU backend (tests, the simulated 8-device mesh) the kernel runs in
Pallas interpret mode (``_pallas.resolve_interpret``); on ``tpu`` it is
always compiled.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ._pallas import resolve_interpret

_NEG_INF = -1e30  # finite "minus infinity": avoids inf-inf NaNs in masking


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False
) -> jax.Array:
    """Plain XLA attention, the numerical ground truth for the kernel.

    Shapes ``[..., seq, head_dim]`` with softmax over the second-to-last
    axis of the score matrix; computed in f32 regardless of input dtype.
    With ``causal=True`` and ``sq != sk`` the mask is bottom-right aligned
    (query row r attends to keys ``<= r + sk - sq``) — the decode-with-
    cache convention.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum(
        "...qd,...kd->...qk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        sq, sk = q.shape[-2], k.shape[-2]
        qi = jnp.arange(sq)[:, None] + (sk - sq)
        ki = jnp.arange(sk)[None, :]
        s = jnp.where(qi >= ki, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "...qk,...kd->...qd", p, v.astype(jnp.float32)
    ).astype(q.dtype)


# k-block rows: the order of the online softmax, so it sets every bit
# of the output. From ``LONG_K`` keys on, k-blocks of ``2 * BLOCK_K``: a
# q-block's pairs and rescales halve (20-30% faster at 4,096 and 8,192
# keys on a v5e).
BLOCK_K = 512
LONG_K = 4096


def _block_q(sq: int) -> int:
    """q-block rows for a sequence of ``sq``: of 256 / 512 / 1,024, the
    fastest on a v5e at 1,024-4,096 rows is 512 and at 8,192 it is 1,024
    (CHANGES.md, PR 35), halved while it does not divide ``sq``; a shorter
    sequence is one q-block. It moves no bit: a row sees the same k-blocks
    in the same order at any q-block."""
    block = 1024 if sq >= 8192 else 512
    if sq <= block:
        return sq
    while block > 256 and sq % block:
        block //= 2
    return block


def blocks(sq: int, sk: int, block_q: int | None = None,
           block_k: int | None = None) -> tuple[int, int]:
    """The ``(block_q, block_k)`` a call of these lengths runs with:
    each from the lengths unless given, each clamped to its sequence."""
    if block_q is None:
        block_q = _block_q(sq)
    if block_k is None:
        block_k = 2 * BLOCK_K if sk >= LONG_K else BLOCK_K
    return min(block_q, sq), min(block_k, sk)


class BlockPlan(NamedTuple):
    """The (q-block, k-block) pairs one head's call visits, in order.

    Pairs of one q-block are contiguous and in ascending k-block order,
    from k-block 0 to the last that holds a visible key: each row sees
    the same k-blocks in the same order whatever ``block_q`` is.
    """

    grid: int              # pairs of the full rectangle
    q_block: np.ndarray    # int32 [visited]
    k_block: np.ndarray    # int32 [visited]
    straddles: np.ndarray  # int32 [visited]: 1 where the pair needs the mask
    last: np.ndarray       # int32 [visited]: 1 at a q-block's last pair

    @property
    def visited(self) -> int:
        return len(self.q_block)

    @property
    def masked(self) -> int:
        return int(self.straddles.sum())


def block_plan(sq: int, sk: int, block_q: int, block_k: int,
               causal: bool) -> BlockPlan:
    """Which block pairs hold a visible key, and which of those straddle
    the diagonal (bottom-right aligned, ``offset = sk - sq``).

    Pair ``(i, j)`` holds one iff ``j·bk < (i+1)·bq + offset`` and needs
    the mask iff its last key is past its first row's limit,
    ``(j+1)·bk - 1 > i·bq + offset``. Without ``causal`` every pair is
    visited and none is masked. Built from static shapes, in numpy: the
    kernel's prefetched tables and the engine's counter both come from it.
    """
    nq, nk = sq // block_q, sk // block_k
    i = np.repeat(np.arange(nq), nk)
    j = np.tile(np.arange(nk), nq)
    if causal:
        offset = sk - sq
        live = j * block_k < (i + 1) * block_q + offset
        straddles = (j + 1) * block_k - 1 > i * block_q + offset
    else:
        live = np.ones(nq * nk, bool)
        straddles = np.zeros(nq * nk, bool)
    i, j, straddles = i[live], j[live], straddles[live]
    last = np.append(i[1:] != i[:-1], True)
    as32 = functools.partial(np.asarray, dtype=np.int32)
    return BlockPlan(nq * nk, as32(i), as32(j), as32(straddles), as32(last))


# Rows of one turn of the loop over a q-block's sub-tiles: the sub-tiles
# of a turn are independent, so the scheduler overlaps one's matrix work
# with another's vector work.
TURN_Q = 512


def _walk(block_q: int, block_k: int) -> tuple[int, int]:
    """``(sub_q, turn_q)``: sub-tiles of a quarter of the k-block's rows
    (128 at 512 keys, 256 at 1,024: the fastest timed on a v5e), in
    turns of ``TURN_Q`` rows; a q-block they do not divide, or whose
    sub-tiles would not be whole (16, 128) bf16 tiles, is one sub-tile."""
    sub_q = block_k // 4
    if block_q <= sub_q or block_q % sub_q or sub_q % 16:
        return block_q, block_q
    turn_q = min(TURN_Q, block_q)
    return sub_q, turn_q if block_q % turn_q == 0 else sub_q


def _flash_kernel(
    q_block_ref, k_block_ref, straddles_ref, last_ref, q_ref, k_ref, v_ref,
    o_ref, acc_ref, m_ref, l_ref, *, block_q, block_k, sub_q, turn_q,
    scale, causal_offset
):
    t = pl.program_id(1)  # the pair's place in the plan (sequential)
    i = q_block_ref[t]
    j = k_block_ref[t]

    @pl.when(j == 0)  # a q-block's first pair
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    row0 = causal_offset + i * block_q  # the q-block's first row's limit
    key0 = j * block_k                  # the k-block's first key
    # Keep native dtype into the MXU (bf16×bf16 with f32 accumulate).
    kt = k_ref[0].T  # (d, block_k)
    v = v_ref[0]     # (block_k, d)

    straddles = straddles_ref[t] == 1

    def visible(s, first):
        """Where a score's row, ``first`` on, sees its key."""
        qi = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ki = key0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return qi >= ki

    def update(r):
        """The online softmax of the q-block's ``r``-th sub-tile."""
        if sub_q == block_q:
            rows = slice(None)
            s = jnp.dot(q_ref[0], kt,
                        preferred_element_type=jnp.float32) * scale
            # A cond on the scores alone: only a pair that straddles the
            # diagonal builds the mask.
            s = jax.lax.cond(
                straddles,
                lambda s: jnp.where(visible(s, row0), s, _NEG_INF),
                lambda s: s, s)
        else:
            rows = pl.ds(pl.multiple_of(r * sub_q, sub_q), sub_q)
            s = jnp.dot(q_ref[0, rows, :], kt,
                        preferred_element_type=jnp.float32) * scale
            # No branch inside a turn, which would keep its sub-tiles
            # apart: a pair that does not straddle keeps every score.
            keep = visible(s, row0 + r * sub_q) | jnp.logical_not(straddles)
            s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_ref[rows, :1]  # (sub_q, 1), lanes replicated
        l_prev = l_ref[rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[rows, :] = acc_ref[rows, :] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[rows, :] = jnp.broadcast_to(m_new, (sub_q, 128))
        l_ref[rows, :] = jnp.broadcast_to(l_new, (sub_q, 128))

    def turn(r, carry):
        for c in range(turn_q // sub_q):
            update(r * (turn_q // sub_q) + c)
        return carry

    if turn_q == block_q:
        turn(0, 0)
    else:  # a loop traced once, whatever the number of turns
        jax.lax.fori_loop(0, block_q // turn_q, turn, 0)

    @pl.when(last_ref[t] == 1)
    def _finish():
        # l is never zero: causal rows always see at least the diagonal.
        o_ref[0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
    block_q: int, block_k: int, interpret: bool
) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"seq lengths ({sq}, {sk}) must be multiples of blocks "
            f"({block_q}, {block_k}); pad upstream"
        )
    plan = block_plan(sq, sk, block_q, block_k, causal)
    sub_q, turn_q = _walk(block_q, block_k)
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, sub_q=sub_q,
        turn_q=turn_q, scale=1.0 / math.sqrt(d),
        causal_offset=sk - sq,
    )

    def q_rows(b, t, q_block, k_block, straddles, last):
        return (b, q_block[t], 0)

    def k_rows(b, t, q_block, k_block, straddles, last):
        return (b, k_block[t], 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(bh, plan.visited),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_rows),
                pl.BlockSpec((1, block_k, d), k_rows),
                pl.BlockSpec((1, block_k, d), k_rows),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), q_rows),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
        name="flash_attention_fwd",
    )(plan.q_block, plan.k_block, plan.straddles, plan.last, q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    return _flash_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out = _flash(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v)


def _chunked_reference(q, k, v, *, causal, chunk):
    """Attention recompute in q-chunks of ``chunk`` rows.

    Each chunk is wrapped in ``jax.checkpoint`` so its O(chunk × sk) score
    matrix is rematerialized during the backward instead of stored —
    differentiating through this keeps peak memory O(chunk × sk), never
    O(sq × sk). Used only inside the custom VJP.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)

    @jax.checkpoint
    def one_chunk(q_chunk, start):
        s = jnp.einsum(
            "bqd,bkd->bqk", q_chunk, k, preferred_element_type=jnp.float32
        ) * scale
        if causal:
            qi = start + (sk - sq) + jnp.arange(chunk)[:, None]
            ki = jnp.arange(sk)[None, :]
            s = jnp.where(qi >= ki, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)

    n = sq // chunk
    q_chunks = q.reshape(bh, n, chunk, d).transpose(1, 0, 2, 3)
    starts = jnp.arange(n) * chunk
    out = jax.lax.map(lambda args: one_chunk(*args), (q_chunks, starts))
    return out.transpose(1, 0, 2, 3).reshape(bh, sq, d)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v = res
    chunk = min(block_q, q.shape[1])
    _, vjp = jax.vjp(
        lambda q, k, v: _chunked_reference(q, k, v, causal=causal, chunk=chunk),
        q, k, v,
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Blockwise flash attention over ``[batch, heads, seq, head_dim]``.

    Differentiable (custom VJP); bf16 in/out with f32 softmax statistics.
    ``interpret=None`` selects Pallas interpret mode on a CPU backend only.
    The blocks are the shapes' (:func:`blocks`); ``block_q``/``block_k``
    override them for tests.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [batch, heads, seq, head_dim], got {q.shape}")
    if causal and q.shape[2] > k.shape[2]:
        # Bottom-right alignment gives the first sq - sk query rows zero
        # visible keys: their softmax denominator is 0 and the kernel
        # emits non-finite rows. No attention semantics want this shape.
        raise ValueError(
            f"causal flash attention needs sq <= sk, got sq={q.shape[2]} "
            f"sk={k.shape[2]} (rows before the first key would attend to "
            "nothing)"
        )
    interpret = resolve_interpret(interpret)
    b, h, sq, d = q.shape
    block_q, block_k = blocks(sq, k.shape[2], block_q, block_k)
    out = _flash(
        q.reshape(b * h, sq, d),
        k.reshape(b * h, k.shape[2], d),
        v.reshape(b * h, v.shape[2], d),
        causal, block_q, block_k, interpret,
    )
    return out.reshape(b, h, sq, d)
