"""Pallas TPU flash attention: blockwise online-softmax attention.

The reference has no attention anywhere (SURVEY.md §5.7) — this op exists
because the framework treats long-context as first-class: it is the
single-device fast path of the attention stack (cross-shard sequence
parallelism lives in :mod:`dss_ml_at_scale_tpu.parallel.ring`, which
shares this module's blockwise-softmax math) and the building block of
the transformer model family.

Design (pallas_guide.md patterns):

- grid ``(batch*heads, q_blocks, k_blocks)``; the k dimension is the
  innermost sequential axis, so VMEM scratch (acc, running max m, running
  denominator l) persists across k steps — the classic TPU flash forward.
- Q·Kᵀ and P·V hit the MXU via ``jnp.dot(..., preferred_element_type=f32)``;
  inputs may be bf16, statistics and accumulation are f32.
- Causal masking via ``broadcasted_iota`` global indices; fully-masked
  k-blocks are skipped with ``pl.when`` (no wasted MXU work past the
  diagonal).
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

import jax
import jax.numpy as jnp
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


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, causal, block_q,
    block_k, scale, causal_offset
):
    i = pl.program_id(1)  # q-block index
    j = pl.program_id(2)  # k-block index (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Under causality, k-blocks wholly above the (offset) diagonal
    # contribute nothing: q rows [i·bq, (i+1)·bq) never see k columns
    # >= (i+1)·bq + offset (bottom-right alignment when sq != sk).
    live = (not causal) or (j * block_k < (i + 1) * block_q + causal_offset)

    @pl.when(live)
    def _step():
        # Keep native dtype into the MXU (bf16×bf16 with f32 accumulate).
        q = q_ref[0]  # (block_q, d)
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]  # (block_k, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qi = causal_offset + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            ki = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qi >= ki, s, _NEG_INF)

        m_prev = m_ref[:, :1]  # (block_q, 1), lanes replicated
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        # l is never zero: causal rows always see at least the diagonal.
        o_ref[0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


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
    grid = (bh, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _flash_kernel, causal=causal, block_q=block_q, block_k=block_k,
        scale=1.0 / math.sqrt(d), causal_offset=sk - sq,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


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
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Blockwise flash attention over ``[batch, heads, seq, head_dim]``.

    Differentiable (custom VJP); bf16 in/out with f32 softmax statistics.
    ``interpret=None`` selects Pallas interpret mode on a CPU backend only.
    Default blocks (256, 512) measured fastest on TPU v5e at seq 2048,
    head_dim 128 — ~1.3× the fused XLA attention on the same shapes.
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
    block_q = min(block_q, sq)
    block_k = min(block_k, k.shape[2])
    out = _flash(
        q.reshape(b * h, sq, d),
        k.reshape(b * h, k.shape[2], d),
        v.reshape(b * h, v.shape[2], d),
        causal, block_q, block_k, interpret,
    )
    return out.reshape(b, h, sq, d)
