"""Ring attention: exact attention over sequence-sharded inputs.

Long-context support is first-class in this framework even though the
reference never touches a sequence dimension (SURVEY.md §5.7 — its
workloads are ResNet-50 / SVC / weekly SARIMAX). The sharding layer is
mesh-based precisely so sequence parallelism falls out of the same
mechanism as data/tensor parallelism.

Design (the standard TPU ring schedule):

- q, k, v are sharded over a mesh axis along the sequence dimension; each
  device keeps its q shard resident and the k/v shards rotate one hop per
  step via ``lax.ppermute`` — P-1 hops ride the ICI ring, overlapping the
  next shard's transfer with the current shard's compute (XLA pipelines
  the permute with the chunk matmuls).
- Each step computes blockwise attention of the local q against the
  visiting k/v chunk, returning a normalized chunk output plus its row
  log-sum-exp; chunks merge in f32 with the online-softmax rescaling, so
  the result is bit-comparable to full attention, not an approximation.
- The per-chunk attention is wrapped in ``jax.checkpoint``: the backward
  pass recomputes chunk scores instead of storing P score matrices, so
  peak memory is O(s_local²) per device regardless of ring size. The scan
  over steps is reverse-differentiable, and ``ppermute``'s transpose is
  itself a ppermute — gradients ride the same ring backwards.
- Causality is decided per (q-shard, kv-chunk) pair by global offsets: a
  fully-masked chunk contributes ``lse ≈ -1e30`` and merges with weight
  exp(-1e30 - lse_total) == 0, so no branching is needed inside the scan.

The Pallas flash kernel (:mod:`dss_ml_at_scale_tpu.ops.flash_attention`)
is the single-device fast path for the same math; the ring path keeps its
chunk compute in plain XLA because the merge needs differentiable
log-sum-exp outputs.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _chunk_attention(q, k, v, q_off, k_off, causal):
    """Attention of a local q shard against one visiting k/v chunk.

    Returns ``(out, lse)``: the chunk-normalized output (f32) and the row
    log-sum-exp (f32) needed to merge chunks exactly. ``q_off``/``k_off``
    are the chunks' global sequence offsets (traced values — causality is
    masked, not branched).
    """
    d = q.shape[-1]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(d))
    if causal:
        qi = q_off + jnp.arange(q.shape[2])[:, None]
        ki = k_off + jnp.arange(k.shape[2])[None, :]
        s = jnp.where(qi >= ki, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)) / l
    lse = (m + jnp.log(l))[..., 0]  # (b, h, sq_local)
    return out, lse


def _merge(o1, lse1, o2, lse2):
    """Exact combination of two chunk-normalized attention outputs."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = w1 + w2
    out = (o1 * w1[..., None] + o2 * w2[..., None]) / denom[..., None]
    return out, m + jnp.log(denom)


def _ring_local(q_l, k_l, v_l, *, axis_name, causal):
    p_sz = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q_l.shape[2]
    perm = [(j, (j + 1) % p_sz) for j in range(p_sz)]

    def step(carry, i):
        # Permute first, then compute: the local (hop-0) chunk is handled
        # outside the scan, so the ring pays exactly p_sz - 1 hops — XLA
        # cannot DCE a trailing collective inside a scan body.
        out, lse, k_c, v_c = carry
        k_c, v_c = jax.lax.ppermute((k_c, v_c), axis_name, perm)
        src = (my - i) % p_sz  # which global chunk is visiting this step
        o_c, lse_c = _chunk_attention(
            q_l, k_c, v_c, my * s_local, src * s_local, causal
        )
        out, lse = _merge(out, lse, o_c, lse_c)
        return (out, lse, k_c, v_c), None

    out0, lse0 = _chunk_attention(
        q_l, k_l, v_l, my * s_local, my * s_local, causal
    )
    (out, _, _, _), _ = jax.lax.scan(
        step, (out0, lse0, k_l, v_l), jnp.arange(1, p_sz)
    )
    return out.astype(q_l.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis_name: str,
    causal: bool = False,
) -> jax.Array:
    """Exact (flash-equivalent) attention, sequence-sharded over ``axis_name``.

    ``q``, ``k``, ``v``: ``[batch, heads, seq, head_dim]`` global arrays
    (jit-traced values are fine); seq must divide evenly by the axis size.
    Returns the attention output with the same sharding layout.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [batch, heads, seq, head_dim], got {q.shape}")
    p_sz = mesh.shape[axis_name]
    if q.shape[2] % p_sz or k.shape[2] % p_sz:
        raise ValueError(
            f"seq lengths {q.shape[2]}/{k.shape[2]} not divisible by "
            f"mesh axis {axis_name!r} size {p_sz}"
        )
    if q.shape[2] != k.shape[2]:
        raise ValueError("ring attention requires sq == sk (self-attention)")
    spec = P(None, None, axis_name, None)
    local = functools.partial(_ring_local, axis_name=axis_name, causal=causal)
    fn = shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
