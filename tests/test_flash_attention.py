"""Flash attention kernel vs the XLA reference (Pallas interpret on CPU),
and the causal block schedule and the row sub-tiles vs the
full-rectangle kernel they replaced, bit for bit."""

import functools
import itertools
import math

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dss_ml_at_scale_tpu.ops import attention_reference, flash_attention
from dss_ml_at_scale_tpu.ops.flash_attention import (
    _NEG_INF,
    TURN_Q,
    _flash_forward,
    block_plan,
    blocks,
)


def _qkv(rng, b=1, h=2, s=256, d=64, dtype=jnp.float32):
    def mk():
        return jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)

    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_matches_reference(rng, causal):
    q, k, v = _qkv(rng)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_multiple_k_blocks_online_softmax(rng):
    # 4 k-blocks exercise the running-max/denominator rescaling path.
    q, k, v = _qkv(rng, s=256)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_bf16_inputs(rng):
    q, k, v = _qkv(rng, s=128, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=2e-2
    )


def test_gradients_match_reference(rng):
    q, k, v = _qkv(rng, s=128, d=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_jit_and_small_seq_block_clamp(rng):
    # seq < block: blocks clamp to seq, still jittable.
    q, k, v = _qkv(rng, s=64, d=32)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
    np.testing.assert_allclose(out, attention_reference(q, k, v), atol=2e-5)


def test_causal_cross_attention_bottom_right_aligned(rng):
    # Decode-with-cache shape: fewer queries than keys. Bottom-right
    # alignment means the last query row sees ALL keys.
    q, _, _ = _qkv(rng, s=64, d=32)
    _, k, v = _qkv(rng, s=256, d=32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # Row parity with a manual full-context softmax for the last row.
    import math

    s_last = (q[0, 0, -1] @ k[0, 0].T) / math.sqrt(32)
    manual = jax.nn.softmax(s_last) @ v[0, 0]
    np.testing.assert_allclose(out[0, 0, -1], manual, atol=2e-5, rtol=2e-5)


def test_rejects_ragged_seq(rng):
    q, k, v = _qkv(rng, s=100)
    with pytest.raises(ValueError, match="multiples"):
        flash_attention(q, k, v, block_q=64, block_k=64)


def test_rejects_causal_sq_gt_sk(rng):
    # Bottom-right-aligned causal with sq > sk leaves the first sq - sk
    # query rows with zero visible keys (0/0 softmax); must be rejected.
    q, _, _ = _qkv(rng, s=64)
    _, k, v = _qkv(rng, s=32)
    with pytest.raises(ValueError, match="sq <= sk"):
        flash_attention(q, k, v, causal=True)


# -- the causal block schedule against the full rectangle (PR 35) --------------


def _rectangle_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      causal, block_q, block_k, scale, causal_offset):
    """The kernel as it was before PR 35: every (q-block, k-block) pair of
    the rectangle is a grid step, dead ones skipped under ``pl.when``, and
    every live causal pair masked."""
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    live = (not causal) or (j * block_k < (i + 1) * block_q + causal_offset)

    @pl.when(live)
    def _step():
        s = jnp.dot(q_ref[0], k_ref[0].T,
                    preferred_element_type=jnp.float32) * scale
        if causal:
            qi = causal_offset + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            ki = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qi >= ki, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p, v_ref[0], preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


def _rectangle(q, k, v, *, causal, block_q, block_k):
    """The oracle: the full-rectangle kernel in interpret mode."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    kernel = functools.partial(
        _rectangle_kernel, causal=causal, block_q=block_q, block_k=block_k,
        scale=1.0 / math.sqrt(d), causal_offset=sk - sq)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        interpret=True,
    )(q, k, v)


# Head size 64: the scale 1/8 is exact, so ``s·scale − m`` rounds once
# whether or not XLA's CPU backend fuses it into one multiply-add, which
# it does where no select stands between the two (the unmasked pairs) and
# cannot where one does (the masked). At head size 128 the two differ in
# the last bit on the CPU; the chip's comparison at 128 is in PERF.md.
_BK = 32
_D = 64


def _bits(rng, sq, sk, dtype=jnp.float32, bh=2):
    def mk(s):
        return jnp.asarray(rng.normal(size=(bh, s, _D)), dtype)

    return mk(sq), mk(sk), mk(sk)


@pytest.mark.parametrize("ratio", [0.5, 1, 2], ids=["half", "equal", "twice"])
@pytest.mark.parametrize("k_blocks", [1, 2, 4, 16])
def test_causal_schedule_is_bitwise_the_full_rectangle(rng, k_blocks, ratio):
    """``sq = sk`` at 1, 2, 4 and 16 k-blocks; ``block_q`` at half, equal
    and twice ``block_k``: dead pairs add nothing and an unmasked pair is
    ``where(True, s, …)``."""
    s = k_blocks * _BK
    bq = min(int(ratio * _BK), s)
    q, k, v = _bits(rng, s, s, bh=1 if k_blocks == 16 else 2)
    got = _flash_forward(q, k, v, causal=True, block_q=bq, block_k=_BK,
                         interpret=True)
    want = _rectangle(q, k, v, causal=True, block_q=bq, block_k=_BK)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("ratio", [0.5, 1, 2], ids=["half", "equal", "twice"])
@pytest.mark.parametrize("sq, sk", [(64, 256), (128, 192)])
def test_bottom_right_offset_is_bitwise_the_full_rectangle(rng, sq, sk,
                                                           ratio):
    q, k, v = _bits(rng, sq, sk, jnp.bfloat16)
    bq = int(ratio * _BK)
    got = _flash_forward(q, k, v, causal=True, block_q=bq, block_k=_BK,
                         interpret=True)
    want = _rectangle(q, k, v, causal=True, block_q=bq, block_k=_BK)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("bq", [16, 64, 128])
def test_block_q_moves_no_bit(rng, bq):
    """A row sees the same k-blocks in the same order at any ``block_q``:
    the output at every q-block equals the full rectangle's at
    ``block_q == block_k``."""
    q, k, v = _bits(rng, 128, 128)
    got = _flash_forward(q, k, v, causal=True, block_q=bq, block_k=_BK,
                         interpret=True)
    want = _rectangle(q, k, v, causal=True, block_q=_BK, block_k=_BK)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("ratio", [0.5, 1, 2], ids=["half", "equal", "twice"])
def test_non_causal_path_is_unchanged(rng, ratio):
    q, k, v = _bits(rng, 128, 128)
    bq = int(ratio * _BK)
    got = _flash_forward(q, k, v, causal=False, block_q=bq, block_k=_BK,
                         interpret=True)
    want = _rectangle(q, k, v, causal=False, block_q=bq, block_k=_BK)
    np.testing.assert_array_equal(got, want)


# -- a q-block walked in row sub-tiles ------------------------------------------
# A sub-tile is a quarter of the k-block's rows: 64 at ``block_k`` 256.


@pytest.mark.parametrize("sq, sk, bq, causal", [
    (1024, 1024, 512, True),      # a q-block of eight sub-tiles, one turn
    (2048, 2048, 1024, True),     # two turns: the rolled loop
    (512, 1024, 512, True),       # bottom-right offset
    (512, 512, 512, False),       # every pair, nothing masked
    (512, 512, 64, True),         # a q-block of exactly one sub-tile
], ids=["causal", "two_turns", "bottom_right", "non_causal", "one_sub_tile"])
def test_sub_tiles_match_reference_and_full_rectangle(rng, sq, sk, bq,
                                                      causal):
    q, k, v = _bits(rng, sq, sk, bh=1)
    got = _flash_forward(q, k, v, causal=causal, block_q=bq, block_k=256,
                         interpret=True)
    np.testing.assert_allclose(
        got, attention_reference(q, k, v, causal=causal), atol=2e-6)
    want = _rectangle(q, k, v, causal=causal, block_q=bq, block_k=256)
    np.testing.assert_array_equal(got, want)


def test_sub_tiles_before_a_k_blocks_first_key_leave_their_rows(rng):
    """q-blocks of eight sub-tiles over k-blocks of half their rows: on
    the pair (i, 2i + 1) the q-block's first four sub-tiles lie wholly
    before the k-block's first key, so their rows' update is alpha = 1,
    p = 0."""
    sq, bq, bk = 1024, 512, 256
    plan = block_plan(sq, sq, bq, bk, causal=True)
    dead = [(i, j) for i, j, st in zip(plan.q_block, plan.k_block,
                                       plan.straddles)
            if st and j * bk - i * bq >= bk // 4]
    assert dead == [(i, 2 * i + 1) for i in range(sq // bq)]
    q, k, v = _bits(rng, sq, sq)
    got = _flash_forward(q, k, v, causal=True, block_q=bq, block_k=bk,
                         interpret=True)
    ref = attention_reference(q, k, v, causal=True)
    for i in range(sq // bq):  # the rows of those sub-tiles
        rows = slice(i * bq, i * bq + bk)
        np.testing.assert_allclose(got[:, rows], ref[:, rows], atol=2e-6)
    np.testing.assert_array_equal(
        got, _rectangle(q, k, v, causal=True, block_q=bq, block_k=bk))


def _sub_jaxprs(eqn):
    for param in eqn.params.values():
        for x in param if isinstance(param, (tuple, list)) else (param,):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def _kernel_jaxpr(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["jaxpr"]
        for sub in _sub_jaxprs(eqn):
            found = _kernel_jaxpr(sub)
            if found is not None:
                return found
    return None


def _equations(jaxpr):
    return sum(1 + sum(_equations(s) for s in _sub_jaxprs(e))
               for e in jaxpr.eqns)


@pytest.mark.parametrize("causal", [True, False])
def test_kernel_body_is_traced_once_whatever_the_sub_tile_count(causal):
    """The walk over a q-block's sub-tiles is a loop, not a Python
    unroll: the kernel's jaxpr holds as many equations at a q-block of
    two turns as at one of eight, so set-up does not grow with the
    q-block."""
    x = jax.ShapeDtypeStruct((1, 2, 8 * TURN_Q, 128), jnp.bfloat16)

    def size(bq):
        traced = jax.make_jaxpr(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=bq))(x, x, x)
        kernel = _kernel_jaxpr(traced.jaxpr)
        assert kernel is not None
        return _equations(kernel)

    assert size(2 * TURN_Q) == size(8 * TURN_Q)


def _brute_force(sq, sk, bq, bk, causal):
    """Per pair, from the element mask: live if any key is visible to any
    row, masked if live and some key is hidden from some row."""
    rows = np.arange(sq)[:, None] + (sk - sq)
    visible = (np.arange(sk)[None, :] <= rows) if causal else np.ones(
        (sq, sk), bool)
    live, masked = [], []
    for i, j in itertools.product(range(sq // bq), range(sk // bk)):
        tile = visible[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
        if tile.any():
            live.append((i, j))
            if not tile.all():
                masked.append((i, j))
    return live, masked


@pytest.mark.parametrize("sq, sk, bq, bk, causal", [
    (8192, 8192, 256, 512, True),    # the doc cell's largest bucket today
    (8192, 8192, 512, 512, True),
    (8192, 8192, 1024, 512, True),
    (2048, 2048, 256, 512, True),
    (1024, 1024, 1024, 512, True),
    (512, 512, 512, 512, True),      # one k-block: every pair straddles
    (128, 1024, 64, 256, True),      # bottom-right offset
    (1024, 1024, 256, 512, False),   # the whole rectangle, nothing masked
])
def test_block_plan_matches_a_brute_force_count(sq, sk, bq, bk, causal):
    plan = block_plan(sq, sk, bq, bk, causal)
    live, masked = _brute_force(sq, sk, bq, bk, causal)
    assert plan.grid == (sq // bq) * (sk // bk)
    assert list(zip(plan.q_block.tolist(), plan.k_block.tolist())) == live
    assert plan.visited == len(live)
    assert plan.masked == len(masked)
    assert [pair for pair, st in zip(live, plan.straddles) if st] == masked
    # a q-block's pairs run from k-block 0, contiguous; ``last`` ends each
    ends = [t for t, last in enumerate(plan.last) if last]
    assert [plan.q_block[t] for t in ends] == list(range(sq // bq))
    starts = [0] + [t + 1 for t in ends[:-1]]
    assert all(plan.k_block[t] == 0 for t in starts)
