"""``ops.decode_attention``: both forms against the whole-slab masked
einsum, which lives here as the oracle (it was ``TransformerBlock``'s
decode branch until PR 32).

On the CPU the kernel runs in Pallas interpret mode; shapes that do not
tile take the function's einsum form. Dead rows (past ``pos``: the tail of
the last live block, every later block) are filled with large finite
values, so one that reaches a score or a sum shows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dss_ml_at_scale_tpu.ops import decode_attention as da
from dss_ml_at_scale_tpu.ops.decode_attention import (
    BLOCK,
    decode_attention,
    rows_fetched,
    tiles,
)

MAX_LEN = 3 * BLOCK
DEAD = 3.0e4  # finite in bfloat16; exp() of a score made from it is not


def oracle(q, k_cache, v_cache, pos):
    """One query a slot against all ``max_len`` rows under ``<= pos``,
    the values widened to float32: the decode branch as it stood."""
    b, _, max_len, head_dim = k_cache.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q[:, :, None], k_cache,
        preferred_element_type=jnp.float32,
    ) / jnp.sqrt(head_dim).astype(jnp.float32)
    mask = jnp.arange(max_len)[None, :] <= pos[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", probs, v_cache.astype(jnp.float32)
    ).astype(q.dtype)[:, :, 0]


def slabs(seed, b, heads, max_len, head_dim, pos, dtype=jnp.bfloat16):
    """q, k, v with every row past a slot's ``pos`` set to ``DEAD``."""
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (b, heads, head_dim), dtype)
    k = jax.random.normal(kk, (b, heads, max_len, head_dim), dtype)
    v = jax.random.normal(kv, (b, heads, max_len, head_dim), dtype)
    pos = np.broadcast_to(np.asarray(pos, np.int32), (b,))
    dead = np.arange(max_len)[None, :] > pos[:, None]
    dead = jnp.asarray(dead)[:, None, :, None]
    return q, jnp.where(dead, DEAD, k), jnp.where(dead, DEAD, v)


def close(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # Both round a float32 result to bfloat16; the sums differ in order.
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("pos", [
    0, BLOCK - 1, BLOCK, BLOCK + 1, MAX_LEN - 1,
], ids=lambda p: f"pos{p}")
def test_the_kernel_at_the_edges_of_a_block(pos):
    assert tiles((2, 4, MAX_LEN, 128), jnp.bfloat16)
    q, k, v = slabs(0, 2, 4, MAX_LEN, 128, pos)
    vec = jnp.full((2,), pos, jnp.int32)
    close(decode_attention(q, k, v, vec), oracle(q, k, v, vec))


def test_the_kernel_with_mixed_lengths_across_slots():
    pos = np.array([0, 5, BLOCK - 1, BLOCK, 2 * BLOCK + 7, MAX_LEN - 1],
                   np.int32)
    q, k, v = slabs(1, len(pos), 2, MAX_LEN, 128, pos)
    close(decode_attention(q, k, v, jnp.asarray(pos)),
          oracle(q, k, v, pos))


@pytest.mark.parametrize("head_dim, max_len", [
    (128, 2 * BLOCK),       # tiles: the kernel
    (16, 2 * BLOCK),        # a head narrower than the lanes: the einsum
    (128, BLOCK + 8),       # a slab that is no whole number of blocks
    (32, 24),               # the tiny models of the serving tests
])
def test_a_scalar_pos_is_every_slot_at_that_position(head_dim, max_len):
    pos = max_len // 2
    q, k, v = slabs(2, 3, 2, max_len, head_dim, pos)
    got = decode_attention(q, k, v, pos)
    assert got.shape == q.shape and got.dtype == q.dtype
    close(got, oracle(q, k, v, pos))
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(decode_attention(q, k, v, jnp.full((3,), pos)),
                   np.float32))


@pytest.mark.parametrize("heads, head_dim, max_len, kernel", [
    (4, 128, 2 * BLOCK, True),
    (3, 128, BLOCK, True),          # heads no multiple of the sublanes
    (2, 256, BLOCK, True),
    (4, 64, 2 * BLOCK, False),
    (4, 128, BLOCK // 2, False),
    (4, 96, 40, False),
])
def test_shapes_decide_the_form(monkeypatch, heads, head_dim, max_len,
                                kernel):
    """Which form ran is seen, not inferred: the other one is broken."""
    shape = (2, heads, max_len, head_dim)
    assert tiles(shape, jnp.bfloat16) == kernel

    def broken(*a, **kw):
        raise AssertionError("the other form was taken")

    monkeypatch.setattr(da, "_whole_slab" if kernel else "_blockwise",
                        broken)
    pos = np.array([max_len - 1, max_len // 3], np.int32)
    q, k, v = slabs(3, *shape, pos)
    close(decode_attention(q, k, v, jnp.asarray(pos)),
          oracle(q, k, v, pos))


def test_a_block_too_large_for_fast_memory_takes_the_einsum():
    # 80 heads x 256 rows x 128 x 2 B = 5 MiB a block.
    assert not tiles((1, 80, 2048, 128), jnp.bfloat16)
    assert tiles((16, 16, 2048, 128), jnp.bfloat16)  # the chat cell's
    assert tiles((16, 16, 2048, 128), jnp.float32)


def test_float32_slabs_take_the_kernel_too():
    pos = np.array([3, BLOCK + 3], np.int32)
    q, k, v = slabs(4, 2, 2, 2 * BLOCK, 128, pos, jnp.float32)
    got = decode_attention(q, k, v, jnp.asarray(pos))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(oracle(q, k, v, pos)),
        atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("head_dim", [128, 32], ids=["kernel", "einsum"])
def test_a_slot_depends_on_its_own_rows_and_pos_only(head_dim):
    """What the engine's bitwise parity leans on: the other slots' rows
    and positions, and the slab's length past the slot's last live
    block, reach nothing."""
    max_len = 2 * BLOCK
    pos = np.array([BLOCK // 2, 7, BLOCK + 1], np.int32)
    q, k, v = slabs(5, 3, 2, max_len, head_dim, pos)
    base = np.asarray(decode_attention(q, k, v, jnp.asarray(pos)),
                      np.float32)

    # Other slots: new rows, new positions, and fewer of them.
    q2, k2, v2 = slabs(6, 3, 2, max_len, head_dim,
                       np.array([BLOCK // 2, max_len - 1, 0], np.int32))
    mixed = [jnp.concatenate([a[:1], b[1:]]) for a, b in
             ((q, q2), (k, k2), (v, v2))]
    got = decode_attention(
        *mixed, jnp.asarray([BLOCK // 2, max_len - 1, 0], jnp.int32))
    np.testing.assert_array_equal(np.asarray(got, np.float32)[0], base[0])
    alone = decode_attention(q[:1], k[:1], v[:1], jnp.asarray(pos[:1]))
    np.testing.assert_array_equal(np.asarray(alone, np.float32)[0], base[0])

    if not tiles(k.shape, k.dtype):
        return  # the einsum form sums over the whole slab, as it always has
    # A longer slab: two more blocks of dead rows behind every slot.
    def grow(a):
        return jnp.concatenate([a, jnp.full(a.shape, DEAD, a.dtype)], axis=2)

    longer = decode_attention(q, grow(k), grow(v), jnp.asarray(pos))
    np.testing.assert_array_equal(np.asarray(longer, np.float32), base)


def test_a_pos_past_the_slab_reads_every_row_and_no_further():
    """A row the engine computes only to throw away (``kvcache.
    slot_decode``): every row is live, no block index leaves the slab."""
    q, k, v = slabs(7, 2, 2, 2 * BLOCK, 128, 2 * BLOCK - 1)
    pos = jnp.asarray([2 * BLOCK + 5, 9 * BLOCK], jnp.int32)
    close(decode_attention(q, k, v, pos), oracle(q, k, v, pos))


@pytest.mark.parametrize("pos, shape, want", [
    # the kernel: each slot's live blocks
    ([0, BLOCK - 1, BLOCK, 3 * BLOCK - 1], (4, 16, 4 * BLOCK, 128),
     (1 + 1 + 2 + 3) * BLOCK),
    # a position past the slab fetches the slab, not more
    ([9 * BLOCK], (1, 16, 2 * BLOCK, 128), 2 * BLOCK),
    # the einsum: the whole slab for every slot
    ([0, 5, 63], (3, 4, 64, 16), 3 * 64),
])
def test_rows_fetched_counts_what_the_form_reads(pos, shape, want):
    assert rows_fetched(np.asarray(pos, np.int32), shape,
                        jnp.bfloat16) == want


def test_the_model_decodes_through_the_kernel_where_its_shapes_tile():
    """``TransformerLM`` at a head of 128 and an arena of whole blocks:
    ``decode_slots`` (a ``pos`` vector) and ``decode_step`` (a scalar)
    against the full-context forward pass."""
    from dss_ml_at_scale_tpu.models.transformer import (
        TransformerLM,
        decode_step,
    )

    model = TransformerLM(vocab_size=64, dim=256, num_heads=2, num_layers=2,
                          max_seq=BLOCK, dtype=jnp.float32,
                          attention="reference")
    assert tiles((2, 2, BLOCK, 128), jnp.float32)
    tokens = jax.random.randint(jax.random.key(0), (2, 12), 0, 64)
    variables = model.init(jax.random.key(1), tokens)
    full = model.apply(variables, tokens)

    step = jax.jit(lambda toks, cache, pos: decode_step(
        model, variables, toks, cache, pos))
    slots = jax.jit(lambda toks, cache, pos: model.decode_slots(
        variables, toks, cache, pos))
    cache = model.init_cache(2, BLOCK)
    for t in range(12):
        logits, cache = step(tokens[:, t:t + 1], cache, t)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, t]),
                                   atol=2e-4, rtol=2e-4)

    # Two slots at different positions: slot 1 runs three tokens behind.
    cache = model.init_cache(2, BLOCK)
    for t in range(12):
        pos = jnp.asarray([t, max(t - 3, 0)], jnp.int32)
        toks = jnp.stack([tokens[0, t], tokens[1, max(t - 3, 0)]])
        logits, _, cache = slots(toks, cache, pos)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(full[0, t]),
                                   atol=2e-4, rtol=2e-4)
        if t >= 3:
            np.testing.assert_allclose(np.asarray(logits[1]),
                                       np.asarray(full[1, t - 3]),
                                       atol=2e-4, rtol=2e-4)
    assert model.decode_rows_read(np.asarray([11, 8]), cache) == 2 * BLOCK


@pytest.mark.parametrize("dim, max_len, kernel", [
    (256, 2 * BLOCK, True),     # heads of 128, an arena of whole blocks
    (64, 48, False),            # the serving tests' size: the einsum
], ids=["kernel", "einsum"])
def test_the_decoder_counts_the_rows_its_steps_fetch(dim, max_len, kernel):
    """``lm_decode_cache_rows_total{kind}``: a step adds the rows its
    attention fetches a layer and the arena's ``slots x max_len``; the
    two are equal where the op took its einsum form."""
    from dss_ml_at_scale_tpu import telemetry
    from dss_ml_at_scale_tpu.models.transformer import TransformerLM
    from dss_ml_at_scale_tpu.serving.lm import TransformerDecoder

    def rows(kind):
        for m in telemetry.snapshot()["metrics"]:
            if (m["name"] == "lm_decode_cache_rows_total"
                    and m["labels"] == {"kind": kind}):
                return m["value"]
        return 0

    slots = 3
    model = TransformerLM(vocab_size=32, dim=dim, num_heads=2, num_layers=1,
                          max_seq=max_len, dtype=jnp.float32,
                          attention="reference")
    assert tiles((slots, 2, max_len, dim // 2), jnp.float32) == kernel
    variables = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    decoder = TransformerDecoder(model, variables, slots=slots,
                                 max_len=max_len, buckets=(4,))
    read0, arena0 = rows("read"), rows("arena")
    steps = [np.array([0, 5, max_len - 1], np.int32),
             np.array([1, 6, 0], np.int32)]
    for pos in steps:
        decoder.fetch(decoder.dispatch(np.zeros(slots, np.int32), pos))
    assert rows("arena") - arena0 == 2 * slots * max_len
    if kernel:
        assert rows("read") - read0 == (1 + 1 + 2 + 1 + 1 + 1) * BLOCK
    else:
        assert rows("read") - read0 == 2 * slots * max_len
