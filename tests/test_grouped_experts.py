"""The grouped-expert kernels (``ops/grouped_experts.py``), the ungated
one and the gated one, against plain per-token references, at small
widths on the CPU (interpret mode).

The reference here is written from the layer's definition, not from the
kernel's sorted segments: each token's assignments one by one, an
assignment counted only where its row is valid and its expert is held,
``relu(u up)^2`` in float32, cast to the operands' width, times ``down``
with float32 accumulation, times the weight, summed over the token's
experts in expert order. The routings are made as ``routing.route``
makes them (the same sort, counts and starts), from chosen experts.

Tolerance. With float32 operands the kernel and the reference differ by
the order of the products' sums only: about 1e-7 of the largest value
(read), held to ``TOL`` 1e-5. With bfloat16 operands both round the
same float32 ``relu(u up)^2`` to bfloat16 and read 0 apart (read); held
to ``TOL`` as well. The gated kernel's reference is the same walk with
``(silu(x gate) * (x up))`` in float32, cast to the operands' width,
times ``down``; the kernel sums ``down``'s product over blocks of F,
which moves the float32 sums' order only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dss_ml_at_scale_tpu.models.routing import Routing, route
from dss_ml_at_scale_tpu.ops.grouped_experts import (
    BLOCK_F,
    gated_experts,
    grouped_experts,
    row_tile,
)

TOL = 1e-5
LATENT, WIDTH = 32, 24


def routing_of(local, valid, weight, held):
    """A :class:`Routing` of ``[T, k]`` chosen experts ``local`` (an index
    outside ``[0, held)`` is an absent expert), as ``route`` sorts them."""
    t, k = local.shape
    here = (local >= 0) & (local < held) & valid[:, None]
    expert = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    counts = jnp.sum(expert[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)
    starts = jnp.cumsum(counts, dtype=jnp.int32) - counts
    return Routing(weight.reshape(-1).astype(jnp.float32), order, counts,
                   starts, None, expert.astype(jnp.int32))


def per_token(u, up, down, local, valid, weight):
    """The layer by its definition, token by token."""
    u, up, down = (np.asarray(a) for a in (u, up, down))
    held = up.shape[0]
    out = np.zeros(u.shape, np.float32)
    for e in range(held):
        mid = np.square(np.maximum(jnp.matmul(
            u, up[e], preferred_element_type=jnp.float32), 0))
        y = np.asarray(jnp.matmul(jnp.asarray(mid).astype(u.dtype), down[e],
                                  preferred_element_type=jnp.float32))
        for t in range(u.shape[0]):
            for s in range(local.shape[1]):
                if valid[t] and local[t, s] == e:
                    out[t] += np.float32(weight[t, s]) * y[t]
    return out


def weights_of(held, dtype, seed):
    k = jax.random.split(jax.random.key(seed), 3)
    up = (0.3 * jax.random.normal(k[0], (held, LATENT, WIDTH))).astype(dtype)
    down = (0.3 * jax.random.normal(k[1], (held, WIDTH, LATENT))
            ).astype(dtype)
    return up, down


def check(local, valid=None, held=8, dtype=jnp.float32, seed=0):
    local = np.asarray(local, np.int32)
    t, k = local.shape
    valid = np.ones(t, bool) if valid is None else np.asarray(valid)
    rng = np.random.default_rng(seed)
    weight = rng.uniform(0.1, 1.0, (t, k)).astype(np.float32)
    u = jax.random.normal(jax.random.key(seed + 1), (t, LATENT)).astype(dtype)
    up, down = weights_of(held, dtype, seed + 2)
    r = routing_of(jnp.asarray(local), jnp.asarray(valid),
                   jnp.asarray(weight), held)
    got = np.asarray(grouped_experts(u, up, down, r.counts, r.starts,
                                     r.order, r.weight, k=k))
    want = per_token(u, up, down, local, valid, weight)
    scale = max(1.0, float(np.max(np.abs(want))))
    assert np.max(np.abs(got - want)) < TOL * scale
    return got, want, r


def distinct_choices(t, k, width, seed):
    """``k`` distinct experts a token out of ``width``."""
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(width)[:k] for _ in range(t)])


@pytest.mark.parametrize("t", [32, 40, 300])
def test_the_kernel_matches_the_layer_token_by_token(t):
    """Every held expert gets rows; at 300 rows some segments take more
    than one tile and none is a multiple of it."""
    _, _, r = check(distinct_choices(t, 4, 8, seed=t))
    counts = np.asarray(r.counts)
    assert np.all(counts > 0)
    if t == 300:
        tile = row_tile(t)
        assert np.any((counts > tile) & (counts % tile != 0))


def test_experts_with_no_rows_are_skipped():
    """Only experts 2, 3 and 5 of 8 get rows: the first, the last and the
    ones between contribute nothing and break nothing."""
    rng = np.random.default_rng(3)
    local = np.stack([rng.permutation([2, 3, 5])[:2] for _ in range(40)])
    _, _, r = check(local)
    assert [int(c > 0) for c in r.counts] == [0, 0, 1, 1, 0, 1, 0, 0]


def test_every_row_sent_to_one_expert():
    """All 300 rows choose expert 6 (its segment three tiles, the last one
    part full) beside one other."""
    rng = np.random.default_rng(4)
    other = np.array([rng.choice([e for e in range(8) if e != 6])
                      for _ in range(300)])
    local = np.stack([np.full(300, 6), other], axis=1)
    _, _, r = check(local)
    assert int(r.counts[6]) == 300 > 2 * row_tile(300)


def test_absent_experts_and_invalid_rows_contribute_nothing():
    """Choices past the held experts (the other chips' share) and every
    choice of a row that is not valid (an idle slot, padding) are left
    out; a row with none of its choices held comes out 0."""
    local = distinct_choices(48, 4, 20, seed=5) - 6     # held: 0..7
    valid = np.arange(48) % 7 != 2
    got, want, r = check(local, valid)
    held_here = ((local >= 0) & (local < 8)) & valid[:, None]
    assert int(np.sum(r.counts)) == int(np.sum(held_here)) > 0
    empty = ~np.any(held_here, axis=1)
    assert np.any(empty) and np.all(got[empty] == 0)


def test_no_expert_touched_gives_zeros():
    got, _, _ = check(np.full((32, 2), 9))
    assert np.all(got == 0)


@pytest.mark.parametrize("t", [32, 256])
def test_bfloat16_operands_round_as_the_layer(t):
    """A decode shape and a chunk shape at bfloat16 operands: ``relu(u
    up)^2`` rounded to bfloat16 before ``down``, float32 accumulation."""
    check(distinct_choices(t, 4, 8, seed=t + 1), dtype=jnp.bfloat16)


@pytest.mark.parametrize("t", [32, 256])
def test_the_routers_own_assignments(t):
    """The decode step's and a chunk's shapes under ``route`` itself
    (sigmoid scores, a selection bias, 8 held of 16 from offset 4, a
    fifth of the rows not valid): each held assignment's expert read
    back from its segment of ``order``."""
    k = jax.random.split(jax.random.key(t), 4)
    x = jax.random.normal(k[0], (t, 16))
    valid = jnp.arange(t) % 5 != 4
    r = route(x, jax.random.normal(k[1], (16, 16)), valid, k=4, held=8,
              offset=4, score="sigmoid", bias=jnp.zeros(16), scale=2.5)
    local = np.full(t * 4, -1, np.int32)
    for e, (start, count) in enumerate(zip(r.starts, r.counts)):
        local[np.asarray(r.order[start:start + count])] = e
    u = jax.random.normal(k[2], (t, LATENT)).astype(jnp.bfloat16)
    up, down = weights_of(8, jnp.bfloat16, t)
    got = np.asarray(grouped_experts(u, up, down, r.counts, r.starts,
                                     r.order, r.weight, k=4))
    want = per_token(u, up, down, local.reshape(t, 4), np.asarray(valid),
                     np.asarray(r.weight).reshape(t, 4))
    assert np.max(np.abs(want)) > 0.5
    assert np.max(np.abs(got - want)) < TOL * np.max(np.abs(want))
    assert np.all(got[~np.asarray(valid)] == 0)


def _kernel_equations(jaxpr):
    def subs(eqn):
        for param in eqn.params.values():
            for x in (param if isinstance(param, (tuple, list))
                      else (param,)):
                if isinstance(x, jax.extend.core.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jax.extend.core.Jaxpr):
                    yield x

    def count(j):
        return sum(1 + sum(count(s) for s in subs(e)) for e in j.eqns)

    def find(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.params["jaxpr"]
            for sub in subs(eqn):
                found = find(sub)
                if found is not None:
                    return found
        return None

    kernel = find(jaxpr)
    assert kernel is not None
    return count(kernel)


def test_the_kernel_body_is_traced_once():
    """The held experts are the grid and an expert's tiles and rows are
    loops: the kernel's jaxpr holds as many equations at 64 experts over
    1,024 rows as at 4 over 32."""

    def size(t, held, k):
        f32, i32 = jnp.float32, jnp.int32
        shapes = (jax.ShapeDtypeStruct((t, LATENT), jnp.bfloat16),
                  jax.ShapeDtypeStruct((held, LATENT, WIDTH), jnp.bfloat16),
                  jax.ShapeDtypeStruct((held, WIDTH, LATENT), jnp.bfloat16),
                  jax.ShapeDtypeStruct((held,), i32),
                  jax.ShapeDtypeStruct((held,), i32),
                  jax.ShapeDtypeStruct((t * k,), i32),
                  jax.ShapeDtypeStruct((t * k,), f32))
        traced = jax.make_jaxpr(
            lambda *a: grouped_experts(*a, k=k))(*shapes)
        return _kernel_equations(traced.jaxpr)

    assert size(32, 4, 2) == size(1024, 64, 22) == size(300, 16, 4)


# -- the gated kernel ---------------------------------------------------------

# Widths of the gated tests: one 128-lane row slab, F in two blocks.
MODEL, INNER = 128, 2 * BLOCK_F


def gated_per_token(x, gate, up, down, local, valid, weight):
    """The gated layer by its definition, token by token."""
    out = np.zeros(x.shape, np.float32)
    for e in range(gate.shape[0]):
        mid = (jax.nn.silu(jnp.matmul(x, gate[e],
                                      preferred_element_type=jnp.float32))
               * jnp.matmul(x, up[e], preferred_element_type=jnp.float32))
        y = np.asarray(jnp.matmul(mid.astype(x.dtype), down[e],
                                  preferred_element_type=jnp.float32))
        for t in range(x.shape[0]):
            for s in range(local.shape[1]):
                if valid[t] and local[t, s] == e:
                    out[t] += np.float32(weight[t, s]) * y[t]
    return out


def check_gated(local, valid=None, held=8, dtype=jnp.float32, seed=0):
    local = np.asarray(local, np.int32)
    t, k = local.shape
    valid = np.ones(t, bool) if valid is None else np.asarray(valid)
    rng = np.random.default_rng(seed)
    weight = rng.uniform(0.1, 1.0, (t, k)).astype(np.float32)
    keys = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(keys[0], (t, MODEL)).astype(dtype)
    gate, up = ((MODEL ** -0.5 * jax.random.normal(
        key, (held, MODEL, INNER))).astype(dtype) for key in keys[1:3])
    down = (INNER ** -0.5 * jax.random.normal(
        keys[3], (held, INNER, MODEL))).astype(dtype)
    r = routing_of(jnp.asarray(local), jnp.asarray(valid),
                   jnp.asarray(weight), held)
    got = np.asarray(gated_experts(x, gate, up, down, r, k=k))
    want = gated_per_token(x, gate, up, down, local, valid, weight)
    scale = max(1.0, float(np.max(np.abs(want))))
    assert np.max(np.abs(got - want)) < TOL * scale
    return got, want, r


DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["f32", "bf16"])


@DTYPES
def test_the_gated_kernel_at_a_decode_step(dtype):
    """32 rows, top-4 of 16 experts of which 8 are held, every fifth row
    an idle slot (not valid): a row each expert or two, one row tile."""
    valid = np.arange(32) % 5 != 4
    got, _, _ = check_gated(distinct_choices(32, 4, 16, seed=11), valid,
                            dtype=dtype)
    assert np.all(got[~valid] == 0)


@DTYPES
def test_the_gated_kernel_over_several_row_tiles_and_blocks_of_f(dtype):
    """300 rows, top-2 of 4 held experts: every segment is 150 rows in the
    mean, more than one tile of 128 and none a multiple of it; F is two
    blocks."""
    _, _, r = check_gated(distinct_choices(300, 2, 4, seed=12), held=4,
                          dtype=dtype)
    counts = np.asarray(r.counts)
    assert np.all(counts > row_tile(300)) and INNER // BLOCK_F == 2
    assert np.any(counts % row_tile(300) != 0)


@DTYPES
def test_the_gated_kernel_at_a_row_count_off_the_tiling(dtype):
    """37 rows: no multiple of 8 or 16."""
    check_gated(distinct_choices(37, 4, 8, seed=13), dtype=dtype)


@DTYPES
def test_a_held_expert_with_no_rows_costs_nothing(dtype):
    """Only experts 2, 3 and 5 of 8 get rows."""
    rng = np.random.default_rng(14)
    local = np.stack([rng.permutation([2, 3, 5])[:2] for _ in range(40)])
    _, _, r = check_gated(local, dtype=dtype)
    assert [int(c > 0) for c in r.counts] == [0, 0, 1, 1, 0, 1, 0, 0]


@DTYPES
def test_no_held_expert_touched_gives_zeros_gated(dtype):
    got, _, _ = check_gated(np.full((32, 2), 9), dtype=dtype)
    assert np.all(got == 0)


def test_the_gated_kernel_body_is_traced_once():
    """Visits, blocks of F and rows are loops: the gated kernel's jaxpr
    holds as many equations at 16 experts over 1,024 rows with F in four
    blocks as at 4 over 32 with F one block."""

    def size(t, held, width, k):
        f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16
        shapes = (jax.ShapeDtypeStruct((t, MODEL), bf16),
                  jax.ShapeDtypeStruct((held, MODEL, width), bf16),
                  jax.ShapeDtypeStruct((held, MODEL, width), bf16),
                  jax.ShapeDtypeStruct((held, width, MODEL), bf16),
                  jax.ShapeDtypeStruct((held,), i32),
                  jax.ShapeDtypeStruct((held,), i32),
                  jax.ShapeDtypeStruct((t * k,), i32),
                  jax.ShapeDtypeStruct((t * k,), f32),
                  jax.ShapeDtypeStruct((t * k,), i32))

        def layer(x, gate, up, down, counts, starts, order, weight, expert):
            r = Routing(weight, order, counts, starts, None, expert)
            return gated_experts(x, gate, up, down, r, k=k)

        return _kernel_equations(jax.make_jaxpr(layer)(*shapes).jaxpr)

    assert size(32, 4, 24, 2) == size(1024, 16, 4 * BLOCK_F, 4) \
        == size(300, 8, BLOCK_F, 4)
