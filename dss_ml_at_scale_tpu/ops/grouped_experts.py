"""Pallas TPU grouped-expert kernels: one expert layer's held experts over
their segments of the sorted assignments, in one call. Two forms, one a
kind of expert, each with its own body and call: which one a model runs
follows from the weights it holds (a ``gate`` or none).

**The ungated form** (``grouped_experts``; Nemotron-H's latent experts).
For rows ``u`` ``[T, L]`` (the layer's latent input) and the held
experts' stacked matrices ``up`` ``[E, L, F]`` and ``down`` ``[E, F, L]``,
the result is, at each token's row,

    out[t] = sum over held assignments a of token t:
             weight[a] * (relu(u[t] up[e_a])^2 down[e_a])

in float32: ``relu(x up)^2`` is taken in float32, cast to the operands'
width, and multiplied by ``down`` with float32 accumulation, the rounding
of the per-expert loop it replaces. The assignments come from
:func:`..models.routing.route`: ``order`` sorts the ``T x k`` assignments
by held expert (those of absent experts and of rows that are not valid
past the last), ``counts`` and ``starts`` give each held expert's
segment. An assignment past the held segments contributes nothing.

Design (pallas_guide.md patterns):

- Grid ``(E,)``, one step a held expert, touched experts first in expert
  order (a scalar-prefetched list). A step's ``up`` and ``down`` blocks
  are that expert's whole matrices, so each touched expert's weights are
  read from HBM once a call and the pipeline fetches the next touched
  expert's under the current one's work. The steps past the touched
  experts name the last touched expert's blocks again: nothing is
  fetched, and their body does nothing.
- ``u`` (as float32) and the float32 output stay in VMEM for the whole
  call (one block each, the same index at every step): no ``T x k``
  intermediate goes to HBM. Both are held in groups of 8 rows
  (``[T / 8, 8, L]``, a float32 sublane tile), since a dynamic index
  reaches a group where it cannot reach one unaligned row. A step walks
  its expert's segment in tiles of ``tile`` rows under a loop traced
  once: each row of a tile is picked out of its token's group by a
  sublane mask, the tile goes through the two products, and each of its
  rows is added, times its weight, into its token's group under the
  same mask. The experts follow one another in expert order, so a
  token's sum over its experts keeps that order.
- The token index and weight of each sorted assignment, each expert's
  segment and the list of touched experts are scalar-prefetched into
  SMEM.
- ``_experts_call`` is jitted, so a program that calls the kernel once a
  layer traces and lowers it once a shape. The call is named
  ``moe_experts``, which is how a device trace names its operation.

**The gated form** (``gated_experts``; the latent-attention model's SiLU
experts). For rows ``x`` ``[T, d]`` and stacked ``gate``, ``up``
``[E, d, F]`` and ``down`` ``[E, F, d]``,

    out[t] = sum over held assignments a of token t:
             weight[a] * ((silu(x[t] gate[e_a]) * (x[t] up[e_a])) down[e_a])

with ``silu(x gate) * (x up)`` in float32, cast to the operands' width,
times ``down`` with float32 accumulation, and the experts added into a
float32 sum in expert order: the rounding of the per-expert loop it
replaces. At the widths it serves (d 4,096, F 2,048) one expert's three
matrices are 48 MiB and the rows of a whole prompt as float32 128 MiB, so
neither the weights whole nor the rows stay in VMEM:

- One grid step. A **visit** is a touched expert's segment in tiles of
  ``tile`` rows (``row_tile``): one visit an expert wherever its segment
  fits one tile, and none for an expert with no row. The kernel walks
  the visits in expert order and, in each, F in blocks of ``BLOCK_F``
  columns: ``silu(x gate_f) * (x up_f)`` is separable over F, and
  ``down_f``'s products add up over the blocks in float32. Each block's
  ``gate``, ``up`` and ``down`` come by DMA into one of two buffers, the
  next block's fetch started before the current block's products (the
  rest of this expert, or the next visit's first block), so the weights
  stream once a visit under the products.
- The rows come from HBM one DMA a row into the visit's tile, fetched
  under the previous visit's products. The layer's input is handed over
  as ``[T, d / 128, 128]``, so a row is whole tiles (a one-row slice of a
  2-D array in HBM is not aligned to its tiling).
- Each row of a finished visit is scaled by its weight and written to its
  token's row of the output, one DMA a row: by the token's first held
  expert as it is, by a later one added to the sum read back (a row's
  code carries which). A visit's writes land before the next visit reads
  back or refills its buffer. A token that no held expert took is never
  written; ``gated_experts`` gives it 0 outside the kernel.
- Each visit's expert and row count, each row's token code and weight
  are scalar-prefetched into SMEM. Visits, blocks and rows are loops
  traced once, and ``_gated_call`` is jitted: a program lowers the
  kernel once a shape. The call is named ``moe_experts`` too.

On a CPU backend (tests) the kernel runs in Pallas interpret mode
(``_pallas.resolve_interpret``); on ``tpu`` it is always compiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ._pallas import resolve_interpret

# Most rows of one tile of an expert's segment.
TILE = 128
# Scoped VMEM a call may use. The ungated form: two buffers of one
# expert's two matrices (22 MB at Nemotron-3-Super's widths), the resident
# rows and output, and a tile's temporaries. The gated form: two buffers
# of a block's three matrices (24 MiB at Mistral-Small-4's widths) and a
# tile's rows, sums and temporaries.
VMEM_LIMIT = 96 * 1024 * 1024


def _experts_kernel(ids_ref, touched_ref, counts_ref, starts_ref, token_ref,
                    weight_ref, u_ref, up_ref, down_ref, out_ref, x_ref,
                    y_ref, *, tile):
    i = pl.program_id(0)
    latent = u_ref.shape[-1]
    # Row ``t`` is sublane ``t % 8`` of group ``t // 8``.
    sub = lax.broadcasted_iota(jnp.int32, (8, latent), 0)
    eight, zero, none = np.int32(8), np.float32(0), np.int32(0)
    step = np.int32(tile)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
        x_ref[...] = jnp.zeros(x_ref.shape, x_ref.dtype)

    @pl.when(i < touched_ref[0])
    def _expert():
        e = ids_ref[i]
        count, start = counts_ref[e], starts_ref[e]

        def one_tile(j, carry):
            first = start + j * step
            rows = jnp.minimum(step, count - j * step)
            groups = (rows + np.int32(7)) // eight

            def gather(g, c):
                group = x_ref[g]
                for q in range(8):
                    # Past the segment's end the last row again: computed,
                    # never added.
                    t = token_ref[first + jnp.minimum(g * eight + np.int32(q),
                                                      rows - np.int32(1))]
                    row = jnp.sum(jnp.where(sub == t % eight,
                                            u_ref[t // eight], zero),
                                  axis=0, keepdims=True)
                    group = jnp.where(sub == np.int32(q), row, group)
                x_ref[g] = group
                return c

            lax.fori_loop(none, groups, gather, None)
            x = x_ref[...].reshape(tile, latent).astype(up_ref.dtype)
            mid = jnp.square(jnp.maximum(jnp.dot(
                x, up_ref[0], preferred_element_type=jnp.float32), zero))
            y = jnp.dot(mid.astype(up_ref.dtype), down_ref[0],
                        preferred_element_type=jnp.float32)
            y_ref[...] = y.reshape(tile // 8, 8, latent)

            def scatter(g, c):
                group = y_ref[g]
                for q in range(8):
                    r = g * eight + np.int32(q)

                    @pl.when(r < rows)
                    def _add():
                        t = token_ref[first + r]
                        row = group[q:q + 1, :] * weight_ref[first + r]
                        out_ref[t // eight] += jnp.where(sub == t % eight,
                                                         row, zero)
                return c

            lax.fori_loop(none, groups, scatter, None)
            return carry

        lax.fori_loop(none, (count + step - np.int32(1)) // step, one_tile,
                      None)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _experts_call(ids, touched, counts, starts, token, weight, u, up, down,
                  *, tile, interpret):
    from jax.experimental.pallas import tpu as pltpu

    groups, _, latent = u.shape
    experts, _, width = up.shape

    def whole(i, *_):
        return (0, 0, 0)

    def expert(i, ids, *_):
        return (ids[i], 0, 0)

    return pl.pallas_call(
        functools.partial(_experts_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(experts,),
            in_specs=[
                pl.BlockSpec((groups, 8, latent), whole),        # u
                pl.BlockSpec((1, latent, width), expert),        # up
                pl.BlockSpec((1, width, latent), expert),        # down
            ],
            out_specs=pl.BlockSpec((groups, 8, latent), whole),
            scratch_shapes=[pltpu.VMEM((tile // 8, 8, latent), jnp.float32),
                            pltpu.VMEM((tile // 8, 8, latent), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, 8, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_experts",
    )(ids, touched, counts, starts, token, weight, u, up, down)


def row_tile(t: int) -> int:
    """Rows of one tile of an expert's segment, for ``t`` rows: at most
    ``TILE``, and ``t`` rounded up to 16 (a bfloat16 sublane tile)."""
    return min(TILE, -(-t // 16) * 16)


def grouped_experts(u, up, down, counts, starts, order, weight, *, k: int,
                    interpret: bool | None = None):
    """The held experts of one layer over their segments of the sorted
    assignments, as the kernel.

    ``u`` ``[T, L]``; ``up`` ``[E, L, F]``, ``down`` ``[E, F, L]`` at
    ``u``'s width; ``counts``, ``starts`` ``[E]`` int32 and ``order``
    ``[T * k]`` int32, ``weight`` ``[T * k]`` float32 (row-major by
    token): a :class:`..models.routing.Routing`'s. Returns ``[T, L]``
    float32."""
    interpret = resolve_interpret(interpret)
    experts = up.shape[0]
    i32 = jnp.int32
    busy = counts > 0
    touched = jnp.sum(busy, dtype=i32)
    # The touched experts first, in expert order; the steps past them
    # repeat the last touched one (expert 0 where none is).
    ids = jnp.argsort(~busy, stable=True).astype(i32)
    ids = jnp.where(jnp.arange(experts) < touched, ids,
                    ids[jnp.maximum(touched - 1, 0)])
    t, latent = u.shape
    padded = -(-t // 8) * 8
    rows = jnp.pad(u.astype(jnp.float32), ((0, padded - t), (0, 0)))
    out = _experts_call(
        ids, touched.reshape(1), counts.astype(i32), starts.astype(i32),
        (order // np.int32(k)).astype(i32), weight[order].astype(jnp.float32),
        rows.reshape(padded // 8, 8, latent), up.astype(u.dtype),
        down.astype(u.dtype), tile=row_tile(t), interpret=interpret)
    return out.reshape(padded, latent)[:t]


# -- the gated form -----------------------------------------------------------

# Columns of one block of the intermediate width F, where it divides F.
BLOCK_F = 512
# Buffers of the blocks' pipeline: the fetch of the block this many less
# one ahead runs under each block's products. On one v5e chip a third
# buffer read no faster at the decode step's shape or a chunk's.
BUFFERS = 2


def _gated_kernel(nv_ref, vexp_ref, vrows_ref, code_ref, w_ref, x_hbm,
                  gate_hbm, up_hbm, down_hbm, out_hbm, xbuf, xs, wg, wu, wd,
                  acc, obuf, ibuf, sem_w, sem_x, sem_r, sem_o, *, tile,
                  block, blocks):
    from jax.experimental.pallas import tpu as pltpu

    nv = nv_ref[0]
    zero, one = np.int32(0), np.int32(1)
    last = np.int32(blocks - 1)

    def weights(v, f, slot):
        """Block ``f`` of visit ``v``'s expert: its three DMAs."""
        e = vexp_ref[v]
        cols = pl.ds(pl.multiple_of(f * np.int32(block), block), block)
        return (pltpu.make_async_copy(gate_hbm.at[e, :, cols], wg.at[slot],
                                      sem_w.at[slot]),
                pltpu.make_async_copy(up_hbm.at[e, :, cols], wu.at[slot],
                                      sem_w.at[slot]),
                pltpu.make_async_copy(down_hbm.at[e, cols, :], wd.at[slot],
                                      sem_w.at[slot]))

    def rows(v, body):
        """``body(r, code)`` for each row ``r`` of visit ``v``."""
        def one_row(r, c):
            body(r, code_ref[v * np.int32(tile) + r])
            return c

        lax.fori_loop(zero, vrows_ref[v], one_row, None)

    # A row's code is its token twice, plus one where an earlier expert
    # of the token wrote its row already.
    def fetch(r, code):
        return pltpu.make_async_copy(x_hbm.at[code >> one], xbuf.at[r],
                                     sem_x.at[0])

    def read(r, code):
        return pltpu.make_async_copy(out_hbm.at[code >> one], ibuf.at[r],
                                     sem_r.at[0])

    def write(r, code):
        return pltpu.make_async_copy(obuf.at[r], out_hbm.at[code >> one],
                                     sem_o.at[0])

    def if_back(fn):
        def body(r, code):
            @pl.when((code & one) == one)
            def _():
                fn(r, code)
        return body

    def combine(v):
        """Visit ``v``'s rows, times their weights, into their tokens'
        rows of the output."""
        # The visit before this one wrote its rows from ``obuf``: they
        # land before ``obuf`` is filled again or any is read back.
        @pl.when(v > zero)
        def _():
            rows(v - one, lambda r, code: write(r, code).wait())

        obuf[...] = acc[...].reshape(obuf.shape)
        rows(v, if_back(lambda r, code: read(r, code).start()))
        rows(v, if_back(lambda r, code: read(r, code).wait()))

        def add(r, code):
            y = obuf[r] * w_ref[v * np.int32(tile) + r]
            obuf[r] = jnp.where((code & one) == one, ibuf[r] + y, y)
            write(r, code).start()

        rows(v, add)

    def advance(v, f, slot):
        """The block after block ``f`` of visit ``v``, and its buffer."""
        end = f == last
        return (jnp.where(end, v + one, v), jnp.where(end, zero, f + one),
                jnp.where(slot == np.int32(BUFFERS - 1), zero, slot + one))

    total = nv * np.int32(blocks)

    def one_block(b, carry):
        # Block ``b`` of the walk is block ``f`` of visit ``v``, in buffer
        # ``slot``; ``BUFFERS - 1`` blocks on is block ``fa`` of ``va``.
        v, f, slot, va, fa, sa = carry

        # That fetch goes out before this block's products, so the reads
        # run on under the products and under a visit's ends.
        @pl.when(b + np.int32(BUFFERS - 1) < total)
        def _():
            for cp in weights(va, fa, sa):
                cp.start()

        @pl.when(f == zero)
        def _():
            rows(v, lambda r, code: fetch(r, code).wait())
            xs[...] = xbuf[...].reshape(xs.shape)

            @pl.when(v + one < nv)
            def _():
                rows(v + one, lambda r, code: fetch(r, code).start())

        for cp in weights(v, f, slot):
            cp.wait()
        # Rows past the visit's own are another visit's (or nothing yet):
        # computed, never written.
        x = xs[...]
        mid = (jax.nn.silu(jnp.dot(x, wg[slot],
                                   preferred_element_type=jnp.float32))
               * jnp.dot(x, wu[slot], preferred_element_type=jnp.float32))
        part = jnp.dot(mid.astype(x.dtype), wd[slot],
                       preferred_element_type=jnp.float32)

        @pl.when(f == zero)
        def _():
            acc[...] = part

        @pl.when(f > zero)
        def _():
            acc[...] += part

        @pl.when(f == last)
        def _():
            combine(v)

        return (*advance(v, f, slot), *advance(va, fa, sa))

    @pl.when(nv > zero)
    def _run():
        ahead = (zero, zero, zero)
        for i in range(BUFFERS - 1):
            @pl.when(np.int32(i) < total)
            def _():
                for cp in weights(*ahead):
                    cp.start()

            ahead = advance(*ahead)
        rows(zero, lambda r, code: fetch(r, code).start())
        lax.fori_loop(zero, total, one_block, (zero, zero, zero, *ahead))
        rows(nv - one, lambda r, code: write(r, code).wait())


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gated_call(nv, vexp, vrows, code, w, x, gate, up, down, *, tile,
                interpret):
    from jax.experimental.pallas import tpu as pltpu

    t, n, lanes = x.shape
    experts, d, width = gate.shape
    block = BLOCK_F if width % BLOCK_F == 0 else width
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_gated_kernel, tile=tile, block=block,
                          blocks=width // block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[any_space] * 4,
            out_specs=any_space,
            scratch_shapes=[
                pltpu.VMEM((tile, n, lanes), x.dtype),        # rows fetched
                pltpu.VMEM((tile, d), x.dtype),               # rows as one
                pltpu.VMEM((BUFFERS, d, block), x.dtype),     # gate blocks
                pltpu.VMEM((BUFFERS, d, block), x.dtype),     # up blocks
                pltpu.VMEM((BUFFERS, block, d), x.dtype),     # down blocks
                pltpu.VMEM((tile, d), jnp.float32),           # sum over F
                pltpu.VMEM((tile, n, lanes), jnp.float32),    # rows out
                pltpu.VMEM((tile, n, lanes), jnp.float32),    # rows back
                pltpu.SemaphoreType.DMA((BUFFERS,)),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, n, lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_experts",
    )(nv, vexp, vrows, code, w, x, gate, up, down)


def gated_experts(x, gate, up, down, r, *, k: int,
                  interpret: bool | None = None):
    """The held gated experts of one layer over their segments of the
    sorted assignments, as the gated kernel.

    ``x`` ``[T, d]``; ``gate``, ``up`` ``[E, d, F]`` and ``down``
    ``[E, F, d]`` at ``x``'s width; ``r`` the layer's
    :class:`..models.routing.Routing` (``counts``, ``starts``, ``order``,
    ``weight`` and ``expert``). Returns ``[T, d]`` float32."""
    interpret = resolve_interpret(interpret)
    i32, zero = jnp.int32, np.int32(0)
    t, d = x.shape
    experts = gate.shape[0]
    tile = row_tile(t)
    step = np.int32(tile)
    visits = experts + -(-t * k // tile)
    # Each touched expert's segment in tiles of ``tile`` rows, one visit a
    # tile, in expert order.
    tiles = (r.counts + step - np.int32(1)) // step
    ends = jnp.cumsum(tiles, dtype=i32)
    v = jnp.arange(visits, dtype=i32)
    vexp = jnp.minimum(jnp.sum(v[:, None] >= ends[None, :], axis=1,
                               dtype=i32), np.int32(experts - 1))
    j = v - (ends[vexp] - tiles[vexp])
    vrows = jnp.where(v < ends[-1],
                      jnp.clip(r.counts[vexp] - j * step, zero, step), zero)
    lane = jnp.arange(tile, dtype=i32)
    live = lane[None, :] < vrows[:, None]
    a = r.order[jnp.where(live, (r.starts[vexp] + j * step)[:, None]
                          + lane[None, :], zero)]
    # Whether an earlier expert of the same token was added before: that
    # row is read back and this expert added to it; the token's first
    # expert writes its row. A token no held expert took stays 0.
    mine = r.expert.reshape(t, k)
    held = mine < experts
    earlier = jnp.any(held[:, None, :] & (mine[:, None, :] < mine[:, :, None]),
                      axis=-1).reshape(-1)
    code = jnp.where(live, (a // np.int32(k)) * np.int32(2)
                     + earlier[a].astype(i32), zero)
    w = jnp.where(live, r.weight[a], np.float32(0))
    lanes = 128 if d % 128 == 0 else d
    out = _gated_call(
        ends[-1:], vexp, vrows, code.reshape(-1), w.reshape(-1),
        x.reshape(t, d // lanes, lanes), gate.astype(x.dtype),
        up.astype(x.dtype), down.astype(x.dtype), tile=tile,
        interpret=interpret)
    return jnp.where(jnp.any(held, axis=1)[:, None], out.reshape(t, d), 0)
