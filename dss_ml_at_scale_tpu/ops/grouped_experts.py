"""Pallas TPU grouped-expert kernel: one expert layer's held experts over
their segments of the sorted assignments, in one call.

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
# Scoped VMEM the call may use: two buffers of one expert's two matrices
# (22 MB at Nemotron-3-Super's widths), the resident rows and output,
# and a tile's temporaries.
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
