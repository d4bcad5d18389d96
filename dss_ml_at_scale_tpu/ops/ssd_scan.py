"""Pallas TPU state-space-duality (SSD) scan: a Mamba-2 layer over a prompt.

Per head ``h`` (of ``H``, each reading the ``B`` and ``C`` of its group
``h // (H / G)``), with the step ``dt``, the input ``x`` (``P`` values a
head) and the input-dependent ``B`` and ``C`` (``N`` values a group) of
each position ``t``, a scalar decay ``A`` a head:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S: [P, N]
    y_t = S_t C_t + D x_t

from the given initial state ``S_{-1}``, in float32. The state returned is
``S`` after the last position. A position whose ``dt`` is 0 and whose
``x`` is 0 leaves the state as it was: that is how a caller pads a prompt
(the kernel itself knows no count of real positions).

The chunked algorithm (Dao and Gu 2024, "Transformers are SSMs", the SSD
minimal form): the positions go in blocks of ``L`` (the configuration's
``chunk_size``). With ``a_t`` the within-block cumulative sum of
``dt A`` (computed beside the kernel, in XLA), a block's outputs are

    y_t = sum_{s <= t} exp(a_t - a_s) (C_t . B_s) dt_s x_s     (in block)
        + exp(a_t) S C_t                                      (from before)
        + D x_t

and its state at the end is ``exp(a_L) S + sum_s exp(a_L - a_s) dt_s x_s
B_s^T``: four matrix products a head a block, no walk over positions.

Design (pallas_guide.md patterns):

- Grid ``(groups, blocks)``: a grid step holds the ``H / G`` heads of one
  group for one block of ``L`` positions, so ``C B^T`` (``[L, L]``) is
  made once for the heads that share it. The blocks follow one another
  and carry the group's state in the output block, which stays in VMEM
  while the block index walks (it is written back once, at the end).
- ``x`` and ``y`` go head by head transposed, ``[H, P, T]``: positions on
  lanes, so a ``[P, L]`` tile fills its vregs (``P`` is 64, half a lane
  row) and every product is a plain or right-transposed matmul. The
  heads of a step go through one ``fori_loop`` (its body traced once);
  a head's ``dt`` and ``a`` rows are read by a dynamic sublane index, its
  ``a`` column picked out of ``[L, heads]`` by a lane mask and a sum.
- Every product is float32 at ``Precision.HIGHEST``: the state is
  float32 and so is what makes it.
- ``_ssd_call`` is jitted, so a program that calls the kernel once a
  layer traces and lowers it once a shape. The call is named
  ``ssd_scan``, which is how a device trace names its operation.

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

_HIGHEST = lax.Precision.HIGHEST


def ssd_scan_reference(x, dt, a, b, c, d, state):
    """The recurrence step by step (a ``lax.scan`` over positions): the
    numerical ground truth.

    ``x`` ``[T, H, P]``; ``dt`` ``[T, H]``; ``a`` ``[H]``; ``b``, ``c``
    ``[T, G, N]``; ``d`` ``[H]``; ``state`` ``[H, P, N]``. Returns ``(y
    [T, H, P], state [H, P, N])``, float32."""
    f32 = jnp.float32
    heads, groups = x.shape[1], b.shape[1]
    group = np.arange(heads) // (heads // groups)
    x, dt, a, b, c, d = (v.astype(f32) for v in (x, dt, a, b, c, d))

    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :])
        return s, jnp.sum(s * c_t[group][:, None, :], axis=-1)

    state, y = lax.scan(step, state.astype(f32), (x, dt, b, c))
    return y + d[None, :, None] * x, state


def _ssd_kernel(x_ref, dt_ref, arow_ref, acol_ref, b_ref, c_ref, d_ref,
                s0_ref, y_ref, s_out_ref, s_ref, *, heads):
    g, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        s_ref[:] = s0_ref[:]

    b = b_ref[0]                                    # [L, N]
    c = c_ref[0]                                    # [L, N]
    length = b.shape[0]
    # bc[s, t] = B_s . C_t, shared by the group's heads.
    bc = lax.dot_general(b, c, (((1,), (1,)), ((), ())), precision=_HIGHEST,
                         preferred_element_type=jnp.float32)
    earlier = (lax.broadcasted_iota(jnp.int32, (length, length), 0)
               <= lax.broadcasted_iota(jnp.int32, (length, length), 1))
    at_end = (lax.broadcasted_iota(jnp.int32, (length, b.shape[1]), 0)
              == length - 1).astype(jnp.float32)   # [L, N]
    cols = acol_ref[0]                              # [L, heads]
    lane = lax.broadcasted_iota(jnp.int32, cols.shape, 1)

    def head(j, carry):
        a_row = arow_ref[0, pl.ds(j, 1), :]         # [1, L]: a_t
        dt_row = dt_ref[0, pl.ds(j, 1), :]          # [1, L]
        a_col = jnp.sum(jnp.where(lane == j, cols, 0.0), axis=1,
                        keepdims=True)              # [L, 1]: a_s
        # m[s, t] = exp(a_t - a_s) B_s . C_t for s <= t.
        m = jnp.where(earlier, bc * jnp.exp(jnp.minimum(a_row - a_col, 0.0)),
                      0.0)
        xt = x_ref[j]                               # [P, L]
        u = xt * dt_row
        s = s_ref[j]                                # [P, N]
        y = jnp.dot(u, m, precision=_HIGHEST,
                    preferred_element_type=jnp.float32)
        y += jnp.exp(a_row) * lax.dot_general(
            s, c, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        y_ref[j] = y + d_ref[g * heads + j] * xt
        last = a_row[:, length - 1:]                # [1, 1]
        # exp(a_L) as a row [1, N]: Mosaic broadcasts a [1, 1] into a
        # [P, N] tile in one direction at a time, not in both.
        keep = jnp.dot(jnp.exp(a_row), at_end, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)
        s_ref[j] = keep * s + jnp.dot(
            u * jnp.exp(last - a_row), b, precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        return carry

    lax.fori_loop(0, heads, head, 0)

    @pl.when(k == pl.num_programs(1) - 1)
    def _finish():
        s_out_ref[:] = s_ref[:]


@functools.partial(jax.jit, static_argnames=("length", "interpret"))
def _ssd_call(xt, dt_rows, a_rows, a_cols, b, c, d, state, *, length,
              interpret):
    from jax.experimental.pallas import tpu as pltpu

    heads, p, t = xt.shape
    groups, n = b.shape[0], b.shape[2]
    per = heads // groups
    kernel = functools.partial(_ssd_kernel, heads=per)

    def by_head(gi, ki):
        return (gi, 0, ki)

    def by_group(gi, ki):
        return (gi, ki, 0)

    def state_of(gi, ki):
        return (gi, 0, 0)

    return pl.pallas_call(
        kernel,
        grid=(groups, t // length),
        in_specs=[
            pl.BlockSpec((per, p, length), by_head),         # x^T
            pl.BlockSpec((1, per, length), by_head),         # dt rows
            pl.BlockSpec((1, per, length), by_head),         # a rows
            pl.BlockSpec((1, length, per), by_group),        # a columns
            pl.BlockSpec((1, length, n), by_group),          # B
            pl.BlockSpec((1, length, n), by_group),          # C
            pl.BlockSpec(memory_space=pltpu.SMEM),           # D
            pl.BlockSpec((per, p, n), state_of),             # initial state
        ],
        out_specs=[
            pl.BlockSpec((per, p, length), by_head),         # y^T
            pl.BlockSpec((per, p, n), state_of),             # final state
        ],
        out_shape=[jax.ShapeDtypeStruct((heads, p, t), jnp.float32),
                   jax.ShapeDtypeStruct((heads, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((per, p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(xt, dt_rows, a_rows, a_cols, b, c, d, state)


def ssd_scan(x, dt, a, b, c, d, state, *, chunk: int = 128,
             interpret: bool | None = None):
    """The SSD scan of one layer over one run of positions, as the kernel.

    Arguments and results as :func:`ssd_scan_reference`. ``T`` need not
    be a multiple of ``chunk``: the positions are padded to one with
    ``dt`` and ``x`` zero, which leaves the state as it was, and ``y`` is
    cut back."""
    interpret = resolve_interpret(interpret)
    t, heads, p = x.shape
    groups = b.shape[1]
    per = heads // groups
    padded = -(-t // chunk) * chunk
    f32 = jnp.float32

    def rows(v):
        v = v.astype(f32)
        pad = [(0, padded - t)] + [(0, 0)] * (v.ndim - 1)
        return jnp.pad(v, pad) if padded != t else v

    x, dt, b, c = rows(x), rows(dt), rows(b), rows(c)
    # a_t: the cumulative sum of dt A within each block, [T, H].
    steps = (dt * a.astype(f32)[None, :]).reshape(padded // chunk, chunk,
                                                   heads)
    acum = jnp.cumsum(steps, axis=1).reshape(padded, heads)

    def head_rows(v):                                # [T, H] -> [G, H/G, T]
        return v.T.reshape(groups, per, padded)

    y, state = _ssd_call(
        x.transpose(1, 2, 0), head_rows(dt), head_rows(acum),
        acum.reshape(padded, groups, per).transpose(1, 0, 2),
        b.transpose(1, 0, 2), c.transpose(1, 0, 2), d.astype(f32),
        state.astype(f32), length=chunk, interpret=interpret)
    return y.transpose(2, 0, 1)[:t], state
