"""Pallas TPU selective scan: the recurrence of a Mamba-1 layer over a prompt.

Per channel ``c`` and state ``n``, with the step ``dt``, the input ``u``
and the input-dependent ``B`` and ``C`` of each position ``t``:

    s_t[n, c] = exp(dt_t[c] * A[n, c]) * s_{t-1}[n, c] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n s_t[n, c] C_t[n] + D[c] u_t[c]

from ``s_{-1} = 0``, in float32. Only the first ``n_real`` positions
advance the state (a bucket-padded prompt's padding leaves it as it
was); the state returned is the one after position ``n_real - 1``.

Design (pallas_guide.md patterns):

- Channels on lanes and the states on sublanes: the state of a block of
  ``block_c`` channels is one ``[d_state, block_c]`` float32 tile, every
  operation of a position is elementwise over it, and ``y`` is a
  reduction over sublanes. ``A`` comes in that layout (``[d_state,
  d_inner]``), and so does the state that is returned.
- Grid ``(channel blocks, time chunks)``: the channel blocks are
  independent, the chunks follow one another and carry the state in a
  VMEM scratch. Inside a chunk a ``fori_loop`` walks the positions in
  turns of ``UNROLL`` (its body traced once, whatever ``T`` or the
  number of chunks). A chunk that
  lies wholly past ``n_real`` does no work and writes zeros; inside the
  last live one a position past it steps with ``dt = 0``, which leaves
  the state as it was.
- A position's ``B_t`` and ``C_t`` arrive as a row ``[1, d_state]``; the
  update wants them as a column. The column is the row under a diagonal
  mask, summed over lanes: elementwise work and one lane reduction, no
  transpose.
- ``_scan_call`` is jitted, so a program that calls the kernel once a
  layer traces and lowers it once a shape: set-up does not grow with the
  layers. The call is named ``ssm_scan``, which is how a device trace
  names its operation.

On a CPU backend (tests) the kernel runs in Pallas interpret mode
(``_pallas.resolve_interpret``); on ``tpu`` it is always compiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._pallas import resolve_interpret

# Positions a grid step walks, and channels a grid step holds: a chunk's
# u, dt and y blocks are 512 KB each at these sizes, the state 8 vregs.
CHUNK = 256
BLOCK_C = 512
# Positions of one turn of the walk, written out in its body: the
# scheduler can start one position's exp while another's update runs.
UNROLL = 4


def selective_scan_reference(u, dt, a, b, c, d, n_real):
    """Plain ``lax.scan`` over positions: the numerical ground truth.

    ``u``, ``dt`` ``[T, d_inner]``; ``a`` ``[d_state, d_inner]``; ``b``,
    ``c`` ``[T, d_state]``; ``d`` ``[d_inner]``; ``n_real`` an int32
    scalar. Returns ``(y [T, d_inner], state [d_state, d_inner])``, both
    float32; rows of ``y`` past ``n_real`` are zeros."""
    f32 = jnp.float32
    live = jnp.arange(u.shape[0]) < n_real
    dt = jnp.where(live[:, None], dt.astype(f32), 0.0)
    u, a, b, c = (x.astype(f32) for x in (u, a, b, c))

    def step(s, row):
        dt_t, u_t, b_t, c_t = row
        s = jnp.exp(dt_t[None, :] * a) * s + b_t[:, None] * (dt_t * u_t)[None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    state, y = jax.lax.scan(step, jnp.zeros(a.shape, f32), (dt, u, b, c))
    return jnp.where(live[:, None], y + d.astype(f32)[None, :] * u, 0.0), state


def _scan_kernel(n_ref, u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref,
                 s_out_ref, s_ref, *, chunk, unroll):
    t = pl.program_id(1)
    start = t * chunk
    n_real = n_ref[0]

    @pl.when(t == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)

    @pl.when(start < n_real)
    def _walk():
        a = a_ref[:]                       # (d_state, block_c)
        skip = d_ref[:]                    # (1, block_c)
        n_state = a.shape[0]
        diagonal = (jax.lax.broadcasted_iota(jnp.int32, (n_state, n_state), 0)
                    == jax.lax.broadcasted_iota(jnp.int32, (n_state, n_state),
                                                1))

        def column(row):
            """``[1, d_state]`` -> ``[d_state, 1]``."""
            return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1,
                           keepdims=True)

        def position(i, s):
            rows = pl.ds(i, 1)
            live = start + i < n_real
            dt = jnp.where(live, dt_ref[rows, :], 0.0)
            u = u_ref[rows, :]
            s = (jnp.exp(dt * a) * s
                 + column(b_ref[rows, :]) * (dt * u))
            y = jnp.sum(s * column(c_ref[rows, :]), axis=0, keepdims=True)
            y_ref[rows, :] = jnp.where(live, y + skip * u, 0.0)
            return s

        def turn(k, s):
            for j in range(unroll):
                s = position(k * unroll + j, s)
            return s

        s_ref[:] = jax.lax.fori_loop(0, chunk // unroll, turn, s_ref[:])

    @pl.when(start >= n_real)
    def _dead():
        y_ref[:] = jnp.zeros_like(y_ref)

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        s_out_ref[:] = s_ref[:]


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_c", "unroll", "interpret"))
def _scan_call(n_real, u, dt, a, b, c, d, *, chunk, block_c, unroll,
               interpret):
    from jax.experimental.pallas import tpu as pltpu

    t, channels = u.shape
    n_state = a.shape[0]
    kernel = functools.partial(_scan_kernel, chunk=chunk, unroll=unroll)

    def rows(ci, ti, n):
        return (ti, ci)

    def per_position(ci, ti, n):
        return (ti, 0)

    def per_channel(ci, ti, n):
        return (0, ci)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(channels // block_c, t // chunk),
            in_specs=[
                pl.BlockSpec((chunk, block_c), rows),           # u
                pl.BlockSpec((chunk, block_c), rows),           # dt
                pl.BlockSpec((chunk, n_state), per_position),   # B
                pl.BlockSpec((chunk, n_state), per_position),   # C
                pl.BlockSpec((n_state, block_c), per_channel),  # A
                pl.BlockSpec((1, block_c), per_channel),        # D
            ],
            out_specs=[
                pl.BlockSpec((chunk, block_c), rows),           # y
                pl.BlockSpec((n_state, block_c), per_channel),  # state
            ],
            scratch_shapes=[pltpu.VMEM((n_state, block_c), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((t, channels), jnp.float32),
                   jax.ShapeDtypeStruct((n_state, channels), jnp.float32)],
        interpret=interpret,
        name="ssm_scan",
    )(n_real, u, dt, b, c, a, d)


def blocks(t: int, channels: int, chunk: int | None = None,
           block_c: int | None = None) -> tuple[int, int]:
    """``(chunk, block_c)`` for ``t`` positions over ``channels``: the
    chunk at most ``CHUNK`` and a multiple of 8 (the float32 sublane
    tile), the channel block ``BLOCK_C`` or the whole width where that
    does not divide it."""
    if chunk is None:
        chunk = min(CHUNK, -(-t // 8) * 8)
    if block_c is None:
        block_c = BLOCK_C if channels % BLOCK_C == 0 else channels
    return chunk, block_c


def selective_scan(u, dt, a, b, c, d, n_real, *, chunk: int | None = None,
                   block_c: int | None = None, interpret: bool | None = None):
    """The selective scan of one layer over one prompt, as the kernel.

    Arguments and results as :func:`selective_scan_reference`. ``T`` need
    not be a multiple of the chunk: the positions are padded to one (the
    padding lies past ``n_real``) and ``y`` is cut back."""
    interpret = resolve_interpret(interpret)
    t, channels = u.shape
    chunk, block_c = blocks(t, channels, chunk, block_c)
    padded = -(-t // chunk) * chunk
    f32 = jnp.float32

    def rows(x):
        x = x.astype(f32)
        return jnp.pad(x, ((0, padded - t), (0, 0))) if padded != t else x

    y, state = _scan_call(
        jnp.reshape(jnp.minimum(jnp.asarray(n_real, jnp.int32), t), (1,)),
        rows(u), rows(dt), a.astype(f32), rows(b), rows(c),
        jnp.reshape(d.astype(f32), (1, channels)),
        chunk=chunk, block_c=block_c, unroll=UNROLL if chunk % UNROLL == 0
        else 1,
        interpret=interpret)
    return y[:t], state
