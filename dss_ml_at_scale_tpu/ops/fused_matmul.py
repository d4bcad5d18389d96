"""Pallas fused BN-apply + 1x1-conv (matmul) with a byte-minimal VJP.

The second HBM byte-cutting lever on top of :mod:`.fused_norm` (which
removed autodiff's *saved-residual* bloat around BatchNorm).  What is
left after that fusion is the normalize/relu **apply** pass itself: at
every BN site the network writes the normalized activation ``a`` to HBM
and the next convolution reads it back — two full activation-sized HBM
trips that exist only because the ops are separate HLOs.

Two of the three convolutions in a ResNet bottleneck block are 1x1 —
i.e. plain matmuls over the flattened ``[batch*H*W, C]`` layout.  For
those sites this module fuses the BN apply INTO the consuming matmul as
a tile **prologue**: the kernel streams the raw conv output ``y`` from
HBM and computes ``a = relu((y - mean) * inv * gamma + beta)`` in
registers immediately before feeding the MXU.  The post-BN activation
never exists in HBM, in either the forward or the backward pass:

    forward:    out = relu(y_hat * gamma + beta) @ W      (one kernel)
    backward:   da  = g @ W^T, masked in-epilogue, with the
                per-channel sums the BN backward needs accumulated
                across the grid in the same pass
                dW  = a^T @ g with a recomputed in-prologue

Division of labour with XLA (why this is not "rewrite convs in Pallas"):

- The batch statistics (mean/var of ``y``) stay a plain HLO reduction,
  computed by the caller (:class:`.fused_norm.BatchNorm` in
  ``stats_only`` mode).  Under a batch-sharded mesh GSPMD turns that
  reduction global, so sync-BN is preserved exactly as in the HLO
  fused path.  Only the elementwise apply — trivially shardable —
  moves into the kernel.
- The 3x3 convolutions stay XLA's (spatial convs are where XLA's conv
  emitter earns its keep); this kernel handles the matmul-shaped sites
  where a prologue costs nothing.

Gradient semantics mirror :mod:`.fused_norm`: the op takes the batch
``mean``/``var`` as explicit inputs but its VJP **internalizes** the
statistics' dependence on ``y`` (the classic ``(n*g - sum_g -
x_hat*sum_gx)/n`` correction), returning zero cotangents for them — the
same total gradient as differentiating through the stats, with flax's
stop-gradient running-average semantics.

SPMD: on one device (the headline benchmark path) the kernel-internal
per-channel sums are exact as-is.  Under a batch-sharded mesh, call
the op inside ``shard_map`` with ``axis_name=`` — the backward then
``psum``s the sums feeding ``dy`` so every shard uses the global
statistics backward, while dgamma/dbeta/dW stay shard-local (the
shard_map transpose of replicated inputs reduces them).  The model
integrates this as ``ResNet(fused_bn="pallas", pallas_mesh=mesh)``
(models/resnet.py), validated end to end by the driver's multichip
dryrun and tests/test_fused_matmul.py on the simulated 8-device mesh.
The HLO fused path (``fused_bn=True``) remains the default for
multi-chip training; compiled-TPU multi-chip pallas awaits real
multi-chip hardware to validate.

Capability parity: the composition equals the reference's
``Conv2d(1x1, bias=False) ∘ ReLU ∘ BatchNorm2d`` sequence inside
torchvision's Bottleneck (reference
``deep_learning/2.distributed-data-loading-petastorm.py:135-165``
fine-tunes exactly that ResNet-50), re-fused for the TPU memory
hierarchy instead of executed as three kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._pallas import resolve_interpret

__all__ = ["bn_relu_matmul"]

# M-dimension tile: small enough that every site's VMEM working set
# (y tile + weight panel + f32 accumulator) fits comfortably in 16 MB,
# large enough to amortize the per-step prologue.
_TM = 512
# Lane width: K and N are padded to multiples of this (TPU lane count;
# zero-padded params/weights make the padding semantically inert).
_LANE = 128


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = size % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


def _n_tile(n: int) -> int:
    """Largest N-tile <= 512 dividing n (n is a multiple of _LANE)."""
    for cand in (512, 256, 128):
        if n % cand == 0:
            return cand
    return _LANE


# ---------------------------------------------------------------------------
# Kernels.  Channel vectors arrive as [1, K] f32 rows.  ``with_res``
# switches the optional pre-relu residual operand (the bottleneck
# shortcut); refs are unpacked positionally to keep each operand a
# separate HBM array (no stacking copies).
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, with_res):
    if with_res:
        y_ref, res_ref, s_ref, t_ref, w_ref, out_ref = refs
    else:
        y_ref, s_ref, t_ref, w_ref, out_ref = refs
    z = y_ref[...].astype(jnp.float32) * s_ref[...] + t_ref[...]
    if with_res:
        z = z + res_ref[...].astype(jnp.float32)
    a = jnp.maximum(z, 0.0)
    out_ref[...] = jnp.dot(
        a.astype(y_ref.dtype), w_ref[...],
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


def _bwd_da_kernel(*refs, with_res):
    """Grid over M: gt = (g @ w^T) * relu_mask, plus the per-channel
    sums the BN backward needs, accumulated across the whole grid."""
    if with_res:
        (g_ref, w_ref, y_ref, res_ref, s_ref, t_ref, m_ref, u_ref,
         gt_ref, sum_g_ref, sum_gx_ref) = refs
    else:
        (g_ref, w_ref, y_ref, s_ref, t_ref, m_ref, u_ref,
         gt_ref, sum_g_ref, sum_gx_ref) = refs
    da = jax.lax.dot_general(
        g_ref[...], w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y32 = y_ref[...].astype(jnp.float32)
    z = y32 * s_ref[...] + t_ref[...]
    if with_res:
        z = z + res_ref[...].astype(jnp.float32)
    gt = jnp.where(z > 0.0, da, 0.0)
    gt_ref[...] = gt.astype(gt_ref.dtype)
    x_hat = (y32 - m_ref[...]) * u_ref[...]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        sum_g_ref[...] = jnp.zeros_like(sum_g_ref)
        sum_gx_ref[...] = jnp.zeros_like(sum_gx_ref)

    sum_g_ref[...] += jnp.sum(gt, axis=0, keepdims=True)
    sum_gx_ref[...] += jnp.sum(gt * x_hat, axis=0, keepdims=True)


def _bwd_dw_kernel(*refs, with_res):
    """Grid over M: dw[K, N] += a^T @ g with a recomputed in-prologue."""
    if with_res:
        y_ref, res_ref, s_ref, t_ref, g_ref, dw_ref = refs
    else:
        y_ref, s_ref, t_ref, g_ref, dw_ref = refs
    z = y_ref[...].astype(jnp.float32) * s_ref[...] + t_ref[...]
    if with_res:
        z = z + res_ref[...].astype(jnp.float32)
    a = jnp.maximum(z, 0.0)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[...] += jax.lax.dot_general(
        a.astype(y_ref.dtype), g_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


# ---------------------------------------------------------------------------
# custom-VJP op over flattened, padded [M, K] inputs (private)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_op(with_res: bool, interpret: bool, eps: float,
             axis_name: str | None = None, batch_stats: bool = True):
    """Op for one configuration; shapes already padded: y [M, K],
    gamma/beta/mean/var [1, K] f32, w [K, N]; M % _TM == 0,
    K % _LANE == 0, N % _LANE == 0.  The op takes an extra trailing
    ``n_count`` operand (f32 scalar, TRACED): the UNPADDED row count —
    the N of the batch statistics' mean, which the backward's stats
    correction divides by (padded rows carry zero cotangents, so the
    sums are unaffected, but the divisor must be the real one). Traced
    rather than baked into this cache key so variable-shape callers
    can't leak one custom_vjp op per distinct M — the key space here is
    a handful of static configurations, a naturally bounded cache.

    With ``axis_name`` (shard_map over the flattened-M axis): the
    channel sums feeding ``dy``'s statistics correction are ``psum``-ed
    (global), while dgamma/dbeta/dw are returned shard-local —
    shard_map's transpose of replicated inputs reduces those itself.
    ``n_count`` must then be the global row count."""

    def _vectors(gamma, beta, mean, var):
        inv = jax.lax.rsqrt(var + eps)
        s = gamma * inv
        t = beta - mean * s
        return s, t, inv

    def _row_spec(k):
        return pl.BlockSpec((1, k), lambda *idx: (0, 0))

    def _call_fwd(y, s, t, w, res):
        m, k = y.shape
        n = w.shape[1]
        tn = _n_tile(n)
        ys = [y] + ([res] if with_res else [])
        y_specs = [
            pl.BlockSpec((_TM, k), lambda i, j: (i, 0)) for _ in ys
        ]
        return pl.pallas_call(
            functools.partial(_fwd_kernel, with_res=with_res),
            grid=(m // _TM, n // tn),
            in_specs=y_specs + [
                _row_spec(k),
                _row_spec(k),
                pl.BlockSpec((k, tn), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((_TM, tn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), y.dtype),
            interpret=interpret,
        )(*ys, s, t, w)

    def f(y, gamma, beta, mean, var, w, n_count, *maybe_res):
        s, t, _ = _vectors(gamma, beta, mean, var)
        res = maybe_res[0] if with_res else None
        return _call_fwd(y, s, t, w, res)

    def f_fwd(y, gamma, beta, mean, var, w, n_count, *maybe_res):
        s, t, inv = _vectors(gamma, beta, mean, var)
        res = maybe_res[0] if with_res else None
        out = _call_fwd(y, s, t, w, res)
        # Saved: y (the raw conv output — the only activation-sized
        # tensor, and the one the surrounding graph keeps alive
        # anyway), the per-channel vectors, w, and the scalar row
        # count.  The normalized activation is never materialized.
        saved = (y, s, t, mean, inv, w, n_count) + (
            (res,) if with_res else ()
        )
        return out, saved

    def f_bwd(saved, g):
        y, s, t, mean, inv, w, n_count = saved[:7]
        res = saved[7] if with_res else None
        m, k = y.shape
        n = w.shape[1]
        ys = [y] + ([res] if with_res else [])

        y_specs1 = [pl.BlockSpec((_TM, k), lambda i: (i, 0)) for _ in ys]
        gt, sum_g, sum_gx = pl.pallas_call(
            functools.partial(_bwd_da_kernel, with_res=with_res),
            grid=(m // _TM,),
            in_specs=[
                pl.BlockSpec((_TM, n), lambda i: (i, 0)),
                pl.BlockSpec((k, n), lambda i: (0, 0)),
            ] + y_specs1 + [
                _row_spec(k), _row_spec(k), _row_spec(k), _row_spec(k),
            ],
            out_specs=[
                pl.BlockSpec((_TM, k), lambda i: (i, 0)),
                _row_spec(k),
                _row_spec(k),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m, k), y.dtype),
                jax.ShapeDtypeStruct((1, k), jnp.float32),
                jax.ShapeDtypeStruct((1, k), jnp.float32),
            ],
            interpret=interpret,
        )(g, w, *ys, s, t, mean, inv)

        y_specs2 = [pl.BlockSpec((_TM, k), lambda i: (i, 0)) for _ in ys]
        dw = pl.pallas_call(
            functools.partial(_bwd_dw_kernel, with_res=with_res),
            grid=(m // _TM,),
            in_specs=y_specs2 + [
                _row_spec(k),
                _row_spec(k),
                pl.BlockSpec((_TM, n), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((k, n), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
            interpret=interpret,
        )(*ys, s, t, g)

        # dy's statistics correction needs the GLOBAL sums (mean/var
        # were global); dgamma/dbeta/dw stay LOCAL — shard_map's
        # transpose of replicated (P()) inputs psums per-shard
        # cotangents itself, so reducing them here would double-count.
        if axis_name is not None:
            g_sum = jax.lax.psum(sum_g, axis_name)
            gx_sum = jax.lax.psum(sum_gx, axis_name)
        else:
            g_sum, gx_sum = sum_g, sum_gx
        dw = dw.astype(w.dtype)

        # Elementwise finish in HLO (XLA fuses it into one pass over
        # gt/y): the BN backward with the stats path internalized —
        #   dy = s * (gt - (sum_g + x_hat * sum_gx) / n_count)
        # dbeta/dgamma are the accumulated sums; dres is gt itself (the
        # masked cotangent), no extra traffic.  (Padded rows produce
        # nonzero dy here, but the caller's pad-VJP slices them off.)
        # With constant (running-average) stats the correction does not
        # exist — mean/var are not functions of y — so dy is s*gt.
        gt32 = gt.astype(jnp.float32)
        if batch_stats:
            x_hat = (y.astype(jnp.float32) - mean) * inv
            # n_count is a traced f32 scalar operand (not part of the
            # op-cache key), so variable-M callers reuse one op.
            dy32 = s * (gt32 - (g_sum + x_hat * gx_sum) / n_count)
        else:
            dy32 = s * gt32
        dy = dy32.astype(y.dtype)
        dgamma = sum_gx
        dbeta = sum_g
        grads = (dy, dgamma, dbeta, jnp.zeros_like(mean),
                 jnp.zeros_like(mean), dw, jnp.zeros_like(n_count))
        if with_res:
            grads = grads + (gt,)
        return grads

    op = jax.custom_vjp(f)
    op.defvjp(f_fwd, f_bwd)
    return op


# ---------------------------------------------------------------------------
# Public entry: NHWC conv-output in, matmul out
# ---------------------------------------------------------------------------

def bn_relu_matmul(
    y: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    mean: jax.Array,
    var: jax.Array,
    kernel: jax.Array,
    *,
    eps: float = 1e-5,
    residual: jax.Array | None = None,
    interpret: bool | None = None,
    axis_name: str | None = None,
    global_count: int | None = None,
    batch_stats: bool = True,
) -> jax.Array:
    """``relu(BN(y)) @ W`` (1x1 conv) without materializing the
    normalized activation.

    Args:
      y: raw conv output ``[..., K]`` (NHWC or already flattened).
      gamma/beta: BN scale/offset ``[K]`` (f32).
      mean/var: batch (or running) statistics ``[K]`` (f32).  With
        ``batch_stats=True`` (training) they must be the actual
        statistics of ``y`` and their dependence on ``y`` is
        internalized by the VJP; with ``batch_stats=False`` (eval /
        frozen BN) they are treated as constants and the backward
        skips the statistics correction — matching autodiff through
        the unfused eval composition.
      kernel: 1x1 conv kernel, shape ``[1, 1, K, N]`` or ``[K, N]``.
      residual: optional tensor added pre-relu (the bottleneck shortcut
        fused exactly as in :func:`.fused_norm.bn_act`).
      axis_name: set when calling from inside ``shard_map`` with the
        leading (batch) axis sharded: the backward ``psum``s the
        channel sums feeding ``dy`` so every shard uses the global
        statistics backward; dgamma/dbeta/dW stay shard-local because
        shard_map's transpose of replicated inputs reduces them.
        ``mean``/``var`` must be the global statistics and
        ``global_count`` the global row count.

    Returns the conv output with shape ``[..., N]``.
    """
    if kernel.ndim == 4:
        if kernel.shape[:2] != (1, 1):
            raise ValueError(f"not a 1x1 kernel: {kernel.shape}")
        kernel = kernel[0, 0]
    k, n = kernel.shape
    if y.shape[-1] != k:
        raise ValueError(f"y channels {y.shape[-1]} != kernel K {k}")
    interpret = resolve_interpret(interpret)

    lead = y.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    y2 = y.reshape(m, k)
    res2 = None
    if residual is not None:
        if residual.shape != y.shape:
            raise ValueError(
                f"residual shape {residual.shape} != y shape {y.shape}"
            )
        res2 = residual.reshape(m, k)

    # Zero-padding is semantically inert everywhere: padded M rows get
    # zero cotangents (g is zero there), padded K channels have
    # gamma=beta=mean=var=0 so a=relu(0)=0 contributes nothing, padded
    # N columns multiply zero kernel columns and are sliced off.
    y2 = _pad_to(_pad_to(y2, 0, _TM), 1, _LANE)
    if res2 is not None:
        res2 = _pad_to(_pad_to(res2, 0, _TM), 1, _LANE)
    w2 = _pad_to(_pad_to(kernel, 0, _LANE), 1, _LANE)

    def row(v):
        return _pad_to(v.astype(jnp.float32).reshape(1, k), 1, _LANE)

    op = _make_op(res2 is not None, bool(interpret), float(eps),
                  axis_name, bool(batch_stats))
    n_count = jnp.asarray(
        global_count if global_count is not None else m, jnp.float32
    )
    args = (y2, row(gamma), row(beta), row(mean), row(var), w2, n_count)
    if res2 is not None:
        args = args + (res2,)
    out = op(*args)
    return out[:m, :n].reshape(*lead, n)
