"""Fused train-mode BatchNorm + activation (+ residual add) with a
hand-written minimal-residual VJP.

Why this exists: the ResNet-50 train step's FLOPs cannot be cut; its
HBM bytes can (what that buys in step time has not been measured on the
chip: ROADMAP A4). The biggest avoidable byte source is
autodiff's residual bloat around BatchNorm: reverse-mode AD of the
``normalize → scale/shift → (add) → relu`` chain saves intermediate
activation-sized tensors (x̂, the pre-activation, relu masks) from the
forward pass for the backward pass, each a full HBM round trip at
activation size.

The fix is NOT a Pallas kernel. The forward math here is plain XLA HLO —
two fused passes (one multi-output reduction for mean/E[x²], one
elementwise normalize+act) is already optimal, and keeping it HLO means
GSPMD partitions it: under a batch-sharded mesh the ``jnp.mean`` over
the batch axis becomes a global (cross-chip) reduction, i.e. sync-BN
falls out for free exactly as in :mod:`..models.resnet` — a property a
``pallas_call`` (an opaque custom call to SPMD) would break. What is
hand-written is the VJP: it saves ONLY ``(x, mean, inv_std, scale)``
where ``x`` is the convolution output that must stay alive anyway for
the conv's own weight gradient — so BatchNorm's backward adds **zero**
saved activation-sized tensors — and recomputes x̂ and the relu mask
in-register inside the backward's two passes:

    pass 1 (reads x, g):          Σg, Σg·x̂  → dβ, dγ
    pass 2 (reads x, g, writes):  dx = γ·inv/n · (n·g − Σg − x̂·Σg·x̂)

Fusing the residual add of a ResNet block into the same op removes the
separate ``relu(residual + y)`` elementwise pass and its saved mask as
well; ``dresidual`` is the masked cotangent already in registers.

Capability parity: train-mode semantics match ``flax.linen.BatchNorm``
(biased variance for both normalization and the running update, f32
statistics accumulation regardless of compute dtype), which is what the
reference's torchvision ResNet-50 wrapper uses per layer (reference
``deep_learning/2.distributed-data-loading-petastorm.py:135-165``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import linen as nn

__all__ = ["BatchNorm", "bn_act"]


@functools.lru_cache(maxsize=None)
def _make_bn_act(eps: float, relu: bool, with_residual: bool):
    """Build (and cache) the custom-VJP fused op for one configuration.

    Configurations are closed over rather than passed as arguments so the
    custom_vjp signature holds arrays only (``residual`` present iff
    ``with_residual``) and tracing never sees a ``None`` pytree.
    """

    def fwd_math(x, scale, bias, residual):
        x32 = x.astype(jnp.float32)
        # Multi-output fusion: mean and E[x²] in ONE read pass over x.
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x32, axes)
        mean2 = jnp.mean(jnp.square(x32), axes)
        var = mean2 - jnp.square(mean)
        inv = jax.lax.rsqrt(var + eps)
        pre = (x32 - mean) * (inv * scale) + bias
        if with_residual:
            pre = pre + residual.astype(jnp.float32)
        out = jnp.maximum(pre, 0.0) if relu else pre
        return out.astype(x.dtype), mean, var, inv

    def f(x, scale, bias, *maybe_res):
        out, mean, var, _ = fwd_math(x, scale, bias,
                                     maybe_res[0] if with_residual else None)
        return out, mean, var

    f = jax.custom_vjp(f)

    def f_fwd(x, scale, bias, *maybe_res):
        residual = maybe_res[0] if with_residual else None
        out, mean, var, inv = fwd_math(x, scale, bias, residual)
        # Residuals: x is the conv output (alive anyway for the conv's
        # dW); mean/inv/scale/bias are per-channel vectors; the block
        # residual is the block input (alive anyway for its own
        # backward). No new activation-sized tensors are saved.
        saved = (x, mean, inv, scale, bias) + (
            (residual,) if with_residual else ()
        )
        return (out, mean, var), saved

    def f_bwd(saved, cotangents):
        x, mean, inv, scale, bias = saved[:5]
        residual = saved[5] if with_residual else None
        g_out, g_mean, g_var = cotangents
        del g_mean, g_var  # stats feed running-average updates only
        # (stop-gradient semantics, as in flax BatchNorm)

        axes = tuple(range(x.ndim - 1))
        n = 1.0
        for d in axes:
            n *= x.shape[d]

        x32 = x.astype(jnp.float32)
        g32 = g_out.astype(jnp.float32)
        x_hat = (x32 - mean) * inv
        if relu:
            # Recompute the relu mask in-register instead of saving it:
            # the forward pre-activation is a function of saved values.
            pre = x_hat * scale + bias
            if with_residual:
                pre = pre + residual.astype(jnp.float32)
            g32 = jnp.where(pre > 0, g32, 0.0)

        sum_g = jnp.sum(g32, axes)
        sum_gx = jnp.sum(g32 * x_hat, axes)
        dscale = sum_gx
        dbias = sum_g
        dx = (scale * inv) * (g32 - (sum_g + x_hat * sum_gx) / n)
        grads = (dx.astype(x.dtype), dscale, dbias)
        if with_residual:
            grads = grads + (g32.astype(residual.dtype),)
        return grads

    f.defvjp(f_fwd, f_bwd)
    return f


def bn_act(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    *,
    eps: float = 1e-5,
    relu: bool = False,
    residual: jax.Array | None = None,
):
    """Fused train-mode BN(+relu)(+residual add) over the last axis.

    Returns ``(out, mean, var)`` with ``out`` in ``x.dtype`` and biased
    ``var`` in float32 (flax semantics — the same var normalizes and
    feeds the running average). Gradients do not flow through the
    returned statistics (matching flax, where the running-average update
    is outside the differentiated graph).
    """
    fn = _make_bn_act(float(eps), bool(relu), residual is not None)
    if residual is not None:
        return fn(x, scale, bias, residual)
    return fn(x, scale, bias)


class BatchNorm(nn.Module):
    """Drop-in ``flax.linen.BatchNorm`` replacement with fused act/residual.

    Deliberately named ``BatchNorm`` so ``nn.compact`` auto-naming
    produces the same ``BatchNorm_k`` parameter paths as the unfused
    model — checkpoints and the torchvision pretrained-weights converter
    (:mod:`..models.pretrained`, which keys on those names) work
    unchanged, and fused/unfused configurations are checkpoint-portable
    in both directions.

    Differences from flax's module: ``act`` ("relu" or None) and an
    optional ``residual`` call argument are applied INSIDE the fused op;
    only channels-last (reduce over all but the last axis) is supported,
    which is the only layout the TPU-native models use.
    """

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = None  # kept for call-site compatibility; out follows x.dtype
    act: str | None = None
    scale_init: Callable = nn.initializers.ones_init()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x, residual=None, *, stats_only: bool = False):
        features = x.shape[-1]
        scale = self.param("scale", self.scale_init, (features,), jnp.float32)
        bias = self.param("bias", self.bias_init, (features,), jnp.float32)
        ra_mean = self.variable(
            "batch_stats", "mean",
            lambda s: jnp.zeros(s, jnp.float32), (features,),
        )
        ra_var = self.variable(
            "batch_stats", "var",
            lambda s: jnp.ones(s, jnp.float32), (features,),
        )
        relu = self.act == "relu"

        if stats_only:
            # The Pallas prologue-fusion path (ops/fused_matmul.py):
            # compute the statistics HERE, in plain HLO — a batch-
            # sharded mesh still gets the global (sync-BN) reduction —
            # update the running averages exactly as the applying path
            # does, and hand (scale, bias, mean, var) to the consuming
            # kernel, which applies normalize+relu in-register.
            if self.use_running_average:
                return scale, bias, ra_mean.value, ra_var.value
            x32 = x.astype(jnp.float32)
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(x32, axes)
            var = jnp.mean(jnp.square(x32), axes) - jnp.square(mean)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = (
                    m * ra_mean.value
                    + (1.0 - m) * jax.lax.stop_gradient(mean)
                )
                ra_var.value = (
                    m * ra_var.value
                    + (1.0 - m) * jax.lax.stop_gradient(var)
                )
            return scale, bias, mean, var

        if self.use_running_average:
            inv = jax.lax.rsqrt(ra_var.value + self.epsilon)
            pre = (x.astype(jnp.float32) - ra_mean.value) * (inv * scale) + bias
            if residual is not None:
                pre = pre + residual.astype(jnp.float32)
            out = jnp.maximum(pre, 0.0) if relu else pre
            return out.astype(x.dtype)

        out, mean, var = bn_act(
            x, scale, bias, eps=self.epsilon, relu=relu, residual=residual
        )
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1.0 - m) * mean
            ra_var.value = m * ra_var.value + (1.0 - m) * var
        return out
