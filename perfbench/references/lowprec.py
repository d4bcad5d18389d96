"""The low-precision controls: the step below bfloat16 is an 8-bit float.

``fp8`` rounds a tensor to float8 (e4m3) under one scale for the whole
tensor, as an fp8 matmul path would, and lets the gradient through
unchanged.  Put on both operands of every product of a reference, it is
the control that ``correct`` has to catch.
"""

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)

