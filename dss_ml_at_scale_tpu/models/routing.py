"""Routing of an expert layer that holds a share of the experts.

What the served expert models (``mla_moe.MlaMoeLM``, ``nemotron_h.
NemotronHLM``) share: a float32 router over all ``router_width`` experts,
the ``k`` best of each token, their weights, and then what this chip does
with them. The layer is told which experts it holds (``offset``,
``held``); an assignment to any other is left out (nothing stands in for
it), and so is every assignment of a row that is not valid (an idle slot,
a prompt's padding). The held ones are sorted by expert, so that each
held expert can run over its own segment of the assignments.

Two scores:

- ``"softmax"``: ``p = softmax(x W)``; the ``k`` largest ``p``.
- ``"sigmoid"``: ``s = sigmoid(x W)``; the ``k`` largest ``s + bias`` (the
  selection bias, DeepSeek-V3's ``e_score_correction_bias``), weighted by
  ``s`` itself.

With ``norm_topk`` the ``k`` weights are divided by their sum; then all
are multiplied by ``scale``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class Routing:
    """One expert layer's assignments of ``T`` rows, ``k`` each.

    ``weight`` ``[T * k]`` float32 (row-major: assignment ``i`` is row
    ``i // k``'s); ``order`` ``[T * k]`` int32, the assignments sorted by
    held expert, those of absent experts and of rows that are not valid
    past the last; ``counts`` and ``starts`` ``[held]`` int32, each held
    expert's segment of ``order``; ``stats`` int32 ``[held assignments,
    absent assignments, experts touched, per held expert...]``;
    ``expert`` ``[T * k]`` int32, each assignment's held expert (``held``
    where it is not held)."""

    weight: jax.Array
    order: jax.Array
    counts: jax.Array
    starts: jax.Array
    stats: jax.Array
    expert: jax.Array


def route(x32, kernel, valid, *, k: int, held: int, offset: int = 0,
          score: str = "softmax", bias=None, norm_topk: bool = True,
          scale: float = 1.0) -> Routing:
    """Route ``x32`` ``[T, d]`` (float32) through the router ``kernel``
    ``[d, router_width]``; ``valid`` ``[T]`` bool. See the module
    docstring."""
    with jax.named_scope("moe_router"):
        gates = jnp.matmul(x32, kernel, precision=lax.Precision.HIGHEST)
        if score == "softmax":
            top_p, top_i = lax.top_k(jax.nn.softmax(gates, axis=-1), k)
        elif score == "sigmoid":
            s = jax.nn.sigmoid(gates)
            _, top_i = lax.top_k(s + bias, k)
            top_p = jnp.take_along_axis(s, top_i, axis=-1)
        else:
            raise ValueError(f"unknown router score {score!r}")
        if norm_topk:
            total = jnp.sum(top_p, axis=-1, keepdims=True)
            if score == "sigmoid":
                total = total + 1e-20
            top_p = top_p / total
        weight = (top_p * scale).reshape(-1)
        local = top_i - offset
        here = (local >= 0) & (local < held) & valid[:, None]
        # Assignments sorted by held expert; those of absent experts (and
        # of rows that are not valid) sort past the last one.
        expert = jnp.where(here, local, held).reshape(-1)
        order = _int32(jnp.argsort(expert, stable=True))
        counts = jnp.sum(expert[:, None] == jnp.arange(held)[None, :],
                         axis=0, dtype=jnp.int32)
        starts = jnp.cumsum(counts, dtype=jnp.int32) - counts
        n_valid = jnp.sum(valid, dtype=jnp.int32) * k
        n_held = jnp.sum(counts, dtype=jnp.int32)
        stats = jnp.concatenate([
            jnp.stack([n_held, n_valid - n_held,
                       jnp.sum(counts > 0, dtype=jnp.int32)]),
            counts])
    return Routing(weight, order, counts, starts, stats, expert)


def stack_held(leaves, release: bool):
    """One matrix of each held expert, ``leaves``, as one ``[held, ...]``
    leaf, the held experts' leaf that the grouped kernels read. With
    ``release`` each leaf is deleted from the device once the stack
    exists."""
    stacked = jnp.stack(leaves)
    if release:
        stacked.block_until_ready()
        for leaf in leaves:
            leaf.delete()
    return stacked


def _int32(x):
    return x if x.dtype == jnp.int32 else x.astype(jnp.int32)
