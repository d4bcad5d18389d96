"""Mixture-of-Experts MLP with expert parallelism over a mesh axis.

No MoE exists in the reference (SURVEY.md §2.3 lists EP as absent), but
the framework's mesh-based sharding layer is built so expert parallelism
is the same mechanism as DP/TP/SP/PP: experts live on an ``"expert"``
mesh axis and XLA inserts the dispatch/combine all-to-alls from the
sharding annotations alone.

Design (Switch-Transformer-style, dense dispatch — the XLA-friendly
shape):

- Top-1 routing with a float32 router. Each token picks one expert; a
  per-expert capacity ``C = ceil(tokens/E · capacity_factor)`` bounds the
  work per expert so every shape stays static. Tokens over capacity fall
  through the residual (their combine weight is zero) — standard Switch
  semantics, never a runtime error. A one-token decode step keeps every
  token (``keep_all``): capacity there is the batch, as it was 1 for a
  sequence decoded alone.
- Dispatch and combine are einsums against a ``[tokens, E, C]`` one-hot
  tensor. On an expert-sharded mesh the ``ecd`` operands are sharded on
  ``e`` while token operands are batch-sharded, so GSPMD lowers the two
  einsums to the canonical all-to-all pair riding ICI.
- The expert FFN itself is one batched einsum over the leading expert
  dimension (``[E, C, d] × [E, d, h]``) — E independent MLPs as a single
  MXU-shaped contraction, no Python loop over experts.
- The standard load-balance auxiliary loss (E · Σ fraction·probability)
  is sowed under ``intermediates/aux_loss`` so any trainer can fold
  ``aux_weight * aux`` into its objective without threading extra
  outputs through the stack.

``TransformerLM(ffn="moe", ...)`` swaps this layer in for the dense MLP
in every block (models/transformer.py), giving the LM track an
expert-parallel configuration that rides the identical Trainer/ring
machinery.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _constrain(x, mesh: Mesh | None, spec: P):
    """Sharding hint that is a no-op off-mesh (single device, tests)."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


class MoEMLP(nn.Module):
    """Top-1 routed MLP over ``num_experts`` experts.

    Input/output: ``[batch, seq, dim]``. When ``mesh``/``axis_name`` are
    set, expert-dimension operands are sharding-constrained to the axis
    (expert parallelism); otherwise the same program runs on one device.
    """

    num_experts: int
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    mesh: Any = None
    axis_name: str = "expert"
    router_noise: float = 0.0  # jitter std at train time (0 = deterministic)

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True,
                 keep_all: bool = False):
        """``keep_all``: room in every expert for every token, so none
        is dropped and a token's output is its own whoever rides beside
        it. A cached decode step asks for it: its tokens are one a
        sequence (one a serving slot, idle slots among them), and a
        queue over the batch axis would let a neighbour take a
        sequence's place."""
        b, s, d = x.shape
        e = self.num_experts
        h = self.mlp_ratio * d
        tokens = x.reshape(b * s, d)
        t = tokens.shape[0]
        capacity = t if keep_all else max(
            1, math.ceil(t * self.capacity_factor / e))

        # -- router (f32: softmax over experts must not run in bf16) ------
        logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, name="router"
        )(tokens.astype(jnp.float32))
        if self.router_noise > 0.0 and not deterministic:
            rng = self.make_rng("router")
            logits = logits + self.router_noise * jax.random.normal(
                rng, logits.shape
            )
        probs = jax.nn.softmax(logits, axis=-1)  # [t, e]
        expert_index = jnp.argmax(probs, axis=-1)  # [t]
        expert_gate = jnp.take_along_axis(
            probs, expert_index[:, None], axis=-1
        )[:, 0]  # [t]

        # -- load-balance aux loss (Switch eq. 4): E · Σ_e f_e · p_e ------
        one_hot = jax.nn.one_hot(expert_index, e, dtype=jnp.float32)  # [t, e]
        fraction = one_hot.mean(axis=0)
        mean_prob = probs.mean(axis=0)
        aux_loss = e * jnp.sum(fraction * mean_prob)
        self.sow("intermediates", "aux_loss", aux_loss)

        # -- capacity assignment ------------------------------------------
        # Position of each token within its chosen expert's queue; tokens
        # whose position exceeds capacity are dropped (combine weight 0).
        position = (jnp.cumsum(one_hot, axis=0) - 1.0) * one_hot  # [t, e]
        pos_in_expert = position.sum(axis=-1)  # [t]
        within = pos_in_expert < capacity
        dispatch = (
            one_hot[:, :, None]
            * jax.nn.one_hot(
                pos_in_expert.astype(jnp.int32), capacity, dtype=jnp.float32
            )[:, None, :]
            * within[:, None, None]
        )  # [t, e, c] one-hot
        combine = dispatch * expert_gate[:, None, None]  # [t, e, c]

        # -- dispatch → batched expert FFN → combine ----------------------
        expert_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(self.dtype), tokens.astype(self.dtype)
        )
        expert_in = _constrain(expert_in, self.mesh, P(self.axis_name, None, None))

        w_up = self.param(
            "w_up", nn.initializers.lecun_normal(), (e, d, h), jnp.float32
        ).astype(self.dtype)
        b_up = self.param(
            "b_up", nn.initializers.zeros, (e, 1, h), jnp.float32
        ).astype(self.dtype)
        w_down = self.param(
            "w_down", nn.initializers.lecun_normal(), (e, h, d), jnp.float32
        ).astype(self.dtype)
        b_down = self.param(
            "b_down", nn.initializers.zeros, (e, 1, d), jnp.float32
        ).astype(self.dtype)
        w_up = _constrain(w_up, self.mesh, P(self.axis_name, None, None))
        w_down = _constrain(w_down, self.mesh, P(self.axis_name, None, None))

        hidden = nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, w_up) + b_up)
        hidden = _constrain(hidden, self.mesh, P(self.axis_name, None, None))
        expert_out = jnp.einsum("ech,ehd->ecd", hidden, w_down) + b_down
        expert_out = _constrain(
            expert_out, self.mesh, P(self.axis_name, None, None)
        )

        out = jnp.einsum(
            "tec,ecd->td", combine.astype(self.dtype), expert_out
        )
        return out.reshape(b, s, d)


def collect_aux_loss(intermediates) -> jax.Array:
    """Sum every sowed ``aux_loss`` in an ``intermediates`` collection."""
    total = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "aux_loss" in keys:
            total = total + jnp.sum(leaf)
    return jnp.asarray(total, jnp.float32)
