"""Least bytes of a Nemotron-H decoder's decode step, from the
configuration's shapes and the step's routing, whatever implements it.

One step advances ``active`` sequences by one token each.  Whatever the
program, it has to read once every weight that every token is multiplied
through (the Mamba-2 mixers, attention, the router, the latent
projections, the shared experts, the norm gains, the output head), once
the two matrices of every held expert that got at least one token
(``experts_touched``, summed over layers: an expert nobody was routed to
need not be read), the embedding rows of its tokens; read and write each
active sequence's Mamba-2 state and conv inputs in every Mamba-2 layer
(float32); read the key and value rows of every position attended over;
and write the logits.  Weights and cache are counted at the
configuration's compute width (bfloat16: 2); the leaves held in float32
(norm gains, the state's own parameters ``A_log``, ``D``, ``dt_bias``,
the conv's kernel and bias, the router and its selection bias) at 4; the
logits at the width the program returns them in (float32: 4).

While the program reads weights at these widths or wider, a share of this
count over the chip's peak cannot pass 100%.
"""

from __future__ import annotations

import flops_nemotron_h_lm as count

COMPUTE_BYTES = 2
FLOAT32_BYTES = 4


def float32_params(cfg: dict) -> int:
    """The leaves held in float32: every norm gain; each Mamba-2 layer's
    ``A_log``, ``D``, ``dt_bias``, conv kernel and bias; each expert
    layer's router and its selection bias."""
    d, heads = cfg["hidden_size"], cfg["mamba_num_heads"]
    mamba, moe, attn = count.layer_counts(cfg)
    per_mamba = (d + count.d_inner(cfg) + 3 * heads
                 + (cfg["conv_kernel"] + 1) * count.conv_dim(cfg))
    per_moe = d + (d + 1) * cfg["router_width"]
    return mamba * per_mamba + moe * per_moe + attn * d + d


def step_weight_bytes(cfg: dict, width: int = COMPUTE_BYTES) -> int:
    """Everything every step reads whatever the routing."""
    mamba, moe, attn = count.layer_counts(cfg)
    d = cfg["hidden_size"]
    at_width = (mamba * count.mamba_params(cfg)
                + attn * count.attention_params(cfg)
                + moe * (count.moe_dense_params(cfg)
                         - d * cfg["router_width"])
                + d * cfg["vocab_size"])
    return at_width * width + float32_params(cfg) * FLOAT32_BYTES


def expert_bytes(cfg: dict, width: int = COMPUTE_BYTES) -> int:
    return count.expert_params(cfg) * width


def state_bytes_per_sequence(cfg: dict) -> int:
    """One sequence's Mamba-2 state and conv inputs in every Mamba-2
    layer, float32."""
    mamba, _, _ = count.layer_counts(cfg)
    return mamba * (count.state_size(cfg) + (cfg["conv_kernel"] - 1)
                    * count.conv_dim(cfg)) * FLOAT32_BYTES


def cache_bytes_per_token(cfg: dict, width: int = COMPUTE_BYTES) -> int:
    """The key and value rows of one position in every attention layer."""
    _, _, attn = count.layer_counts(cfg)
    return attn * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * width


def decode_step_bytes(cfg: dict, context_tokens: float, active: float,
                      experts_touched: float,
                      width: int = COMPUTE_BYTES) -> float:
    """``context_tokens``: positions attended over, summed over the
    ``active`` sequences; ``experts_touched``: held experts with at least
    one token, summed over layers."""
    return (step_weight_bytes(cfg, width)
            + experts_touched * expert_bytes(cfg, width)
            + active * cfg["hidden_size"] * width
            + active * 2 * state_bytes_per_sequence(cfg)
            + context_tokens * cache_bytes_per_token(cfg, width)
            + active * cfg["vocab_size"] * FLOAT32_BYTES)
