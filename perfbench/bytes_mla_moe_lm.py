"""Least bytes of a latent-attention expert decoder's decode step, from
the configuration's shapes and the step's routing, whatever implements it.

One step advances ``active`` sequences by one token each.  Whatever the
program, it has to read once every weight that every token is multiplied
through (attention, router, shared experts, norm gains, the output head),
once the three matrices of every held expert that got at least one token
(``experts_touched``, summed over layers: an expert nobody was routed to
need not be read), the embedding rows of its tokens, and the latent row of
every position attended over; and it has to write the logits.  Weights and
cache are counted at the configuration's compute width (bfloat16: 2), the
router and the norm gains at float32 (the routing is decided in float32),
the logits at the width the program returns them in (float32: 4).

While the program reads weights at these widths or wider, a share of this
count over the chip's peak cannot pass 100%.
"""

from __future__ import annotations

import flops_mla_moe_lm as count

COMPUTE_BYTES = 2
FLOAT32_BYTES = 4


def expert_bytes(cfg: dict, width: int = COMPUTE_BYTES) -> int:
    return count.expert_params(cfg) * width


def step_weight_bytes(cfg: dict, width: int = COMPUTE_BYTES) -> int:
    """Everything every step reads whatever the routing."""
    d = cfg["hidden_size"]
    gains = 2 * d + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    layer = ((count.attention_params(cfg)
              + cfg["n_shared_experts"] * count.expert_params(cfg)) * width
             + (d * cfg["router_width"] + gains) * FLOAT32_BYTES)
    return (cfg["num_layers"] * layer + d * FLOAT32_BYTES
            + d * cfg["vocab_size"] * width)


def cache_bytes_per_token(cfg: dict, width: int = COMPUTE_BYTES) -> int:
    """The latent row of one position in every layer."""
    return (cfg["num_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * width)


def decode_step_bytes(cfg: dict, context_tokens: float, active: float,
                      experts_touched: float,
                      width: int = COMPUTE_BYTES) -> float:
    """``context_tokens``: positions attended over, summed over the
    ``active`` sequences; ``experts_touched``: held experts with at least
    one token, summed over layers."""
    return (step_weight_bytes(cfg, width)
            + experts_touched * expert_bytes(cfg, width)
            + active * cfg["hidden_size"] * width
            + context_tokens * cache_bytes_per_token(cfg, width)
            + active * cfg["vocab_size"] * FLOAT32_BYTES)
