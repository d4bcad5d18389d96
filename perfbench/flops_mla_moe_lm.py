"""Operations of a latent-attention expert decoder from the
configuration's shapes: what the algorithm needs on THIS chip's share.

The routed experts are counted at their expected share: a token picks
``num_experts_per_tok`` of ``router_width`` experts, ``n_routed_experts``
of which are held here, so on average it passes
``num_experts_per_tok * n_routed_experts / router_width`` held experts
(4 x 16/128 = half an expert a token in the benchmark's configuration).
The count of one run differs from that by the run's routing; the program's
``lm_moe_assignments_total`` says by how much.  The prefill's head is
applied to ONE row (the last real token's), as the program computes it.
"""

from __future__ import annotations


def _qk(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def attention_params(cfg: dict) -> int:
    """The matrices of one layer's attention (both paths multiply a token
    through the same count: the absorbed path's two folded halves are
    ``kv_b``'s)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rank = cfg["kv_lora_rank"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * _qk(cfg)
            + d * (rank + cfg["qk_rope_head_dim"])
            + rank * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_experts_per_token(cfg: dict) -> float:
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_width"])


def layer_macs_per_token(cfg: dict) -> float:
    """Multiply-adds of one token through one layer's matrices: attention,
    router, shared experts, and the held routed experts at their expected
    share."""
    return (attention_params(cfg)
            + cfg["hidden_size"] * cfg["router_width"]
            + cfg["n_shared_experts"] * expert_params(cfg)
            + held_experts_per_token(cfg) * expert_params(cfg))


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """One prompt of ``n_tokens`` real tokens through the expanded path:
    token i attends over i + 1 positions at ``qk`` dimensions for the
    scores and ``v_head_dim`` for the values, a head; one logits row."""
    h, layers = cfg["num_attention_heads"], cfg["num_layers"]
    dense = n_tokens * layers * layer_macs_per_token(cfg)
    attn = (layers * h * (_qk(cfg) + cfg["v_head_dim"])
            * n_tokens * (n_tokens + 1) // 2)
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return 2 * (dense + attn + head)


def decode_flops(cfg: dict, context: int) -> float:
    """One token through the absorbed path attending over ``context``
    latent rows: scores over ``kv_lora_rank + rope`` values a row a head,
    values over ``kv_lora_rank``; the head at its row."""
    h, layers, rank = (cfg["num_attention_heads"], cfg["num_layers"],
                       cfg["kv_lora_rank"])
    attn = layers * h * context * (2 * rank + cfg["qk_rope_head_dim"])
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return 2 * (layers * layer_macs_per_token(cfg) + attn + head)


def flash_prefill_call(cfg: dict, bucket: int, bytes_per_el: int = 2) -> dict:
    """One causal flash-attention call of the prefill program (batch 1,
    all heads at ``qk == v`` head size, sequence ``bucket``): FLOPs with
    the causal half counted once, and the least bytes: q, k, v read and o
    written once."""
    width = cfg["num_attention_heads"] * _qk(cfg)
    flops = 2 * 2 * width * bucket * (bucket + 1) // 2   # qk^T and pv
    return {"flops": flops, "bytes": 4 * bucket * width * bytes_per_el}
