"""Operations and bytes of a decoder-only transformer from the
configuration's shapes."""

from __future__ import annotations


def params_nonembedding(cfg: dict) -> int:
    """Weights of the matrices every token is multiplied through, the
    output head not among them (qkv, proj, mlp up and down, per layer)."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (3 * d * d + d * d + 2 * d * ff)


def token_macs(cfg: dict, context: int) -> int:
    """Multiply-adds for one token that attends over ``context``
    positions (itself included): layer matrices, attention scores and
    values, and the output head."""
    d = cfg["hidden_size"]
    attn = cfg["num_hidden_layers"] * 2 * context * d
    return params_nonembedding(cfg) + attn + d * cfg["vocab_size"]


def prefill_flops(cfg: dict, n_tokens: int) -> int:
    """One prompt of ``n_tokens`` real tokens through a causal pass: token
    i attends over i + 1 positions; the head is applied at every position
    (the program computes all rows of the logits)."""
    d = cfg["hidden_size"]
    dense = n_tokens * (params_nonembedding(cfg) + d * cfg["vocab_size"])
    attn = cfg["num_hidden_layers"] * 2 * d * n_tokens * (n_tokens + 1) // 2
    return 2 * (dense + attn)


def decode_flops(cfg: dict, context: int) -> int:
    return 2 * token_macs(cfg, context)


def flash_prefill_call(cfg: dict, bucket: int, bytes_per_el: int = 2) -> dict:
    """One causal flash-attention call of the prefill program (batch 1,
    all heads, sequence ``bucket``): FLOPs with the causal half counted
    once, and the least bytes: q, k, v read and o written once."""
    d = cfg["hidden_size"]
    flops = 2 * 2 * d * bucket * (bucket + 1) // 2   # qk^T and pv
    return {"flops": flops, "bytes": 4 * bucket * d * bytes_per_el}
