"""Operations of a Jamba decoder (Mamba-1 layers beside multi-query
attention, a tied head) from the configuration's shapes: what the
algorithm needs, whatever implements it.

Layer ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset``, else Mamba; every layer has the gated MLP.  A
multiply-add is 2 FLOPs.  The scan's elementwise work is counted too
(``SCAN_FLOPS_PER_STATE`` a channel and state a position, and
``SCAN_FLOPS_PER_CHANNEL`` a channel); its ``exp`` is not.  The prefill's
head is applied to ONE row (the last real token's), as the program
computes it (``num_logits_to_keep`` 1).
"""

from __future__ import annotations

FLOAT32_BYTES = 4
# A channel and state a position: dt A, the decay times the state, B times
# dt u and its add, C times the state and its add into y.
SCAN_FLOPS_PER_STATE = 6
# A channel a position: dt u, D u and its add.
SCAN_FLOPS_PER_CHANNEL = 3


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_counts(cfg: dict) -> tuple:
    """(Mamba layers, attention layers)."""
    attn = sum(1 for i in range(cfg["num_hidden_layers"])
               if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"])
    return cfg["num_hidden_layers"] - attn, attn


def mamba_params(cfg: dict) -> int:
    """The four matrices of one Mamba mixer."""
    d, di = cfg["hidden_size"], d_inner(cfg)
    r, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def attention_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def matrix_macs_per_token(cfg: dict) -> int:
    """Multiply-adds of one token through every layer's matrices and the
    conv (``mamba_d_conv`` a channel), head excluded."""
    mamba, attn = layer_counts(cfg)
    conv = cfg["mamba_d_conv"] * d_inner(cfg)
    return (mamba * (mamba_params(cfg) + conv) + attn * attention_params(cfg)
            + cfg["num_hidden_layers"] * mlp_params(cfg))


def scan_flops_per_token(cfg: dict) -> int:
    """The scan's elementwise work of one position in one Mamba layer."""
    di = d_inner(cfg)
    return di * (SCAN_FLOPS_PER_STATE * cfg["mamba_d_state"]
                 + SCAN_FLOPS_PER_CHANNEL)


def head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """One prompt of ``n_tokens`` real tokens: every token through every
    layer's matrices and scans, token i's attention over i + 1 positions
    (``head_dim`` for the scores and for the values, every query head),
    one logits row."""
    mamba, attn = layer_counts(cfg)
    scores = (attn * cfg["num_attention_heads"] * 2 * head_dim(cfg)
              * n_tokens * (n_tokens + 1) // 2)
    return (2 * n_tokens * matrix_macs_per_token(cfg) + 2 * scores
            + mamba * n_tokens * scan_flops_per_token(cfg) + head_flops(cfg))


def decode_flops(cfg: dict, context: int) -> float:
    """One token attending over ``context`` positions in each attention
    layer, one step of every Mamba layer's state, one logits row."""
    mamba, attn = layer_counts(cfg)
    scores = attn * cfg["num_attention_heads"] * 2 * head_dim(cfg) * context
    return (2 * matrix_macs_per_token(cfg) + 2 * scores
            + mamba * scan_flops_per_token(cfg) + head_flops(cfg))


def ssm_scan_call(cfg: dict, positions: int) -> dict:
    """One call of the prefill's scan kernel over ``positions`` (the
    bucket): its elementwise FLOPs, and the least bytes, all float32:
    ``u`` and ``dt`` read and ``y`` written, ``[positions, d_inner]``
    each; ``B`` and ``C`` read, ``[positions, d_state]`` each; ``A`` read
    and the state written, ``[d_state, d_inner]`` each; ``D`` read."""
    di, n = d_inner(cfg), cfg["mamba_d_state"]
    flops = positions * scan_flops_per_token(cfg)
    nbytes = FLOAT32_BYTES * (3 * positions * di + 2 * positions * n
                              + 2 * n * di + di)
    return {"flops": flops, "bytes": nbytes}
