"""Least bytes of a Jamba decoder's decode step, from the configuration's
shapes alone, whatever implements it.

One step advances ``active`` sequences by one token each.  Whatever the
program, it has to read once every weight (the projection matrices and
the MLPs at the configuration's compute width, bfloat16: 2; the tied
embedding table whole, since the head multiplies by all of it, which
also covers the rows the step's tokens gather; the norm gains and the
state's own parameters, ``A_log``, ``D``, the conv and ``dt_proj``'s
bias, at float32), read and write each active sequence's recurrent
state in every Mamba layer (``d_state x d_inner`` and the ``d_conv - 1``
conv inputs, float32), read the key and value rows of every position
attended over in every attention layer, and write the logits (float32).

While the program reads weights at these widths or wider, a share of this
count over the chip's peak cannot pass 100%.
"""

from __future__ import annotations

import flops_jamba_lm as count

COMPUTE_BYTES = 2
FLOAT32_BYTES = 4


def float32_params(cfg: dict) -> int:
    """The leaves held in float32: every norm gain, and each Mamba
    layer's ``A_log``, ``D``, conv kernel and bias, ``dt_proj`` bias."""
    d, di = cfg["hidden_size"], count.d_inner(cfg)
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    mamba, _ = count.layer_counts(cfg)
    per_mamba = (r + 2 * n + di * n + di + cfg["mamba_d_conv"] * di + di
                 + di)
    return (cfg["num_hidden_layers"] * 2 * d + mamba * per_mamba + d)


def matrix_params(cfg: dict) -> int:
    """The leaves held at the compute width: every layer's matrices and
    the embedding table."""
    mamba, attn = count.layer_counts(cfg)
    return (mamba * count.mamba_params(cfg)
            + attn * count.attention_params(cfg)
            + cfg["num_hidden_layers"] * count.mlp_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def weight_bytes(cfg: dict, width: int = COMPUTE_BYTES) -> int:
    return matrix_params(cfg) * width + float32_params(cfg) * FLOAT32_BYTES


def state_bytes_per_sequence(cfg: dict) -> int:
    """One sequence's recurrent state in every Mamba layer, float32."""
    mamba, _ = count.layer_counts(cfg)
    di = count.d_inner(cfg)
    return mamba * (cfg["mamba_d_state"] + cfg["mamba_d_conv"] - 1) * di \
        * FLOAT32_BYTES


def cache_bytes_per_token(cfg: dict, width: int = COMPUTE_BYTES) -> int:
    """The key and value rows of one position in every attention layer."""
    _, attn = count.layer_counts(cfg)
    return attn * 2 * cfg["num_key_value_heads"] * count.head_dim(cfg) * width


def decode_step_bytes(cfg: dict, context_tokens: float, active: float,
                      width: int = COMPUTE_BYTES) -> float:
    """``context_tokens``: positions attended over, summed over the
    ``active`` sequences."""
    return (weight_bytes(cfg, width)
            + active * 2 * state_bytes_per_sequence(cfg)
            + context_tokens * cache_bytes_per_token(cfg, width)
            + active * cfg["vocab_size"] * FLOAT32_BYTES)
