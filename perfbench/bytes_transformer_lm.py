"""Least bytes of a decoder-only transformer's decode step, from the
configuration's shapes alone and whatever implements the step.

One step advances ``active`` sequences by one token each.  Whatever the
program, it has to read every weight that every token is multiplied
through once (the layers' matrices, biases and norm gains, and the output
head), the embedding rows of the tokens it was given, and the keys and
values of every position attended over; and it has to write the logits.
Weights and cache are counted at the configuration's compute width
(``compute_bytes``, bfloat16: 2), the logits at the width the program
returns them in (float32: 4).  The new position's keys and values
(``active`` rows) are left out: under a thousandth of the rest.

While the program reads weights at this width or wider, a share of this
count over the chip's peak cannot pass 100%.  A program that stores
weights narrower (int8, fp8) needs this file's count restated first, by
a ``benchmark`` PR.
"""

from __future__ import annotations

COMPUTE_BYTES = 2     # bfloat16: the width the program multiplies in
LOGIT_BYTES = 4       # float32 logits, as the program returns them


def step_weights(cfg: dict) -> int:
    """Parameters every decode step is multiplied through: per layer qkv
    and proj (no bias), the feed-forward pair with its biases and the two
    norm gains; the final norm gain; the untied output head (no bias)."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    layer = 3 * d * d + d * d + (d * ff + ff) + (ff * d + d) + 2 * d
    return cfg["num_hidden_layers"] * layer + d + d * cfg["vocab_size"]


def cache_bytes_per_token(cfg: dict, width: int = COMPUTE_BYTES) -> int:
    """Keys and values of one position in every layer."""
    return cfg["num_hidden_layers"] * 2 * cfg["hidden_size"] * width


def decode_step_bytes(cfg: dict, context_tokens: int, active: int,
                      width: int = COMPUTE_BYTES) -> int:
    """``context_tokens``: positions attended over, summed over the
    ``active`` sequences of the step."""
    d = cfg["hidden_size"]
    weights = step_weights(cfg) * width
    embeddings = active * 2 * d * width          # one token row, one position row
    cache = context_tokens * cache_bytes_per_token(cfg, width)
    logits = active * cfg["vocab_size"] * LOGIT_BYTES
    return weights + embeddings + cache + logits
