"""Operations of a Nemotron-H decoder (Mamba-2 mixers, latent routed
experts, grouped-query attention, an untied head) from the
configuration's shapes: what the algorithm needs on THIS chip's share,
whatever implements it.

The layers are the first ``num_layers`` of ``hybrid_override_pattern``
(``M`` Mamba-2, ``E`` experts, ``*`` attention).  A multiply-add is 2
FLOPs.  The routed experts are counted at their expected share: a token
picks ``num_experts_per_tok`` of ``router_width`` experts,
``n_routed_experts`` of which are held here, so on average it passes
``num_experts_per_tok * n_routed_experts / router_width`` held experts
(22 x 64/512 = 2.75 in the benchmark's configuration); the program's
``lm_moe_assignments_total`` says how far one run's routing differs.

A decode step advances each Mamba-2 state by the recurrence
(``RECURRENCE_FLOPS_PER_STATE`` an element of the ``[heads, head_dim,
state]`` state, ``RECURRENCE_FLOPS_PER_CHANNEL`` an element of ``x``); a
prefill runs the chunked state-space-duality algorithm in blocks of
``chunk_size`` positions, whose matrix products are counted in full
(:func:`ssd_flops_per_position`).  The prefill's head is applied to ONE
row (the last real token's), as the program computes it.
"""

from __future__ import annotations

FLOAT32_BYTES = 4
# An element of the state a position: the decay times the state, dt x
# times B and its add, C times the state and its add into y.
RECURRENCE_FLOPS_PER_STATE = 5
# An element of x a position: dt x, D x and its add.
RECURRENCE_FLOPS_PER_CHANNEL = 3


def kinds(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg.get(
        "num_layers", cfg["num_hidden_layers"])]


def layer_counts(cfg: dict) -> tuple:
    """(Mamba-2 layers, expert layers, attention layers)."""
    pattern = kinds(cfg)
    return pattern.count("M"), pattern.count("E"), pattern.count("*")


def d_inner(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def state_size(cfg: dict) -> int:
    """Elements of one Mamba-2 layer's state: heads x head_dim x state."""
    return d_inner(cfg) * cfg["ssm_state_size"]


def mamba_params(cfg: dict) -> int:
    """The two matrices of one Mamba-2 mixer."""
    d, di = cfg["hidden_size"], d_inner(cfg)
    return d * (di + conv_dim(cfg) + cfg["mamba_num_heads"]) + di * d


def attention_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def expert_params(cfg: dict) -> int:
    """One routed expert's two matrices, in the latent."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def moe_dense_params(cfg: dict) -> int:
    """What every token of an expert layer passes: the router, the latent
    projections down and up, the shared expert."""
    d = cfg["hidden_size"]
    return (d * cfg["router_width"] + 2 * d * cfg["moe_latent_size"]
            + 2 * d * cfg["moe_shared_expert_intermediate_size"])


def held_experts_per_token(cfg: dict) -> float:
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_width"])


def matrix_macs_per_token(cfg: dict) -> float:
    """Multiply-adds of one token through every layer's matrices and the
    conv (``conv_kernel`` a channel), the held experts at their expected
    share, the head excluded."""
    mamba, moe, attn = layer_counts(cfg)
    conv = cfg["conv_kernel"] * conv_dim(cfg)
    return (mamba * (mamba_params(cfg) + conv)
            + attn * attention_params(cfg)
            + moe * (moe_dense_params(cfg)
                     + held_experts_per_token(cfg) * expert_params(cfg)))


def recurrence_flops_per_token(cfg: dict) -> int:
    """One position of one Mamba-2 layer's recurrence."""
    return (RECURRENCE_FLOPS_PER_STATE * state_size(cfg)
            + RECURRENCE_FLOPS_PER_CHANNEL * d_inner(cfg))


def ssd_flops_per_position(cfg: dict) -> int:
    """The chunked algorithm's matrix products, a position of one layer,
    blocks of ``L = chunk_size``: ``B C^T`` a group (``2 L N``), and a
    head the block's own part (``2 P L``), the state's part of ``y``
    (``2 P N``) and the state's update (``2 P N``)."""
    length, n = cfg["chunk_size"], cfg["ssm_state_size"]
    p, heads = cfg["mamba_head_dim"], cfg["mamba_num_heads"]
    return (2 * length * n * cfg["n_groups"]
            + heads * (2 * p * length + 4 * p * n))


def head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def _scores(cfg: dict) -> int:
    """FLOPs of one query over one key in every attention layer: scores
    and values at ``head_dim``, every query head."""
    _, _, attn = layer_counts(cfg)
    return attn * cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"]


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """One prompt of ``n_tokens`` real tokens: every token through every
    layer's matrices and the chunked scan, token i's attention over
    i + 1 positions, one logits row."""
    mamba, _, _ = layer_counts(cfg)
    return (2 * n_tokens * matrix_macs_per_token(cfg)
            + _scores(cfg) * n_tokens * (n_tokens + 1) // 2
            + mamba * n_tokens * ssd_flops_per_position(cfg)
            + head_flops(cfg))


def decode_flops(cfg: dict, context: int) -> float:
    """One token attending over ``context`` positions in each attention
    layer, one step of every Mamba-2 layer's recurrence, one logits
    row."""
    mamba, _, _ = layer_counts(cfg)
    return (2 * matrix_macs_per_token(cfg) + _scores(cfg) * context
            + mamba * recurrence_flops_per_token(cfg) + head_flops(cfg))


def ssd_scan_call(cfg: dict, positions: int) -> dict:
    """One call of the prefill's SSD kernel over ``positions`` (a whole
    number of blocks): its matrix products' FLOPs, and the least bytes,
    all float32: ``x`` read and ``y`` written (``positions x d_inner``
    each); the step and the cumulative decay read (``positions x heads``,
    as rows and as columns, three in all); ``B`` and ``C`` read
    (``positions x groups x state`` each); the initial state read and the
    final one written; ``D`` read."""
    heads = cfg["mamba_num_heads"]
    bc = cfg["n_groups"] * cfg["ssm_state_size"]
    nbytes = FLOAT32_BYTES * (2 * positions * d_inner(cfg)
                              + 3 * positions * heads + 2 * positions * bc
                              + 2 * state_size(cfg) + heads)
    return {"flops": positions * ssd_flops_per_position(cfg),
            "bytes": nbytes}
