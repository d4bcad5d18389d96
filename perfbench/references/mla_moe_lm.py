"""Plain reference of what the `mla_moe_lm` configurations compute.

A decoder-only transformer of the DeepSeek-V3 family as Mistral-Small-4
(``model_type`` ``mistral4``) configures it, in straightforward
``jax.numpy``, float32, matmul precision ``highest``.  Per layer, ``x``
``[T, hidden]``, every norm RMSNorm (eps ``rms_norm_eps``, gain), no bias:

- ``h = norm(x)``; ``c_q = norm(h W_qa)``; ``q = c_q W_qb`` as
  ``[T, heads, nope + rope]``; ``[c_kv | k_r] = h W_kva``;
  ``c_kv = norm(c_kv)``; ``k_r`` is one head shared by all.  ``q_rope`` and
  ``k_r`` are rotated: pairs ``(2i, 2i+1)``, base ``rope_theta`` over the
  rope dimensions, YaRN frequencies (the blend of ``1/f`` and
  ``1/(factor f)`` by the linear ramp between the correction dimensions of
  ``beta_fast`` and ``beta_slow`` at ``original_max_position_embeddings``;
  the cos/sin factor ``mscale/mscale_all_dim`` is 1 here and left out).
- the expanded path only: ``[k_nope | v] = c_kv W_kvb``;
  ``k = [k_nope | k_r]``, ``q = [q_nope | q_rope]``; scores ``q k^T s``
  with ``s = (nope + rope)^-0.5 (0.1 mscale_all_dim ln factor + 1)^2``; q of
  position ``p`` also times ``1 + beta ln(1 + floor(p / original_max))``
  (``llama_4_scaling_beta``); causal softmax; ``o = P v``; ``x += o W_o``.
- ``h2 = norm(x)``; router ``p = softmax(h2 W_r)`` over ``router_width``
  experts, the ``num_experts_per_tok`` largest, ``w = p_top / sum(p_top)``
  times ``routed_scaling_factor``; an expert is
  ``(silu(h2 W_gate) * (h2 W_up)) W_down``;
  ``x += sum over the chosen experts HELD HERE of w_i expert_i(h2)
  + shared(h2)``.  The experts held are ``expert_offset ..
  expert_offset + n_routed_experts``; what the absent ones would have added
  is left out (the configuration is one chip's share of a deployment).
- after the last layer ``norm``, then the head over the rows of the
  vocabulary held.

No cache, no kernel, no batching, no sorting: one sequence, all positions
at once, every held expert applied to every token and masked by the
routing, attention in blocks of queries so that 8,704 positions fit.  Each
layer's weights are made from the seed when the layer is reached, so the
whole model is never held.  ``quant`` is where the low-precision control
enters (both operands of every product).  ``max_position_embeddings`` is
not read: the driver overwrites it with the server's length and nothing
here depends on it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import weights

QUERY_BLOCK = 256


def layer_shapes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, f = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    shapes = {
        "attn_norm/scale": (d,),
        "q_a/kernel": (d, cfg["q_lora_rank"]),
        "q_norm/scale": (cfg["q_lora_rank"],),
        "q_b/kernel": (cfg["q_lora_rank"], h * (nope + rope)),
        "kv_a/kernel": (d, rank + rope),
        "kv_norm/scale": (rank,),
        "kv_b/kernel": (rank, h * (nope + cfg["v_head_dim"])),
        "o/kernel": (h * cfg["v_head_dim"], d),
        "ffn_norm/scale": (d,),
        "router/kernel": (d, cfg["router_width"]),
        "shared/gate/kernel": (d, fs),
        "shared/up/kernel": (d, fs),
        "shared/down/kernel": (fs, d),
    }
    for e in range(cfg["n_routed_experts"]):
        shapes[f"expert_{e}/gate/kernel"] = (d, f)
        shapes[f"expert_{e}/up/kernel"] = (d, f)
        shapes[f"expert_{e}/down/kernel"] = (f, d)
    return shapes


def param_shapes(cfg: dict) -> dict[str, tuple]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"params/tok_embed/embedding": (v, d),
              "params/final_norm/scale": (d,),
              "params/lm_head/kernel": (d, v)}
    for i in range(cfg["num_layers"]):
        for name, shape in layer_shapes(cfg).items():
            shapes[f"params/layer_{i}/{name}"] = shape
    return shapes


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=lax.Precision.HIGHEST)


def _inv_freq(cfg: dict) -> np.ndarray:
    """YaRN inverse frequencies (``transformers``'
    ``_compute_yarn_parameters``, truncating the correction range)."""
    rp = cfg["rope_parameters"]
    dim, base = cfg["qk_rope_head_dim"], rp["rope_theta"]
    factor, orig = rp["factor"], rp["original_max_position_embeddings"]
    freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                       # the share of 1/f
    return ((1.0 / (factor * freqs)) * (1.0 - keep) + (1.0 / freqs) * keep
            ).astype(np.float32)


def _rotate(x, cos, sin):
    """Pairs (2i, 2i+1) of the last axis rotated by the angle of i."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def _attention(q, k, v, scale, quant):
    """Causal softmax attention ``[heads, T, .]`` in blocks of queries."""
    h, t, _ = q.shape
    size = min(QUERY_BLOCK, t)
    if t % size:
        raise ValueError(f"{t} positions are no multiple of {size}")
    kt = k.transpose(0, 2, 1)
    cols = jnp.arange(t)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * size, size, 1)
        rows = i * size + jnp.arange(size)
        scores = _mm(qb, kt, quant) * scale
        scores = jnp.where(rows[:, None] >= cols[None, :], scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, axis=-1), v, quant)

    out = lax.map(block, jnp.arange(t // size))       # [blocks, h, qb, dv]
    return out.transpose(1, 0, 2, 3).reshape(h, t, v.shape[-1])


def _gated(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def _layer(x, w, cfg, quant):
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, dv, eps = cfg["kv_lora_rank"], cfg["v_head_dim"], cfg["rms_norm_eps"]
    rp = cfg["rope_parameters"]
    pos = jnp.arange(t, dtype=jnp.float32)
    angles = pos[:, None] * jnp.asarray(_inv_freq(cfg))[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)

    hid = _rms(x, w["attn_norm/scale"], eps)
    c_q = _rms(_mm(hid, w["q_a/kernel"], quant), w["q_norm/scale"], eps)
    q = _mm(c_q, w["q_b/kernel"], quant).reshape(t, h, nope + rope)
    kv_a = _mm(hid, w["kv_a/kernel"], quant)
    c_kv = _rms(kv_a[:, :rank], w["kv_norm/scale"], eps)
    k_r = _rotate(kv_a[:, rank:], cos, sin)
    q_rope = _rotate(q[..., nope:], cos[:, None, :], sin[:, None, :])
    kv = _mm(c_kv, w["kv_b/kernel"], quant).reshape(t, h, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (t, h, rope))], -1)
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    # the position-dependent scale of the query
    q = q * (1.0 + rp["llama_4_scaling_beta"] * jnp.log1p(jnp.floor(
        pos / rp["original_max_position_embeddings"])))[:, None, None]
    mscale = 0.1 * rp["mscale_all_dim"] * math.log(rp["factor"]) + 1.0 \
        if rp["mscale_all_dim"] and rp["factor"] > 1 else 1.0
    scale = (nope + rope) ** -0.5 * mscale * mscale
    attn = _attention(q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                      kv[..., nope:].transpose(1, 0, 2), scale, quant)
    x = x + _mm(attn.transpose(1, 0, 2).reshape(t, h * dv), w["o/kernel"],
                quant)

    h2 = _rms(x, w["ffn_norm/scale"], eps)
    probs = jax.nn.softmax(_mm(h2, w["router/kernel"], quant), axis=-1)
    top_p, top_i = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * cfg["routed_scaling_factor"]
    out = _gated(h2, w["shared/gate/kernel"], w["shared/up/kernel"],
                 w["shared/down/kernel"], quant)
    for e in range(cfg["n_routed_experts"]):
        # this expert's weight for each token: 0 where it was not chosen
        share = jnp.sum(
            jnp.where(top_i == cfg["expert_offset"] + e, top_p, 0.0), -1)
        out = out + share[:, None] * _gated(
            h2, w[f"expert_{e}/gate/kernel"], w[f"expert_{e}/up/kernel"],
            w[f"expert_{e}/down/kernel"], quant)
    return x + out


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _layer_from_seed(x, key, salts, *, cfg_key, quant):
    cfg = _cfg_of(cfg_key)
    w = {name: weights.leaf(key, name, shape, salts[name])
         for name, shape in layer_shapes(cfg).items()}
    return _layer(x, w, cfg, quant)


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _embed(tokens, key, *, cfg_key):
    cfg = _cfg_of(cfg_key)
    tok = weights.leaf(key, "params/tok_embed/embedding",
                       (cfg["vocab_size"], cfg["hidden_size"]))
    return tok[tokens]


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _head(x, key, *, cfg_key, quant):
    cfg = _cfg_of(cfg_key)
    d = cfg["hidden_size"]
    scale = weights.leaf(key, "params/final_norm/scale", (d,))
    head = weights.leaf(key, "params/lm_head/kernel", (d, cfg["vocab_size"]))
    return _mm(_rms(x, scale, cfg["rms_norm_eps"]), head, quant)


_KEYS = ("hidden_size", "num_layers", "num_attention_heads", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
         "router_width", "expert_offset", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor", "rms_norm_eps",
         "vocab_size")
_ROPE_KEYS = ("rope_theta", "factor", "beta_fast", "beta_slow",
              "original_max_position_embeddings", "mscale_all_dim",
              "llama_4_scaling_beta")


def _cfg_key(cfg: dict) -> tuple:
    return (tuple((k, cfg[k]) for k in _KEYS),
            tuple((k, cfg["rope_parameters"][k]) for k in _ROPE_KEYS))


def _cfg_of(cfg_key: tuple) -> dict:
    return {**dict(cfg_key[0]), "rope_parameters": dict(cfg_key[1])}


def _exact(a):
    return a


LONG_PAD = 17 * QUERY_BLOCK     # 4,352: two shapes up to 8,704 positions


def logits(tokens, seed: int, cfg: dict, quant=_exact, pad_to=None):
    """Logits [len(tokens), vocab] of one sequence, float32.  The sequence
    is padded to a multiple of ``pad_to`` (fewer shapes to compile: 256
    for a sequence of up to 1,024 tokens, else 4,352, so that the
    document cell's lengths give two shapes); under a causal mask the
    padding cannot reach the real positions, and the expert layer is per
    token."""
    n = len(tokens)
    if pad_to is None:
        pad_to = QUERY_BLOCK if n <= 4 * QUERY_BLOCK else LONG_PAD
    if pad_to % QUERY_BLOCK:
        raise ValueError(f"pad_to must be a multiple of {QUERY_BLOCK}")
    padded = -(-n // pad_to) * pad_to
    ids = jnp.zeros((padded,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    key, ck = weights.key_for(seed), _cfg_key(cfg)
    names = tuple(layer_shapes(cfg))
    with jax.default_matmul_precision("highest"):
        x = _embed(ids, key, cfg_key=ck)
        for i in range(cfg["num_layers"]):
            salts = {name: jnp.int32(weights.salt(f"params/layer_{i}/{name}"))
                     for name in names}
            x = _layer_from_seed(x, key, salts, cfg_key=ck, quant=quant)
        return _head(x, key, cfg_key=ck, quant=quant)[:n]
