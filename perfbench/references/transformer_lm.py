"""Plain reference of what the `transformer_lm` configurations compute.

A decoder-only transformer as the program's ``TransformerLM`` runs it,
in straightforward ``jax.numpy``, float32, matmul precision ``highest``:
token plus learned position embeddings; per layer RMSNorm (eps 1e-6,
gain), one bias-free projection to q, k, v, causal softmax attention
over heads scaled by 1/sqrt(head size), bias-free output projection,
RMSNorm, GELU (tanh form) feed-forward with biases; a final RMSNorm and
an untied, bias-free output head.  It is a reference of the code's
arithmetic, not of GPT-2 (see the configuration's ``assumed``).

No cache, no kernel, no batching: one sequence, all positions at once,
layer by layer.  Each layer's weights are made from the seed when the
layer is reached, so the whole model is never held.  ``quant`` is where
the low-precision control enters (both operands of every product).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

import weights

RMS_EPS = 1e-6
LAYER_LEAVES = ("RMSNorm_0/scale", "qkv/kernel", "proj/kernel",
                "RMSNorm_1/scale", "mlp_up/kernel", "mlp_up/bias",
                "mlp_down/kernel", "mlp_down/bias")


def layer_shapes(cfg: dict) -> dict:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return {"RMSNorm_0/scale": (d,), "qkv/kernel": (d, 3 * d),
            "proj/kernel": (d, d), "RMSNorm_1/scale": (d,),
            "mlp_up/kernel": (d, ff), "mlp_up/bias": (ff,),
            "mlp_down/kernel": (ff, d), "mlp_down/bias": (d,)}


def param_shapes(cfg: dict) -> dict[str, tuple]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"params/tok_embed/embedding": (v, d),
              "params/pos_embed": (cfg["max_position_embeddings"], d),
              "params/RMSNorm_0/scale": (d,),
              "params/lm_head/kernel": (d, v)}
    for i in range(cfg["num_hidden_layers"]):
        for name, shape in layer_shapes(cfg).items():
            shapes[f"params/block_{i}/{name}"] = shape
    return shapes


def _rms(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) * scale


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=lax.Precision.HIGHEST)


def _layer(x, w, heads, quant):
    s, d = x.shape
    hd = d // heads
    q, k, v = jnp.split(_mm(_rms(x, w["RMSNorm_0/scale"]), w["qkv/kernel"],
                            quant), 3, axis=-1)
    q, k, v = (t.reshape(s, heads, hd).transpose(1, 0, 2) for t in (q, k, v))
    scores = _mm(q, k.transpose(0, 2, 1), quant) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = _mm(probs, v, quant).transpose(1, 0, 2).reshape(s, d)
    x = x + _mm(attn, w["proj/kernel"], quant)
    h = _mm(_rms(x, w["RMSNorm_1/scale"]), w["mlp_up/kernel"], quant)
    h = jax.nn.gelu(h + w["mlp_up/bias"], approximate=True)
    return x + _mm(h, w["mlp_down/kernel"], quant) + w["mlp_down/bias"]


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _layer_from_seed(x, key, salts, *, cfg_key, quant):
    cfg = dict(cfg_key)
    w = {name: weights.leaf(key, name, shape, salts[name])
         for name, shape in layer_shapes(cfg).items()}
    return _layer(x, w, cfg["num_attention_heads"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _embed(tokens, key, *, cfg_key):
    cfg = dict(cfg_key)
    d = cfg["hidden_size"]
    tok = weights.leaf(key, "params/tok_embed/embedding",
                       (cfg["vocab_size"], d))
    pos = weights.leaf(key, "params/pos_embed",
                       (cfg["max_position_embeddings"], d))
    return tok[tokens] + pos[: tokens.shape[0]]


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _head(x, key, *, cfg_key, quant):
    cfg = dict(cfg_key)
    d = cfg["hidden_size"]
    scale = weights.leaf(key, "params/RMSNorm_0/scale", (d,))
    head = weights.leaf(key, "params/lm_head/kernel", (d, cfg["vocab_size"]))
    return _mm(_rms(x, scale), head, quant)


def _cfg_key(cfg: dict) -> tuple:
    keep = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_hidden_layers", "vocab_size", "max_position_embeddings")
    return tuple((k, cfg[k]) for k in keep)


def _exact(a):
    return a


def logits(tokens, seed: int, cfg: dict, quant=_exact, pad_to: int = 256):
    """Logits [len(tokens), vocab] of one sequence, float32.  The sequence
    is padded to a multiple of ``pad_to`` (fewer shapes to compile); under
    a causal mask the padding cannot reach the real positions."""
    n = len(tokens)
    padded = min(-(-n // pad_to) * pad_to, cfg["max_position_embeddings"])
    ids = jnp.zeros((padded,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    key, ck = weights.key_for(seed), _cfg_key(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(ids, key, cfg_key=ck)
        for i in range(cfg["num_hidden_layers"]):
            salts = {name: jnp.int32(weights.salt(f"params/block_{i}/{name}"))
                     for name in LAYER_LEAVES}
            x = _layer_from_seed(x, key, salts, cfg_key=ck, quant=quant)
        return _head(x, key, cfg_key=ck, quant=quant)[:n]
