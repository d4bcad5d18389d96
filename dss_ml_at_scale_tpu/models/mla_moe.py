"""Decoder-only LM with latent attention (MLA) and a routed expert layer.

The block of the DeepSeek-V3 family as Mistral-Small-4 (``model_type``
``mistral4``) configures it: RMSNorm pre-norm residuals, no bias
anywhere, an untied output head, and per layer

- **latent attention**: queries through a low-rank pair (``q_a``, norm,
  ``q_b``) into heads of ``qk_nope + qk_rope`` dimensions; keys and
  values from one latent row a token, ``[c_kv | k_r]``: ``kv_lora_rank``
  normed values plus ONE rotary key shared by every head. Rotary is
  interleaved pairs over the rope dimensions only, at YaRN frequencies,
  with the position-dependent query scale
  (``llama_4_scaling_beta``). The cache holds the latent row, nothing
  else, and two paths read it: **expanded** (prefill: keys and values
  rebuilt through ``kv_b``, the flash kernel at head size
  ``qk_nope + qk_rope == v_head_dim``) and **absorbed** (decode:
  ``kv_b``'s two halves folded into the query and into the output, so
  attention runs over the latent rows themselves);
- **an expert layer**: a float32 softmax router over ``router_width``
  experts, the ``num_experts_per_tok`` largest, their weights
  renormalised; a gated-SiLU expert; one shared expert every token
  passes. The layer is *told which experts it holds*
  (``expert_offset``, ``n_routed_experts``): it routes over all of them
  and adds the part of the result its own experts give. What absent
  experts would have added is left out; nothing stands in for them.
  No token is dropped whatever the imbalance: assignments are sorted by
  expert and each held expert runs over its own segment in tiles, as
  many as it got (none where it got none, so a decode step reads only
  the experts it touched).

The model is a frozen dataclass (hashable: the serving programs take it
as their static argument) over a plain tree of arrays; it gives the
decoder what :mod:`..serving.lm.kvcache` asks of a served model:
``init_cache``, ``prefill_cache``, ``decode_slots``,
``serving_variables``. ``stats`` beside the logits is one small int32
array, ``[held, absent, touched, per held expert...]`` summed over
layers, from which the decoder feeds the ``lm_moe_*`` counters.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.flash_attention import attention_reference, flash_attention
from .transformer import rms_norm

# Leaves multiplied in float32 whatever the model's dtype: norm gains
# and the router (a float32 softmax decides the routing).
_FLOAT32_ENDINGS = (("scale",), ("router", "kernel"))


def yarn_inv_freq(dim: int, base: float, factor: float, beta_fast: float,
                  beta_slow: float, original_max: int):
    """YaRN inverse frequencies over ``dim`` rotary dimensions: the
    blend of ``1/f`` (extrapolation) and ``1/(factor f)``
    (interpolation) by the linear ramp between the correction dimensions
    of ``beta_fast`` and ``beta_slow`` rotations at ``original_max``
    positions (floor and ceiling taken, clamped to the dimensions)."""
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1
    )
    extrapolated = 1.0 - ramp
    return ((1.0 / (factor * pos_freqs)) * (1.0 - extrapolated)
            + (1.0 / pos_freqs) * extrapolated)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotate_interleaved(x, angles):
    """Rotate the pairs ``(2i, 2i+1)`` of the last axis by ``angles``
    (broadcastable to ``x[..., ::2]``), in float32."""
    x32 = _cast(x, jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


@dataclasses.dataclass(frozen=True)
class MlaMoeLM:
    """The architecture's numbers; see the module docstring."""

    vocab_size: int
    hidden_size: int
    num_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    moe_intermediate_size: int
    n_routed_experts: int        # held here
    router_width: int            # the router's outputs: all experts
    expert_offset: int = 0       # the first held expert's index
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    original_max_position_embeddings: int = 8192
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    llama_4_scaling_beta: float = 0.0
    attention: str = "flash"     # the prefill's: "flash" | "reference"
    dtype: Any = jnp.bfloat16
    expert_tile: int = 256       # rows of one pass over a held expert

    cache_kind = "latent"

    @classmethod
    def from_config(cls, config: dict | str, *, attention: str = "flash",
                    dtype: Any = jnp.bfloat16) -> "MlaMoeLM":
        """From an architecture file (a path or its object): the
        published ``config.json`` keys, with ``num_layers``,
        ``n_routed_experts`` and ``vocab_size`` as held here,
        ``router_width`` and ``expert_offset`` beside them."""
        if not isinstance(config, dict):
            with open(config) as f:
                config = json.load(f)
        rope = config.get("rope_parameters", {})
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config.get("num_layers",
                                  config.get("num_hidden_layers")),
            num_attention_heads=config["num_attention_heads"],
            q_lora_rank=config["q_lora_rank"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            moe_intermediate_size=config["moe_intermediate_size"],
            n_routed_experts=config["n_routed_experts"],
            router_width=config.get("router_width",
                                    config["n_routed_experts"]),
            expert_offset=config.get("expert_offset", 0),
            num_experts_per_tok=config["num_experts_per_tok"],
            n_shared_experts=config.get("n_shared_experts", 1),
            norm_topk_prob=bool(config.get("norm_topk_prob", True)),
            routed_scaling_factor=float(
                config.get("routed_scaling_factor", 1.0)),
            rms_norm_eps=float(config.get("rms_norm_eps", 1e-6)),
            rope_theta=float(rope.get("rope_theta", 10000.0)),
            rope_factor=float(rope.get("factor", 1.0)),
            beta_fast=float(rope.get("beta_fast", 32.0)),
            beta_slow=float(rope.get("beta_slow", 1.0)),
            original_max_position_embeddings=int(
                rope.get("original_max_position_embeddings", 8192)),
            mscale=float(rope.get("mscale", 1.0)),
            mscale_all_dim=float(rope.get("mscale_all_dim", 0.0)),
            llama_4_scaling_beta=float(
                rope.get("llama_4_scaling_beta", 0.0)),
            attention=attention, dtype=dtype,
        )

    def __post_init__(self):
        if self.attention not in ("flash", "reference"):
            raise ValueError(f"unknown attention backend {self.attention!r}")
        if self.qk_nope_head_dim + self.qk_rope_head_dim != self.v_head_dim:
            raise ValueError(
                "the prefill's attention takes one head size for q, k and "
                f"v: qk {self.qk_nope_head_dim}+{self.qk_rope_head_dim} "
                f"!= v {self.v_head_dim}"
            )
        if self.expert_offset + self.n_routed_experts > self.router_width:
            raise ValueError(
                f"experts [{self.expert_offset}, "
                f"{self.expert_offset + self.n_routed_experts}) lie past "
                f"the router's {self.router_width}"
            )
        if self.num_experts_per_tok > self.router_width:
            raise ValueError("more experts a token than the router has")
        if self.mscale_all_dim and self.mscale != self.mscale_all_dim:
            raise ValueError(
                "the rotary cos/sin factor mscale/mscale_all_dim is taken "
                f"as 1: mscale {self.mscale} != mscale_all_dim "
                f"{self.mscale_all_dim}"
            )

    # -- sizes ---------------------------------------------------------

    @property
    def n_stats(self) -> int:
        return 3 + self.n_routed_experts

    def layer_shapes(self) -> dict:
        """One layer's leaves, '/'-joined path -> shape."""
        d, h = self.hidden_size, self.num_attention_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        f = self.moe_intermediate_size
        shapes = {
            "attn_norm/scale": (d,),
            "q_a/kernel": (d, self.q_lora_rank),
            "q_norm/scale": (self.q_lora_rank,),
            "q_b/kernel": (self.q_lora_rank, h * qk),
            "kv_a/kernel": (d, self.kv_lora_rank + self.qk_rope_head_dim),
            "kv_norm/scale": (self.kv_lora_rank,),
            "kv_b/kernel": (self.kv_lora_rank,
                            h * (self.qk_nope_head_dim + self.v_head_dim)),
            "o/kernel": (h * self.v_head_dim, d),
            "ffn_norm/scale": (d,),
            "router/kernel": (d, self.router_width),
            "shared/gate/kernel": (d, f * self.n_shared_experts),
            "shared/up/kernel": (d, f * self.n_shared_experts),
            "shared/down/kernel": (f * self.n_shared_experts, d),
        }
        for e in range(self.n_routed_experts):
            shapes[f"expert_{e}/gate/kernel"] = (d, f)
            shapes[f"expert_{e}/up/kernel"] = (d, f)
            shapes[f"expert_{e}/down/kernel"] = (f, d)
        return shapes

    def variable_shapes(self) -> dict:
        """Every leaf of the model's tree, '/'-joined path -> shape."""
        d, v = self.hidden_size, self.vocab_size
        shapes = {"params/tok_embed/embedding": (v, d),
                  "params/final_norm/scale": (d,),
                  "params/lm_head/kernel": (d, v)}
        for i in range(self.num_layers):
            for name, shape in self.layer_shapes().items():
                shapes[f"params/layer_{i}/{name}"] = shape
        return shapes

    # -- weights -------------------------------------------------------

    def _width(self, path: str):
        names = tuple(path.split("/"))
        wide = any(names[-len(end):] == end for end in _FLOAT32_ENDINGS)
        return jnp.float32 if wide else jnp.dtype(self.dtype)

    def init(self, key) -> dict:
        """Random weights, each leaf made at the width it is served in
        (there is no checkpoint format for this family yet): normal
        ``1/sqrt(fan-in)`` kernels, ``0.02`` embeddings, unit gains."""
        tree: dict = {}
        for n, (path, shape) in enumerate(self.variable_shapes().items()):
            k = jax.random.fold_in(key, n)
            if path.endswith("scale"):
                leaf = jnp.ones(shape, jnp.float32)
            else:
                std = 0.02 if path.endswith("embedding") else shape[0] ** -0.5
                leaf = (std * jax.random.normal(k, shape, jnp.float32)
                        ).astype(self._width(path))
            node = tree
            *parents, last = path.split("/")
            for p in parents:
                node = node.setdefault(p, {})
            node[last] = leaf
        return tree

    def serving_variables(self, variables, *, release: bool = False):
        """``variables`` with each leaf at the width the model multiplies
        it in: cast once here, leaf by leaf. With ``release`` each wider
        original is deleted from the device as soon as its narrow copy
        exists (the caller hands its tree over and reads it no more), so
        the two trees are never alive together."""

        def at_width(path, leaf):
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            want = self._width(name)
            if not jnp.issubdtype(leaf.dtype, jnp.floating) \
                    or leaf.dtype == want:
                return jnp.asarray(leaf)
            narrow = jnp.asarray(leaf, want)
            if release and isinstance(leaf, jax.Array):
                narrow.block_until_ready()
                leaf.delete()
            return narrow

        return jax.tree_util.tree_map_with_path(at_width, variables)

    # -- the cache -----------------------------------------------------

    def init_cache(self, slots: int, max_len: int):
        """One latent row a token a layer, ``[c_kv | k_r]``, kept as the
        two slabs the absorbed path reads apart: ``c_kv``
        ``[slots, max_len, kv_lora_rank]`` (scores and values) and
        ``k_r`` ``[slots, max_len, qk_rope_head_dim]`` (scores only)."""
        return tuple(
            {"c_kv": jnp.zeros((slots, max_len, self.kv_lora_rank),
                               self.dtype),
             "k_r": jnp.zeros((slots, max_len, self.qk_rope_head_dim),
                              self.dtype)}
            for _ in range(self.num_layers))

    # -- pieces of a layer ---------------------------------------------

    def _norm(self, x, scale):
        return _cast(rms_norm(x, scale, self.rms_norm_eps), self.dtype)

    def _angles(self, pos):
        dim = self.qk_rope_head_dim
        if self.rope_factor > 1:
            inv_freq = yarn_inv_freq(
                dim, self.rope_theta, self.rope_factor, self.beta_fast,
                self.beta_slow, self.original_max_position_embeddings)
        else:
            inv_freq = 1.0 / self.rope_theta ** (
                jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
        return _cast(pos, jnp.float32)[:, None] * inv_freq[None, :]

    def _query_scale(self, pos):
        """What q is multiplied by beyond the kernels' own
        ``1/sqrt(head size)``: the YaRN softmax-scale factor squared and
        the position-dependent scale of the query, float32 ``[T]``."""
        m = yarn_mscale(self.rope_factor, self.mscale_all_dim) \
            if self.mscale_all_dim else 1.0
        floor = jnp.floor(
            _cast(pos, jnp.float32) / self.original_max_position_embeddings)
        return (m * m) * (1.0 + self.llama_4_scaling_beta
                          * jnp.log1p(floor))

    def _qkv_latent(self, x, w, pos):
        """The projections both paths share. ``x`` ``[T, d]``, ``pos``
        ``[T]``. Returns q_nope ``[T, h, nope]``, q_rope ``[T, h, rope]``
        (rotated, float32), the cache row's two parts ``c_kv``
        ``[T, rank]`` and ``k_r`` ``[T, rope]``."""
        t = x.shape[0]
        h = self.num_attention_heads
        nope, rope = self.qk_nope_head_dim, self.qk_rope_head_dim
        angles = self._angles(pos)
        with jax.named_scope("mla_q"):
            hid = self._norm(x, w["attn_norm"]["scale"])
            c_q = self._norm(_mm(hid, w["q_a"]["kernel"], self.dtype),
                             w["q_norm"]["scale"])
            q = _mm(c_q, w["q_b"]["kernel"], self.dtype).reshape(
                t, h, nope + rope)
            q_nope = q[..., :nope]
            q_rope = rotate_interleaved(q[..., nope:], angles[:, None, :])
        with jax.named_scope("mla_kv"):
            kv = _mm(hid, w["kv_a"]["kernel"], self.dtype)
            c_kv = self._norm(kv[:, : self.kv_lora_rank],
                              w["kv_norm"]["scale"])
            k_r = _cast(
                rotate_interleaved(kv[:, self.kv_lora_rank:], angles),
                self.dtype)
        return q_nope, q_rope, {"c_kv": c_kv, "k_r": k_r}

    def _kv_b(self, w):
        """``kv_b`` as ``[rank, heads, nope + v]``."""
        return w["kv_b"]["kernel"].reshape(
            self.kv_lora_rank, self.num_attention_heads,
            self.qk_nope_head_dim + self.v_head_dim)

    def _attend_expanded(self, q_nope, q_rope, row, w, pos):
        """Prefill: keys and values rebuilt from the latent rows, one
        causal pass over the whole prompt. Returns ``[T, h * v]``."""
        t, h = q_nope.shape[0], self.num_attention_heads
        nope = self.qk_nope_head_dim
        with jax.named_scope("mla_attn"):
            c_kv, k_r = row["c_kv"], row["k_r"]
            kv = _cast(jnp.einsum("tc,chn->thn", c_kv, self._kv_b(w),
                                  preferred_element_type=jnp.float32),
                       self.dtype)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_r[:, None, :], (t, h, k_r.shape[-1]))],
                axis=-1)
            v = kv[..., nope:]
            q = jnp.concatenate([_cast(q_nope, jnp.float32), q_rope], -1)
            q = _cast(q * self._query_scale(pos)[:, None, None], self.dtype)
            attend = (flash_attention if self.attention == "flash"
                      else attention_reference)
            out = attend(*(a.transpose(1, 0, 2)[None] for a in (q, k, v)),
                         causal=True)
            return out[0].transpose(1, 0, 2).reshape(t, h * self.v_head_dim)

    def _attend_absorbed(self, q_nope, q_rope, cache, w, pos):
        """Decode: one query a slot over that slot's latent rows up to
        ``pos``; ``kv_b``'s key half is folded into the query and its
        value half into the output. ``cache`` holds ``c_kv``
        ``[S, L, rank]`` and ``k_r`` ``[S, L, rope]``. Returns
        ``[S, h * v]``.

        The slabs are the left operand of the score products
        (``[L, rank] x [rank, h]``), so each is read in the layout it is
        stored in: with the query on the left XLA relays the whole slab
        out for every step."""
        s, h = q_nope.shape[0], self.num_attention_heads
        nope = self.qk_nope_head_dim
        with jax.named_scope("mla_attn"):
            kv_b = self._kv_b(w)
            head = self.qk_nope_head_dim + self.qk_rope_head_dim
            scale = (self._query_scale(pos) / math.sqrt(head))[:, None, None]
            q_lat = _cast(
                jnp.einsum("shn,chn->shc", q_nope, kv_b[..., :nope],
                           preferred_element_type=jnp.float32) * scale,
                self.dtype)
            q_rope = _cast(q_rope * scale, self.dtype)
            scores = (
                jnp.einsum("slc,shc->slh", cache["c_kv"], q_lat,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("slr,shr->slh", cache["k_r"], q_rope,
                             preferred_element_type=jnp.float32))
            length = cache["c_kv"].shape[1]
            mask = jnp.arange(length, dtype=jnp.int32)[None, :] \
                <= pos[:, None]
            scores = jnp.where(mask[:, :, None], scores, np.float32(-1e30))
            probs = _cast(jax.nn.softmax(scores, axis=1), self.dtype)
            o_lat = _cast(
                jnp.einsum("slh,slc->shc", probs, cache["c_kv"],
                           preferred_element_type=jnp.float32), self.dtype)
            out = jnp.einsum("shc,chv->shv", o_lat, kv_b[..., nope:],
                             preferred_element_type=jnp.float32)
            return _cast(out, self.dtype).reshape(s, h * self.v_head_dim)

    def _gated(self, x, gate, up, down):
        """``(silu(x gate) * (x up)) down``; float32 out."""
        mid = _cast(jax.nn.silu(_mm(x, gate, jnp.float32))
                    * _mm(x, up, jnp.float32), self.dtype)
        return _mm(mid, down, jnp.float32)

    def _experts(self, x, x32, valid, w):
        """The routed part of the expert layer for the experts held.

        ``x`` ``[T, d]`` (normed; ``x32`` the same before it was rounded
        to ``dtype``: the router's input), ``valid`` ``[T]`` bool: a row
        that is not valid (an idle slot, a prompt's padding) is routed
        nowhere and counted nowhere. Returns (``[T, d]`` float32,
        stats)."""
        t, k = x.shape[0], self.num_experts_per_tok
        held = self.n_routed_experts
        with jax.named_scope("moe_router"):
            gates = jnp.matmul(
                x32, w["router"]["kernel"], precision=lax.Precision.HIGHEST)
            top_p, top_i = lax.top_k(jax.nn.softmax(gates, axis=-1), k)
            if self.norm_topk_prob:
                top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
            weight = (top_p * self.routed_scaling_factor).reshape(-1)
            local = top_i - self.expert_offset
            here = (local >= 0) & (local < held) & valid[:, None]
            # Assignments sorted by held expert; those of absent experts
            # (and of rows that are not valid) sort past the last one.
            expert = jnp.where(here, local, held).reshape(-1)
            order = _cast(jnp.argsort(expert, stable=True), jnp.int32)
            counts = jnp.sum(
                expert[:, None] == jnp.arange(held)[None, :], axis=0,
                dtype=jnp.int32)
            starts = jnp.cumsum(counts, dtype=jnp.int32) - counts
            n_valid = jnp.sum(valid, dtype=jnp.int32) * k
            n_held = jnp.sum(counts, dtype=jnp.int32)
            stats = jnp.concatenate([
                jnp.stack([n_held, n_valid - n_held,
                           jnp.sum(counts > 0, dtype=jnp.int32)]),
                counts])
        with jax.named_scope("moe_experts"):
            # A token is in one expert's segment at most once, so one
            # pass of min(T, tile) rows holds no token twice.
            tile = min(self.expert_tile, t)
            step, lane = np.int32(tile), jnp.arange(tile, dtype=jnp.int32)
            passes = (counts + (step - 1)) // step
            order = jnp.concatenate([order, jnp.zeros(tile, jnp.int32)])
            out = jnp.zeros((t, x.shape[1]), jnp.float32)
            for e in range(held):
                ew = w[f"expert_{e}"]

                def one_pass(i, acc, e=e, ew=ew):
                    idx = lax.dynamic_slice(
                        order, (starts[e] + i * step,), (tile,))
                    inside = (i * step + lane) < counts[e]
                    token = idx // np.int32(k)
                    y = self._gated(x[token], ew["gate"]["kernel"],
                                    ew["up"]["kernel"], ew["down"]["kernel"])
                    # Rows past the segment's end are another expert's:
                    # computed, weighted 0.
                    scale = jnp.where(inside, weight[idx], np.float32(0))
                    return acc.at[token].add(y * scale[:, None])

                out = lax.fori_loop(np.int32(0), passes[e], one_pass, out)
        return out, stats

    def _ffn(self, x, valid, w):
        hid32 = rms_norm(x, w["ffn_norm"]["scale"], self.rms_norm_eps)
        hid = _cast(hid32, self.dtype)
        routed, stats = self._experts(hid, hid32, valid, w)
        with jax.named_scope("moe_shared"):
            sw = w["shared"]
            shared = self._gated(hid, sw["gate"]["kernel"],
                                 sw["up"]["kernel"], sw["down"]["kernel"])
        return x + _cast(routed + shared, x.dtype), stats

    def _head(self, x, params):
        with jax.named_scope("lm_head"):
            hid = self._norm(x, params["final_norm"]["scale"])
            return _mm(hid, params["lm_head"]["kernel"], jnp.float32)

    # -- what the decoder asks of a served model -----------------------

    def _expanded(self, params, ids, valid):
        """``ids`` ``[T]`` from position 0 through every layer's expanded
        path. Returns the last hidden states ``[T, d]``, each layer's
        cache rows, the stats."""
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        with jax.named_scope("embed"):
            x = _cast(params["tok_embed"]["embedding"][ids], self.dtype)
        stats = jnp.zeros(self.n_stats, jnp.int32)
        rows = []
        for i in range(self.num_layers):
            w = params[f"layer_{i}"]
            q_nope, q_rope, row = self._qkv_latent(x, w, pos)
            attn = self._attend_expanded(q_nope, q_rope, row, w, pos)
            x = x + _mm(attn, w["o"]["kernel"], self.dtype)
            x, layer_stats = self._ffn(x, valid, w)
            stats = stats + layer_stats
            rows.append(row)
        return x, rows, stats

    def prefill_cache(self, variables, tokens, cache, n_real):
        """One bucket-padded prompt ``[1, bucket]`` through the expanded
        path into a one-slot cache. Returns the float32 logits row of
        the last REAL token ``[1, vocab]`` (not every row: at a bucket of
        8,192 those would be half a gigabyte for one row read), the
        stats, the cache. Padding is routed to no expert."""
        params = variables["params"]
        valid = jnp.arange(tokens.shape[1], dtype=jnp.int32) < n_real
        x, rows, stats = self._expanded(params, tokens[0], valid)
        cache = tuple(
            {name: lax.dynamic_update_slice(
                slabs[name], row[name][None], (0, 0, 0)) for name in slabs}
            for slabs, row in zip(cache, rows))
        last = lax.dynamic_slice_in_dim(x, n_real - 1, 1, axis=0)
        return self._head(last, params), stats, cache

    def decode_slots(self, variables, tokens, cache, pos):
        """One token for every slot at once (the expert layer routes all
        slots' tokens together), through the absorbed path. ``tokens``,
        ``pos`` ``[slots]``. A slot at ``pos`` 0 is idle (a prompt has at
        least one token, so an active slot's position is at least 1): it
        is routed to no expert and counted nowhere; its row is computed
        and dropped like the rest of an idle slot's. Returns
        (logits ``[slots, vocab]`` float32, stats, cache)."""
        params = variables["params"]
        active = pos > 0
        with jax.named_scope("embed"):
            x = _cast(params["tok_embed"]["embedding"][tokens], self.dtype)
        stats = jnp.zeros(self.n_stats, jnp.int32)
        new_cache = []
        for i in range(self.num_layers):
            w = params[f"layer_{i}"]
            q_nope, q_rope, row = self._qkv_latent(x, w, pos)
            # A write at or past max_len clamps into the slot's own last
            # row: no slot can reach another's.
            layer_cache = {
                name: jax.vmap(
                    lambda slab, r, p: lax.dynamic_update_slice(
                        slab, r[None], (p, np.int32(0)))
                )(slab, row[name], pos)
                for name, slab in cache[i].items()}
            new_cache.append(layer_cache)
            attn = self._attend_absorbed(q_nope, q_rope, layer_cache, w, pos)
            x = x + _mm(attn, w["o"]["kernel"], x.dtype)
            x, layer_stats = self._ffn(x, active, w)
            stats = stats + layer_stats
        return self._head(x, params), stats, tuple(new_cache)

    def logits(self, variables, tokens):
        """Every row's logits of one sequence ``[T]`` through the
        expanded path, no cache: what tests hold against the plain
        reference. Returns (``[T, vocab]`` float32, stats)."""
        params = variables["params"]
        x, _, stats = self._expanded(
            params, tokens, jnp.ones(tokens.shape[0], bool))
        return self._head(x, params), stats


def _cast(x, dtype):
    """``x`` at ``dtype``; ``x`` itself where it is there already (no
    same-width convert in the traced program)."""
    return x if x.dtype == jnp.dtype(dtype) else x.astype(dtype)


def _mm(a, b, out_dtype):
    """``a @ b`` at the operands' width with float32 accumulation."""
    return _cast(jnp.matmul(a, _cast(b, a.dtype),
                            preferred_element_type=jnp.float32), out_dtype)
