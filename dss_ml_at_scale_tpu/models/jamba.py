"""Hybrid decoder LM: Mamba-1 state-space layers beside multi-query attention.

The block of AI21's Jamba (``model_type`` ``jamba``) as Jamba2-3B
configures it: RMSNorm pre-norm residuals, and layer ``i`` is attention
where ``i % attn_layer_period == attn_layer_offset``, a Mamba mixer
everywhere else. Every layer then has a gated-SiLU MLP (``num_experts``
is 1). Per layer, ``x += mixer(norm_in(x))``, ``x += mlp(norm_ff(x))``:

- **Mamba mixer** (``d_inner = expand * hidden``): ``[u | z] = h W_in``;
  ``u = silu(causal depthwise conv(u) + b_conv)`` over ``d_conv``
  positions; ``[d | B | C] = u W_x`` (``dt_rank | d_state | d_state``),
  each RMS-normed (Jamba's own dt / b / c norms); ``dt = softplus(d W_dt
  + b_dt)``; ``A = -exp(A_log)``; the selective scan
  (:mod:`..ops.selective_scan`) with the ``D`` skip; ``(y * silu(z))
  W_out``. No bias but the conv's and ``dt_proj``'s.
- **Attention**: query heads over fewer key/value heads (one in
  Jamba2-3B), head size ``hidden / heads``, NO positional encoding of any
  kind, scale ``head^-0.5``, causal softmax.
- A tied head: the logits are ``x E^T`` over the embedding table.

What a slot holds differs by layer: a Mamba layer its recurrent state
(``ssm`` ``[slots, d_state, d_inner]`` and the last ``d_conv - 1`` conv
inputs ``conv`` ``[slots, d_conv - 1, d_inner]``, both float32), an
attention layer its keys and values (``k``, ``v`` ``[slots, kv_heads,
max_len, head]``). A prefill starts every state from zero and never
reads the scratch cache's old state; it stops the scan at the last real
token, so the state it leaves is the one after the prompt whatever the
bucket. A decode step advances every slot's state by one token.

The model is a frozen dataclass (hashable: the serving programs take it
as their static argument) over a plain tree of arrays, its shared
part (:class:`.served.ServedLM`) inherited, and gives the
decoder what :mod:`..serving.lm.kvcache` asks of a served model.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.flash_attention import attention_reference, flash_attention
from ..ops.selective_scan import selective_scan, selective_scan_reference
from .served import ServedLM, _cast, _mm
from .transformer import rms_norm


@dataclasses.dataclass(frozen=True)
class JambaLM(ServedLM):
    """The architecture's numbers; see the module docstring."""

    vocab_size: int
    hidden_size: int
    num_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    attn_layer_period: int = 8
    attn_layer_offset: int = 4
    rms_norm_eps: float = 1e-6
    # The prefill's kernels: "flash" runs the flash kernel and the scan
    # kernel, "reference" plain XLA for both.
    attention: str = "flash"
    dtype: Any = jnp.bfloat16

    # Leaves held in float32 whatever the model's dtype: norm gains, the
    # state's own parameters (A_log, D, the conv) and the biases. The
    # recurrence runs in float32.
    float32_endings = (("scale",), ("bias",), ("conv", "kernel"))
    # ``ssm``: a Mamba layer's state and conv inputs; ``kv``: keys and
    # values.
    cache_kinds = {"ssm": "ssm", "conv": "ssm", "k": "kv", "v": "kv"}

    @classmethod
    def _from_keys(cls, config: dict, *, attention: str,
                   dtype: Any) -> "JambaLM":
        """The published ``config.json`` keys."""
        unsupported = {
            "num_experts": (config.get("num_experts", 1), 1),
            "tie_word_embeddings": (config.get("tie_word_embeddings", True),
                                    True),
            "mamba_proj_bias": (config.get("mamba_proj_bias", False), False),
            "mamba_conv_bias": (config.get("mamba_conv_bias", True), True),
        }
        for key, (got, want) in unsupported.items():
            if got != want:
                raise ValueError(f"{key} {got!r} is not served here "
                                 f"(only {want!r})")
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"],
            intermediate_size=config["intermediate_size"],
            d_state=config["mamba_d_state"],
            d_conv=config["mamba_d_conv"],
            expand=config["mamba_expand"],
            dt_rank=config["mamba_dt_rank"],
            attn_layer_period=config["attn_layer_period"],
            attn_layer_offset=config["attn_layer_offset"],
            rms_norm_eps=float(config.get("rms_norm_eps", 1e-6)),
            attention=attention, dtype=dtype,
        )

    def __post_init__(self):
        super().__post_init__()
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is no multiple of the heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are no multiple of key/value heads")

    # -- sizes ---------------------------------------------------------

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layer_kinds(self) -> tuple:
        """Layer ``i`` is attention where ``i % attn_layer_period ==
        attn_layer_offset``, else Mamba."""
        return tuple(
            "attn" if i % self.attn_layer_period == self.attn_layer_offset
            else "mamba" for i in range(self.num_layers))

    def layer_shapes(self, kind: str) -> dict:
        """One layer's leaves of ``kind``, '/'-joined path -> shape."""
        d, di, f = self.hidden_size, self.d_inner, self.intermediate_size
        shapes = {"norm_in/scale": (d,)}
        if kind == "attn":
            q = self.num_attention_heads * self.head_dim
            kv = self.num_key_value_heads * self.head_dim
            shapes.update({"attn/q/kernel": (d, q), "attn/k/kernel": (d, kv),
                           "attn/v/kernel": (d, kv), "attn/o/kernel": (q, d)})
        else:
            n, r = self.d_state, self.dt_rank
            shapes.update({
                "mamba/in_proj/kernel": (d, 2 * di),
                "mamba/conv/kernel": (self.d_conv, di),
                "mamba/conv/bias": (di,),
                "mamba/x_proj/kernel": (di, r + 2 * n),
                "mamba/dt_norm/scale": (r,),
                "mamba/b_norm/scale": (n,),
                "mamba/c_norm/scale": (n,),
                "mamba/dt_proj/kernel": (r, di),
                "mamba/dt_proj/bias": (di,),
                "mamba/A_log/scale": (di, n),
                "mamba/D/scale": (di,),
                "mamba/out_proj/kernel": (di, d),
            })
        shapes.update({"norm_ff/scale": (d,), "mlp/gate/kernel": (d, f),
                       "mlp/up/kernel": (d, f), "mlp/down/kernel": (f, d)})
        return shapes

    def variable_shapes(self) -> dict:
        """Every leaf of the model's tree, '/'-joined path -> shape."""
        d = self.hidden_size
        shapes = {"params/embed/embedding": (self.vocab_size, d),
                  "params/final_norm/scale": (d,)}
        for i, kind in enumerate(self.layer_kinds):
            for name, shape in self.layer_shapes(kind).items():
                shapes[f"params/layer_{i}/{name}"] = shape
        return shapes

    # -- weights -------------------------------------------------------

    def _init_leaf(self, path: str, shape, key):
        """Mamba's own initialisation: ``A_log = log(1..d_state)`` on
        every channel, ``D = 1``, ``dt_proj``'s bias the inverse softplus
        of a step drawn log-uniform in [0.001, 0.1] and its kernel
        uniform within ``dt_rank^-0.5``; the conv uniform within
        ``d_conv^-0.5``; the rest as :meth:`ServedLM._init_leaf`."""
        name = path.split("/", 2)[-1]          # the path in its layer
        if name == "mamba/A_log/scale":
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=jnp.float32)), shape)
        if name == "mamba/dt_proj/bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        if name == "mamba/dt_proj/kernel":
            bound = self.dt_rank ** -0.5
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        if name.startswith("mamba/conv/"):
            bound = self.d_conv ** -0.5
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        return super()._init_leaf(path, shape, key)

    # -- the cache -----------------------------------------------------

    def init_cache(self, slots: int, max_len: int):
        """A Mamba layer's recurrent state, ``ssm`` ``[slots, d_state,
        d_inner]`` (the scan kernel's layout) and ``conv`` ``[slots,
        d_conv - 1, d_inner]``, float32; an attention layer's ``k`` and
        ``v`` ``[slots, kv_heads, max_len, head]`` at the model's dtype."""
        di = self.d_inner
        kv = (slots, self.num_key_value_heads, max_len, self.head_dim)
        return tuple(
            {"k": jnp.zeros(kv, self.dtype), "v": jnp.zeros(kv, self.dtype)}
            if kind == "attn" else
            {"ssm": jnp.zeros((slots, self.d_state, di), jnp.float32),
             "conv": jnp.zeros((slots, self.d_conv - 1, di), jnp.float32)}
            for kind in self.layer_kinds)

    # -- pieces of a layer ---------------------------------------------

    def _ssm_inputs(self, u, w):
        """From the conv's output ``u`` ``[T, d_inner]`` (float32): the
        step ``dt`` ``[T, d_inner]``, ``B``, ``C`` ``[T, d_state]`` (all
        float32) and ``A`` ``[d_state, d_inner]``."""
        r, n = self.dt_rank, self.d_state
        xdbc = _mm(_cast(u, self.dtype), w["x_proj"]["kernel"], jnp.float32)
        eps = self.rms_norm_eps
        d = rms_norm(xdbc[:, :r], w["dt_norm"]["scale"], eps)
        b = rms_norm(xdbc[:, r:r + n], w["b_norm"]["scale"], eps)
        c = rms_norm(xdbc[:, r + n:], w["c_norm"]["scale"], eps)
        dt = jax.nn.softplus(
            _mm(_cast(d, self.dtype), w["dt_proj"]["kernel"], jnp.float32)
            + w["dt_proj"]["bias"])
        a = -jnp.exp(w["A_log"]["scale"]).T
        return dt, b, c, a

    def _gate_out(self, y, z, w):
        return _mm(_cast(y * jax.nn.silu(z), self.dtype),
                   w["out_proj"]["kernel"], self.dtype)

    def _mamba_prefill(self, x, w, n_real, slabs):
        """``x`` ``[T, d]`` from position 0, the first ``n_real`` rows
        real. Returns the mixer's output ``[T, d]`` and the layer's state
        after row ``n_real - 1``, written over the one-slot ``slabs``
        whole: their old values are not read, but the donated buffers
        are where the new state goes."""
        t, di, k = x.shape[0], self.d_inner, self.d_conv
        with jax.named_scope("mamba"):
            uz = _mm(self._norm(x, w["norm_in"]["scale"]),
                     w["mamba"]["in_proj"]["kernel"], jnp.float32)
            m = w["mamba"]
            # The conv's inputs with d_conv - 1 zeros before the prompt.
            raw = jnp.concatenate(
                [jnp.zeros((k - 1, di), jnp.float32), uz[:, :di]])
            u = jax.nn.silu(m["conv"]["bias"] + sum(
                m["conv"]["kernel"][j] * raw[j:j + t] for j in range(k)))
            dt, b, c, a = self._ssm_inputs(u, m)
            scan = (selective_scan if self.attention == "flash"
                    else selective_scan_reference)
            y, ssm = scan(u, dt, a, b, c, m["D"]["scale"], n_real)
            new = {"ssm": ssm,
                   "conv": lax.dynamic_slice_in_dim(raw, n_real, k - 1)}
            state = {name: lax.dynamic_update_slice(
                slabs[name], new[name][None], (0, 0, 0)) for name in new}
            return self._gate_out(y, uz[:, di:], m), state

    def _mamba_decode(self, x, w, state):
        """One token a slot, ``x`` ``[S, d]``, from each slot's state.
        Returns the mixer's output ``[S, d]`` and the state after it."""
        di = self.d_inner
        with jax.named_scope("mamba"):
            m = w["mamba"]
            uz = _mm(self._norm(x, w["norm_in"]["scale"]),
                     m["in_proj"]["kernel"], jnp.float32)
            window = jnp.concatenate([state["conv"], uz[:, None, :di]], 1)
            u = jax.nn.silu(m["conv"]["bias"] + jnp.sum(
                window * m["conv"]["kernel"][None], axis=1))
            dt, b, c, a = self._ssm_inputs(u, m)
            ssm = (jnp.exp(dt[:, None, :] * a[None]) * state["ssm"]
                   + b[:, :, None] * (dt * u)[:, None, :])
            y = jnp.sum(ssm * c[:, :, None], axis=1) + m["D"]["scale"] * u
            return (self._gate_out(y, uz[:, di:], m),
                    {"ssm": ssm, "conv": window[:, 1:]})

    def _attn_prefill(self, x, w, slabs):
        """Causal attention over the prompt; its keys and values written
        into the one-slot ``slabs`` from row 0. Returns ``[T, d]`` and the
        slabs."""
        t, h = x.shape[0], self.num_attention_heads
        with jax.named_scope("attn"):
            q, k, v = self._qkv(x, w, "norm_in")
            slabs = {name: lax.dynamic_update_slice(
                slabs[name], rows.transpose(1, 0, 2)[None], (0, 0, 0, 0))
                for name, rows in (("k", k), ("v", v))}
            # Every query head reads its group's one key/value head.
            group = h // self.num_key_value_heads
            k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
            attend = (flash_attention if self.attention == "flash"
                      else attention_reference)
            out = attend(*(a.transpose(1, 0, 2)[None] for a in (q, k, v)),
                         causal=True)
            out = out[0].transpose(1, 0, 2).reshape(t, h * self.head_dim)
            return _mm(out, w["attn"]["o"]["kernel"], self.dtype), slabs

    def _mlp(self, x, w):
        with jax.named_scope("mlp"):
            hid = self._norm(x, w["norm_ff"]["scale"])
            m = w["mlp"]
            mid = _cast(jax.nn.silu(_mm(hid, m["gate"]["kernel"], jnp.float32))
                        * _mm(hid, m["up"]["kernel"], jnp.float32), self.dtype)
            return x + _mm(mid, m["down"]["kernel"], x.dtype)

    def _head(self, x, params):
        """The final norm, then the tied head ``x E^T``: float32."""
        with jax.named_scope("lm_head"):
            hid = self._norm(x, params["final_norm"]["scale"])
            table = params["embed"]["embedding"]
            return jnp.einsum("td,vd->tv", hid, _cast(table, hid.dtype),
                              preferred_element_type=jnp.float32)

    # -- what the decoder asks of a served model -----------------------

    def _forward(self, params, ids, n_real, cache):
        """``ids`` ``[T]`` from position 0 through every layer. Returns
        the last hidden states ``[T, d]`` and each layer's state."""
        x = self._embed(params, ids)
        states = []
        for i, kind in enumerate(self.layer_kinds):
            w = params[f"layer_{i}"]
            if kind == "attn":
                out, state = self._attn_prefill(x, w, cache[i])
            else:
                out, state = self._mamba_prefill(x, w, n_real, cache[i])
            x = self._mlp(x + out, w)
            states.append(state)
        return x, tuple(states)

    def prefill_cache(self, variables, tokens, cache, n_real):
        """One bucket-padded prompt ``[1, bucket]`` into a one-slot cache.
        Returns the float32 logits row of the last REAL token ``[1,
        vocab]``, no stats, the cache: keys and values written from row
        0, every Mamba layer's state the one after the last real token,
        made from zero (the scratch's old state is not read)."""
        params = variables["params"]
        x, cache = self._forward(params, tokens[0], n_real, cache)
        last = lax.dynamic_slice_in_dim(x, n_real - 1, 1, axis=0)
        return self._head(last, params), None, cache

    def decode_slots(self, variables, tokens, cache, pos):
        """One token for every slot at once, at each slot's ``pos``.
        Returns (logits ``[slots, vocab]`` float32, no stats, cache). An
        idle slot decodes a token nobody reads; its state is overwritten
        whole when the slot is next admitted."""
        params = variables["params"]
        x = self._embed(params, tokens)
        new_cache = []
        for i, kind in enumerate(self.layer_kinds):
            w = params[f"layer_{i}"]
            if kind == "attn":
                out, state = self._attn_decode(x, w, cache[i], pos,
                                               "norm_in")
            else:
                out, state = self._mamba_decode(x, w, cache[i])
            x = self._mlp(x + out, w)
            new_cache.append(state)
        return self._head(x, params), None, tuple(new_cache)

    def logits(self, variables, tokens):
        """Every row's logits of one sequence ``[T]``, no arena: what
        tests hold against the plain reference. ``[T, vocab]`` float32."""
        params = variables["params"]
        t = tokens.shape[0]
        cache = self.init_cache(1, t)
        x, _ = self._forward(params, tokens, jnp.int32(t), cache)
        return self._head(x, params)

