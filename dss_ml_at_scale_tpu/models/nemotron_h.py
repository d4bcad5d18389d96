"""Hybrid decoder LM: Mamba-2 mixers, latent routed experts, attention.

The block of NVIDIA's Nemotron-H (``model_type`` ``nemotron_h``) as
Nemotron-3-Super configures it. Layer ``i``'s kind is character ``i`` of
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``E`` an expert
layer, ``*`` attention. Each block is ``x + mixer(RMSNorm(x))`` with one
mixer a layer and no separate MLP; every norm is RMSNorm (eps
``layer_norm_epsilon``, a gain), and no projection has a bias.

- **Mamba-2** (``H`` heads of ``P``, ``d_inner = H P``; ``G`` groups of
  ``N``): ``[z | xBC | dt] = h W_in``; ``xBC = silu(causal depthwise
  conv(xBC) + b_conv)`` over ``conv_kernel`` positions, split into ``x``
  ``[H, P]``, ``B`` and ``C`` ``[G, N]`` (head ``i`` reads group
  ``i // (H / G)``); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``,
  one a head; the state-space scan from the slot's state
  (:mod:`..ops.ssd_scan`) with the ``D`` skip; ``y = RMSNorm_grouped(y *
  silu(z))`` over ``G`` groups of ``d_inner / G``; ``y W_out``.
- **Expert layer**: a float32 sigmoid router over all ``router_width``
  experts of the full-width ``h``, the ``num_experts_per_tok`` best by
  score plus selection bias, weighted by the score renormalised and
  times ``routed_scaling_factor`` (:mod:`.routing`); the routed experts
  ``relu(u W_up)^2 W_down`` run in the latent ``u = h W_latent_down``,
  their weighted sum goes back through ``W_latent_up``; one shared expert
  ``relu(h W_up)^2 W_down`` at full width. The layer is told which
  experts it holds (``expert_offset``, ``n_routed_experts``): what the
  absent ones would add is left out, nothing stands in for them. The
  held experts are stacked (``[E, latent, width]``) and one grouped
  kernel (:mod:`..ops.grouped_experts`) runs them over their segments of
  the sorted assignments: each touched expert's matrices are read once a
  layer, an expert nobody chose not at all.
- **Attention**: query heads over fewer key/value heads, ``head_dim``
  each, no positional encoding of any kind, scale ``head_dim^-0.5``.
- An untied head: final norm, then ``lm_head``, float32 logits.

What a slot holds differs by layer: a Mamba layer its state ``ssm``
``[slots, H, P, N]`` and the last ``conv_kernel - 1`` conv inputs
``conv`` ``[slots, conv_kernel - 1, conv_dim]``, both float32; an
attention layer ``k``, ``v`` ``[slots, kv_heads, max_len, head_dim]``; an
expert layer nothing. A prompt is prefilled a chunk at a time
(``prefill_chunk``): a chunk at ``start`` resumes from the state, conv
inputs and key/value rows the chunks before it left in the one-slot
cache (the first chunk starts from zero and reads none of the cache's old
state). Its rows at or past ``n_real`` are padding: their ``dt`` and
input are zero, so they leave the state as it was, and they are routed
to no expert.

The model is a frozen dataclass (hashable: the serving programs take it
as their static argument) over a plain tree of arrays, its shared
part (:class:`.served.ServedLM`) inherited; it gives the
decoder what :mod:`..serving.lm.kvcache` asks of a served model. Its
``variables`` hold each expert apart (``expert_{e}``), as a checkpoint
does; ``serving_variables`` stacks them, and every program reads the
stacked tree. ``stats`` beside the logits is ``[held, absent, touched,
per held expert...]`` summed over the expert layers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.flash_attention import attention_reference, flash_attention
from ..ops.grouped_experts import grouped_experts
from ..ops.ssd_scan import ssd_scan, ssd_scan_reference
from .routing import route
from .served import ServedLM, _cast, _mm
from .transformer import rms_norm

_KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def layer_kinds(pattern: str) -> tuple:
    """The kind of each layer of ``hybrid_override_pattern``: ``M``
    ``"mamba"``, ``E`` ``"moe"``, ``*`` ``"attn"``."""
    unknown = sorted(set(pattern) - set(_KINDS))
    if unknown:
        raise ValueError(f"layer kinds {unknown} of {pattern!r} are not "
                         f"served here (only {''.join(_KINDS)})")
    return tuple(_KINDS[c] for c in pattern)


@dataclasses.dataclass(frozen=True)
class NemotronHLM(ServedLM):
    """The architecture's numbers; see the module docstring."""

    vocab_size: int
    hidden_size: int
    pattern: str                 # the kinds of the layers held, in order
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_num_heads: int
    mamba_head_dim: int
    ssm_state_size: int
    n_groups: int
    moe_intermediate_size: int
    moe_latent_size: int
    moe_shared_expert_intermediate_size: int
    n_routed_experts: int        # held here
    router_width: int            # the router's outputs: all experts
    expert_offset: int = 0       # the first held expert's index
    num_experts_per_tok: int = 22
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    conv_kernel: int = 4
    chunk_size: int = 128
    layer_norm_epsilon: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # The prefill's kernels: "flash" runs the flash kernel and the SSD
    # kernel, "reference" plain XLA for both.
    attention: str = "flash"
    dtype: Any = jnp.bfloat16

    # Leaves held in float32 whatever the model's dtype: norm gains, the
    # state's own parameters (A_log, D, dt_bias, the conv), the router and
    # its selection bias. The recurrence and the routing run in float32.
    float32_endings = (("scale",), ("bias",), ("conv", "kernel"),
                       ("router", "kernel"))
    # ``ssm``: the Mamba layers' states; ``conv``: their conv inputs;
    # ``kv``: keys and values.
    cache_kinds = {"ssm": "ssm", "conv": "conv", "k": "kv", "v": "kv"}
    expert_matrices = ("up", "down")
    expert_scope = "moe"

    @classmethod
    def _from_keys(cls, config: dict, *, attention: str,
                   dtype: Any) -> "NemotronHLM":
        """The published ``config.json`` keys, with ``num_layers`` (the
        first layers of the pattern), ``n_routed_experts`` and
        ``vocab_size`` as held here, ``router_width`` and
        ``expert_offset`` beside them."""
        unsupported = {
            "mamba_hidden_act": ("silu",), "mlp_hidden_act": ("relu2",),
            "n_group": (1,), "n_shared_experts": (1,),
            "tie_word_embeddings": (False,), "residual_in_fp32": (False,),
            "use_bias": (False,), "mamba_proj_bias": (False,),
            "attention_bias": (False,), "mlp_bias": (False,),
            "use_conv_bias": (True,),
        }
        for key, allowed in unsupported.items():
            if key in config and config[key] not in allowed:
                raise ValueError(f"{key} {config[key]!r} is not served here "
                                 f"(only {allowed[0]!r})")
        layers = config.get("num_layers", config["num_hidden_layers"])
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            pattern=config["hybrid_override_pattern"][:layers],
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            mamba_num_heads=config["mamba_num_heads"],
            mamba_head_dim=config["mamba_head_dim"],
            ssm_state_size=config["ssm_state_size"],
            n_groups=config["n_groups"],
            moe_intermediate_size=config["moe_intermediate_size"],
            moe_latent_size=config["moe_latent_size"],
            moe_shared_expert_intermediate_size=config[
                "moe_shared_expert_intermediate_size"],
            n_routed_experts=config["n_routed_experts"],
            router_width=config.get("router_width",
                                    config["n_routed_experts"]),
            expert_offset=config.get("expert_offset", 0),
            num_experts_per_tok=config["num_experts_per_tok"],
            norm_topk_prob=bool(config.get("norm_topk_prob", True)),
            routed_scaling_factor=float(
                config.get("routed_scaling_factor", 1.0)),
            conv_kernel=config.get("conv_kernel", 4),
            chunk_size=config.get("chunk_size", 128),
            layer_norm_epsilon=float(config.get("layer_norm_epsilon", 1e-5)),
            time_step_min=float(config.get("time_step_min", 0.001)),
            time_step_max=float(config.get("time_step_max", 0.1)),
            time_step_floor=float(config.get("time_step_floor", 1e-4)),
            attention=attention, dtype=dtype,
        )

    def __post_init__(self):
        super().__post_init__()
        layer_kinds(self.pattern)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are no multiple of key/value heads")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("Mamba heads are no multiple of the groups")

    # -- sizes ---------------------------------------------------------

    @property
    def layer_kinds(self) -> tuple:
        return layer_kinds(self.pattern)

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def norm_eps(self) -> float:
        return self.layer_norm_epsilon

    def layer_shapes(self, kind: str) -> dict:
        """One layer's leaves of ``kind``, '/'-joined path -> shape."""
        d = self.hidden_size
        shapes = {"norm/scale": (d,)}
        if kind == "mamba":
            di, h = self.d_inner, self.mamba_num_heads
            shapes.update({
                "mamba/in_proj/kernel": (d, di + self.conv_dim + h),
                "mamba/conv/kernel": (self.conv_kernel, self.conv_dim),
                "mamba/conv/bias": (self.conv_dim,),
                "mamba/dt_bias/bias": (h,),
                "mamba/A_log/scale": (h,),
                "mamba/D/scale": (h,),
                "mamba/norm/scale": (di,),
                "mamba/out_proj/kernel": (di, d),
            })
        elif kind == "attn":
            q = self.num_attention_heads * self.head_dim
            kv = self.num_key_value_heads * self.head_dim
            shapes.update({"attn/q/kernel": (d, q), "attn/k/kernel": (d, kv),
                           "attn/v/kernel": (d, kv), "attn/o/kernel": (q, d)})
        else:
            lat, f = self.moe_latent_size, self.moe_intermediate_size
            fs = self.moe_shared_expert_intermediate_size
            shapes.update({
                "moe/router/kernel": (d, self.router_width),
                "moe/router/bias": (self.router_width,),
                "moe/latent_down/kernel": (d, lat),
                "moe/latent_up/kernel": (lat, d),
                "moe/shared/up/kernel": (d, fs),
                "moe/shared/down/kernel": (fs, d),
            })
            for e in range(self.n_routed_experts):
                shapes[f"moe/expert_{e}/up/kernel"] = (lat, f)
                shapes[f"moe/expert_{e}/down/kernel"] = (f, lat)
        return shapes

    def variable_shapes(self) -> dict:
        """Every leaf of the model's tree, '/'-joined path -> shape."""
        d, v = self.hidden_size, self.vocab_size
        shapes = {"params/embed/embedding": (v, d),
                  "params/final_norm/scale": (d,),
                  "params/lm_head/kernel": (d, v)}
        for i, kind in enumerate(self.layer_kinds):
            for name, shape in self.layer_shapes(kind).items():
                shapes[f"params/layer_{i}/{name}"] = shape
        return shapes

    # -- weights -------------------------------------------------------

    def _init_leaf(self, path: str, shape, key):
        """Mamba-2's own initialisation: ``A`` uniform in [1, 16] a head
        (``A_log`` its log), ``D = 1``, ``dt_bias`` the inverse softplus
        of a step drawn log-uniform in [time_step_min, time_step_max]
        (floored at time_step_floor); the conv uniform within
        ``conv_kernel^-0.5``; biases (the conv's, the router's selection
        bias) normal ``0.05``; the rest as :meth:`ServedLM._init_leaf`."""
        name = path.split("/", 2)[-1]          # the path in its layer
        if name == "mamba/A_log/scale":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if name == "mamba/dt_bias/bias":
            step = jnp.maximum(jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(self.time_step_min),
                math.log(self.time_step_max))), self.time_step_floor)
            return step + jnp.log(-jnp.expm1(-step))
        if name == "mamba/conv/kernel":
            bound = self.conv_kernel ** -0.5
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        if path.endswith("bias"):
            return 0.05 * jax.random.normal(key, shape, jnp.float32)
        return super()._init_leaf(path, shape, key)

    # -- the cache -----------------------------------------------------

    def init_cache(self, slots: int, max_len: int):
        """A Mamba layer's ``ssm`` ``[slots, H, P, N]`` and ``conv``
        ``[slots, conv_kernel - 1, conv_dim]`` (float32); an attention
        layer's ``k`` and ``v`` ``[slots, kv_heads, max_len, head_dim]``
        at the model's dtype; nothing for an expert layer."""
        kv = (slots, self.num_key_value_heads, max_len, self.head_dim)
        state = (slots, self.mamba_num_heads, self.mamba_head_dim,
                 self.ssm_state_size)
        conv = (slots, self.conv_kernel - 1, self.conv_dim)
        out = []
        for kind in self.layer_kinds:
            if kind == "attn":
                out.append({"k": jnp.zeros(kv, self.dtype),
                            "v": jnp.zeros(kv, self.dtype)})
            elif kind == "mamba":
                out.append({"ssm": jnp.zeros(state, jnp.float32),
                            "conv": jnp.zeros(conv, jnp.float32)})
            else:
                out.append({})
        return tuple(out)

    # -- pieces of a layer ---------------------------------------------

    def _mamba_split(self, zxd):
        """``[z | xBC | dt]`` ``[T, in_proj]`` -> its three parts."""
        di = self.d_inner
        return (zxd[:, :di], zxd[:, di:di + self.conv_dim],
                zxd[:, di + self.conv_dim:])

    def _ssm_inputs(self, xbc, dt, m):
        """From the conv's output ``[T, conv_dim]`` and the raw step
        ``[T, H]``: ``x`` ``[T, H, P]``, ``B``, ``C`` ``[T, G, N]``, ``dt``
        ``[T, H]`` and ``A`` ``[H]``, all float32."""
        t, di = xbc.shape[0], self.d_inner
        gn = self.n_groups * self.ssm_state_size
        x = xbc[:, :di].reshape(t, self.mamba_num_heads, self.mamba_head_dim)
        b = xbc[:, di:di + gn].reshape(t, self.n_groups, self.ssm_state_size)
        c = xbc[:, di + gn:].reshape(t, self.n_groups, self.ssm_state_size)
        dt = jax.nn.softplus(dt + m["dt_bias"]["bias"])
        return x, b, c, dt, -jnp.exp(m["A_log"]["scale"])

    def _gate_out(self, y, z, m):
        """``RMSNorm_grouped(y * silu(z)) W_out``, ``y`` and ``z``
        ``[T, d_inner]`` float32."""
        t = y.shape[0]
        g = y * jax.nn.silu(z)
        g = g.reshape(t, self.n_groups, -1)
        g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + self.layer_norm_epsilon)
        g = g.reshape(t, self.d_inner) * m["norm"]["scale"]
        return _mm(_cast(g, self.dtype), m["out_proj"]["kernel"], self.dtype)

    def _mamba_chunk(self, x, w, slabs, start: int, n_real):
        """Rows ``x`` ``[T, d]`` at positions ``start .. start + T``
        (``start`` static), from the one-slot ``slabs``' state and conv
        inputs (from zero where ``start`` is 0). Rows at or past
        ``n_real`` leave the state as it was. Returns the mixer's output
        ``[T, d]`` and the slabs with the state after the last real
        row."""
        t, k = x.shape[0], self.conv_kernel
        m = w["mamba"]
        with jax.named_scope("mamba2"):
            z, xbc, dt = self._mamba_split(_mm(
                self._norm(x, w["norm"]["scale"]), m["in_proj"]["kernel"],
                jnp.float32))
            if start == 0:
                before = jnp.zeros((k - 1, self.conv_dim), jnp.float32)
                state = jnp.zeros(slabs["ssm"].shape[1:], jnp.float32)
            else:
                before, state = slabs["conv"][0], slabs["ssm"][0]
            raw = jnp.concatenate([before, xbc])
            conv = jax.nn.silu(m["conv"]["bias"] + sum(
                m["conv"]["kernel"][j] * raw[j:j + t] for j in range(k)))
            xs, b, c, dt, a = self._ssm_inputs(conv, dt, m)
            live = start + jnp.arange(t, dtype=jnp.int32) < n_real
            dt = jnp.where(live[:, None], dt, np.float32(0))
            xs = jnp.where(live[:, None, None], xs, np.float32(0))
            with jax.named_scope("ssd_scan"):
                if self.attention == "flash":
                    y, state = ssd_scan(xs, dt, a, b, c, m["D"]["scale"],
                                        state, chunk=self.chunk_size)
                else:
                    y, state = ssd_scan_reference(xs, dt, a, b, c,
                                                  m["D"]["scale"], state)
            # The conv's last k - 1 real inputs (the chunk's real rows
            # end at raw row n_real - start + k - 1).
            tail = lax.dynamic_slice_in_dim(
                raw, jnp.clip(n_real - np.int32(start), np.int32(0),
                              np.int32(t)), k - 1)
            new = {"ssm": state, "conv": tail}
            slabs = {name: lax.dynamic_update_slice(
                slabs[name], new[name][None], (0,) * slabs[name].ndim)
                for name in new}
            return self._gate_out(y.reshape(t, self.d_inner), z, m), slabs

    def _mamba_decode(self, x, w, state):
        """One token a slot, ``x`` ``[S, d]``, from each slot's state.
        Returns the mixer's output ``[S, d]`` and the state after it."""
        s = x.shape[0]
        heads, groups = self.mamba_num_heads, self.n_groups
        m = w["mamba"]
        with jax.named_scope("mamba2"):
            z, xbc, dt = self._mamba_split(_mm(
                self._norm(x, w["norm"]["scale"]), m["in_proj"]["kernel"],
                jnp.float32))
            window = jnp.concatenate([state["conv"], xbc[:, None]], 1)
            conv = jax.nn.silu(m["conv"]["bias"] + jnp.sum(
                window * m["conv"]["kernel"][None], axis=1))
            xs, b, c, dt, a = self._ssm_inputs(conv, dt, m)
            # Heads by group, so that B and C broadcast over a group's.
            shape = (s, groups, heads // groups)
            decay = jnp.exp(dt * a).reshape(shape)
            inp = (dt[..., None] * xs).reshape(shape + (-1,))
            ssm = (decay[..., None, None]
                   * state["ssm"].reshape(shape + state["ssm"].shape[2:])
                   + inp[..., None] * b[:, :, None, None, :])
            y = jnp.sum(ssm * c[:, :, None, None, :], axis=-1)
            y = y.reshape(s, heads, -1) + m["D"]["scale"][:, None] * xs
            return (self._gate_out(y.reshape(s, self.d_inner), z, m),
                    {"ssm": ssm.reshape(state["ssm"].shape),
                     "conv": window[:, 1:]})

    def _attn_chunk(self, x, w, slabs, start: int):
        """Rows ``x`` ``[T, d]`` at positions ``start .. start + T``: their
        keys and values written into the one-slot ``slabs`` at ``start``,
        their queries over the rows ``[0, start + T)`` (the mask bottom-
        right aligned: the last query sees every row). Returns ``[T, d]``
        and the slabs."""
        t, h = x.shape[0], self.num_attention_heads
        with jax.named_scope("attn"):
            q, k, v = self._qkv(x, w, "norm")
            slabs = {name: lax.dynamic_update_slice(
                slabs[name], rows.transpose(1, 0, 2)[None], (0, 0, start, 0))
                for name, rows in (("k", k), ("v", v))}
            # Every query head reads its group's key/value head.
            group = h // self.num_key_value_heads
            k, v = (jnp.repeat(slabs[name][0, :, :start + t], group, axis=0)
                    for name in ("k", "v"))
            attend = (flash_attention if self.attention == "flash"
                      else attention_reference)
            out = attend(q.transpose(1, 0, 2)[None], k[None], v[None],
                         causal=True)
            out = out[0].transpose(1, 0, 2).reshape(t, h * self.head_dim)
            return _mm(out, w["attn"]["o"]["kernel"], self.dtype), slabs

    def _relu2(self, x, up, down):
        """``relu(x up)^2 down``; float32 out."""
        mid = jnp.square(jax.nn.relu(_mm(x, up, jnp.float32)))
        return _mm(_cast(mid, self.dtype), down, jnp.float32)

    def _moe(self, x, w, valid):
        """The expert layer of rows ``x`` ``[T, d]``; ``valid`` ``[T]``:
        a row that is not valid (an idle slot, a prompt's padding) is
        routed nowhere and counted nowhere. Returns ``[T, d]`` and the
        stats."""
        m = w["moe"]
        hid32 = rms_norm(x, w["norm"]["scale"], self.layer_norm_epsilon)
        hid = _cast(hid32, self.dtype)
        r = route(hid32, m["router"]["kernel"], valid,
                  k=self.num_experts_per_tok, held=self.n_routed_experts,
                  offset=self.expert_offset, score="sigmoid",
                  bias=m["router"]["bias"], norm_topk=self.norm_topk_prob,
                  scale=self.routed_scaling_factor)
        with jax.named_scope("moe_latent"):
            u = _mm(hid, m["latent_down"]["kernel"], self.dtype)
        with jax.named_scope("moe_experts"):
            routed = self._experts(u, r, m["experts"])
        with jax.named_scope("moe_latent"):
            routed = _mm(_cast(routed, self.dtype),
                         m["latent_up"]["kernel"], jnp.float32)
        with jax.named_scope("moe_shared"):
            shared = self._relu2(hid, m["shared"]["up"]["kernel"],
                                 m["shared"]["down"]["kernel"])
        return _cast(routed + shared, self.dtype), r.stats

    def _experts(self, u, r, experts):
        """The held experts over their segments of the sorted assignments
        ``r``, in the latent ``u`` ``[T, latent]``: one grouped kernel
        call (:mod:`..ops.grouped_experts`), each touched expert's two
        matrices read once. Returns ``[T, latent]`` float32."""
        return grouped_experts(u, experts["up"], experts["down"], r.counts,
                               r.starts, r.order, r.weight,
                               k=self.num_experts_per_tok)

    # -- what the decoder asks of a served model -----------------------

    def _run(self, params, ids, cache, start: int, n_real):
        """``ids`` ``[T]`` at positions ``start .. start + T`` through
        every layer over the one-slot ``cache``. Returns the last hidden
        states ``[T, d]``, the cache, the stats."""
        x = self._embed(params, ids)
        valid = start + jnp.arange(ids.shape[0], dtype=jnp.int32) < n_real
        stats = jnp.zeros(self.n_stats, jnp.int32)
        new_cache = []
        for i, kind in enumerate(self.layer_kinds):
            w, slabs = params[f"layer_{i}"], cache[i]
            if kind == "mamba":
                out, slabs = self._mamba_chunk(x, w, slabs, start, n_real)
            elif kind == "attn":
                out, slabs = self._attn_chunk(x, w, slabs, start)
            else:
                out, layer_stats = self._moe(x, w, valid)
                stats = stats + layer_stats
            x = x + out
            new_cache.append(slabs)
        return x, tuple(new_cache), stats

    def prefill_cache(self, variables, tokens, cache, n_real):
        """One bucket-padded prompt ``[1, bucket]`` into a one-slot cache,
        from zero. Returns the float32 logits row of the last REAL token
        ``[1, vocab]``, the stats, the cache."""
        params = variables["params"]
        x, cache, stats = self._run(params, tokens[0], cache, 0, n_real)
        last = lax.dynamic_slice_in_dim(x, n_real - 1, 1, axis=0)
        return self._head(last, params), stats, cache

    def prefill_chunk(self, variables, tokens, cache, start, n_real):
        """One chunk ``[1, C]`` of a padded prompt at positions ``start ..
        start + C`` (``start`` static) into a one-slot cache that holds
        what the chunks before it left: each Mamba layer resumes from its
        state and conv inputs, each attention layer's queries attend over
        the rows ``[0, start + C)``. Returns the float32 logits row of
        the last REAL token ``[1, vocab]`` where the chunk holds it (zeros
        where it does not: no head is computed), the stats, the cache."""
        params = variables["params"]
        x, cache, stats = self._run(params, tokens[0], cache, start, n_real)
        return self._chunk_logits(x, params, start, n_real), stats, cache

    def decode_slots(self, variables, tokens, cache, pos):
        """One token for every slot at once, at each slot's ``pos``. A
        slot at ``pos`` 0 is idle (a prompt has at least one token): it is
        routed to no expert and counted nowhere; its state is overwritten
        whole when the slot is next admitted. Returns (logits ``[slots,
        vocab]`` float32, stats, cache)."""
        params = variables["params"]
        x = self._embed(params, tokens)
        active = pos > 0
        stats = jnp.zeros(self.n_stats, jnp.int32)
        new_cache = []
        for i, kind in enumerate(self.layer_kinds):
            w, slabs = params[f"layer_{i}"], cache[i]
            if kind == "mamba":
                out, slabs = self._mamba_decode(x, w, slabs)
            elif kind == "attn":
                out, slabs = self._attn_decode(x, w, slabs, pos, "norm")
            else:
                out, layer_stats = self._moe(x, w, active)
                stats = stats + layer_stats
            x = x + out
            new_cache.append(slabs)
        return self._head(x, params), stats, tuple(new_cache)

    def logits(self, variables, tokens):
        """Every row's logits of one sequence ``[T]`` from position 0, no
        arena: what tests hold against the plain reference. Returns
        (``[T, vocab]`` float32, stats)."""
        params = variables["params"]
        t = tokens.shape[0]
        x, _, stats = self._run(params, tokens, self.init_cache(1, t), 0,
                                jnp.int32(t))
        return self._head(x, params), stats

