"""Decoder-only Transformer LM with pluggable attention backends.

No transformer exists in the reference (SURVEY.md §5.7) — this family is
here because long-context is first-class in the TPU build: it is the
workload that exercises flash attention (single device) and ring
attention (sequence-parallel over a mesh axis), the same way ResNet-50
exercises the data-parallel trainer.

TPU-first choices: bf16 activations by default (MXU-native), RMSNorm +
pre-norm residuals, fused-friendly GELU MLP, static shapes throughout,
and attention selected at construction ("flash" | "ring" | "reference")
so the same module runs single-chip or sequence-sharded without code
changes.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.decode_attention import decode_attention, rows_fetched
from ..ops.flash_attention import attention_reference, flash_attention


def rms_norm(x, scale, eps: float = 1e-6):
    """The pure RMSNorm expression (f32 math), shared by the flax module
    and non-flax models (PipelinedLM)."""
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps
    )
    return norm * scale


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, scale, self.eps).astype(self.dtype)


def _select_attention(kind: str, **ring_kwargs) -> Callable:
    if kind == "flash":
        return lambda q, k, v: flash_attention(q, k, v, causal=True)
    if kind == "reference":
        return lambda q, k, v: attention_reference(q, k, v, causal=True)
    if kind == "ring":
        from ..parallel.ring import ring_attention

        mesh = ring_kwargs.get("mesh")
        axis_name = ring_kwargs.get("axis_name")
        if mesh is None or axis_name is None:
            raise ValueError("attention='ring' needs mesh= and axis_name=")
        return lambda q, k, v: ring_attention(
            q, k, v, mesh=mesh, axis_name=axis_name, causal=True
        )
    raise ValueError(f"unknown attention backend {kind!r}")


class TransformerBlock(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    mlp_ratio: int = 4
    attention_fn: Callable = None  # bound by TransformerLM
    # "dense" | "moe" — MoE swaps the MLP for an expert-parallel
    # MoEMLP (models/moe.py) routed top-1 over num_experts.
    ffn: str = "dense"
    num_experts: int = 0
    capacity_factor: float = 1.25
    expert_mesh: Any = None
    expert_axis: str = "expert"
    router_noise: float = 0.0

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True, cache=None,
                 pos=None):
        """Full-context training/eval pass, or — with ``cache``/``pos``
        — a KV-cached pass returning ``(x, new_cache)``: one decode
        step when ``x`` is [b, 1, dim], or a pos-0 prefill writing the
        whole chunk's k/v when longer. All branches call the SAME
        submodules in the SAME order, so the parameter tree is
        identical and trained checkpoints decode without conversion."""
        if self.ffn not in ("dense", "moe"):
            raise ValueError(f"unknown ffn {self.ffn!r}: expected 'dense' or 'moe'")
        if self.ffn == "moe" and self.num_experts < 1:
            raise ValueError("ffn='moe' requires num_experts >= 1")
        b, s, dim = x.shape
        head_dim = dim // self.num_heads

        with jax.named_scope("attn"):
            h = RMSNorm(dtype=self.dtype)(x)
            qkv = nn.Dense(
                3 * dim, use_bias=False, dtype=self.dtype, name="qkv"
            )(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)

            def heads(t):  # [b, s, dim] -> [b, heads, s, head_dim]
                return t.reshape(
                    b, s, self.num_heads, head_dim
                ).transpose(0, 2, 1, 3)

            if cache is not None:
                # Both cached modes write this call's k/v into the cache
                # slab at ``pos`` (one position for the batch, or one a
                # slot); they differ only in how attn is computed.
                def write(slab, rows):
                    if jnp.ndim(pos) == 0:
                        return jax.lax.dynamic_update_slice_in_dim(
                            slab, rows, pos, axis=2)
                    return jax.vmap(
                        lambda a, r, p: jax.lax.dynamic_update_slice_in_dim(
                            a, r, p, axis=1)
                    )(slab, rows, pos)

                k_cache = write(cache["k"], heads(k))
                v_cache = write(cache["v"], heads(v))
                new_cache = {"k": k_cache, "v": v_cache}
                if s == 1:
                    # Decode step: the one query over the slot's rows
                    # ``0..pos``, the row just written among them.
                    attn = decode_attention(
                        heads(q)[:, :, 0], k_cache, v_cache, pos
                    )[:, :, None]
                else:
                    # Prefill (pos == 0, enforced by TransformerLM): the
                    # whole prompt in ONE causal parallel pass — the
                    # training-shaped matmuls, nothing earlier to attend to.
                    attn = self.attention_fn(heads(q), heads(k), heads(v))
            else:
                attn = self.attention_fn(heads(q), heads(k), heads(v))
                new_cache = None
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, dim)
            x = x + nn.Dense(
                dim, use_bias=False, dtype=self.dtype, name="proj"
            )(attn)

        with jax.named_scope("mlp"):
            h = RMSNorm(dtype=self.dtype)(x)
            if self.ffn == "moe":
                from .moe import MoEMLP

                x = x + MoEMLP(
                    num_experts=self.num_experts,
                    mlp_ratio=self.mlp_ratio,
                    capacity_factor=self.capacity_factor,
                    dtype=self.dtype,
                    mesh=self.expert_mesh,
                    axis_name=self.expert_axis,
                    router_noise=self.router_noise,
                    name="moe",
                )(h, deterministic=deterministic,
                  # a decode step: one token a sequence, each routed as
                  # if alone (no slot's neighbours decide its experts)
                  keep_all=cache is not None and s == 1)
            else:
                h = nn.Dense(
                    self.mlp_ratio * dim, dtype=self.dtype, name="mlp_up"
                )(h)
                h = nn.gelu(h)
                x = x + nn.Dense(dim, dtype=self.dtype, name="mlp_down")(h)
        return x if cache is None else (x, new_cache)


class TransformerLM(nn.Module):
    """Causal LM: token + learned position embeddings, N pre-norm blocks.

    ``attention``: "flash" (Pallas kernel, single device), "ring"
    (sequence-parallel — pass ``mesh`` and ``axis_name``), or "reference".
    """

    vocab_size: int
    dim: int = 512
    num_heads: int = 8
    num_layers: int = 4
    max_seq: int = 2048
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    attention: str = "flash"
    mesh: Any = None
    axis_name: str | None = None
    # Expert-parallel MoE FFN (models/moe.py): ffn="moe" with
    # num_experts > 0 swaps every block's MLP; expert_mesh/expert_axis
    # shard the experts (EP) — None runs the same program on one device.
    ffn: str = "dense"
    num_experts: int = 0
    capacity_factor: float = 1.25
    expert_mesh: Any = None
    expert_axis: str = "expert"
    # Router jitter std at train time; needs an apply-time "router" rng
    # and deterministic=False to take effect.
    router_noise: float = 0.0

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True, cache=None,
                 pos=None):
        # [b, s] int32 -> [b, s, vocab] f32 logits; with ``cache``/
        # ``pos``: a KV-cached pass returning ``(logits, new_cache)`` —
        # one decode step on [b, 1] tokens (logits [b, vocab]) or a
        # pos-0 prefill on the whole prompt (logits [b, s, vocab]); see
        # ``generate``.
        b, s = tokens.shape
        if s > self.max_seq:
            raise ValueError(f"seq {s} > max_seq {self.max_seq}")
        decoding = cache is not None
        if decoding and self.attention == "ring":
            raise ValueError(
                "KV-cache decode is single-device; a sequence-sharded "
                "(ring) model should decode with attention='flash' or "
                "'reference' on the gathered sequence"
            )
        if decoding and s > 1 and (not isinstance(pos, int) or pos != 0):
            # A multi-token cached pass attends only WITHIN the chunk;
            # continuing from a non-empty cache would silently ignore
            # the cached prefix. Prefill is pos=0 only.
            raise ValueError(
                "multi-token cached calls are prefill only (pos=0); "
                "continue from a prefilled cache one token at a time"
            )
        # Single-token decode needs no parallel attention kernel; the
        # multi-token cases (training pass, or PREFILL writing the
        # prompt's k/v into the cache in one causal pass) do.
        attention_fn = (
            None if decoding and s == 1 else _select_attention(
                self.attention, mesh=self.mesh, axis_name=self.axis_name
            )
        )
        tok = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype, name="tok_embed")
        pos_table = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (self.max_seq, self.dim),
        )
        with jax.named_scope("embed"):
            if decoding and jnp.ndim(pos):
                # One position a slot, clamped as the slice below is.
                pos_emb = pos_table.at[pos].get(mode="clip")[:, None]
            elif decoding:
                pos_emb = jax.lax.dynamic_slice_in_dim(pos_table, pos, s)[None]
            else:
                pos_emb = pos_table[None, :s]
            x = tok(tokens) + pos_emb.astype(self.dtype)
        new_cache = []
        for i in range(self.num_layers):
            block = TransformerBlock(
                num_heads=self.num_heads,
                dtype=self.dtype,
                mlp_ratio=self.mlp_ratio,
                attention_fn=attention_fn,
                ffn=self.ffn,
                num_experts=self.num_experts,
                capacity_factor=self.capacity_factor,
                expert_mesh=self.expert_mesh,
                expert_axis=self.expert_axis,
                router_noise=self.router_noise,
                name=f"block_{i}",
            )
            if decoding:
                x, layer_cache = block(
                    x, deterministic=deterministic, cache=cache[i], pos=pos
                )
                new_cache.append(layer_cache)
            else:
                x = block(x, deterministic=deterministic)
        with jax.named_scope("lm_head"):
            x = RMSNorm(dtype=self.dtype)(x)
            # Logits in f32 for a stable softmax cross-entropy.
            logits = nn.Dense(
                self.vocab_size, use_bias=False, dtype=jnp.float32,
                name="lm_head",
            )(x)
        if decoding:
            # Single-step callers get the one row; prefill callers get
            # the full [b, s, vocab] (the last row seeds sampling).
            return (logits[:, 0] if s == 1 else logits), tuple(new_cache)
        return logits

    # -- what the serving decoder asks of a served model ---------------
    # (:mod:`..serving.lm.kvcache`: the model, not the cache module,
    # says what one slot's cache is and how a step reads it.)

    cache_kind = "kv"

    @nn.nowrap
    def init_cache(self, slots: int, max_len: int):
        """One ``[slots, heads, max_len, head_dim]`` k and v slab a layer.

        ``max_len`` may be smaller than ``max_seq``: the attention mask
        and the cache writes both derive their length from the cache's
        own shape, so a short arena is a working (cheaper) cache.
        """
        if max_len > self.max_seq:
            raise ValueError(
                f"arena max_len {max_len} > model max_seq {self.max_seq}"
            )
        shape = (slots, self.num_heads, max_len, self.dim // self.num_heads)
        return tuple(
            {
                "k": jnp.zeros(shape, dtype=self.dtype),
                "v": jnp.zeros(shape, dtype=self.dtype),
            }
            for _ in range(self.num_layers)
        )

    @nn.nowrap
    def serving_variables(self, variables):
        return serving_variables(self, variables)

    @nn.nowrap
    def prefill_cache(self, variables, tokens, cache, n_real=None):
        """One bucket-padded prompt ``[1, bucket]`` into a one-slot
        cache. Returns every row's logits (``[1, bucket, vocab]``, or
        ``[1, vocab]`` for a bucket of one token), no stats, the cache;
        ``n_real`` is not read."""
        logits, cache = self.apply(variables, tokens, cache=cache, pos=0)
        return logits, None, cache

    @nn.nowrap
    def decode_slots(self, variables, tokens, cache, pos):
        """One token for every slot: one batched call of the cached
        decode with the per-slot ``pos`` vector (each slot's k/v row
        written at its own position, each slot's attention over its own
        rows ``0..pos``). Returns (logits ``[slots, vocab]``, no stats,
        cache)."""
        logits, cache = self.apply(
            variables, tokens[:, None], cache=cache, pos=pos
        )
        return logits, None, cache

    @nn.nowrap
    def decode_rows_read(self, pos, cache) -> int:
        """Cache rows a layer that one ``decode_slots`` over ``cache``
        at ``pos`` (host ``[slots]``) fetches: what the engine's
        ``lm_decode_cache_rows_total{kind="read"}`` counts. The slab's
        own shape and dtype decide, as they do in the op."""
        slab = cache[0]["k"]
        return rows_fetched(pos, slab.shape, slab.dtype)


# The leaves the model multiplies in float32 whatever its ``dtype``, by
# the end of their path: every ``RMSNorm`` scale (``rms_norm`` takes
# ``norm * scale`` in f32), the ``lm_head`` kernel (f32 logits) and the
# MoE block's ``router`` kernel (f32 softmax over experts).
_FLOAT32_LEAVES = (("scale",), ("lm_head", "kernel"), ("router", "kernel"))


def serving_variables(model: TransformerLM, variables):
    """``variables`` with each leaf at the width the model multiplies it in.

    Every module of the model built with ``dtype=model.dtype`` converts
    its float32 parameters on each call; a server that applies the same
    tree for its whole life pays that convert (and reads the wide leaf)
    in every program. This is the same rounding made once: every product
    is the one it was. (On the CPU the logits are bitwise the given
    tree's. On the TPU they are only under
    ``--xla_allow_excess_precision=false``: by default XLA may skip a
    rounding to bfloat16 inside a fusion, it fuses a program with narrow
    arguments otherwise, and the logits differ by that rounding noise.)
    Leaves consumed in float32 (``_FLOAT32_LEAVES``: flax makes every
    parameter float32), and every leaf already at its width (a float32
    model's whole tree), come back as the arrays they were; host (numpy)
    leaves are converted on the host and placed narrow
    (:func:`.served.cast_tree`).
    """
    from .served import cast_tree, width

    return cast_tree(
        variables, lambda path: width(path, _FLOAT32_LEAVES, model.dtype))


def init_kv_cache(model: TransformerLM, batch: int):
    """Zeroed per-layer K/V buffers sized [b, heads, max_seq, head_dim]."""
    return model.init_cache(batch, model.max_seq)


def decode_step(model: TransformerLM, variables, tokens, cache, pos):
    """One KV-cache decode step: ``[b, t]`` tokens at ``pos`` → logits.

    The single-token apply that :func:`generate`'s scan iterates — and
    the program the ``dsst audit`` registry lowers with the cache
    donated (the continuous-batching serving tier will hold one live
    cache per slot, so the step must alias it, not copy it). Factored
    out so the audited program and the sampling loop can never diverge.
    """
    return model.apply(variables, tokens, cache=cache, pos=pos)


def generate(
    model: TransformerLM,
    variables,
    prompt: jax.Array,  # [b, p] int32
    n_tokens: int,
    *,
    temperature: float = 0.0,
    top_k: int | None = None,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Autoregressive sampling: ``[b, p + n_tokens]`` continuations.

    Two phases, both static-shaped: a CHUNKED PREFILL — the whole
    prompt through one causal parallel pass (the training-shaped
    matmuls; flash attention applies) that also writes the prompt's
    k/v into the cache — then one ``lax.scan`` of the single-token
    decode step for sampling. ``temperature=0`` is greedy argmax;
    otherwise softmax sampling at the given temperature, optionally
    truncated to the ``top_k`` most likely tokens.
    """
    b, p = prompt.shape
    cache = init_kv_cache(model, b)
    # Cap against the CACHE SLAB, not the caller's arithmetic: the
    # scatter at position ``pos`` is bounded by the preallocated k/v
    # length (``cache[l]["k"].shape[2]``), so that shape — not whatever
    # budget the caller computed — is the one capacity that matters.
    # (Today the two agree at ``model.max_seq``; deriving from the
    # buffer keeps the guard correct if they ever diverge, e.g. a
    # short-arena cache like the serving tier's slot arenas.)
    max_len = cache[0]["k"].shape[2]
    total = p + int(n_tokens)
    if total > max_len:
        raise ValueError(
            f"prompt + n_tokens = {total} > max_seq {max_len} "
            "(the preallocated KV-cache capacity)"
        )
    if rng is None:
        rng = jax.random.key(0)

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / temperature
        if top_k is not None:
            kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
            scaled = jnp.where(scaled < kth, -1e30, scaled)
        return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)

    if n_tokens <= 0:
        return prompt

    try:
        prefill_logits, cache = model.apply(
            variables, prompt, cache=cache, pos=0
        )
    except ValueError:
        # The flash kernel rejects some awkward prompt lengths (block
        # divisibility); the reference path accepts any shape and the
        # cache contents are identical.  Only the flash model gets this
        # fallback: for any other attention mode a ValueError is a real
        # configuration error (e.g. a ring model whose decode step
        # cannot run here anyway) and must stay loud rather than be
        # masked by a retry that would fail later in the scan.
        if getattr(model, "attention", None) != "flash":
            raise
        prefill_logits, cache = model.clone(
            attention="reference"
        ).apply(variables, prompt, cache=cache, pos=0)
    # Prefill returns [b, vocab] for a 1-token prompt (the decode-step
    # shape) and [b, p, vocab] otherwise.
    last_logits = prefill_logits if p == 1 else prefill_logits[:, -1]

    def step(carry, i):
        cache, logits, key = carry
        key, sub = jax.random.split(key)
        nxt = sample(logits, sub)  # the token at position p + i
        logits, cache = decode_step(
            model, variables, nxt[:, None], cache, p + i
        )
        return (cache, logits, key), nxt

    # n_tokens - 1 decode steps; the final token needs no model call
    # (its logits are already in the carry).
    (_, final_logits, key), sampled = jax.lax.scan(
        step, (cache, last_logits, rng), jnp.arange(n_tokens - 1)
    )
    key, sub = jax.random.split(key)
    last = sample(final_logits, sub)
    gen = jnp.concatenate(
        [jnp.swapaxes(sampled, 0, 1), last[:, None]], axis=1
    )
    return jnp.concatenate([prompt, gen], axis=1)


def next_token_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean cross entropy of positions 0..s-2 predicting tokens 1..s-1."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)
