"""Plain reference of what the `nemotron_h_lm` configurations compute.

NVIDIA's Nemotron-H block (``model_type`` ``nemotron_h``) as Hugging Face
``transformers`` writes it (``NemotronHMamba2Mixer.torch_forward``,
``NemotronHMOE``, ``NemotronHTopkRouter``, ``NemotronHAttention``), in
straightforward ``jax.numpy``, float32, matmul precision ``highest``.
``x`` is ``[T, hidden]``; every norm is RMSNorm (eps
``layer_norm_epsilon``, a gain); no projection has a bias.  Layer ``i``'s
kind is character ``i`` of ``hybrid_override_pattern`` (the first
``num_layers`` of it): ``M`` Mamba-2, ``E`` experts, ``*`` attention, and
it computes ``x += mixer(norm(x))``.

- Mamba-2 (``H = mamba_num_heads`` of ``P = mamba_head_dim``, ``G =
  n_groups`` of ``N = ssm_state_size``): ``[z | xBC | dt] = h W_in``;
  ``xBC = silu(conv(xBC) + b_conv)``, a causal depthwise conv over
  ``conv_kernel`` positions with zeros before the sequence; split into
  ``x`` ``[T, H, P]``, ``B``, ``C`` ``[T, G, N]``; head ``i`` reads group
  ``i // (H / G)``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  from ``S = 0``, for each position in turn ``S = exp(dt_t A) S + dt_t
  x_t B_t^T`` and ``y_t = S C_t + D x_t`` (a ``lax.scan``, not the
  chunked algorithm); ``y = RMSNorm over G groups(y * silu(z))``;
  ``out = y W_out``.
- Experts: ``s = sigmoid(h W_r)`` over ``router_width``; the
  ``num_experts_per_tok`` largest ``s + b_r`` (the selection bias); their
  ``s`` divided by their sum (``norm_topk_prob``; plus 1e-20, as
  ``transformers``), times ``routed_scaling_factor``; ``u = h W_down``
  (``moe_latent_size``); ``out = (sum over the chosen experts HELD HERE of
  w_e relu(u W_up_e)^2 W_down_e) W_up + relu(h W_s_up)^2 W_s_down``.  The
  experts held are ``expert_offset .. expert_offset + n_routed_experts``;
  what the absent ones would have added is left out (the configuration
  is one chip's share of a deployment).  The held experts are an
  explicit loop, each over every token, masked by the routing.
- Attention: ``q`` ``[T, heads, head_dim]``, ``k``, ``v`` ``[T, kv_heads,
  head_dim]`` (query head ``j`` reads key/value head ``j // (heads /
  kv_heads)``), no positional encoding, scale ``head_dim^-0.5``, causal
  softmax in blocks of queries, ``o W_o``.
- After the last layer the final norm, then the untied head over the rows
  of the vocabulary held.

Departures from ``transformers``: the router reads the normed block input
in float32 (the model casts it there too); nothing else.

No cache, no kernel, no batching: one sequence, all positions at once.
Each layer's weights are made from the seed when the layer is reached
(an expert's inside the loop over experts), so the whole model is never
held; the configuration's ``serve_weight_map`` takes the leaves it names
to the range it gives (``weight_map``), as the family's adapter does for
the program.  ``quant`` is where the low-precision control enters (both
operands of every matrix product).  ``max_position_embeddings`` is not
read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

import weight_map
import weights

QUERY_BLOCK = 256
# Every sequence of the agent cell (prompt and output, up to its 9,216
# positions) is one shape: each layer's program at matmul precision
# "highest" is large, and the machine's compile cache, which the serving
# programs share, is small.
PAD_TO = 9216
_KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def _exact(a):
    return a


def kinds(cfg: dict) -> tuple:
    layers = cfg.get("num_layers", cfg["num_hidden_layers"])
    return tuple(_KINDS[c] for c in cfg["hybrid_override_pattern"][:layers])


def _sizes(cfg: dict) -> tuple:
    """(d_inner, conv_dim)."""
    di = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return di, di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def layer_shapes(cfg: dict, kind: str, experts: bool = True) -> dict:
    """One layer's leaves; ``experts=False`` leaves the routed experts
    out (the reference makes those inside its loop over them)."""
    d = cfg["hidden_size"]
    shapes = {"norm/scale": (d,)}
    if kind == "mamba":
        di, conv = _sizes(cfg)
        h = cfg["mamba_num_heads"]
        shapes.update({
            "mamba/in_proj/kernel": (d, di + conv + h),
            "mamba/conv/kernel": (cfg["conv_kernel"], conv),
            "mamba/conv/bias": (conv,),
            "mamba/dt_bias/bias": (h,),
            "mamba/A_log/scale": (h,),
            "mamba/D/scale": (h,),
            "mamba/norm/scale": (di,),
            "mamba/out_proj/kernel": (di, d),
        })
    elif kind == "attn":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        shapes.update({"attn/q/kernel": (d, q), "attn/k/kernel": (d, kv),
                       "attn/v/kernel": (d, kv), "attn/o/kernel": (q, d)})
    else:
        lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
        fs = cfg["moe_shared_expert_intermediate_size"]
        shapes.update({
            "moe/router/kernel": (d, cfg["router_width"]),
            "moe/router/bias": (cfg["router_width"],),
            "moe/latent_down/kernel": (d, lat),
            "moe/latent_up/kernel": (lat, d),
            "moe/shared/up/kernel": (d, fs),
            "moe/shared/down/kernel": (fs, d),
        })
        if experts:
            for e in range(cfg["n_routed_experts"]):
                shapes[f"moe/expert_{e}/up/kernel"] = (lat, f)
                shapes[f"moe/expert_{e}/down/kernel"] = (f, lat)
    return shapes


def param_shapes(cfg: dict) -> dict[str, tuple]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"params/embed/embedding": (v, d),
              "params/final_norm/scale": (d,),
              "params/lm_head/kernel": (d, v)}
    for i, kind in enumerate(kinds(cfg)):
        for name, shape in layer_shapes(cfg, kind).items():
            shapes[f"params/layer_{i}/{name}"] = shape
    return shapes


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=lax.Precision.HIGHEST)


def _relu2(x, up, down, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(x, up, quant))), down, quant)


def _mamba(h, w, cfg, quant):
    t = h.shape[0]
    di, conv = _sizes(cfg)
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    zxd = _mm(h, w["mamba/in_proj/kernel"], quant)
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + conv], zxd[:, di + conv:]
    padded = jnp.concatenate([jnp.zeros((k - 1, conv), xbc.dtype), xbc])
    kernel = w["mamba/conv/kernel"]
    xbc = jax.nn.silu(sum(kernel[j] * padded[j:j + t] for j in range(k))
                      + w["mamba/conv/bias"])
    x = xbc[:, :di].reshape(t, heads, p)
    b = xbc[:, di:di + groups * n].reshape(t, groups, n)
    c = xbc[:, di + groups * n:].reshape(t, groups, n)
    group = jnp.arange(heads) // (heads // groups)
    dt = jax.nn.softplus(dt + w["mamba/dt_bias/bias"])       # [T, H]
    a = -jnp.exp(w["mamba/A_log/scale"])                     # [H]

    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :])
        return s, jnp.sum(s * c_t[group][:, None, :], axis=-1)

    _, y = lax.scan(step, jnp.zeros((heads, p, n), jnp.float32),
                    (x, dt, b, c))
    y = (y + w["mamba/D/scale"][None, :, None] * x).reshape(t, di)
    g = (y * jax.nn.silu(z)).reshape(t, groups, -1)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                      + cfg["layer_norm_epsilon"])
    g = g.reshape(t, di) * w["mamba/norm/scale"]
    return _mm(g, w["mamba/out_proj/kernel"], quant)


def _attention(h, w, cfg, quant):
    t = h.shape[0]
    heads, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
    q = _mm(h, w["attn/q/kernel"], quant).reshape(t, heads, hd)
    k = _mm(h, w["attn/k/kernel"], quant).reshape(t, kvh, hd)
    v = _mm(h, w["attn/v/kernel"], quant).reshape(t, kvh, hd)
    group = jnp.arange(heads) // (heads // kvh)
    q, k, v = (q.transpose(1, 0, 2), k[:, group].transpose(1, 0, 2),
               v[:, group].transpose(1, 0, 2))
    size = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    kt, cols = k.transpose(0, 2, 1), jnp.arange(t)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * size, size, 1)
        rows = i * size + jnp.arange(size)
        scores = _mm(qb, kt, quant) * hd ** -0.5
        scores = jnp.where(rows[:, None] >= cols[None, :], scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, axis=-1), v, quant)

    out = lax.map(block, jnp.arange(t // size))          # [blocks, h, qb, hd]
    out = out.transpose(0, 2, 1, 3).reshape(t, heads * hd)
    return _mm(out, w["attn/o/kernel"], quant)


def _routing(h, w, cfg, quant):
    """Each token's weight for each held expert, ``[T, held]``."""
    s = jax.nn.sigmoid(_mm(h, w["moe/router/kernel"], quant))
    _, top_i = lax.top_k(s + w["moe/router/bias"], cfg["num_experts_per_tok"])
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    top_w = top_w * cfg["routed_scaling_factor"]
    held = cfg["expert_offset"] + jnp.arange(cfg["n_routed_experts"])
    return jnp.sum(jnp.where(top_i[:, :, None] == held[None, None, :],
                             top_w[:, :, None], 0.0), axis=1)


def _moe(h, w, cfg, quant, expert):
    """``expert(e, u)`` gives held expert ``e`` applied to ``u``."""
    share = _routing(h, w, cfg, quant)
    u = _mm(h, w["moe/latent_down/kernel"], quant)

    def one(e, acc):
        return acc + share[:, e, None] * expert(e, u)

    routed = lax.fori_loop(0, cfg["n_routed_experts"], one,
                           jnp.zeros(u.shape, jnp.float32))
    return (_mm(routed, w["moe/latent_up/kernel"], quant)
            + _relu2(h, w["moe/shared/up/kernel"],
                     w["moe/shared/down/kernel"], quant))


def layer(x, w, cfg, kind, quant=_exact, expert=None):
    """One whole layer; ``w`` maps the layer's leaf names to arrays, and
    an expert layer takes ``expert(e, u)`` or its experts from ``w``."""
    h = _rms(x, w["norm/scale"], cfg["layer_norm_epsilon"])
    if kind == "mamba":
        return x + _mamba(h, w, cfg, quant)
    if kind == "attn":
        return x + _attention(h, w, cfg, quant)
    if expert is None:
        up = jnp.stack([w[f"moe/expert_{e}/up/kernel"]
                        for e in range(cfg["n_routed_experts"])])
        down = jnp.stack([w[f"moe/expert_{e}/down/kernel"]
                          for e in range(cfg["n_routed_experts"])])

        def expert(e, u):
            return _relu2(u, up[e], down[e], quant)

    return x + _moe(h, w, cfg, quant, expert)


def head(x, scale, kernel, cfg, quant=_exact):
    return _mm(_rms(x, scale, cfg["layer_norm_epsilon"]), kernel, quant)


@functools.partial(jax.jit, static_argnames=("rule", "shape"))
def _leaf(key, salt, *, rule, shape):
    """One leaf by the benchmark's rule for its last path part ``rule``:
    compiled once a rule and shape, whatever the layer (the salt is
    traced)."""
    return weights.leaf(key, rule, shape, salt)


@functools.partial(jax.jit, static_argnames=("cfg_key", "kind", "quant"))
def _layer(x, w, key, salts, *, cfg_key, kind, quant):
    """One layer; an expert layer makes each held expert's two leaves
    from the seed inside its loop (``salts`` ``[held, 2]``)."""
    cfg = dict(cfg_key)
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]

    def expert(e, u):
        up = weights.leaf(key, "kernel", (lat, f), salts[e, 0])
        down = weights.leaf(key, "kernel", (f, lat), salts[e, 1])
        return _relu2(u, up, down, quant)

    return layer(x, w, cfg, kind, quant,
                 expert if kind == "moe" else None)


def _layer_weights(key, cfg: dict, i: int, kind: str) -> dict:
    """Layer ``i``'s leaves but its routed experts, from the seed's key,
    the configuration's ``serve_weight_map`` applied."""
    rules = weight_map.maps(cfg)
    return {name: weight_map.mapped(name, _leaf(
        key, jnp.int32(weights.salt(f"params/layer_{i}/{name}")),
        rule=name.rsplit("/", 1)[-1], shape=tuple(shape)), rules)
        for name, shape in layer_shapes(cfg, kind, experts=False).items()}


def _expert_salts(cfg: dict, i: int):
    return jnp.asarray(
        [[weights.salt(f"params/layer_{i}/moe/expert_{e}/{part}/kernel")
          for part in ("up", "down")]
         for e in range(cfg["n_routed_experts"])], jnp.int32).reshape(-1, 2)


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _embed(tokens, key, *, cfg_key):
    cfg = dict(cfg_key)
    table = weights.leaf(key, "params/embed/embedding",
                         (cfg["vocab_size"], cfg["hidden_size"]))
    return table[tokens]


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _head_from_seed(x, key, *, cfg_key, quant):
    cfg = dict(cfg_key)
    d = cfg["hidden_size"]
    scale = weights.leaf(key, "params/final_norm/scale", (d,))
    kernel = weights.leaf(key, "params/lm_head/kernel",
                          (d, cfg["vocab_size"]))
    return head(x, scale, kernel, cfg, quant)


_KEYS = ("vocab_size", "hidden_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "mamba_num_heads",
         "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
         "moe_intermediate_size", "moe_latent_size",
         "moe_shared_expert_intermediate_size", "n_routed_experts",
         "router_width", "expert_offset", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor", "layer_norm_epsilon")


def _cfg_key(cfg: dict) -> tuple:
    return tuple((k, cfg.get(k, 0) if k == "expert_offset" else cfg[k])
                 for k in _KEYS)


def logits(tokens, seed: int, cfg: dict, quant=_exact, pad_to=None):
    """Logits [len(tokens), vocab] of one sequence, float32, with the
    weights made from ``seed`` (and the configuration's
    ``serve_weight_map`` applied to them: ``weight_map``).  The sequence
    is padded at its end to a multiple of ``pad_to`` (fewer shapes to
    compile): under the causal mask, a scan that runs forward and an
    expert layer that is per token, padding cannot reach the real
    positions."""
    n = len(tokens)
    if pad_to is None:
        pad_to = QUERY_BLOCK if n <= QUERY_BLOCK else PAD_TO
    size = -(-n // pad_to) * pad_to
    ids = jnp.zeros((size,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    key, ck = weights.key_for(seed), _cfg_key(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(ids, key, cfg_key=ck)
        for i, kind in enumerate(kinds(cfg)):
            salts = (_expert_salts(cfg, i) if kind == "moe"
                     else jnp.zeros((1, 2), jnp.int32))
            x = _layer(x, _layer_weights(key, cfg, i, kind), key, salts,
                       cfg_key=ck, kind=kind, quant=quant)
        return _head_from_seed(x, key, cfg_key=ck, quant=quant)[:n]


def logits_of(tokens, leaves: dict, cfg: dict, quant=_exact):
    """The same forward pass over given weights (``leaves``: the
    '/'-joined paths of :func:`param_shapes` -> arrays), unpadded."""
    with jax.default_matmul_precision("highest"):
        x = leaves["params/embed/embedding"][jnp.asarray(tokens, jnp.int32)]
        for i, kind in enumerate(kinds(cfg)):
            w = {name: leaves[f"params/layer_{i}/{name}"]
                 for name in layer_shapes(cfg, kind)}
            x = layer(x, w, cfg, kind, quant)
        return head(x, leaves["params/final_norm/scale"],
                    leaves["params/lm_head/kernel"], cfg, quant)
