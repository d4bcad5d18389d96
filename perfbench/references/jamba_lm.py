"""Plain reference of what the `jamba_lm` configurations compute.

AI21's Jamba block (``model_type`` ``jamba``) as Hugging Face
``transformers`` writes it (``JambaMambaMixer.slow_forward``,
``JambaAttention``, ``JambaMLP``), in straightforward ``jax.numpy``,
float32, matmul precision ``highest``.  ``x`` is ``[T, hidden]``; every
norm is RMSNorm (eps ``rms_norm_eps``, a gain).  Layer ``i`` is attention
where ``i % attn_layer_period == attn_layer_offset``, else Mamba, and
computes ``x += mixer(norm_in(x))``, then ``x += mlp(norm_ff(x))`` with
``mlp(h) = (silu(h W_gate) * (h W_up)) W_down``.

- Mamba (``d_inner = mamba_expand * hidden``): ``[u | z] = h W_in``;
  ``u = silu(conv(u) + b_conv)``, a causal depthwise conv over
  ``mamba_d_conv`` positions with zeros before the sequence;
  ``[d | B | C] = u W_x`` (``mamba_dt_rank | d_state | d_state``), each
  RMS-normed; ``dt = softplus(d W_dt + b_dt)``; ``A = -exp(A_log)``
  ``[d_inner, d_state]``; from ``s = 0``, for each position in turn
  ``s = exp(dt_t A) * s + (dt_t u_t) B_t^T`` and ``y_t = s C_t + D u_t``
  (a ``lax.scan``); ``out = (y * silu(z)) W_out``.
- Attention: ``q = h W_q`` as ``[T, heads, head]``, ``k = h W_k``,
  ``v = h W_v`` as ``[T, kv_heads, head]`` (query head ``j`` reads key
  and value head ``j // (heads / kv_heads)``), no positional encoding,
  scale ``head^-0.5``, causal softmax in blocks of queries, ``o W_o``.
- After the last layer the final norm, then the tied head ``x E^T``.

No cache, no kernel, no batching: one sequence, all positions at once.
Each layer's weights are made from the seed when the layer is reached, so
the whole model is never held; the configuration's ``serve_weight_map``
takes the leaves it names to the range it gives (``weight_map``), as the
family's adapter does for the program.  ``quant`` is where the low-precision
control enters (both operands of every matrix product).
``max_position_embeddings`` is not read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

import weight_map
import weights

QUERY_BLOCK = 256
# Every sequence of the reasoning cell (up to its 6,144 positions) is one
# shape: each layer's program at matmul precision "highest" is some 8 MB
# of code a shape, and the machine's compile cache, which the serving
# programs share, is small.
PAD_TO = 6144


def _exact(a):
    return a


def layer_shapes(cfg: dict, kind: str) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    di = cfg["mamba_expand"] * d
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    shapes = {"norm_in/scale": (d,)}
    if kind == "attn":
        hd = d // cfg["num_attention_heads"]
        q, kv = d, cfg["num_key_value_heads"] * hd
        shapes.update({"attn/q/kernel": (d, q), "attn/k/kernel": (d, kv),
                       "attn/v/kernel": (d, kv), "attn/o/kernel": (q, d)})
    else:
        shapes.update({
            "mamba/in_proj/kernel": (d, 2 * di),
            "mamba/conv/kernel": (cfg["mamba_d_conv"], di),
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


def kind_of(cfg: dict, i: int) -> str:
    return ("attn" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
            else "mamba")


def param_shapes(cfg: dict) -> dict[str, tuple]:
    d = cfg["hidden_size"]
    shapes = {"params/embed/embedding": (cfg["vocab_size"], d),
              "params/final_norm/scale": (d,)}
    for i in range(cfg["num_hidden_layers"]):
        for name, shape in layer_shapes(cfg, kind_of(cfg, i)).items():
            shapes[f"params/layer_{i}/{name}"] = shape
    return shapes


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=lax.Precision.HIGHEST)


def _mlp(x, w, cfg, quant):
    h = _rms(x, w["norm_ff/scale"], cfg["rms_norm_eps"])
    return x + _mm(jax.nn.silu(_mm(h, w["mlp/gate/kernel"], quant))
                   * _mm(h, w["mlp/up/kernel"], quant),
                   w["mlp/down/kernel"], quant)


def _mamba(x, w, cfg, quant):
    t, eps = x.shape[0], cfg["rms_norm_eps"]
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    n, r, k = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    h = _rms(x, w["norm_in/scale"], eps)
    uz = _mm(h, w["mamba/in_proj/kernel"], quant)
    u, z = uz[:, :di], uz[:, di:]
    padded = jnp.concatenate([jnp.zeros((k - 1, di), u.dtype), u])
    kernel = w["mamba/conv/kernel"]
    u = jax.nn.silu(sum(kernel[j] * padded[j:j + t] for j in range(k))
                    + w["mamba/conv/bias"])
    xdbc = _mm(u, w["mamba/x_proj/kernel"], quant)
    d = _rms(xdbc[:, :r], w["mamba/dt_norm/scale"], eps)
    b = _rms(xdbc[:, r:r + n], w["mamba/b_norm/scale"], eps)
    c = _rms(xdbc[:, r + n:], w["mamba/c_norm/scale"], eps)
    dt = jax.nn.softplus(_mm(d, w["mamba/dt_proj/kernel"], quant)
                         + w["mamba/dt_proj/bias"])
    a = -jnp.exp(w["mamba/A_log/scale"])                  # [d_inner, n]

    def step(s, row):
        dt_t, u_t, b_t, c_t = row
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * u_t)[:, None] * b_t[None]
        return s, jnp.sum(s * c_t[None, :], axis=1)

    _, y = lax.scan(step, jnp.zeros((di, n), jnp.float32), (dt, u, b, c))
    y = y + w["mamba/D/scale"] * u
    return x + _mm(y * jax.nn.silu(z), w["mamba/out_proj/kernel"], quant)


def _attention(x, w, cfg, quant):
    t = x.shape[0]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    hid = _rms(x, w["norm_in/scale"], cfg["rms_norm_eps"])
    q = _mm(hid, w["attn/q/kernel"], quant).reshape(t, h, hd)
    k = _mm(hid, w["attn/k/kernel"], quant).reshape(t, kvh, hd)
    v = _mm(hid, w["attn/v/kernel"], quant).reshape(t, kvh, hd)
    group = jnp.arange(h) // (h // kvh)
    q, k, v = q.transpose(1, 0, 2), k[:, group].transpose(1, 0, 2), \
        v[:, group].transpose(1, 0, 2)
    size = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    kt, cols = k.transpose(0, 2, 1), jnp.arange(t)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * size, size, 1)
        rows = i * size + jnp.arange(size)
        scores = _mm(qb, kt, quant) * hd ** -0.5
        scores = jnp.where(rows[:, None] >= cols[None, :], scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, axis=-1), v, quant)

    out = lax.map(block, jnp.arange(t // size))          # [blocks, h, qb, hd]
    out = out.transpose(0, 2, 1, 3).reshape(t, h * hd)
    return x + _mm(out, w["attn/o/kernel"], quant)


def layer(x, w, cfg, kind, quant=_exact):
    """One whole layer: its mixer, then its MLP; ``w`` maps the layer's
    leaf names to arrays."""
    mix = _attention if kind == "attn" else _mamba
    return _mlp(mix(x, w, cfg, quant), w, cfg, quant)


def head(x, table, scale, cfg, quant=_exact):
    return _mm(_rms(x, scale, cfg["rms_norm_eps"]), table.T, quant)


@functools.partial(jax.jit, static_argnames=("rule", "shape"))
def _leaf(key, salt, *, rule, shape):
    """One leaf by the benchmark's rule for its last path part ``rule``:
    compiled once a rule and shape, whatever the layer (the salt is
    traced), and apart from the layers' arithmetic, whose programs then
    stay small."""
    return weights.leaf(key, rule, shape, salt)


@functools.partial(jax.jit, static_argnames=("cfg_key", "kind", "quant"))
def _layer(x, w, *, cfg_key, kind, quant):
    return layer(x, w, dict(cfg_key), kind, quant)


def _layer_weights(key, cfg: dict, i: int, kind: str) -> dict:
    """Layer ``i``'s leaves from the seed's key, the configuration's
    ``serve_weight_map`` applied."""
    rules = weight_map.maps(cfg)
    return {name: weight_map.mapped(name, _leaf(
        key, jnp.int32(weights.salt(f"params/layer_{i}/{name}")),
        rule=name.rsplit("/", 1)[-1], shape=tuple(shape)), rules)
        for name, shape in layer_shapes(cfg, kind).items()}


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
    table = weights.leaf(key, "params/embed/embedding",
                         (cfg["vocab_size"], d))
    scale = weights.leaf(key, "params/final_norm/scale", (d,))
    return head(x, table, scale, cfg, quant)


_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "intermediate_size",
         "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
         "attn_layer_period", "attn_layer_offset", "rms_norm_eps")


def _padded(tokens, pad_to):
    """The sequence padded at its end to a multiple of ``pad_to`` (fewer
    shapes to compile): under the causal mask and a scan that runs
    forward, padding cannot reach the real positions."""
    n = len(tokens)
    size = -(-n // pad_to) * pad_to
    return jnp.zeros((size,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))


def logits(tokens, seed: int, cfg: dict, quant=_exact, pad_to=None):
    """Logits [len(tokens), vocab] of one sequence, float32, with the
    weights made from ``seed`` (and the configuration's
    ``serve_weight_map`` applied to them: ``weight_map``)."""
    n = len(tokens)
    if pad_to is None:
        pad_to = QUERY_BLOCK if n <= QUERY_BLOCK else PAD_TO
    ids = _padded(tokens, pad_to)
    key = weights.key_for(seed)
    ck = tuple((k, cfg[k]) for k in _KEYS)
    with jax.default_matmul_precision("highest"):
        x = _embed(ids, key, cfg_key=ck)
        for i in range(cfg["num_hidden_layers"]):
            kind = kind_of(cfg, i)
            x = _layer(x, _layer_weights(key, cfg, i, kind), cfg_key=ck,
                       kind=kind, quant=quant)
        return _head_from_seed(x, key, cfg_key=ck, quant=quant)[:n]


def logits_of(tokens, leaves: dict, cfg: dict, quant=_exact):
    """The same forward pass over given weights (``leaves``: the
    '/'-joined paths of :func:`param_shapes` -> arrays), unpadded."""
    with jax.default_matmul_precision("highest"):
        x = leaves["params/embed/embedding"][jnp.asarray(tokens, jnp.int32)]
        for i in range(cfg["num_hidden_layers"]):
            kind = kind_of(cfg, i)
            w = {name: leaves[f"params/layer_{i}/{name}"]
                 for name in layer_shapes(cfg, kind)}
            x = layer(x, w, cfg, kind, quant)
        return head(x, leaves["params/embed/embedding"],
                    leaves["params/final_norm/scale"], cfg, quant)
