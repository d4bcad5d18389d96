"""The seam where a config-built model plugs into the LM server.

``MlaMoeLM`` (:mod:`.mla_moe`), ``JambaLM`` (:mod:`.jamba`) and
``NemotronHLM`` (:mod:`.nemotron_h`) are frozen dataclasses over a plain
tree of arrays, built from a published ``config.json``. What they share
is here, once:

- ``_cast`` and ``_mm``, the casts and products every layer is made of;
- the width rule (``width``): a leaf is multiplied in float32 where the
  end of its path is one of the model's ``float32_endings``, else at the
  model's ``dtype``; and the tree cast (``cast_tree``) that puts each
  leaf at its width once, releasing the wider original on request;
- the stacking of each layer's held experts (``stack_experts``);
- ``ServedLM``, the mixin that gives each model ``from_config``,
  ``init``, ``serving_variables``, ``cache_bytes_by_kind``, its norm,
  embedding and untied head, and the grouped-query attention of the
  hybrids' decode step;
- ``build_served``, the one place that maps ``model_type`` to a class.

The mixin has no dataclass field: the models are static arguments of the
serving programs, so their hash and equality stay the dataclass's, over
their own fields. A model says what differs by class attributes (which
leaves are float32, what its cache slabs hold, where its experts sit)
and by ``_init_leaf`` for the leaves it draws its own way.
"""

from __future__ import annotations

import importlib
import json
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .routing import stack_held
from .transformer import rms_norm

# model_type -> (module, class), imported when built: a server imports
# no model it does not build. A config without model_type is the
# latent-attention expert model's.
_MODEL_TYPES = {
    "mistral4": ("mla_moe", "MlaMoeLM"),
    "jamba": ("jamba", "JambaLM"),
    "nemotron_h": ("nemotron_h", "NemotronHLM"),
}


def build_served(arch: dict, *, attention: str, dtype: Any = jnp.bfloat16):
    """The served model an architecture file's object describes, by its
    ``model_type``; ValueError for one not served here."""
    model_type = arch.get("model_type", "mistral4")
    if model_type not in _MODEL_TYPES:
        raise ValueError(f"model_type {model_type!r} is not served here "
                         f"(known: {', '.join(_MODEL_TYPES)})")
    module, name = _MODEL_TYPES[model_type]
    cls = getattr(importlib.import_module(f".{module}", __package__), name)
    return cls.from_config(arch, attention=attention, dtype=dtype)


def _cast(x, dtype):
    """``x`` at ``dtype``; ``x`` itself where it is there already (no
    same-width convert in the traced program)."""
    return x if x.dtype == jnp.dtype(dtype) else x.astype(dtype)


def _mm(a, b, out_dtype):
    """``a @ b`` at the operands' width with float32 accumulation."""
    return _cast(jnp.matmul(a, _cast(b, a.dtype),
                            preferred_element_type=jnp.float32), out_dtype)


def width(path: str, wide_endings, dtype):
    """The width the leaf at ``path`` ('/'-joined) is multiplied in:
    float32 where its path ends in one of ``wide_endings`` (tuples of
    names), else ``dtype``."""
    names = tuple(path.split("/"))
    wide = any(names[-len(end):] == end for end in wide_endings)
    return jnp.float32 if wide else jnp.dtype(dtype)


def cast_tree(variables, width_of, *, release: bool = False):
    """``variables`` with each float leaf at ``width_of(path)``, cast once
    here, leaf by leaf; a leaf at its width already, or not a float,
    comes back as the array it was (a host leaf placed as it is). With
    ``release`` each original is deleted from the device as soon as its
    cast copy exists (the caller hands its tree over and reads it no
    more), so the two trees are never alive together."""

    def at_width(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        want = width_of(name)
        if not jnp.issubdtype(leaf.dtype, jnp.floating) \
                or leaf.dtype == want:
            return jnp.asarray(leaf)
        narrow = jnp.asarray(leaf, want)
        if release and isinstance(leaf, jax.Array):
            narrow.block_until_ready()
            leaf.delete()
        return narrow

    return jax.tree_util.tree_map_with_path(at_width, variables)


def stack_experts(params, num_layers: int, held: int, scope: str,
                  matrices, release: bool):
    """``params`` with each layer's held experts, ``{scope}/expert_{e}/
    {matrix}/kernel`` (``scope`` "" for the layer itself), as one
    ``[held, ...]`` leaf a matrix, ``{scope}/experts/{matrix}``
    (:func:`.routing.stack_held`; with ``release`` one layer's experts
    are held twice at most). A layer without them, an expert layer's
    stacked already or one of another kind, stays as it is."""
    params = dict(params)
    for i in range(num_layers):
        layer = params[f"layer_{i}"]
        node = layer.get(scope, {}) if scope else layer
        if "expert_0" not in node:
            continue
        node = dict(node)
        parts = [node.pop(f"expert_{e}") for e in range(held)]
        node["experts"] = {
            name: stack_held([p[name]["kernel"] for p in parts], release)
            for name in matrices}
        params[f"layer_{i}"] = {**layer, scope: node} if scope else node
    return params


class ServedLM:
    """What a config-built served model shares; no fields of its own.

    A model sets, as class attributes:

    - ``float32_endings``: the leaves multiplied in float32 whatever its
      ``dtype``, by the end of their path;
    - ``cache_kinds``: each cache slab's name -> the kind of state
      ``cache_bytes_by_kind`` counts it under;
    - ``expert_matrices`` and ``expert_scope``, where it holds experts:
      each expert's matrix names and their layer's sub-path;
    - ``embed_name``: the embedding table's leaf.

    and writes ``_from_keys`` (the architecture's keys -> the model),
    ``variable_shapes()`` and the decoder's programs; ``_init_leaf``
    where it draws a leaf its own way, ``__post_init__`` (calling this
    one) where it checks more.
    """

    expert_matrices = ()
    expert_scope = ""
    embed_name = "embed"

    @classmethod
    def from_config(cls, config: dict | str, *, attention: str = "flash",
                    dtype: Any = jnp.bfloat16):
        """From an architecture file, a path or its object (see the
        model's ``_from_keys`` for the keys it reads)."""
        if not isinstance(config, dict):
            with open(config) as f:
                config = json.load(f)
        return cls._from_keys(config, attention=attention, dtype=dtype)

    def __post_init__(self):
        if self.attention not in ("flash", "reference"):
            raise ValueError(f"unknown attention backend {self.attention!r}")
        if self.expert_matrices:
            if self.expert_offset + self.n_routed_experts > self.router_width:
                raise ValueError(
                    f"experts [{self.expert_offset}, "
                    f"{self.expert_offset + self.n_routed_experts}) lie past "
                    f"the router's {self.router_width}")
            if self.num_experts_per_tok > self.router_width:
                raise ValueError("more experts a token than the router has")

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def n_stats(self) -> int:
        """An expert model's stats: ``[held, absent, touched, per held
        expert...]``."""
        return 3 + self.n_routed_experts

    # -- weights -------------------------------------------------------

    def _width(self, path: str):
        return width(path, self.float32_endings, self.dtype)

    def _init_leaf(self, path: str, shape, key):
        """A leaf drawn in float32: unit gains, ``0.02`` normal
        embeddings, normal ``1/sqrt(fan-in)`` otherwise."""
        if path.endswith("scale"):
            return jnp.ones(shape, jnp.float32)
        std = 0.02 if path.endswith("embedding") else shape[0] ** -0.5
        return std * jax.random.normal(key, shape, jnp.float32)

    def init(self, key) -> dict:
        """Random weights (there is no checkpoint format for these
        families yet), leaf ``n`` of ``variable_shapes()`` drawn from
        ``fold_in(key, n)`` by ``_init_leaf`` and held at the width it
        is served in."""
        tree: dict = {}
        for n, (path, shape) in enumerate(self.variable_shapes().items()):
            leaf = self._init_leaf(path, shape, jax.random.fold_in(key, n))
            node = tree
            *parents, last = path.split("/")
            for p in parents:
                node = node.setdefault(p, {})
            node[last] = jnp.asarray(leaf, self._width(path))
        return tree

    def serving_variables(self, variables, *, release: bool = False):
        """``variables`` with each leaf at the width the model multiplies
        it in (:func:`cast_tree`) and, where the model holds experts,
        each layer's stacked (:func:`stack_experts`), the tree every
        program reads; a tree stacked already stays as it is. With
        ``release`` each original is deleted from the device as soon as
        what replaces it exists, so the two trees are never alive
        together."""
        tree = cast_tree(variables, self._width, release=release)
        if not self.expert_matrices:
            return tree
        params = stack_experts(tree["params"], self.num_layers,
                               self.n_routed_experts, self.expert_scope,
                               self.expert_matrices, release)
        return {**tree, "params": params}

    def cache_bytes_by_kind(self, cache) -> dict:
        """Bytes of ``cache`` by the kind of state (``cache_kinds``)."""
        out = dict.fromkeys(self.cache_kinds.values(), 0)
        for slabs in cache:
            for name, slab in slabs.items():
                out[self.cache_kinds[name]] += slab.nbytes
        return out

    # -- pieces of a layer ---------------------------------------------

    def _norm(self, x, scale):
        return _cast(rms_norm(x, scale, self.norm_eps), self.dtype)

    def _embed(self, params, ids):
        with jax.named_scope("embed"):
            return _cast(params[self.embed_name]["embedding"][ids],
                         self.dtype)

    def _head(self, x, params):
        """The final norm, then the untied ``lm_head``: float32."""
        with jax.named_scope("lm_head"):
            hid = self._norm(x, params["final_norm"]["scale"])
            return _mm(hid, params["lm_head"]["kernel"], jnp.float32)

    def _chunk_logits(self, x, params, start, n_real):
        """The float32 logits row ``[1, vocab]`` of the last REAL token of
        a chunk's rows ``x`` ``[C, d]`` at ``start`` where the chunk holds
        it, zeros where it does not (no head is computed)."""
        c, last = x.shape[0], n_real - 1 - start
        return lax.cond(
            (last >= 0) & (last < c),
            lambda x: self._head(
                lax.dynamic_slice_in_dim(x, jnp.clip(last, 0, c - 1), 1,
                                         axis=0), params),
            lambda x: jnp.zeros((1, self.vocab_size), jnp.float32), x)

    # -- grouped-query attention (the hybrids') ------------------------

    def _qkv(self, x, w, norm_leaf: str):
        """``[T, heads, head]`` q and ``[T, kv_heads, head]`` k, v of the
        rows ``x`` normed by ``w[norm_leaf]``."""
        t, hd = x.shape[0], self.head_dim
        hid = self._norm(x, w[norm_leaf]["scale"])
        a = w["attn"]
        q = _mm(hid, a["q"]["kernel"], self.dtype).reshape(t, -1, hd)
        k = _mm(hid, a["k"]["kernel"], self.dtype).reshape(t, -1, hd)
        v = _mm(hid, a["v"]["kernel"], self.dtype).reshape(t, -1, hd)
        return q, k, v

    def _attn_decode(self, x, w, slabs, pos, norm_leaf: str):
        """One query a slot over that slot's rows ``0..pos`` (its new row
        written at ``pos`` first), by a masked einsum over the slab. The
        slabs are the left operand of both products, read in the layout
        they are stored in. Returns ``[S, d]`` and the slabs."""
        s, h, hd = x.shape[0], self.num_attention_heads, self.head_dim
        kvh = self.num_key_value_heads
        with jax.named_scope("attn"):
            q, k, v = self._qkv(x, w, norm_leaf)
            # A write at or past max_len clamps into the slot's own last
            # row: no slot can reach another's.
            slabs = {name: jax.vmap(
                lambda slab, r, p: lax.dynamic_update_slice(
                    slab, r[:, None, :], (np.int32(0), p, np.int32(0)))
            )(slabs[name], rows, pos)
                for name, rows in (("k", k), ("v", v))}
            q = q.reshape(s, kvh, h // kvh, hd)
            scores = jnp.einsum("skld,skgd->sklg", slabs["k"], q,
                                preferred_element_type=jnp.float32)
            length = slabs["k"].shape[2]
            live = jnp.arange(length, dtype=jnp.int32)[None, :] \
                <= pos[:, None]
            scores = jnp.where(live[:, None, :, None],
                               scores * np.float32(hd ** -0.5),
                               np.float32(-1e30))
            probs = _cast(jax.nn.softmax(scores, axis=2), self.dtype)
            out = jnp.einsum("skld,sklg->skgd", slabs["v"], probs,
                             preferred_element_type=jnp.float32)
            out = _cast(out, self.dtype).reshape(s, h * hd)
            return _mm(out, w["attn"]["o"]["kernel"], self.dtype), slabs
