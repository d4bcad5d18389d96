"""Weights from the seed: one rule per kind of leaf, one jitted call.

The benchmark makes the weights, not the program: the driver hands them
to the program's entry point and the plain reference makes the same
arrays again from the same seed, so neither takes anything the other
made.  A leaf's value depends on the seed, its path and its shape only,
so the reference can make one layer at a time.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def salt(path: str) -> int:
    """The number that sets a leaf's stream apart: a hash of its path."""
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


def leaf(key: jax.Array, path: str, shape: tuple, salt_value=None,
         overrides: dict | None = None) -> jax.Array:
    """The float32 leaf at ``path`` ('/'-joined, as the program names it).
    ``salt_value`` may be a traced ``salt(path)``, so that one compiled
    program makes the same kind of leaf for any layer.  ``overrides``
    (a configuration's ``init_overrides``) maps a path's ending to
    ``{"uniform": [lo, hi]}`` and wins over the rules below."""
    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(
        key, salt(path) if salt_value is None else salt_value)
    for ending, rule in (overrides or {}).items():
        if path.endswith(ending):
            lo, hi = rule["uniform"]
            return jax.random.uniform(k, shape, jnp.float32, lo, hi)
    if name == "mean":                      # BatchNorm running mean
        return jnp.zeros(shape, jnp.float32)
    if name == "var":                       # BatchNorm running variance
        return jnp.ones(shape, jnp.float32)
    if name == "scale":                     # norm gains, away from 0 and 1
        return jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5)
    if name == "bias":
        return 0.05 * jax.random.normal(k, shape, jnp.float32)
    if name in ("embedding", "pos_embed"):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)
    if name == "kernel" and len(shape) == 4:  # conv HWIO, He fan-in
        fan_in = shape[0] * shape[1] * shape[2]
        return jax.random.normal(k, shape, jnp.float32) * (2.0 / fan_in) ** 0.5
    if name == "kernel" and len(shape) == 2:  # dense [in, out]
        return jax.random.normal(k, shape, jnp.float32) * shape[0] ** -0.5
    raise ValueError(f"no rule for leaf {path!r} of shape {shape}")


def make(shapes: dict[str, tuple], seed: int, *, sharding=None,
         overrides: dict | None = None) -> dict:
    """Every leaf of ``shapes`` (path -> shape) in one jitted call."""
    items = tuple(sorted(shapes.items()))

    def build(key):
        return {p: leaf(key, p, tuple(s), overrides=overrides)
                for p, s in items}

    return jax.jit(build, out_shardings=sharding)(key_for(seed))


def nest(flat: dict) -> dict:
    """'a/b/c' -> value  becomes  {'a': {'b': {'c': value}}}."""
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
    return tree


def flatten(tree, prefix: str = "") -> dict:
    """The inverse of :func:`nest` for dicts of dicts (flax trees)."""
    out = {}
    for name, value in tree.items():
        path = f"{prefix}/{name}" if prefix else str(name)
        if hasattr(value, "items"):
            out.update(flatten(value, path))
        else:
            out[path] = value
    return out
