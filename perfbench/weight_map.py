"""A configuration's ``serve_weight_map``: affine maps of leaves that the
benchmark's weight rules draw (``weights.leaf``), for a family whose
leaves need another range than those rules give.

    "serve_weight_map": {"mamba/dt_proj/bias": [shift, factor], ...}

takes every leaf whose path ends so to ``shift + factor * value``.  The
family's adapter applies it to the tree the driver made, before the
program sees it, and the family's reference to each leaf it makes from
the seed, so the two still take nothing the other made.  The serve driver
passes no ``init_overrides`` to ``weights.make`` (the train driver does);
where it does, such a map gives way to them.
"""

from __future__ import annotations


def maps(cfg: dict) -> tuple:
    """The configuration's map as a hashable ``((ending, shift, factor),
    ...)``; empty where it has none."""
    return tuple((ending, float(shift), float(factor)) for ending,
                 (shift, factor) in sorted(cfg.get("serve_weight_map",
                                                   {}).items()))


def mapped(path: str, value, rules: tuple):
    """``value`` of the leaf at ``path`` under ``rules`` (from
    :func:`maps`)."""
    for ending, shift, factor in rules:
        if path.endswith(ending):
            return shift + factor * value
    return value


def apply(tree: dict, rules: tuple, prefix: str = "") -> dict:
    """A nested tree of leaves with each mapped leaf replaced."""
    out = {}
    for name, value in tree.items():
        path = f"{prefix}/{name}" if prefix else name
        out[name] = (apply(value, rules, path) if isinstance(value, dict)
                     else mapped(path, value, rules))
    return out
