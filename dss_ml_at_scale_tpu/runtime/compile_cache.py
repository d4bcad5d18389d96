"""Where JAX's persistent compilation cache lives: one rule, one place.

The cache's directory is part of its key, so it must not move between
runs. Whoever places the program decides it from outside through
``JAX_COMPILATION_CACHE_DIR`` (JAX reads the variable itself; nothing is
set in code then). Otherwise it is ``<checkout>/.jax_cache``, computed
from where this package sits — never a temp dir, a pid or a time.

Nothing else in the tree sets ``jax_compilation_cache_dir``
(``tests/test_compile_cache.py`` holds that).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Switch the persistent cache on; return its directory.

    Call before the first compile, after any ``jax_platforms`` update.
    Returns None, and changes nothing, when the platform is configured
    to ``cpu`` (the tier-1 suite and every forced-CPU child): a cached
    CPU executable saves little and is tied to the host's CPU features.
    Reading ``jax_platforms`` initialises no backend.
    """
    import jax

    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return None
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
