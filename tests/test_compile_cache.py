"""``runtime.enable_compile_cache``: the cache directory is placed from
outside (``JAX_COMPILATION_CACHE_DIR``) or is the fixed in-checkout
path, and nothing else in the tree sets it."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_PROBE = """
import json, os, sys
sys.path.insert(0, %r)
import jax
updates = []
real = jax.config.update
def spy(name, value):
    updates.append([name, str(value)])
    return real(name, value)
jax.config.update = spy
from dss_ml_at_scale_tpu.runtime import enable_compile_cache
returned = enable_compile_cache()
print(json.dumps({"returned": returned, "updates": updates,
                  "configured": jax.config.jax_compilation_cache_dir}))
""" % str(REPO)


def _probe(env_extra: dict, platforms: str = "") -> dict:
    """Run the function in a fresh process; no backend is initialised."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = platforms
    env.update(env_extra)
    proc = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd="/")
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_var_set_means_no_update_in_code(tmp_path):
    placed = str(tmp_path / "placed")
    out = _probe({"JAX_COMPILATION_CACHE_DIR": placed})
    assert out["returned"] == placed
    assert out["updates"] == []            # JAX reads the variable itself
    assert out["configured"] == placed


def test_unset_means_the_fixed_checkout_path_in_every_process():
    a, b = _probe({}), _probe({})
    want = str(REPO / ".jax_cache")
    assert a["returned"] == b["returned"] == want
    assert a["updates"] == [["jax_compilation_cache_dir", want]]
    assert a["configured"] == want


@pytest.mark.parametrize("env", [{}, {"JAX_COMPILATION_CACHE_DIR": "/x"}],
                         ids=["unset", "placed"])
def test_no_op_when_the_platform_is_configured_to_cpu(env):
    """The tier-1 suite calls ``config.cli.main`` in-process on the CPU:
    the cache must stay off there."""
    out = _probe(env, platforms="cpu")
    assert out["returned"] is None and out["updates"] == []


def test_suite_runs_with_the_cache_off():
    import jax

    from dss_ml_at_scale_tpu.runtime import enable_compile_cache

    assert enable_compile_cache() is None
    assert not jax.config.jax_compilation_cache_dir


def test_nothing_else_sets_the_cache_directory():
    """grep-style: one file in the tree may name the config key in a
    ``config.update`` call — runtime/compile_cache.py."""
    pat = re.compile(
        r"""(config\.update\(\s*['"]jax_compilation_cache_dir"""
        r"""|set_cache_dir\(|initialize_cache\()"""
    )
    allowed = {"dss_ml_at_scale_tpu/runtime/compile_cache.py"}
    listed = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard",
         "*.py", "*.sh"],
        cwd=REPO, capture_output=True, text=True,
    )
    files = listed.stdout.split() if listed.returncode == 0 else [
        str(p.relative_to(REPO)) for p in REPO.rglob("*.py")
        if ".jax_cache" not in p.parts
    ]
    offenders = []
    for rel in files:
        path = REPO / rel
        if rel in allowed or rel == "tests/test_compile_cache.py":
            continue
        if path.is_file() and pat.search(path.read_text(errors="replace")):
            offenders.append(rel)
    assert offenders == []
