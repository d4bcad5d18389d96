"""The served-model seam (models/served.py): what the three config-built
LMs share, held to each of them at the tiny configs their own tests use.

- ``build_served`` builds the class each ``model_type`` names (none is
  the latent-attention expert model's) and refuses any other, as ``dsst
  serve-lm --model-config`` does;
- ``serving_variables(release=True)`` puts each float leaf at the
  model's ``_width`` and deletes every original it replaced;
- ``cache_bytes_by_kind`` accounts for every byte of the arena;
- the mixin adds no dataclass field, so a model's hash and equality, as
  the serving programs' static argument, are over its own fields.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from dss_ml_at_scale_tpu.config.cli import main
from dss_ml_at_scale_tpu.models.jamba import JambaLM
from dss_ml_at_scale_tpu.models.mla_moe import MlaMoeLM
from dss_ml_at_scale_tpu.models.nemotron_h import NemotronHLM
from dss_ml_at_scale_tpu.models.served import ServedLM, build_served
from dss_ml_at_scale_tpu.serving.lm import engine, kvcache
from test_jamba_lm import CFG as JAMBA_CFG
from test_mla_moe import CFG as MLA_MOE_CFG
from test_nemotron_h import CFG as NEMOTRON_H_CFG

FAMILIES = {
    "mla_moe": (MlaMoeLM, MLA_MOE_CFG),
    "jamba": (JambaLM, JAMBA_CFG),
    "nemotron_h": (NemotronHLM, NEMOTRON_H_CFG),
}


def model_of(family, dtype=jnp.bfloat16):
    cls, cfg = FAMILIES[family]
    return cls.from_config(cfg, attention="reference", dtype=dtype)


def paths_and_leaves(tree):
    return [("/".join(str(getattr(k, "key", k)) for k in path), leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)]


@pytest.mark.parametrize("model_type, family", [
    ("mistral4", "mla_moe"), (None, "mla_moe"), ("jamba", "jamba"),
    ("nemotron_h", "nemotron_h")])
def test_each_model_type_builds_the_class_it_names(model_type, family):
    cls, cfg = FAMILIES[family]
    arch = {k: v for k, v in cfg.items() if k != "model_type"}
    if model_type is not None:
        arch["model_type"] = model_type
    model = build_served(arch, attention="reference")
    assert type(model) is cls
    assert model == cls.from_config(cfg, attention="reference")
    assert model.dtype == jnp.bfloat16


def test_an_unknown_model_type_is_refused_with_the_known_ones():
    arch = {**MLA_MOE_CFG, "model_type": "deepseek_v3"}
    with pytest.raises(ValueError, match="deepseek_v3") as err:
        build_served(arch, attention="reference")
    for known in ("mistral4", "jamba", "nemotron_h"):
        assert known in str(err.value)


def test_serve_lm_prints_the_refusal_and_exits_1(tmp_path, capsys):
    path = tmp_path / "arch.json"
    path.write_text(json.dumps({**MLA_MOE_CFG, "model_type": "llama"}))
    assert main(["serve-lm", "--model-config", str(path), "--port", "0",
                 "--slots", "2", "--max-len", "48",
                 "--prefill-buckets", "8,16"]) == 1
    out = capsys.readouterr().out
    assert "model_type 'llama' is not served here" in out
    assert "nemotron_h" in out


@pytest.mark.parametrize("family", FAMILIES)
def test_release_frees_each_wide_leaf_once_it_is_cast(family):
    """A float32 tree served at bfloat16 with ``release``: every leaf
    comes back at the model's ``_width`` (the experts stacked where the
    model holds them), and an original is deleted exactly where it was
    replaced."""
    model = model_of(family)
    variables = model_of(family, jnp.float32).init(jax.random.key(0))
    wide = jax.tree_util.tree_leaves(variables)
    served = model.serving_variables(variables, release=True)
    kept = {id(a) for a in jax.tree_util.tree_leaves(served)}
    for leaf in wide:
        assert leaf.is_deleted() != (id(leaf) in kept)
    for path, leaf in paths_and_leaves(served):
        assert leaf.dtype == model._width(path), path
    assert {str(leaf.dtype) for _, leaf in paths_and_leaves(served)} \
        == {"bfloat16", "float32"}


@pytest.mark.parametrize("family", FAMILIES)
def test_cache_bytes_by_kind_sum_to_the_arenas_bytes(family):
    model = model_of(family)
    arena = kvcache.make_arena(model, 3, 32)
    split = model.cache_bytes_by_kind(arena)
    assert set(split) == set(model.cache_kinds.values())
    assert all(n > 0 for n in split.values())
    assert sum(split.values()) == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(arena))
    assert engine.cache_bytes_by_kind(model, arena) == split


@pytest.mark.parametrize("family", FAMILIES)
def test_the_mixin_adds_no_field(family):
    cls, _ = FAMILIES[family]
    assert issubclass(cls, ServedLM)
    assert not dataclasses.is_dataclass(ServedLM)
    assert [f.name for f in dataclasses.fields(cls)] \
        == list(cls.__annotations__)
    assert hash(model_of(family)) == hash(model_of(family))
    assert model_of(family) != model_of(family, jnp.float32)
