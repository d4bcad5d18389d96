"""The LM decoder holds each weight at the width the model multiplies it
in (``models.transformer.serving_variables``, called once by
``TransformerDecoder``): the same rounding made once instead of inside
every program, so every logit is bitwise what the float32 tree gave.
"""

from __future__ import annotations

import gc
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dss_ml_at_scale_tpu import telemetry
from dss_ml_at_scale_tpu.models import TransformerLM
from dss_ml_at_scale_tpu.models.transformer import (
    generate,
    serving_variables,
)
from dss_ml_at_scale_tpu.serving.lm import (
    LMConfig,
    LMEngine,
    TransformerDecoder,
    kvcache,
)
from dss_ml_at_scale_tpu.telemetry import catalog

SLOTS, MAX_LEN, BUCKETS = 3, 48, (8, 16)
# The ends of the paths the model consumes in float32, as the tests see
# them: stated here a second time, on purpose (the bitwise cases below
# are what hold either statement to the model).
FLOAT32_ENDS = (("scale",), ("lm_head", "kernel"), ("router", "kernel"))


def _model(ffn="dense", dtype=jnp.bfloat16):
    return TransformerLM(
        vocab_size=64, dim=32, num_heads=4, num_layers=2, max_seq=64,
        dtype=dtype, attention="reference", ffn=ffn,
        num_experts=4 if ffn == "moe" else 0)


def _init(model):
    """``model.init`` with every leaf drawn afresh: gains of exactly 1 and
    biases of 0 round to themselves, and would hide a leaf cast that
    should not be."""
    tree = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        jax.random.uniform(k, a.shape, a.dtype, 0.5, 1.5) if a.ndim == 1
        else a + 0.02 * jax.random.normal(k, a.shape, a.dtype)
        for k, a in zip(keys, leaves)])


def _leaves(tree):
    return {
        tuple(str(k.key) for k in path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def _kept_float32(path):
    return any(path[-len(end):] == end for end in FLOAT32_ENDS)


@pytest.fixture(scope="module", params=["dense", "moe"])
def trees(request):
    model = _model(request.param)
    wide = _init(model)
    return model, wide, serving_variables(model, wide)


def _slot_decode(model, variables):
    rng = np.random.default_rng(3)
    arena = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        kvcache.make_arena(model, SLOTS, MAX_LEN))
    tokens = jnp.asarray(rng.integers(1, 64, SLOTS), jnp.int32)
    pos = jnp.asarray([5, 0, 17], jnp.int32)
    logits, _, _, _ = jax.jit(kvcache.slot_decode, static_argnums=0)(
        model, variables, tokens, arena, pos)
    return logits


def _prefill(bucket):
    def run(model, variables):
        tokens = jnp.asarray(
            np.random.default_rng(bucket).integers(1, 64, (1, bucket)),
            jnp.int32)
        logits, _, _ = jax.jit(kvcache.prefill_bucket, static_argnums=0)(
            model, variables, tokens, kvcache.make_arena(model, 1, MAX_LEN))
        return logits
    return run


# -- (a) the same work: logits bitwise the float32 tree's -------------------


@pytest.mark.parametrize("program", [
    pytest.param(_slot_decode, id="slot_decode"),
    pytest.param(_prefill(8), id="prefill_8"),
    pytest.param(_prefill(16), id="prefill_16"),
])
def test_logits_bitwise_equal_to_the_float32_trees(trees, program):
    model, wide, held = trees
    want = np.asarray(program(model, wide))
    got = np.asarray(program(model, held))
    assert want.dtype == got.dtype == np.float32
    assert np.isfinite(want).all() and np.ptp(want) > 0
    np.testing.assert_array_equal(got, want)


# -- (b) the dtype map ------------------------------------------------------


def test_each_leaf_sits_at_the_width_it_is_multiplied_in(trees):
    model, wide, held = trees
    wide, held = _leaves(wide), _leaves(held)
    assert wide.keys() == held.keys()
    kept = [p for p in held if _kept_float32(p)]
    # Both norms of each block and the final one, and the head.
    assert sum(p[-1] == "scale" for p in kept) == 2 * model.num_layers + 1
    assert ("params", "lm_head", "kernel") in kept
    if model.ffn == "moe":
        assert sum(p[-2:] == ("router", "kernel") for p in kept) == 2
    for path, leaf in held.items():
        if path in kept:
            assert leaf.dtype == jnp.float32, path
            assert leaf is wide[path], path
        else:
            assert leaf.dtype == model.dtype, path
            assert leaf.shape == wide[path].shape, path


# -- (c) a float32 model: the identity --------------------------------------


def test_a_float32_model_gets_its_own_arrays_back():
    model = _model(dtype=jnp.float32)
    wide = _init(model)
    held = _leaves(serving_variables(model, wide))
    for path, leaf in _leaves(wide).items():
        assert held[path] is leaf, path


# -- (d) host leaves --------------------------------------------------------


def test_host_numpy_leaves_are_placed_at_the_narrow_width():
    model = _model()
    wide = _init(model)
    on_host = jax.tree_util.tree_map(np.asarray, wide)
    held = _leaves(serving_variables(model, on_host))
    want = _leaves(serving_variables(model, wide))
    for path, leaf in held.items():
        assert isinstance(leaf, jax.Array), path
        assert leaf.dtype == want[path].dtype, path
        np.testing.assert_array_equal(
            np.asarray(leaf.astype(jnp.float32)),
            np.asarray(want[path].astype(jnp.float32)))


# -- (e) the decoder lets go of what it cast --------------------------------


def test_the_decoder_holds_no_reference_to_a_cast_leafs_original():
    model = _model()
    variables = _init(model)
    originals = {path: weakref.ref(leaf)
                 for path, leaf in _leaves(variables).items()}
    decoder = TransformerDecoder(model, variables, slots=SLOTS,
                                 max_len=MAX_LEN, buckets=BUCKETS)
    del variables
    gc.collect()
    for path, ref in originals.items():
        if _kept_float32(path):
            assert ref() is _leaves(decoder.variables)[path], path
        else:
            assert ref() is None, path


# -- (f) the program the decoder lowers -------------------------------------


def test_the_lowered_slot_decode_takes_no_wide_kernel_or_embedding():
    model = _model()
    wide = _init(model)
    decoder = TransformerDecoder(model, wide, slots=SLOTS, max_len=MAX_LEN,
                                 buckets=BUCKETS)
    # Every block kernel and both embedding tables are matrices.
    cast_shapes = {leaf.shape for path, leaf in _leaves(wide).items()
                   if leaf.ndim == 2 and not _kept_float32(path)}
    assert (model.vocab_size, model.dim) in cast_shapes      # tok_embed
    assert (model.dim, 3 * model.dim) in cast_shapes         # qkv
    vec = jnp.zeros(SLOTS, jnp.int32)
    lowered = decoder._step_fn.lower(
        model, decoder.variables, vec, decoder._arena, vec, vec)
    args = jax.tree_util.tree_leaves(lowered.in_avals)
    float32_args = [a for a in args if a.dtype == jnp.float32]
    # What stays float32: the norms' scales and the head, nothing else.
    assert len(float32_args) == 2 * model.num_layers + 2
    for a in float32_args:
        assert a.shape not in cast_shapes, a
    text = lowered.as_text()
    sig = text[text.index("@main("):].split("\n", 1)[0]
    assert f"tensor<{model.vocab_size}x{model.dim}xf32>" not in sig
    assert f"tensor<{model.dim}x{3 * model.dim}xf32>" not in sig


# -- (g) a churned engine over the bfloat16 model ---------------------------


def _collect(gen, timeout=60.0):
    tokens, deadline = [], time.monotonic() + timeout
    while True:
        event = gen.next_event(timeout=max(0.1, deadline - time.monotonic()))
        if event[0] == "token":
            tokens.append(event[1])
        else:
            return tokens, event


def test_a_churned_engine_streams_what_generate_gives_the_float32_tree():
    model = _model()
    wide = _init(model)
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(1, 64, int(n))) for n in (3, 7, 11, 5, 14)]
    n_new = 6
    expected = []
    for prompt in prompts:
        out = generate(model, wide, jnp.asarray([prompt], jnp.int32), n_new)
        expected.append([int(t) for t in np.asarray(out)[0, len(prompt):]])
    engine = LMEngine(
        TransformerDecoder(model, wide, slots=SLOTS, max_len=MAX_LEN,
                           buckets=BUCKETS),
        LMConfig(slots=SLOTS, max_len=MAX_LEN, prefill_buckets=BUCKETS),
    ).start()
    try:
        gens = []
        for prompt in prompts:
            gens.append(engine.submit(prompt, n_new))
            time.sleep(0.02)
        for want, gen in zip(expected, gens):
            tokens, terminal = _collect(gen)
            assert terminal == ("done", "max_tokens")
            assert tokens == want
    finally:
        engine.drain(10.0)


# -- (h) the gauge that says it engaged -------------------------------------


def test_lm_weights_bytes_is_set_by_dtype_and_in_the_catalog():
    assert catalog.KNOWN_METRICS["lm_weights_bytes"] == "gauge"
    telemetry.reset()
    model = _model()
    wide = _init(model)
    decoder = TransformerDecoder(model, wide, slots=SLOTS, max_len=MAX_LEN,
                                 buckets=BUCKETS)
    read = {m["labels"]["dtype"]: m["value"]
            for m in telemetry.snapshot()["metrics"]
            if m["name"] == "lm_weights_bytes"}
    held = _leaves(decoder.variables)
    want = {"bfloat16": 0, "float32": 0}
    for leaf in held.values():
        want[str(leaf.dtype)] += leaf.nbytes
    assert read == want
    wide_bytes = sum(leaf.nbytes for leaf in _leaves(wide).values())
    assert 2 * read["bfloat16"] + read["float32"] == wide_bytes
