"""The Pallas kernels compile for the real chip, at the real widths.

The TPU's compiler is installed with jaxlib and compiles for a chip that
is described, not attached: what it refuses here (a block not aligned to
the tiling, more fast memory than a kernel may use) it refuses on the
chip, at no chip time. Shapes are ``chip_smoke.py``'s ``kernels`` phase.

Kept in ONE file, the topology described inside a module-scoped fixture
and nowhere at import time: only one process may hold libtpu, and under
pytest-xdist only the worker that is given this file loads it. Compiles
run in the test's own process, with the persistent compilation cache off
around them (a described-topology executable can be written to it but
never read back).
"""

import os

import pytest

FLASH_SHAPE = (8, 8, 2048, 128)  # batch, heads, seq, head_dim (chip_smoke's)
DECODE_SLAB = (16, 16, 2048, 128)  # slots, heads, max_len, head_dim (chat cell)
BN_BATCH = 212
BN_STAGES = [(56, 64, 256), (28, 128, 512), (14, 256, 1024), (7, 512, 2048)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, *shapes):
    import jax

    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_compiles_for_v5e(one_chip, no_compile_cache,
                                          causal, grad):
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct(FLASH_SHAPE, jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)

    fn = jax.value_and_grad(loss, argnums=(0, 1, 2)) if grad else fwd
    _compile(fn, x, x, x)


@pytest.mark.parametrize("seq", [128, 256, 100, 250])
def test_flash_attention_prefill_lengths_compile(one_chip, no_compile_cache,
                                                 seq):
    """The serve-lm prefill buckets (128, 256), and raw prompt lengths
    (100, 250) whose whole-sequence block is not a multiple of the
    (16, 128) bf16 tile."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((1, 8, seq, 128), jnp.bfloat16,
                             sharding=one_chip)
    _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        x, x, x,
    )


def test_flash_attention_compiles_at_the_document_cells_prefill(
        one_chip, no_compile_cache):
    """32 heads of 64 + 64 = 128 over the largest bucket of the
    latent-attention model's cell (8,192): the expanded path's call."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        x, x, x,
    )


@pytest.mark.parametrize("hw,k,n", BN_STAGES)
@pytest.mark.parametrize("with_res", [False, True], ids=["plain", "residual"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_bn_relu_matmul_compiles_for_v5e(one_chip, no_compile_cache,
                                         hw, k, n, with_res, grad):
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.ops.fused_matmul import bn_relu_matmul

    y = jax.ShapeDtypeStruct((BN_BATCH, hw, hw, k), jnp.bfloat16,
                             sharding=one_chip)
    vec = jax.ShapeDtypeStruct((k,), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((1, 1, k, n), jnp.bfloat16, sharding=one_chip)

    def fwd(y, gamma, beta, mean, var, w, *res):
        return bn_relu_matmul(y, gamma, beta, mean, var, w,
                              residual=res[0] if res else None,
                              interpret=False)

    def loss(*a):
        return jnp.sum(fwd(*a).astype(jnp.float32) ** 2)

    args = (y, vec, vec, vec, vec, w) + ((y,) if with_res else ())
    diff = tuple(i for i in range(len(args)) if i not in (3, 4))
    fn = jax.value_and_grad(loss, argnums=diff) if grad else fwd
    _compile(fn, *args)


def test_interpret_mode_is_refused_on_a_tpu_backend(monkeypatch):
    """One rule, one place: ``interpret=None`` means interpret on a CPU
    backend only, and interpret mode on ``tpu`` is an error."""
    import jax

    from dss_ml_at_scale_tpu.ops import _pallas

    assert _pallas.resolve_interpret(None) is True  # the suite's backend
    assert _pallas.resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _pallas.resolve_interpret(None) is False
    assert _pallas.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret mode"):
        _pallas.resolve_interpret(True)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_decode_attention_compiles_at_the_chat_cells_arena(
        one_chip, no_compile_cache, dtype):
    """One layer's k and v slabs as the serving cell holds them, one query
    a slot, a per-slot ``pos``: the kernel form, compiled (the function
    itself would take interpret mode under this CPU backend)."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.ops import decode_attention as da

    assert da.tiles(DECODE_SLAB, dtype)
    slots, heads, _, head_dim = DECODE_SLAB
    q = jax.ShapeDtypeStruct((slots, heads, head_dim), dtype,
                             sharding=one_chip)
    slab = jax.ShapeDtypeStruct(DECODE_SLAB, dtype, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda q, k, v, pos: da._blockwise(q, k, v, pos, interpret=False),
        q, slab, slab, pos,
    )
    # No copy of a slab, no widened twin: the kernel reads the arena.
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_slot_decode_keeps_the_arena_in_place_around_the_kernel(
        one_chip, no_compile_cache, monkeypatch):
    """The whole decode program with the kernel in it: the donated arena
    is aliased to the returned one, rows scattered in place, no slab
    copied on the way into the custom call."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.models import TransformerLM
    from dss_ml_at_scale_tpu.ops import decode_attention as da
    from dss_ml_at_scale_tpu.serving.lm import kvcache

    monkeypatch.setattr(da, "resolve_interpret", lambda _: False)
    slots, max_len = 8, 4 * da.BLOCK
    model = TransformerLM(vocab_size=512, dim=256, num_heads=2,
                          num_layers=2, max_seq=max_len)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    variables = described(jax.eval_shape(
        lambda: model.serving_variables(model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))))
    arena = described(jax.eval_shape(
        lambda: kvcache.make_arena(model, slots, max_len)))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        kvcache.slot_decode, static_argnums=0, donate_argnums=(3,)
    ).lower(model, variables, vec, arena, vec, vec).compile()
    assert compiled.as_text().count("tpu_custom_call") >= model.num_layers
    slabs = [a.size * a.dtype.itemsize
             for a in jax.tree_util.tree_leaves(arena)]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(slabs)
    assert memory.temp_size_in_bytes < min(slabs)
