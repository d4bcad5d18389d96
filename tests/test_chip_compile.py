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


# (heads, bucket): the document cell's latent-attention prefill (32 heads of
# 64 + 64 = 128) and the chat cell's (16 heads of 128).
SERVING_BUCKETS = ([(32, s) for s in (2048, 4096, 8192)]
                   + [(16, s) for s in (128, 256, 512, 1024)])


@pytest.mark.parametrize("heads,seq", SERVING_BUCKETS)
def test_flash_attention_compiles_at_the_serving_buckets(
        one_chip, no_compile_cache, heads, seq):
    """The causal kernel at each serving cell's prefill buckets, with the
    blocks the shape rule picks and the plan built from them."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.ops.flash_attention import (
        block_plan,
        blocks,
        flash_attention,
    )

    block_q, block_k = blocks(seq, seq)
    assert block_k == min(1024 if seq >= 4096 else 512, seq)
    assert seq % block_q == 0
    plan = block_plan(seq, seq, block_q, block_k, causal=True)
    assert plan.masked <= plan.visited <= plan.grid
    x = jax.ShapeDtypeStruct((1, heads, seq, 128), jnp.bfloat16,
                             sharding=one_chip)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        x, x, x,
    )
    # the benchmark's reader takes the first bf16[a,b,c] of the kernel's
    # HLO text as [heads, seq, d]: the output, before the int32 tables
    call = next(line for line in compiled.as_text().splitlines()
                if "tpu_custom_call" in line and "custom-call(" in line)
    assert f"bf16[{heads},{seq},128]" in call.split("custom-call(")[0]


@pytest.mark.parametrize("keys", [4096, 6144, 8192])
def test_flash_attention_compiles_at_the_document_cells_chunks(
        one_chip, no_compile_cache, keys):
    """The document cell's prefill in chunks of 2,048: a chunk's queries
    over its prefix and itself, the mask bottom-right; the benchmark's
    reader finds the call's query length first in its HLO line."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 32, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 32, keys, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        q, kv, kv,
    )
    call = next(line for line in compiled.as_text().splitlines()
                if "tpu_custom_call" in line and "custom-call(" in line)
    assert "bf16[32,2048,128]" in call.split("custom-call(")[0]


@pytest.mark.parametrize("positions", [256, 512, 1024, 2048, 100])
def test_selective_scan_compiles_at_the_reason_cells_buckets(
        one_chip, no_compile_cache, positions):
    """The prefill's scan at Jamba2-3B's widths (5,120 channels, 16
    states) at each bucket of the reasoning cell, and a length that is no
    multiple of the chunk; the op's output shape is what the benchmark's
    reader parses from the kernel's HLO text."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.ops.selective_scan import blocks, selective_scan

    chunk, _ = blocks(positions, 5120)
    rows = -(-positions // chunk) * chunk

    def shaped(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda u, dt, a, b, c, d, n: selective_scan(
            u, dt, a, b, c, d, n, interpret=False),
        shaped(positions, 5120), shaped(positions, 5120), shaped(16, 5120),
        shaped(positions, 16), shaped(positions, 16), shaped(5120),
        shaped(dtype=jnp.int32),
    )
    call = next(line for line in compiled.as_text().splitlines()
                if "tpu_custom_call" in line and "custom-call(" in line)
    assert "ssm_scan" in call.split(" = ")[0]
    assert f"f32[{rows},5120]" in call.split("custom-call(")[0]


@pytest.mark.parametrize("positions", [1024, 100])
def test_ssd_scan_compiles_at_the_agent_cells_chunk(
        one_chip, no_compile_cache, positions):
    """The prefill's SSD kernel at Nemotron-3-Super's widths (128 heads
    of 64, 8 groups of a 128 state) over a chunk of the agent cell, and a
    length that is no multiple of the block; the op's first output shape
    is what the benchmark's reader parses from the kernel's HLO text."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.ops.ssd_scan import ssd_scan

    rows = -(-positions // 128) * 128

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = _compile(
        lambda x, dt, a, b, c, d, s: ssd_scan(x, dt, a, b, c, d, s,
                                              interpret=False),
        shaped(positions, 128, 64), shaped(positions, 128), shaped(128),
        shaped(positions, 8, 128), shaped(positions, 8, 128), shaped(128),
        shaped(128, 64, 128),
    )
    call = next(line for line in compiled.as_text().splitlines()
                if "tpu_custom_call" in line and "custom-call(" in line)
    assert "ssd_scan" in call.split(" = ")[0]
    assert f"f32[128,64,{rows}]" in call.split("custom-call(")[0]


@pytest.mark.parametrize("rows", [32, 1024])
def test_grouped_experts_compile_at_the_agent_cells_widths(
        one_chip, no_compile_cache, rows):
    """The expert layer's grouped kernel at Nemotron-3-Super's widths (64
    held experts of 1,024 -> 2,688 -> 1,024, top-22) over the decode
    step's 32 rows and a chunk's 1,024: each expert's two matrices one
    block, the rows and the output resident, the sorted assignments'
    token indices and weights in SMEM. The op is named ``moe_experts``,
    which is what the benchmark's reader selects."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.ops.grouped_experts import grouped_experts

    def shaped(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16, i32 = jnp.bfloat16, jnp.int32
    compiled = _compile(
        lambda u, up, down, c, s, o, w: grouped_experts(
            u, up, down, c, s, o, w, k=22, interpret=False),
        shaped(bf16, rows, 1024), shaped(bf16, 64, 1024, 2688),
        shaped(bf16, 64, 2688, 1024), shaped(i32, 64), shaped(i32, 64),
        shaped(i32, rows * 22), shaped(jnp.float32, rows * 22),
    )
    call = next(line for line in compiled.as_text().splitlines()
                if "tpu_custom_call" in line and "custom-call(" in line)
    assert "moe_experts" in call.split(" = ")[0]


@pytest.mark.parametrize("rows", [32, 2048, 8192])
def test_gated_experts_compile_at_the_document_cells_widths(
        one_chip, no_compile_cache, rows):
    """The expert layer's gated kernel at Mistral-Small-4's widths (16
    held experts of 4,096 -> 2,048 -> 4,096, top-4) over the decode
    step's 32 rows, a chunk's 2,048 and a whole 8,192-token prompt: the
    weights in blocks of F, the rows fetched and written back a row a
    DMA, the visits' token codes and weights in SMEM. The op is named
    ``moe_experts``."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.models.routing import Routing
    from dss_ml_at_scale_tpu.ops.grouped_experts import gated_experts

    def shaped(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16, i32 = jnp.bfloat16, jnp.int32
    compiled = _compile(
        lambda x, gate, up, down, c, s, o, w, e: gated_experts(
            x, gate, up, down, Routing(w, o, c, s, None, e), k=4,
            interpret=False),
        shaped(bf16, rows, 4096), shaped(bf16, 16, 4096, 2048),
        shaped(bf16, 16, 4096, 2048), shaped(bf16, 16, 2048, 4096),
        shaped(i32, 16), shaped(i32, 16), shaped(i32, rows * 4),
        shaped(jnp.float32, rows * 4), shaped(i32, rows * 4),
    )
    call = next(line for line in compiled.as_text().splitlines()
                if "tpu_custom_call" in line and "custom-call(" in line)
    assert "moe_experts" in call.split(" = ")[0]


@pytest.mark.parametrize("program", ["decode", "last_chunk"])
def test_the_document_cells_programs_compile_whole(one_chip, no_compile_cache,
                                                   monkeypatch, program):
    """The document cell's own programs at its sizes, all six layers: the
    decode step over 32 slots of 9,216 positions and the last chunk of
    an 8,192-token prompt (start 6,144): one ``moe_experts`` call a
    layer and no loop over the experts. The donated cache comes back
    aliased, and what the program needs beside its arguments stays
    small."""
    import importlib
    import json
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.models.mla_moe import MlaMoeLM
    from dss_ml_at_scale_tpu.serving.lm import kvcache

    for name in ("flash_attention", "grouped_experts"):
        monkeypatch.setattr(
            importlib.import_module(f"dss_ml_at_scale_tpu.ops.{name}"),
            "resolve_interpret", lambda _: False)
    cfg = json.loads((Path(__file__).resolve().parents[1] / "perfbench"
                      / "configs" / "mistral_small_4_119b_ep8.json"
                      ).read_text())
    model = MlaMoeLM.from_config(cfg)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    variables = described(jax.eval_shape(
        lambda: model.serving_variables(model.init(jax.random.key(0)))))
    if program == "decode":
        cache = described(jax.eval_shape(
            lambda: kvcache.make_arena(model, 32, 9216)))
        vec = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
        compiled = jax.jit(
            kvcache.slot_decode, static_argnums=0, donate_argnums=(3,)
        ).lower(model, variables, vec, cache, vec, vec).compile()
    else:
        cache = described(jax.eval_shape(
            lambda: kvcache.make_arena(model, 1, 9216)))
        tokens = jax.ShapeDtypeStruct((1, 2048), jnp.int32,
                                      sharding=one_chip)
        n_real = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        compiled = jax.jit(
            kvcache.prefill_bucket, static_argnums=0,
            static_argnames=("start",), donate_argnums=(3,)
        ).lower(model, variables, tokens, cache, n_real,
                start=6144).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line
             and line.split(" = ")[0].strip().lstrip("%").startswith(
                 "moe_experts")]
    assert len(calls) == cfg["num_layers"]
    # the loops left are the decode step's cache-row scatters
    assert not [line for line in text.splitlines()
                if " while(" in line and "moe_experts" in line]
    slabs = sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(cache))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slabs
    assert memory.temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("program", ["decode", "last_chunk"])
def test_the_agent_cells_programs_compile_whole(one_chip, no_compile_cache,
                                                monkeypatch, program):
    """The agent cell's own programs at its sizes, all eleven layers:
    the decode step over 32 slots of 9,216 positions, and the last chunk
    of an 8,192-token prompt (start 7,168, the flash and SSD kernels in
    it). The donated cache comes back aliased, and what the program needs
    beside its arguments stays small."""
    import importlib
    import json
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.models.nemotron_h import NemotronHLM
    from dss_ml_at_scale_tpu.serving.lm import kvcache

    for name in ("flash_attention", "grouped_experts", "ssd_scan"):
        monkeypatch.setattr(
            importlib.import_module(f"dss_ml_at_scale_tpu.ops.{name}"),
            "resolve_interpret", lambda _: False)
    cfg = json.loads((Path(__file__).resolve().parents[1] / "perfbench"
                      / "configs" / "nemotron3_super_120b_ep8.json"
                      ).read_text())
    model = NemotronHLM.from_config(cfg)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    variables = described(jax.eval_shape(
        lambda: model.serving_variables(model.init(jax.random.key(0)))))
    if program == "decode":
        cache = described(jax.eval_shape(
            lambda: kvcache.make_arena(model, 32, 9216)))
        vec = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
        compiled = jax.jit(
            kvcache.slot_decode, static_argnums=0, donate_argnums=(3,)
        ).lower(model, variables, vec, cache, vec, vec).compile()
    else:
        cache = described(jax.eval_shape(
            lambda: kvcache.make_arena(model, 1, 9216)))
        tokens = jax.ShapeDtypeStruct((1, 1024), jnp.int32,
                                      sharding=one_chip)
        n_real = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        compiled = jax.jit(
            kvcache.prefill_bucket, static_argnums=0,
            static_argnames=("start",), donate_argnums=(3,)
        ).lower(model, variables, tokens, cache, n_real,
                start=7168).compile()
        assert "ssd_scan" in compiled.as_text()
    assert "moe_experts" in compiled.as_text()
    slabs = sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(cache))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slabs
    assert memory.temp_size_in_bytes < 1 << 30


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
