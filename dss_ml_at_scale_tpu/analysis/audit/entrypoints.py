"""The audited entrypoint registry: the package's REAL compiled programs.

Every builder here constructs the production callable through the same
factory production uses (``parallel.trainer.make_train_step``,
``config.checkpoints.make_scorer``, ``models.transformer.decode_step``,
the fused-op public entries, the vmapped SARIMAX fitter) over tiny
abstract inputs placed with the production sharding machinery
(``runtime.mesh.get_batch_placer``) on the 8-device audit mesh. The
audit then certifies the lowered IR of exactly these programs — an
entrypoint that only exists in a test twin would certify nothing.

Adding an entrypoint: write a ``build(mesh) -> ProgramSpec`` here and
add it to :data:`_BUILDERS`; the first ``dsst audit`` run will report
it ``unbaselined`` until ``--update-baseline --reason`` pins its
program hash and cost budgets into ``AUDIT_BASELINE.json``.

Suppressions live HERE, next to the entrypoint they silence, with a
mandatory reason — the IR-tier analogue of ``# dsst: ignore[rule]``.

Shapes are tiny on purpose: the audit reasons about program STRUCTURE
(aliasing, collectives, dtypes, cost ratios), which is shape-stable,
and tier-1 compiles every entrypoint on CPU — structure must stay
cheap to certify.
"""

from __future__ import annotations

from typing import Callable, Mapping

from .core import ProgramSpec

# -- shared tiny-input helpers ------------------------------------------------


def _replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def _place_batch(mesh, batch):
    """Production placement path: the SAME cached placer the feeder
    uses (leading dim sharded over "data", scalars replicated)."""
    from ...runtime.mesh import get_batch_placer

    return get_batch_placer(mesh)(batch)


def _classifier_task():
    import jax.numpy as jnp
    import optax

    from ...models.resnet import ResNet, ResNetBlock
    from ...parallel.trainer import ClassifierTask

    model = ResNet(
        stage_sizes=[1, 1], block_cls=ResNetBlock, num_classes=4,
        num_filters=8, dtype=jnp.float32,
    )
    return ClassifierTask(model=model, tx=optax.adam(1e-3))


def _classifier_state_and_batch(mesh, task):
    import jax
    import numpy as np

    batch = {
        "image": np.zeros((16, 16, 16, 3), np.float32),
        "label": np.zeros((16,), np.int32),
    }
    state = task.init_state(jax.random.key(0), batch)
    replicated = _replicated(mesh)
    shardings = jax.tree_util.tree_map(lambda _: replicated, state)
    state = jax.device_put(state, shardings)
    return state, shardings, _place_batch(mesh, batch), replicated


def _lm_task():
    import jax.numpy as jnp
    import optax

    from ...models.transformer import TransformerLM
    from ...parallel.trainer import LMTask

    model = TransformerLM(
        vocab_size=64, dim=32, num_heads=4, num_layers=2, max_seq=64,
        dtype=jnp.float32, attention="reference",
    )
    return LMTask(model=model, tx=optax.adam(1e-3))


# -- trainer steps ------------------------------------------------------------


def train_step_classifier(mesh) -> ProgramSpec:
    from ...parallel.trainer import make_train_step

    task = _classifier_task()
    state, shardings, batch, replicated = _classifier_state_and_batch(
        mesh, task
    )
    return ProgramSpec(
        name="train_step.classifier",
        fn=task.train_step,
        args=(state, batch),
        jit_kwargs={
            "donate_argnums": 0,
            "out_shardings": (shardings, replicated),
        },
        jitted=make_train_step(task, shardings, replicated),
        expect_donated=(0,),
    )


def train_step_classifier_health(mesh) -> ProgramSpec:
    """The health-supervised variant: commit-or-discard fused into the
    one jitted program — audited separately because its carry (state,
    HealthState) and its select-laden jaxpr are a different program."""
    import jax
    import jax.numpy as jnp

    from ...parallel.trainer import health_state_shardings, make_train_step
    from ...resilience import health

    task = _classifier_task()
    state, shardings, batch, replicated = _classifier_state_and_batch(
        mesh, task
    )
    cfg = health.HealthConfig()
    h_shardings = health_state_shardings(replicated)
    hstate = jax.device_put(health.HealthState.create(), h_shardings)
    inject = jax.device_put(jnp.zeros((), jnp.int32), replicated)
    return ProgramSpec(
        name="train_step.classifier.health",
        fn=health.guard_train_step(task.train_step, cfg),
        args=((state, hstate), batch, inject),
        jit_kwargs={
            "donate_argnums": 0,
            "out_shardings": ((shardings, h_shardings), replicated),
        },
        jitted=make_train_step(task, shardings, replicated, health_cfg=cfg),
        expect_donated=(0,),
    )


def eval_step_classifier(mesh) -> ProgramSpec:
    from ...parallel.trainer import make_eval_step

    task = _classifier_task()
    state, _shardings, batch, replicated = _classifier_state_and_batch(
        mesh, task
    )
    return ProgramSpec(
        name="eval_step.classifier",
        fn=task.eval_step,
        args=(state, batch),
        jit_kwargs={"out_shardings": replicated},
        jitted=make_eval_step(task, replicated),
    )


def train_step_lm(mesh) -> ProgramSpec:
    import jax
    import numpy as np

    from ...parallel.trainer import make_train_step

    task = _lm_task()
    batch = {"tokens": np.zeros((16, 32), np.int32)}
    state = task.init_state(jax.random.key(0), batch)
    replicated = _replicated(mesh)
    shardings = jax.tree_util.tree_map(lambda _: replicated, state)
    state = jax.device_put(state, shardings)
    return ProgramSpec(
        name="train_step.lm",
        fn=task.train_step,
        args=(state, _place_batch(mesh, batch)),
        jit_kwargs={
            "donate_argnums": 0,
            "out_shardings": (shardings, replicated),
        },
        jitted=make_train_step(task, shardings, replicated),
        expect_donated=(0,),
    )


def train_step_pipelined_lm(mesh) -> ProgramSpec:
    """Pipeline-parallel LM step on a {"pipe": 4, "data": 2} view of
    the same 8 devices — the stage ring's ppermute traffic is the
    collective pattern this entrypoint pins."""
    import jax
    import numpy as np

    from ...models.pipelined_lm import PipelinedLM, PipelinedLMTask
    from ...parallel.trainer import make_train_step
    from ...runtime.mesh import make_mesh

    pipe_mesh = make_mesh(
        {"pipe": 4, "data": 2}, devices=list(mesh.devices.flat)
    )
    model = PipelinedLM(
        vocab_size=64, dim=32, num_heads=4, mesh=pipe_mesh,
        max_seq=32, dtype=np.float32,
    )
    task = PipelinedLMTask(model=model)
    # [n_micro, micro_batch, seq] — the pipeline's microbatch layout.
    batch = {"tokens": np.zeros((4, 4, 16), np.int32)}
    state = task.init_state(jax.random.key(0), batch)
    shardings = task.state_shardings(state, pipe_mesh)
    state = jax.device_put(state, shardings)
    replicated = _replicated(pipe_mesh)
    return ProgramSpec(
        name="train_step.pipelined_lm",
        fn=task.train_step,
        args=(state, jax.device_put(batch, replicated)),
        jit_kwargs={
            "donate_argnums": 0,
            "out_shardings": (shardings, replicated),
        },
        jitted=make_train_step(task, shardings, replicated),
        expect_donated=(0,),
        # The ring schedule IS cross-chip activation movement; permits
        # stay at the rule default (collective-permute gets headroom).
    )


# -- LM decode + serving score ------------------------------------------------


def decode_step_lm(mesh) -> ProgramSpec:
    import jax
    import jax.numpy as jnp

    from ...models.transformer import decode_step, init_kv_cache

    task = _lm_task()
    model = task.model
    variables = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )
    replicated = _replicated(mesh)
    cache = jax.device_put(init_kv_cache(model, 8), replicated)
    variables = jax.device_put(variables, replicated)
    tokens = jax.device_put(jnp.zeros((8, 1), jnp.int32), replicated)
    pos = jax.device_put(jnp.zeros((), jnp.int32), replicated)
    return ProgramSpec(
        name="decode_step.lm",
        fn=decode_step,
        args=(model, variables, tokens, cache, pos),
        # out_shardings pinned: with committed inputs and UNSPECIFIED
        # outputs jax silently drops the cache aliasing (found by this
        # very rule) — the serving decode loop must pin its layouts.
        jit_kwargs={
            "static_argnums": 0,
            "donate_argnums": (3,),
            "out_shardings": replicated,
        },
        expect_donated=(3,),
    )


# -- the served LMs -----------------------------------------------------------

# The tiny config-built served models, at the dtype ``dsst serve-lm
# --model-config`` serves (bfloat16, the classes' default): family ->
# (module.class under ``models``, constructor arguments).
_TINY_SERVED = {
    # Latent attention, holding experts 2..5 of the 8 its router scores.
    "mla_moe": ("mla_moe.MlaMoeLM", dict(
        vocab_size=128, hidden_size=64, num_layers=2,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
        moe_intermediate_size=32, n_routed_experts=4, router_width=8,
        expert_offset=2, num_experts_per_tok=2, rope_factor=4.0,
        original_max_position_embeddings=16, mscale_all_dim=1.0,
        llama_4_scaling_beta=0.1)),
    # One period of four layers, the third attention (one key/value head
    # under four query heads), the others Mamba.
    "jamba": ("jamba.JambaLM", dict(
        vocab_size=128, hidden_size=64, num_layers=4,
        num_attention_heads=4, num_key_value_heads=1, intermediate_size=96,
        d_state=16, d_conv=4, expand=2, dt_rank=8, attn_layer_period=4,
        attn_layer_offset=2)),
    # The pattern MEM*E: two Mamba-2 mixers of 8 heads in 2 groups, two
    # expert layers holding experts 4..11 of the 16 their sigmoid router
    # scores, attention of 4 query heads over 2 key/value heads.
    "nemotron_h": ("nemotron_h.NemotronHLM", dict(
        vocab_size=128, hidden_size=64, pattern="MEM*E",
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
        n_groups=2, moe_intermediate_size=24, moe_latent_size=32,
        moe_shared_expert_intermediate_size=48, n_routed_experts=8,
        router_width=16, expert_offset=4, num_experts_per_tok=4,
        routed_scaling_factor=2.5, chunk_size=8)),
}

# Each served model's prefill of a 16-token bucket: name -> (family, the
# count of real tokens, an argument: None for the LM's, which takes the
# whole bucket; the start, static, where the decoder prefills the model
# a chunk at a time: the second chunk, over the rows the first wrote).
_PREFILLS = {
    "prefill.lm": ("lm", None, None),
    "prefill.mla_moe": ("mla_moe", 9, None),
    "prefill_chunk.mla_moe": ("mla_moe", 25, 16),
    "prefill.jamba": ("jamba", 9, None),
    "prefill.nemotron_h": ("nemotron_h", 25, 16),
}


def _served(mesh, family):
    """The audit's served ``family`` (``lm``, the chat model at the
    dtype ``dsst serve-lm`` builds, bfloat16, or one of
    :data:`_TINY_SERVED`) and the tree its ``TransformerDecoder`` holds:
    each leaf at the width the model multiplies it in, each layer's held
    experts stacked, through the ``serving_variables`` the decoder calls
    — so the audited arguments are the served ones."""
    import importlib

    import jax
    import jax.numpy as jnp

    if family == "lm":
        model = _lm_task().model.clone(dtype=jnp.bfloat16)
        variables = model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )
    else:
        path, kwargs = _TINY_SERVED[family]
        module, name = path.rsplit(".", 1)
        cls = getattr(importlib.import_module(
            f"...models.{module}", __package__), name)
        model = cls(**kwargs, attention="reference")
        variables = model.init(jax.random.key(0))
    return model, jax.device_put(
        model.serving_variables(variables), _replicated(mesh)
    )


def _served_jit_kwargs(mesh, static_argnums=0):
    # out_shardings pinned: with committed inputs and UNSPECIFIED
    # outputs jax silently drops the arena aliasing (as decode_step.lm).
    return {
        "static_argnums": static_argnums,
        "donate_argnums": (3,),
        "out_shardings": _replicated(mesh),
    }


def _slot_decode(family):
    """The continuous-batching serving step of ``family``: one batched
    decode over the slot arena with a PER-SLOT position vector, as the
    engine calls it (the tokens the previous step's ids merged on the
    device with the host's overrides, the greedy ids back beside the
    logits). The donation pin is the point: the engine holds ONE live
    arena for the life of the server, and this certifies that every
    step aliases it in place (zero per-token cache copies)."""

    def build(mesh) -> ProgramSpec:
        import jax
        import jax.numpy as jnp

        from ...serving.lm import kvcache

        model, variables = _served(mesh, family)
        replicated = _replicated(mesh)
        arena = jax.device_put(kvcache.make_arena(model, 4, 32), replicated)
        zeros = jax.device_put(jnp.zeros((4,), jnp.int32), replicated)
        return ProgramSpec(
            name=f"slot_decode.{family}",
            fn=kvcache.slot_decode,
            args=(model, variables, zeros, arena, zeros, zeros),
            jit_kwargs=_served_jit_kwargs(mesh),
            expect_donated=(3,),
        )

    return build


def _prefill(name):
    """One bucketed prefill (``_PREFILLS[name]``) into a donated
    one-slot cache the engine recycles across admissions; one logits row
    comes back. The production jit names ``start`` static
    (``static_argnames``); here it is the sixth argument, static by
    number, which lowers the same program."""
    family, n_real, start = _PREFILLS[name]

    def build(mesh) -> ProgramSpec:
        import jax
        import jax.numpy as jnp

        from ...serving.lm import kvcache

        model, variables = _served(mesh, family)
        replicated = _replicated(mesh)
        cache = jax.device_put(kvcache.make_arena(model, 1, 32), replicated)
        tokens = jax.device_put(jnp.zeros((1, 16), jnp.int32), replicated)
        args = (model, variables, tokens, cache)
        if n_real is not None:
            args += (jax.device_put(jnp.int32(n_real), replicated),)
        if start is not None:
            args += (start,)
        return ProgramSpec(
            name=name,
            fn=kvcache.prefill_bucket,
            args=args,
            jit_kwargs=_served_jit_kwargs(
                mesh, 0 if start is None else (0, 5)),
            expect_donated=(3,),
        )

    return build


def serving_score(mesh) -> ProgramSpec:
    import jax
    import numpy as np

    from ...config.checkpoints import make_scorer

    task = _classifier_task()
    variables = task.model.init(
        jax.random.key(0), np.zeros((1, 16, 16, 3), np.float32),
        train=False,
    )
    scorer = make_scorer(task, variables)
    images = _place_batch(
        mesh, {"image": np.zeros((16, 16, 16, 3), np.float32)}
    )["image"]
    return ProgramSpec(
        name="serving.score",
        fn=scorer,
        args=(images,),
        jitted=scorer,
    )


# -- fused ops ----------------------------------------------------------------


def fused_matmul_grad(mesh) -> ProgramSpec:
    """bn_relu_matmul forward+backward, REPLICATED on the audit mesh:
    the Pallas kernel has no GSPMD partitioning story yet (ROADMAP item
    1 — compiled multi-chip is refused by the model integration), so
    the audit pins the single-logical-device program; when partitioning
    lands this entrypoint gets sharded inputs and the baseline reopens
    by construction."""
    import jax
    import jax.numpy as jnp

    from ...ops.fused_matmul import bn_relu_matmul

    def fwd_loss(y, gamma, beta, mean, var, w):
        return bn_relu_matmul(y, gamma, beta, mean, var, w).sum()

    grad = jax.value_and_grad(fwd_loss, argnums=(0, 1, 2, 5))
    replicated = _replicated(mesh)
    k = 128
    args = jax.device_put(
        (
            jnp.zeros((512, k), jnp.float32),
            jnp.ones((k,), jnp.float32),
            jnp.zeros((k,), jnp.float32),
            jnp.zeros((k,), jnp.float32),
            jnp.ones((k,), jnp.float32),
            jnp.zeros((k, k), jnp.float32),
        ),
        replicated,
    )
    return ProgramSpec(
        name="ops.fused_matmul.grad",
        fn=grad,
        args=args,
    )


def fused_norm_grad(mesh) -> ProgramSpec:
    import jax
    import jax.numpy as jnp

    from ...ops.fused_norm import bn_act

    def fwd_loss(x, scale, bias):
        out, _mean, _var = bn_act(x, scale, bias, relu=True)
        return out.sum()

    grad = jax.value_and_grad(fwd_loss, argnums=(0, 1, 2))
    replicated = _replicated(mesh)
    args = jax.device_put(
        (
            jnp.zeros((256, 64), jnp.float32),
            jnp.ones((64,), jnp.float32),
            jnp.zeros((64,), jnp.float32),
        ),
        replicated,
    )
    return ProgramSpec(
        name="ops.fused_norm.grad",
        fn=grad,
        args=args,
    )


def flash_attention_grad(mesh) -> ProgramSpec:
    import jax
    import jax.numpy as jnp

    from ...ops.flash_attention import flash_attention

    def fwd_loss(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    grad = jax.value_and_grad(fwd_loss, argnums=(0, 1, 2))
    replicated = _replicated(mesh)
    shape = (2, 2, 128, 32)  # [b, heads, seq, head_dim]
    args = jax.device_put(
        tuple(jnp.zeros(shape, jnp.float32) for _ in range(3)), replicated
    )
    return ProgramSpec(
        name="ops.flash_attention.grad",
        fn=grad,
        args=args,
    )


# -- batched SARIMAX fitter ---------------------------------------------------


def sarimax_batched_fit(mesh) -> ProgramSpec:
    """The grid-fused group-fit chunk: one launch, 32 groups x the full
    8-order grid of the reduced bench bounds, fit-tune-scored with the
    per-group argmin reduced on device — the paper's
    one-launch-vs-many-tasks thesis as production ships it.

    Built through the SAME factory the workload driver launches
    (``parallel.group_apply.make_grid_fit``) at the `dsst bench`
    ``group_fit`` geometry (``workloads.forecasting.GROUP_FIT_BENCH_*``),
    so the audited IR, the pinned FLOPs budget, and the bench scenario's
    measured launches describe identical XLA. The demand panel (arg 0)
    is donated and must alias the predictions output; a surprise
    collective would mean the groups are not actually independent in
    the lowered program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ...ops.sarimax import grid_orders
    from ...parallel.group_apply import make_grid_fit
    from ...workloads.forecasting import (
        GROUP_FIT_BENCH_CFG,
        GROUP_FIT_BENCH_GROUPS,
        GROUP_FIT_BENCH_HORIZON,
        GROUP_FIT_BENCH_WEEKS,
    )

    cfg = GROUP_FIT_BENCH_CFG
    g, t = GROUP_FIT_BENCH_GROUPS, GROUP_FIT_BENCH_WEEKS
    groups = NamedSharding(mesh, P("data"))
    replicated = _replicated(mesh)
    jitted = make_grid_fit(cfg, select="mse", mesh=mesh,
                           axis_name="data", donate=True)
    args = (
        jax.device_put(jnp.zeros((g, t), jnp.float32), groups),
        jax.device_put(
            jnp.zeros((g, t, cfg.k_exog), jnp.float32), groups
        ),
        jax.device_put(
            jnp.full((g,), t - GROUP_FIT_BENCH_HORIZON, jnp.int32),
            groups,
        ),
        jax.device_put(jnp.full((g,), t, jnp.int32), groups),
        jax.device_put(jnp.asarray(grid_orders(cfg)), replicated),
    )
    return ProgramSpec(
        name="sarimax.batched_fit",
        fn=jitted,
        args=args,
        jit_kwargs={"donate_argnums": (0,)},
        jitted=jitted,
        expect_donated=(0,),
    )


# -- the registry -------------------------------------------------------------

_BUILDERS: dict[str, Callable] = {
    "train_step.classifier": train_step_classifier,
    "train_step.classifier.health": train_step_classifier_health,
    "eval_step.classifier": eval_step_classifier,
    "train_step.lm": train_step_lm,
    "train_step.pipelined_lm": train_step_pipelined_lm,
    "decode_step.lm": decode_step_lm,
    **{f"slot_decode.{family}": _slot_decode(family)
       for family in ("lm", *_TINY_SERVED)},
    **{name: _prefill(name) for name in _PREFILLS},
    "serving.score": serving_score,
    "ops.fused_matmul.grad": fused_matmul_grad,
    "ops.fused_norm.grad": fused_norm_grad,
    "ops.flash_attention.grad": flash_attention_grad,
    "sarimax.batched_fit": sarimax_batched_fit,
}


def builders() -> Mapping[str, Callable]:
    return dict(_BUILDERS)


def entrypoint_names() -> list[str]:
    return sorted(_BUILDERS)
