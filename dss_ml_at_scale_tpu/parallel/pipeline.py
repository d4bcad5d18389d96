"""Pipeline parallelism: GPipe-style SPMD microbatch pipeline over a mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.3 — its only
gradient parallelism is DDP data parallel), but the framework's sharding
layer is mesh-based precisely so every parallelism family falls out of
the same mechanism. This module adds the PP column: N sequential stages
laid out over a ``"pipe"`` mesh axis, microbatches streamed through with
one ``lax.ppermute`` hop per tick riding the ICI ring.

Design (the standard TPU SPMD pipeline schedule):

- Stage parameters are *stacked* on a leading stage dimension and sharded
  over the pipe axis — device i holds only stage i's weights. There is no
  per-stage program: every device runs the SAME jitted computation
  (SPMD), applying its resident stage to whatever activation is currently
  in flight on it.
- A scan over ``n_micro + n_stages - 1`` ticks drives the schedule.
  Each tick: device 0 ingests the next microbatch, every device applies
  its stage, the last device banks its finished microbatch, and all
  activations shift one hop along the ring (``ppermute``). The first
  ``n_stages - 1`` ticks are the classic GPipe bubble: utilization is
  ``n_micro / (n_micro + n_stages - 1)``, so callers pick
  ``n_micro >> n_stages``.
- The whole schedule is reverse-differentiable: ``ppermute``'s transpose
  is the reverse ppermute, so ``jax.grad`` through the pipeline yields
  the 1F1B-style backward sweep automatically — gradients visit stages
  in reverse order over the same ring, with XLA overlapping the hop with
  each stage's backward matmuls. Each stage application is wrapped in
  ``jax.checkpoint`` so the backward pass rematerializes stage compute
  instead of storing every tick's activations.

``spmd_pipeline`` is deliberately functional — ``stage_fn(params, x)``
is any jittable per-stage function (a Flax ``Module.apply`` bound to
stacked params, a bare matmul, a transformer block) — and composes with
data parallelism via ``batch_axis``: on a ``{"pipe": P, "data": D}``
mesh the within-microbatch batch dimension is sharded over "data", so
each of the D columns pipelines its own batch shard (PP × DP).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "PipelinedTask",
    "check_same_mesh",
    "moment_sharding",
    "pipeline_utilization",
    "spmd_pipeline",
    "stack_stage_params",
    "stage_sharding",
]


def check_same_mesh(task_mesh: Mesh, mesh: Mesh, what: str) -> None:
    """Require ``mesh`` to be the mesh a pipeline schedule was built on.

    A distinct mesh with equal axis sizes but a different device order
    would pass a shape-only check and then silently place state on one
    device assignment while ``shard_map`` executes over another —
    per-step resharding single-host, wrong placement multi-host. Equal
    axis names AND an identical device array are both required.
    """
    import numpy as np

    if mesh is task_mesh:
        return
    if dict(mesh.shape) != dict(task_mesh.shape) or not np.array_equal(
        mesh.devices, task_mesh.devices
    ):
        raise ValueError(
            f"Trainer mesh {dict(mesh.shape)} (devices "
            f"{mesh.devices.ravel().tolist()}) != {what} mesh "
            f"{dict(task_mesh.shape)} (devices "
            f"{task_mesh.devices.ravel().tolist()}); construct the task "
            "with the Trainer's mesh"
        )


def stack_stage_params(init_fn: Callable[[jax.Array], Any], rng: jax.Array,
                       n_stages: int):
    """Initialize ``n_stages`` independent stage params, stacked on axis 0.

    ``init_fn(rng) -> pytree`` initializes ONE stage; the result's leaves
    gain a leading ``[n_stages, ...]`` dimension, ready to shard over the
    pipe axis with :func:`stage_sharding`.
    """
    return jax.vmap(init_fn)(jax.random.split(rng, n_stages))


def stage_sharding(params: Any, mesh: Mesh, axis_name: str = "pipe"):
    """NamedSharding tree placing each stacked leaf's stage dim on the axis."""
    def leaf(l):
        ndim = getattr(l, "ndim", 0)
        if ndim < 1:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*([axis_name] + [None] * (ndim - 1))))

    return jax.tree_util.tree_map(leaf, params)


def spmd_pipeline(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    axis_name: str = "pipe",
    batch_axis: str | None = None,
) -> Callable[[Any, jax.Array], jax.Array]:
    """Build ``run(stacked_params, microbatches) -> outputs``.

    ``stacked_params``: pytree with a leading stage dimension of size
    ``mesh.shape[axis_name]`` on every array leaf (see
    :func:`stack_stage_params`), sharded or shardable over the axis.

    ``microbatches``: ``[n_micro, micro_batch, ...]`` activations; the
    output has the same shape after every microbatch passed through all
    stages in order. ``stage_fn`` must preserve the activation shape
    (equal widths — the GPipe regime; unequal-width stages belong to
    tensor sharding, not the pipeline).

    ``batch_axis``: optional second mesh axis carrying data parallelism —
    the per-microbatch batch dimension (``microbatches`` axis 1) is
    sharded over it, so a ``{"pipe": P, "data": D}`` mesh runs D
    batch-shards through P stages concurrently (PP × DP). When None the
    activations are replicated over every non-pipe axis.
    """
    n = mesh.shape[axis_name]
    fwd = [(i, (i + 1) % n) for i in range(n)]
    checkpointed = jax.checkpoint(stage_fn)

    def local(stacked, xs):
        # stacked leaves arrive as [1, ...] local shards — drop the stage dim.
        params = jax.tree_util.tree_map(lambda l: l[0], stacked)
        idx = jax.lax.axis_index(axis_name)
        n_micro = xs.shape[0]
        state = jnp.zeros_like(xs[0])
        ys = jnp.zeros_like(xs)

        def tick(carry, t):
            state, ys = carry
            # Device 0 ingests microbatch t (a clipped gather keeps the
            # index in range through the drain ticks; the value is unused
            # once t >= n_micro because those outputs are never banked).
            x_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
            )
            state = jnp.where(idx == 0, x_in, state)
            out = checkpointed(params, state)
            # After applying stage ``idx`` at tick t, device idx holds
            # microbatch t - idx processed through stages 0..idx; the last
            # device therefore banks microbatch t - (n-1).
            t_out = t - (n - 1)
            banked = jax.lax.dynamic_update_index_in_dim(
                ys, out, jnp.clip(t_out, 0, n_micro - 1), 0
            )
            ys = jnp.where((idx == n - 1) & (t_out >= 0), banked, ys)
            state = jax.lax.ppermute(out, axis_name, fwd)
            return (state, ys), None

        (state, ys), _ = jax.lax.scan(
            tick, (state, ys), jnp.arange(n_micro + n - 1)
        )
        # Only the last stage holds real outputs; the masked psum over the
        # pipe axis broadcasts them so the result is replicated along
        # "pipe" (and stays sharded over ``batch_axis`` if one was given).
        return jax.lax.psum(
            jnp.where(idx == n - 1, ys, jnp.zeros_like(ys)), axis_name
        )

    stage_spec = P(axis_name)  # leading stage dim on every leaf
    # Microbatch activations: replicated along the pipe axis, optionally
    # batch-sharded over ``batch_axis`` (axis 1 = within-microbatch batch).
    io_spec = P(None, batch_axis) if batch_axis is not None else P()

    def run(stacked, xs):
        specs = (
            jax.tree_util.tree_map(lambda _: stage_spec, stacked),
            io_spec,
        )
        fn = shard_map(
            local, mesh=mesh, in_specs=specs, out_specs=io_spec,
            check_vma=False,
        )
        return fn(stacked, xs)

    return run


def pipeline_utilization(n_micro: int, n_stages: int) -> float:
    """GPipe bubble accounting: fraction of ticks doing useful work."""
    return n_micro / (n_micro + n_stages - 1)


def moment_sharding(tree, mesh: Mesh, axis_name: str, n_stages: int):
    """Sharding tree for optimizer state mirroring stacked stage params.

    Adam moments mirror param shapes, so any leaf with a leading
    ``n_stages`` dim is a stage stack (callers must guarantee no other
    leaf leads with that size — see PipelinedLM's collision guard);
    scalars and optax counters replicate.
    """
    replicated = NamedSharding(mesh, P())

    def leaf(l):
        ndim = getattr(l, "ndim", 0)
        shape = getattr(l, "shape", ())
        if ndim >= 1 and shape[0] == n_stages:
            return NamedSharding(
                mesh, P(axis_name, *([None] * (ndim - 1)))
            )
        return replicated

    return jax.tree_util.tree_map(leaf, tree)


class PipelinedTask:
    """Pipeline-parallel regression task for the standard Trainer loop.

    The PP analogue of ``LMTask``/``ClassifierTask``: stage parameters
    are stacked and STAGE-SHARDED over ``axis_name`` (declared via the
    ``state_shardings`` hook the Trainer honors — PP params are the one
    task family that must not be replicated), and every train step runs
    the GPipe microbatch schedule end-to-end with the optimizer update.

    Batches: ``{"x": [n_micro, micro_batch, d], "y": like x}``; loss is
    MSE of the pipeline output against ``y``. With a ``batch_axis``, pass
    ``TrainerConfig(batch_specs={"x": P(None, axis), "y": P(None, axis)})``
    so batch placement matches the pipeline's PP × DP layout.
    """

    def __init__(self, stage_fn, init_stage_fn, mesh: Mesh,
                 axis_name: str = "pipe", batch_axis: str | None = None,
                 tx=None, learning_rate: float = 1e-2):
        import optax

        self.stage_fn = stage_fn
        self.init_stage_fn = init_stage_fn
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_stages = mesh.shape[axis_name]
        self.tx = tx if tx is not None else optax.adam(learning_rate)
        self.run = spmd_pipeline(stage_fn, mesh, axis_name, batch_axis)

    # Lower is better for the Trainer's best-checkpoint tracking.
    default_best_metric = "val_loss"
    default_best_mode = "min"

    def batch_size_of(self, batch) -> int:
        """Examples per batch = n_micro × micro_batch (Trainer hook)."""
        x = batch["x"]
        n_micro = int(x.shape[0])
        # The bubble fraction is fixed by (n_micro, n_stages); publish it
        # whenever batch geometry is (re)observed so operators see when a
        # too-small microbatch count is wasting ticks.
        from .. import telemetry

        telemetry.gauge(
            "pipeline_utilization",
            "GPipe schedule utilization n_micro/(n_micro+n_stages-1)",
        ).set(pipeline_utilization(n_micro, self.n_stages))
        return n_micro * int(x.shape[1])

    def init_state(self, rng, sample_batch):
        from .trainer import TrainState

        params = stack_stage_params(self.init_stage_fn, rng, self.n_stages)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats={},
            opt_state=self.tx.init(params),
        )

    def state_shardings(self, state, mesh: Mesh):
        """Stage-shard params AND the mirrored optimizer moments; scalars
        (step, optax counters) replicate."""
        # The schedule (self.run) was built against self.mesh; a Trainer
        # running a different mesh would place state on one mesh and
        # execute shard_map over another.
        check_same_mesh(self.mesh, mesh, "PipelinedTask")
        replicated = NamedSharding(mesh, P())
        return type(state)(
            step=replicated,
            params=stage_sharding(state.params, mesh, self.axis_name),
            batch_stats=jax.tree_util.tree_map(lambda _: replicated,
                                               state.batch_stats),
            opt_state=moment_sharding(
                state.opt_state, mesh, self.axis_name, self.n_stages
            ),
        )

    def train_step(self, state, batch):
        import optax

        xs, ys = batch["x"], batch["y"]

        def loss_fn(params):
            return jnp.mean((self.run(params, xs) - ys) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return (
            type(state)(
                step=state.step + 1,
                params=new_params,
                batch_stats=state.batch_stats,
                opt_state=new_opt,
            ),
            {"train_loss": loss},
        )

    def eval_step(self, state, batch):
        loss = jnp.mean((self.run(state.params, batch["x"]) - batch["y"]) ** 2)
        return {"val_loss": loss}
