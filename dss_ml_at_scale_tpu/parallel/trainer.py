"""Data-parallel trainer: explicit jitted step loop over a device mesh.

Replaces the reference's PyTorch-Lightning ``Trainer(strategy="ddp")`` +
``TorchDistributor`` stack (reference
``deep_learning/2.distributed-data-loading-petastorm.py:351-415``) with the
TPU-native shape: one jitted train step compiled over a batch-sharded mesh.
Gradient averaging needs no NCCL and no ``psum`` written by hand — the loss
is a mean over the *global* (sharded) batch, so XLA emits the cross-chip
reduction on ICI as part of backprop.

Semantics carried over from the reference driver:

- epoch boundaries by step count on an infinite reader:
  ``steps_per_epoch = rows // (batch × world)`` (``:387-388``), the
  Lightning ``limit_train_batches`` trick made explicit;
- eval every epoch, capped at ``limit_val_batches`` (``:402-405``);
- no sanity-val prologue (``num_sanity_val_steps=0``);
- per-epoch wall-clock + throughput reporting (``:183-188``);
- checkpoint each epoch, best tracked on a val metric, best path returned
  (``:407-415``) — here via Orbax sharded checkpoints with resume.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging
import math
import threading
import time
from pathlib import Path
from typing import Any, Callable, Iterable, Mapping

import jax
import jax.numpy as jnp
import optax
from flax import struct
from flax.core import FrozenDict
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..telemetry import tracecontext
from ..data.prefetch import MeshFeeder, split_provenance
from ..resilience import checkpoint as integrity
from ..resilience import durability
from ..resilience import health
from ..resilience.faults import maybe_fail
from ..resilience.preemption import PreemptionGuard
from ..models.metrics import (
    cross_entropy_loss,
    multiclass_accuracy,
    topk_accuracy,
)
from ..runtime.mesh import make_mesh
from ..runtime.topology import local_topology
from ..utils.profiling import StepTimer

log = logging.getLogger(__name__)

Batch = Mapping[str, Any]

# 1-in-N sampling for the per-step histograms (step time, data wait):
# distribution estimates don't need every step, and the exact totals
# ride counters (feeder_stall_seconds_total) / per-epoch StepTimer
# summaries instead.
_HIST_SAMPLE_EVERY = 4


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    batch_stats: Any
    opt_state: Any


@dataclasses.dataclass
class ClassifierTask:
    """Image-classification task: Flax model + optax optimizer.

    The functional analogue of the reference's
    ``ImageNetClassificationModel(pl.LightningModule)``
    (``deep_learning/2...py:135-208``): Adam(lr=1e-5) default, softmax
    cross-entropy, top-1 accuracy on eval.

    Expects batches with ``image`` (NHWC or NCHW) and ``label`` (int).
    The decode pipeline emits NHWC by default (TPU convs are NHWC-native,
    so the hot path never transposes on device); CHW input
    (``layout="chw"`` torchvision-parity specs) is transposed once here.
    uint8 images (``output_dtype="uint8"`` specs — 4x cheaper to queue
    and transfer) are raw [0, 255] bytes: they are scaled and normalized
    with ``norm_mean``/``norm_std`` inside the jitted step, where XLA
    fuses the arithmetic into the first convolution.
    """

    model: Any
    tx: optax.GradientTransformation | None = None
    learning_rate: float = 1e-5
    image_key: str = "image"
    label_key: str = "label"
    # Device-side normalization constants for uint8 input — the SAME
    # arrays the host-side float path uses, so the two dtypes can never
    # normalize differently.
    norm_mean: Any = None
    norm_std: Any = None
    # On-device train-time augmentation (RandomResizedCrop + flip inside
    # the jitted step, keyed by state.step — see data/augment.py). None
    # disables; eval/predict are never augmented.
    augment: Any = None
    # Extra top-k accuracies for eval (e.g. (5,) adds val_top5_acc —
    # the standard ImageNet companion metric). Empty keeps epoch
    # summaries unchanged.
    eval_topk: tuple = ()

    @property
    def _norm_constants(self):
        from ..data.transform import IMAGENET_MEAN, IMAGENET_STD

        mean = IMAGENET_MEAN if self.norm_mean is None else self.norm_mean
        std = IMAGENET_STD if self.norm_std is None else self.norm_std
        return mean, std

    # Best-checkpoint selection when TrainerConfig doesn't specify one.
    default_best_metric = "val_acc"
    default_best_mode = "max"

    def __post_init__(self):
        if self.tx is None:
            self.tx = optax.adam(self.learning_rate)

    # -- state ------------------------------------------------------------

    def init_state(self, rng, sample_batch: Batch) -> TrainState:
        images = self._images(sample_batch)
        return self.state_from_variables(
            self.model.init(rng, images[:1], train=False)
        )

    def state_from_variables(self, variables: Mapping[str, Any]) -> TrainState:
        """TrainState from externally-supplied variables (pretrained
        weights — reference fine-tunes torchvision IMAGENET1K_V2,
        ``deep_learning/2...py:150``) with a fresh optimizer."""
        params = variables["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=variables.get("batch_stats", FrozenDict()),
            opt_state=self.tx.init(params),
        )

    def _images(self, batch: Batch):
        x = jnp.asarray(batch[self.image_key])
        if x.ndim == 4 and x.shape[1] in (1, 3) and x.shape[-1] not in (1, 3):
            x = jnp.transpose(x, (0, 2, 3, 1))  # NCHW -> NHWC
        if x.dtype == jnp.uint8:
            mean, std = self._norm_constants
            x = (
                x.astype(jnp.float32) / 255.0 - jnp.asarray(mean, jnp.float32)
            ) / jnp.asarray(std, jnp.float32)
        return x

    # -- steps (pure; jitted by the Trainer) ------------------------------

    def train_step(self, state: TrainState, batch: Batch):
        images, labels = self._images(batch), jnp.asarray(batch[self.label_key])
        if self.augment is not None:
            from ..data.augment import augment_for_step

            images = augment_for_step(
                state.step, images, images.shape[1], self.augment
            )
        # Stat-free models (ViT: no BatchNorm anywhere) carry an empty
        # batch_stats collection; passing it to apply (or asking for it
        # back via mutable) would be a Flax error. Emptiness is static
        # pytree structure, so this branch resolves at trace time.
        has_stats = bool(state.batch_stats)

        def loss_fn(params):
            if has_stats:
                logits, updates = self.model.apply(
                    {"params": params, "batch_stats": state.batch_stats},
                    images,
                    train=True,
                    mutable=["batch_stats"],
                )
                new_stats = updates["batch_stats"]
            else:
                logits = self.model.apply(
                    {"params": params}, images, train=True
                )
                new_stats = state.batch_stats
            with jax.named_scope("loss"):
                loss = cross_entropy_loss(logits, labels)
            return loss, (logits, new_stats)

        (loss, (logits, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt = self.tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        metrics = {
            "train_loss": loss,
            "train_acc": multiclass_accuracy(logits, labels),
            # Global grad-norm: a standard training-curve diagnostic,
            # and one of the two fused health signals (with the loss)
            # the health supervisor's isfinite reduction watches.
            "grad_norm": optax.global_norm(grads),
        }
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                batch_stats=new_stats,
                opt_state=new_opt,
            ),
            metrics,
        )

    def eval_step(self, state: TrainState, batch: Batch):
        images, labels = self._images(batch), jnp.asarray(batch[self.label_key])
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        logits = self.model.apply(variables, images, train=False)
        out = {
            "val_loss": cross_entropy_loss(logits, labels),
            "val_acc": multiclass_accuracy(logits, labels),
        }
        for k in self.eval_topk:
            out[f"val_top{k}_acc"] = topk_accuracy(logits, labels, k)
        return out


@dataclasses.dataclass
class LMTask:
    """Causal language-model task for the same Trainer loop.

    The classifier track is the reference's only trained model family;
    the LM task extends the trainer to the transformer stack (flash /
    ring attention) so sequence-parallel training rides the identical
    epoch/step/checkpoint machinery. Batches carry ``tokens`` [B, S]
    int32; loss is next-token cross entropy.
    """

    model: Any
    tx: optax.GradientTransformation | None = None
    learning_rate: float = 3e-4
    tokens_key: str = "tokens"
    # MoE models sow a load-balance loss under intermediates/aux_loss
    # (models/moe.py); a positive weight folds it into the objective.
    aux_loss_weight: float = 0.0

    def __post_init__(self):
        if self.tx is None:
            self.tx = optax.adam(self.learning_rate)

    # Best-checkpoint selection when TrainerConfig doesn't specify one:
    # language models track validation loss (lower is better).
    default_best_metric = "val_loss"
    default_best_mode = "min"

    def init_state(self, rng, sample_batch: Batch) -> TrainState:
        tokens = jnp.asarray(sample_batch[self.tokens_key])
        params = self.model.init(rng, tokens[:1])["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=FrozenDict(),
            opt_state=self.tx.init(params),
        )

    def train_step(self, state: TrainState, batch: Batch):
        from ..models.transformer import next_token_loss

        tokens = jnp.asarray(batch[self.tokens_key])

        def loss_fn(params):
            if self.aux_loss_weight > 0.0:
                from ..models.moe import collect_aux_loss

                logits, inter = self.model.apply(
                    {"params": params}, tokens, mutable=["intermediates"]
                )
                aux = collect_aux_loss(inter["intermediates"])
                with jax.named_scope("loss"):
                    return (next_token_loss(logits, tokens)
                            + self.aux_loss_weight * aux)
            logits = self.model.apply({"params": params}, tokens)
            with jax.named_scope("loss"):
                return next_token_loss(logits, tokens)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt = self.tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                batch_stats=state.batch_stats,
                opt_state=new_opt,
            ),
            {
                "train_loss": loss,
                "train_ppl": jnp.exp(loss),
                # Health signal (see ClassifierTask.train_step).
                "grad_norm": optax.global_norm(grads),
            },
        )

    def eval_step(self, state: TrainState, batch: Batch):
        from ..models.transformer import next_token_loss

        tokens = jnp.asarray(batch[self.tokens_key])
        logits = self.model.apply({"params": state.params}, tokens)
        loss = next_token_loss(logits, tokens)
        return {"val_loss": loss, "val_ppl": jnp.exp(loss)}


def health_state_shardings(replicated):
    """The replicated sharding tree for the health supervisor's EWMA
    carry — the ONE definition :func:`make_train_step`'s out_shardings,
    ``Trainer.fit``'s ``device_put``, and the audit registry all share,
    so the carry's placement can never diverge from the jitted
    program's contract."""
    return jax.tree_util.tree_map(
        lambda _: replicated, health.HealthState.create()
    )


def make_train_step(task, state_shardings, replicated, health_cfg=None):
    """The ONE train-step program constructor.

    ``Trainer.fit`` and ``dsst audit`` both compile exactly this jit —
    so what the auditor certifies (params+opt_state donation, dtype
    discipline, collective shapes, the program-baseline hash) is the
    program production runs, not a parallel reconstruction that could
    drift. Donating argnum 0 (the :class:`TrainState`) is the contract
    the audit's ``donation`` rule holds this function to.

    With ``health_cfg`` the SAME task step is wrapped by the health
    supervisor's commit-or-discard guard and the jitted program carries
    the (state, HealthState) pair as its donated carry.
    """
    if health_cfg is None:
        return jax.jit(task.train_step, donate_argnums=0,
                       out_shardings=(state_shardings, replicated))
    h_shardings = health_state_shardings(replicated)
    return jax.jit(
        health.guard_train_step(task.train_step, health_cfg),
        donate_argnums=0,
        out_shardings=((state_shardings, h_shardings), replicated),
    )


def make_eval_step(task, replicated):
    """The eval-step program constructor shared by ``Trainer.fit`` and
    ``dsst audit`` (eval donates nothing: the state must survive the
    call)."""
    return jax.jit(task.eval_step, out_shardings=replicated)


@dataclasses.dataclass
class TrainerConfig:
    max_epochs: int = 2                      # reference MAX_EPOCHS (2...py:343)
    steps_per_epoch: int | None = None       # else rows // (batch × world)
    total_train_rows: int | None = None
    limit_val_batches: int | None = 5        # reference :405
    log_every_steps: int = 10
    checkpoint_dir: str | None = None
    keep_checkpoints: int = 2
    # None = use the task's default_best_metric/default_best_mode
    # (val_acc/max for classifiers, val_loss/min for LMs).
    best_metric: str | None = None
    best_mode: str | None = None
    resume: bool = False
    # Crash-only restart entry point (dsst train/lm --resume-auto, the
    # watchdog's `runs doctor --resume`, and the future arbiter's
    # revive path): resume from the newest manifest-intact checkpoint
    # when one exists — falling back past torn steps, quarantining
    # wreckage, sweeping stranded tmp files — and start FRESH (instead
    # of erroring) when nothing restorable survives. Unlike `resume`,
    # it never needs the operator to know whether the previous process
    # got as far as a checkpoint.
    resume_auto: bool = False
    # Bound of the background feeder's on-device batch queue (HBM held:
    # feeder_depth batches beyond the in-flight step). ``prefetch_depth``
    # is the legacy name for the same knob; ``feeder_depth`` wins when
    # both are set.
    feeder_depth: int | None = None
    prefetch_depth: int = 2
    # jax.profiler trace capture (SURVEY.md §5.1): when profile_dir is
    # set, a trace covering steps [profile_start_step,
    # profile_start_step + profile_num_steps) is written there.
    profile_dir: str | None = None
    profile_start_step: int = 5
    profile_num_steps: int = 5
    # ZeRO-1-style optimizer-state sharding over the mesh axis: each
    # leaf of opt_state is split along its largest divisible dimension
    # instead of replicated, cutting optimizer memory by ~world size.
    # Pure GSPMD — the same train_step, with XLA inserting the
    # gather/scatter around the update. The reference has no analogue
    # (DDP replicates optimizer state on every rank).
    shard_opt_state: bool = False
    shard_axis: str = "data"
    # Per-key PartitionSpec overrides for batch placement (default: shard
    # the leading dim over "data"). Sequence-parallel LM training passes
    # {"tokens": P(None, "sp")} so batches shard the sequence dimension
    # and ring attention sees its expected layout.
    batch_specs: Mapping[str, Any] | None = None
    # Training-health supervision (resilience.health.HealthConfig), or
    # None (default) for the unsupervised loop — identical hot path to
    # before, no per-step verdict fetch. With a config, every train step
    # carries fused isfinite(loss/grad-norm) + EWMA loss-z-score signals
    # on device, bad updates are discarded before commit, and the
    # skip -> rollback -> abort policy ladder handles streaks.
    health: Any = None


@dataclasses.dataclass
class FitResult:
    state: TrainState
    best_checkpoint_step: int | None
    best_metric_value: float | None
    history: list[dict]
    best_checkpoint_path: str | None = None
    # True when fit stopped early on SIGTERM (spot/TPU-VM eviction): the
    # in-flight step finished and a resumable checkpoint was saved;
    # fit(resume=True) continues from exactly that step.
    preempted: bool = False
    # Health-supervisor accounting (0 when TrainerConfig.health is None):
    # updates discarded for non-finite signals / loss spikes, and
    # checkpoint rollbacks performed.
    skipped_steps: int = 0
    health_rollbacks: int = 0
    # True only when resume_auto actually RESTORED a checkpoint — False
    # when it found nothing, or found only wreckage and fell back to a
    # fresh start (an operator reading "auto_resumed" must be able to
    # trust that prior work continued).
    auto_resumed: bool = False


# dsst: ignore[lock-discipline] no lock-guarded state: the manifest-finalizer thread shares no mutable attribute with fit — _manifest_thread is written and joined only on the fit thread, and the finalizer body touches files + the RunStore journal (which declares its own contract)
class Trainer:
    """Explicit epoch/step loop, one compiled train step, mesh-sharded."""

    def __init__(self, config: TrainerConfig, mesh: Mesh | None = None,
                 tracker=None):
        self.config = config
        self.mesh = mesh if mesh is not None else make_mesh()
        self.tracker = tracker
        self.topology = local_topology()

    # -- accounting -------------------------------------------------------

    @staticmethod
    def _feeder_depth(cfg: TrainerConfig) -> int:
        return (
            cfg.feeder_depth
            if cfg.feeder_depth is not None
            else cfg.prefetch_depth
        )

    def _steps_per_epoch(self, per_process_batch: int) -> int:
        cfg = self.config
        if cfg.steps_per_epoch is not None:
            return cfg.steps_per_epoch
        if cfg.total_train_rows is None:
            raise ValueError(
                "TrainerConfig needs steps_per_epoch or total_train_rows "
                "(row counts come from DeltaTable.num_records())"
            )
        global_batch = per_process_batch * self.topology.process_count
        steps = cfg.total_train_rows // global_batch
        if steps == 0:
            raise ValueError(
                f"total_train_rows={cfg.total_train_rows} < global batch "
                f"{global_batch}; no full step per epoch"
            )
        return steps

    # -- fit --------------------------------------------------------------

    def fit(
        self,
        task,
        train_data: Iterable[Batch],
        val_data_factory: Callable[[], Iterable[Batch]] | None = None,
        *,
        rng: jax.Array | None = None,
        state: TrainState | None = None,
        epoch_callback: Callable[[dict], None] | None = None,
    ) -> FitResult:
        """``epoch_callback`` (if given) receives a copy of each epoch's
        summary dict right after it is appended to the history — the
        Lightning-callback seam (reference trains under
        ``pl.Trainer(...callbacks=...)``,
        ``deep_learning/2...py:190-208``) for progress artifacts,
        early-stop bookkeeping, or external monitors. Exceptions
        propagate: a broken callback should fail the run loudly."""
        # Resolve task-default best metric into a LOCAL cfg only — the same
        # Trainer may fit different task types, so self.config must keep
        # its None sentinels.
        cfg = self.config
        if cfg.best_metric is None or cfg.best_mode is None:
            cfg = dataclasses.replace(
                cfg,
                best_metric=cfg.best_metric
                or getattr(task, "default_best_metric", "val_acc"),
                best_mode=cfg.best_mode
                or getattr(task, "default_best_mode", "max"),
            )
        mesh = self.mesh
        rng = rng if rng is not None else jax.random.key(0)

        train_iter = iter(train_data)
        raw_first = next(train_iter)
        # Provenance is stripped by the feeder; this peek only sizes and
        # initializes, so the side channel is popped locally too.
        first, _ = split_provenance(raw_first)
        # Examples per batch: the leading dim by default; tasks whose
        # batches aren't [batch, ...] (PipelinedTask: [n_micro, mb, ...])
        # declare a ``batch_size_of`` hook so steps/epoch and throughput
        # accounting stay correct.
        size_hook = getattr(task, "batch_size_of", None)
        per_process_batch = (
            size_hook(first) if size_hook is not None
            else len(next(iter(first.values())))
        )
        steps_per_epoch = self._steps_per_epoch(per_process_batch)

        replicated = NamedSharding(mesh, P())
        if state is None:
            state = task.init_state(rng, first)
        # Tasks whose parameters are NOT replicated (pipeline stages live
        # on their own devices; a fully tensor-sharded model would too)
        # declare their layout via a ``state_shardings(state, mesh)``
        # hook; everything else defaults to replicated params.
        shardings_hook = getattr(task, "state_shardings", None)
        if shardings_hook is not None:
            if cfg.shard_opt_state:
                # ZeRO-1 would overwrite the task's own optimizer layout
                # (e.g. stage-sharded Adam moments) — conflicting intents.
                raise ValueError(
                    "shard_opt_state=True conflicts with a task that "
                    "declares its own state_shardings; the task's layout "
                    "already places the optimizer state"
                )
            state_shardings = shardings_hook(state, mesh)
        else:
            state_shardings = jax.tree_util.tree_map(
                lambda _: replicated, state
            )
            if cfg.shard_opt_state:
                state_shardings = state_shardings.replace(
                    opt_state=_zero1_shardings(
                        state.opt_state, mesh, cfg.shard_axis
                    )
                )
        state = jax.device_put(state, state_shardings)

        supervisor = (
            health.HealthSupervisor(cfg.health)
            if cfg.health is not None else None
        )
        hstate = None
        if supervisor is None:
            train_step = make_train_step(task, state_shardings, replicated)
        else:
            # Health-supervised step: the SAME task train_step with the
            # on-device isfinite/z-score signals and the commit-or-
            # discard select fused into the one jitted program. The tiny
            # EWMA HealthState rides the carry, replicated.
            train_step = make_train_step(
                task, state_shardings, replicated, health_cfg=cfg.health
            )
            h_shardings = health_state_shardings(replicated)
            hstate = jax.device_put(health.HealthState.create(), h_shardings)
        eval_step = make_eval_step(task, replicated)

        # Track-best only matters when something produces the metric.
        # Pass the RESOLVED cfg — self.config keeps None sentinels.
        manager = self._checkpoint_manager(
            cfg, use_best=val_data_factory is not None
        )
        if manager is not None:
            # Journal the checkpoint dir BEFORE any training: a run
            # killed during startup or inside its very first save window
            # must still be revivable by `runs doctor --resume` (the
            # committed-step events alone land only after a manifest).
            self._journal(
                "config",
                checkpoint_dir=str(Path(cfg.checkpoint_dir).absolute()),
            )
        start_epoch = 0
        auto_resumed = False
        resume_requested = cfg.resume or cfg.resume_auto
        if manager is not None and resume_requested and (
            self.topology.process_index == 0
        ):
            # Crash-only hygiene: a hard-killed predecessor may have
            # stranded durable-write tmps (torn manifest staging) or a
            # half-written orbax tmp step dir; recovery owns the sweep.
            # Process 0 only — N processes sweeping one shared
            # checkpoint FS would race each other (the sweeper's
            # single-sweeper contract), same discipline as manifest
            # writes and step quarantine.
            swept = durability.sweep_stranded_tmp(cfg.checkpoint_dir)
            if swept:
                log.warning(
                    "resume: removed %d stranded tmp artifact(s) under %s",
                    len(swept), cfg.checkpoint_dir,
                )
        if manager is not None and resume_requested and (
            manager.latest_step() is not None
        ):
            try:
                state = self._restore(manager, state)
            except FileNotFoundError:
                if not cfg.resume_auto:
                    raise
                # Nothing restorable survived the crash (every step torn
                # or pre-manifest damage). Crash-only semantics: rename
                # the wreckage aside and converge to a fresh start —
                # the same outcome as if no checkpoint had ever landed.
                log.warning(
                    "--resume-auto: no intact checkpoint under %s; "
                    "quarantining remains and starting fresh",
                    cfg.checkpoint_dir,
                )
                manager = self._drop_stale_steps(
                    manager, cfg, -1,
                    use_best=val_data_factory is not None,
                )
            else:
                manager = self._drop_stale_steps(
                    manager, cfg, int(state.step),
                    use_best=val_data_factory is not None,
                )
                # A preemption checkpoint lands mid-epoch: the resumed
                # first epoch runs only the REMAINING steps (the
                # step-driven inner loop below), so the final step count
                # matches an uninterrupted run exactly.
                start_epoch = int(state.step) // steps_per_epoch
                if cfg.resume_auto:
                    auto_resumed = True
                    telemetry.counter(
                        "auto_resume_total",
                        "fits that auto-resumed from a journaled "
                        "checkpoint without an operator-named step",
                    ).inc()
                self._journal("resume", step=int(state.step))
                self._repair_manifest(cfg, int(state.step))

        history: list[dict] = []
        best_value, best_step = self._prior_best(manager, cfg)
        sign = 1.0 if cfg.best_mode == "max" else -1.0
        step = int(state.step)  # host-side mirror, synced once before the loop
        data_exhausted = False
        # Telemetry series (process registry): step time, data wait,
        # throughput, compile events. Handles hoisted out of the loop
        # and the two step-rate histograms SAMPLED (1-in-N observes;
        # exact totals ride the feeder's counters) — the per-step cost
        # is one queue.get, one clock read, and a cache probe; no device
        # sync on the hot path.
        step_hist = telemetry.histogram(
            "train_step_seconds", "wall time between dispatched train steps"
        )
        wait_hist = telemetry.histogram(
            "train_data_wait_seconds",
            "per-step time blocked on the input pipeline",
        )
        throughput_gauge = telemetry.gauge(
            "train_throughput_rows_per_sec",
            "last epoch's global training throughput",
        )
        compiles = telemetry.CompileTracker(
            train_step,
            telemetry.counter(
                "train_compile_events_total",
                "train_step executable compiles (first step + retraces)",
            ),
        )
        # Step times feed three sinks: the sampled cumulative histogram
        # (cheap long-run distribution), the sliding-window sketch
        # (live p95 on /metrics), and the SLO engine's step-time
        # objective. The window/SLO observes are full-rate on purpose —
        # a windowed p95 sampled 1-in-8 would lag exactly the
        # regressions it exists to catch — and each costs one bisect.
        _sampled_step = telemetry.SampledObserver(
            step_hist, _HIST_SAMPLE_EVERY
        ).observe
        _step_window = telemetry.window(
            "train_step_window_seconds",
            "windowed wall time between dispatched train steps",
        )
        _slo_note_step = telemetry.slo.get_engine().note_train_step

        def _observe_step(dt: float) -> None:
            _sampled_step(dt)
            _step_window.observe(dt)
            _slo_note_step(dt)

        step_timer = StepTimer(observer=_observe_step)
        tracing = False
        preempted = False
        guard = PreemptionGuard()

        # The background feeder: pulls reader batches, strips row
        # provenance (it rides the queue WITH its device batch, so the
        # supervised loop's row accounting keeps exact parity), stages +
        # shards them through the cached placer, and overlaps all of it
        # with step dispatch. Closed in the ``finally`` on EVERY exit —
        # exhaustion, health abort, preemption — so no feeder thread
        # outlives fit.
        feeder = MeshFeeder(
            itertools.chain([raw_first], train_iter),
            mesh,
            depth=self._feeder_depth(cfg),
            specs=cfg.batch_specs,
            name="train",
            wait_observer=telemetry.SampledObserver(
                wait_hist, _HIST_SAMPLE_EVERY
            ).observe,
        )

        # The run's root span: a "fit" begin event hits the flight
        # recorder before the first step, so ANY kill from here on
        # leaves at least one open span naming the run that died.
        # ExitStack (not a with-block) keeps the 200-line loop body at
        # its current indentation; closed FIRST in the finally so the
        # span closes even on a health abort.
        trace_scope = contextlib.ExitStack()
        trace_scope.enter_context(tracecontext.trace(kind="run"))
        trace_scope.enter_context(
            telemetry.span("fit", max_epochs=cfg.max_epochs)
        )
        step_handoff = tracecontext.Handoff(None)
        try:
            with guard:
                for epoch in range(start_epoch, cfg.max_epochs):
                    if data_exhausted:
                        log.warning(
                            "train data exhausted at step %d; stopping before "
                            "epoch %d of %d", step, epoch, cfg.max_epochs,
                        )
                        break
                    t0_wall = time.time()
                    t0 = time.perf_counter()
                    metrics = {}
                    epoch_steps = 0
                    # Step-driven (not iteration-driven) epoch boundary: the
                    # epoch ends when `step` COMMITTED steps exist, so a
                    # health-discarded update pulls a make-up batch instead
                    # of silently shrinking the epoch (this is what makes a
                    # poisoned run's update sequence identical to a clean run
                    # whose reader excluded the poison rows), and a rollback
                    # simply re-runs the restored span. Mid-epoch resume
                    # falls out of the same arithmetic.
                    epoch_end_step = (epoch + 1) * steps_per_epoch
                    # dsst: hotpath — per-step cost budget is one queue.get (host-sync lint enforces it)
                    while step < epoch_end_step:
                        # One queue.get: the feeder already staged,
                        # sharded, and enqueued the batch (and accounted
                        # the wait into train_data_wait_seconds /
                        # feeder_stall_seconds_total).
                        try:
                            batch, prov = next(feeder)
                        except StopIteration:
                            data_exhausted = True
                            break
                        if cfg.profile_dir is not None and not tracing and (
                            step >= cfg.profile_start_step
                        ):
                            jax.profiler.start_trace(cfg.profile_dir)
                            tracing = True
                            trace_stop_at = step + cfg.profile_num_steps
                        # The step runs under the batch's OWN trace (born
                        # on the feeder thread): reader pull, staging,
                        # and this dispatch share one step_id, and the
                        # begin event makes a kill mid-step leave an
                        # open train_step span in the flight recorder.
                        step_handoff = feeder.last_handoff
                        with step_handoff.activate(), telemetry.span(
                            "train_step", step=step
                        ):
                            if supervisor is None:
                                state, metrics = train_step(state, batch)
                                action = "commit"
                            else:
                                inject = supervisor.next_injection()
                                (state, hstate), step_metrics = train_step(
                                    (state, hstate), batch, inject
                                )
                                # One scalar fetch: the verdict (and on a
                                # bad step, the loss/z diagnostics). This
                                # is the supervised loop's per-step
                                # metrics fetch; the discard already
                                # happened on device.
                                action = supervisor.observe(
                                    step + 1, step_metrics, prov
                                )
                                if action == "commit":
                                    metrics = step_metrics
                        if action == "commit":
                            epoch_steps += 1
                            step += 1  # host-side mirror: no device sync
                            step_timer.tick()
                            compiles.update()
                            if tracing and step >= trace_stop_at:
                                # dsst: ignore[host-sync] profiler stop: one deliberate sync when the trace window closes
                                jax.block_until_ready(state.params)
                                jax.profiler.stop_trace()
                                tracing = False
                                cfg = dataclasses.replace(cfg, profile_dir=None)
                            if step % cfg.log_every_steps == 0:
                                self._log(
                                    # dsst: ignore[host-sync] deliberate scalar fetch, throttled to log_every_steps
                                    {k: float(v) for k, v in metrics.items()},
                                    step,
                                )
                        elif action == "skip":
                            # Update discarded on device; step not committed.
                            # The executable still ran — keep compile
                            # accounting honest.
                            compiles.update()
                        elif action == "rollback":
                            state, hstate, manager, step = self._health_rollback(
                                manager, cfg, state, h_shardings, supervisor,
                                step + 1, use_best=val_data_factory is not None,
                            )
                            if best_step is not None and best_step > step:
                                # The best step may have been rolled over
                                # (quarantined aside as <step>.corrupt, or
                                # itself the corruption that forced the
                                # fallback) — re-derive from the steps the
                                # rebuilt manager still holds, or
                                # best_checkpoint_path would point at a
                                # ghost.
                                best_value, best_step = (
                                    self._best_from_manager(manager, cfg)
                                )
                        else:  # abort
                            raise supervisor.abort(
                                step + 1,
                                f"{supervisor.bad_streak} consecutive unhealthy "
                                f"steps under policy {cfg.health.policy!r} "
                                f"({supervisor.rollbacks}/"
                                f"{cfg.health.max_rollbacks} rollbacks used)",
                                cfg.checkpoint_dir,
                            )
                        if guard.triggered:
                            break
                    if guard.triggered:
                        # Preemption (SIGTERM): the in-flight step finished
                        # above; save a resumable checkpoint NOW — mid-epoch —
                        # and hand back a result marked preempted so the
                        # caller's --resume continues from this exact step.
                        preempted = True
                        telemetry.counter(
                            "preemption_signals_total",
                            "preemption signals honored by Trainer.fit",
                        ).inc()
                        jax.block_until_ready(state.params)
                        latest = (
                            manager.latest_step() if manager is not None else None
                        )
                        if manager is not None and step > (
                            latest if latest is not None else -1
                        ):
                            # use_best=False deliberately: a metrics-carrying
                            # save would rank -inf under best_fn retention and
                            # orbax would prune the preemption step IMMEDIATELY
                            # (verified against the installed version); a
                            # metrics-less save is exempt from best-ranking
                            # retention, so the preserved work survives until
                            # --resume. synchronous: the eviction grace window
                            # is the one place the trainer must not return
                            # before the write (and its manifest) commit.
                            self._save(
                                manager, cfg, state, step,
                                metric_val=None,
                                use_best=False,
                                synchronous=True,
                                trace=step_handoff,
                            )
                        log.warning(
                            "preempted at step %d (epoch %d); resumable "
                            "checkpoint %s", step, epoch,
                            "saved" if manager is not None else
                            "NOT saved (no checkpoint_dir)",
                        )
                        break
                    if epoch_steps == 0:
                        break
                    jax.block_until_ready(state.params)
                    dt = time.perf_counter() - t0
                    # dsst: ignore[span-discipline] args (step count) are only known at close; a raw record keeps the exact legacy start/duration semantics
                    telemetry.get_span_log().record(
                        "train_epoch", t0_wall, dt, epoch=epoch, steps=epoch_steps
                    )
                    images_per_sec = (
                        epoch_steps
                        * per_process_batch
                        * self.topology.process_count
                        / dt
                    )
                    throughput_gauge.set(images_per_sec)
                    epoch_summary = {
                        "epoch": epoch,
                        "epoch_time_s": dt,
                        "images_per_sec": images_per_sec,
                        **step_timer.summary(),
                        **{k: float(v) for k, v in metrics.items()},
                    }
                    step_timer.reset()

                    if val_data_factory is not None:
                        with telemetry.span("eval", epoch=epoch):
                            epoch_summary.update(
                                self._evaluate(eval_step, state, val_data_factory)
                            )

                    history.append(epoch_summary)
                    self._log(
                        {k: v for k, v in epoch_summary.items() if k != "epoch"},
                        step,
                    )
                    if epoch_callback is not None:
                        epoch_callback(dict(epoch_summary))

                    metric_val = epoch_summary.get(cfg.best_metric)
                    is_best = metric_val is not None and (
                        best_value is None or sign * metric_val > sign * best_value
                    )
                    if is_best:
                        best_value, best_step = metric_val, step
                    if manager is not None:
                        self._save(
                            manager, cfg, state, step,
                            metric_val=metric_val,
                            use_best=val_data_factory is not None,
                            trace=step_handoff,
                        )
        finally:
            # Teardown runs on EVERY exit, including a health abort
            # (TrainingHealthError is an expected, caught-by-the-CLI
            # exception): the feeder thread must be stopped and joined
            # (a daemon thread must not outlive fit, and a producer
            # blocked on a full queue must be unblocked), a live
            # profiler trace must be closed, and the in-flight async
            # save + manifest finalizer joined, or the process continues
            # with a truncated trace and a checkpoint whose manifest
            # never lands.
            trace_scope.close()
            feeder.close()
            if tracing:
                jax.block_until_ready(state.params)
                jax.profiler.stop_trace()
            if manager is not None:
                # Join the last step's manifest finalizer FIRST — it is
                # itself inside manager.wait_until_finished(), which must
                # not run concurrently with ours. It must land before
                # callers read (or verify) the checkpoint dir.
                self._join_manifest_writer()
                manager.wait_until_finished()
        return FitResult(
            state=state,
            best_checkpoint_step=best_step,
            best_metric_value=best_value,
            history=history,
            best_checkpoint_path=(
                str(Path(cfg.checkpoint_dir) / str(best_step))
                if manager is not None and best_step is not None
                else None
            ),
            preempted=preempted,
            skipped_steps=(
                supervisor.skipped_steps if supervisor is not None else 0
            ),
            health_rollbacks=(
                supervisor.rollbacks if supervisor is not None else 0
            ),
            auto_resumed=auto_resumed,
        )

    # -- eval -------------------------------------------------------------

    def _evaluate(self, eval_step, state, val_data_factory) -> dict:
        cfg = self.config
        totals: dict[str, float] = {}
        count = 0
        val_data = val_data_factory()
        feeder = None
        try:
            # Limit BEFORE the feeder so no extra batches are decoded and
            # shipped to HBM just to be discarded.
            source = iter(val_data)
            if cfg.limit_val_batches is not None:
                source = itertools.islice(source, cfg.limit_val_batches)
            feeder = MeshFeeder(
                source, self.mesh, depth=self._feeder_depth(cfg),
                specs=cfg.batch_specs, name="eval",
            )
            for batch, _prov in feeder:
                m = eval_step(state, batch)
                for k, v in m.items():
                    totals[k] = totals.get(k, 0.0) + float(v)
                count += 1
        finally:
            # Join the feeder thread, then stop streaming readers
            # eagerly — limit_val_batches may leave the source
            # mid-stream with worker threads still decoding.
            if feeder is not None:
                feeder.close()
            stop = getattr(val_data, "stop", None)
            if callable(stop):
                stop()
        return {k: v / max(count, 1) for k, v in totals.items()}

    # -- checkpointing ----------------------------------------------------

    def _checkpoint_manager(self, cfg: TrainerConfig, use_best: bool):
        # cfg must be the fit()-resolved config: self.config may still hold
        # the best_metric/best_mode None sentinels, which orbax rejects.
        if cfg.checkpoint_dir is None:
            return None
        ocp = _ocp()
        options = ocp.CheckpointManagerOptions(
            max_to_keep=cfg.keep_checkpoints,
            # best_fn only when metrics will actually be saved: with best_fn
            # configured and metrics=None, orbax keeps every step (verified
            # against the installed version) and retention silently breaks.
            best_fn=(lambda m: m[cfg.best_metric]) if use_best else None,
            best_mode=cfg.best_mode,
        )
        return ocp.CheckpointManager(Path(cfg.checkpoint_dir).absolute(), options=options)

    def _prior_best(
        self, manager, cfg: TrainerConfig
    ) -> tuple[float | None, int | None]:
        """Recover best-so-far from a resumed manager so a worse post-resume
        epoch can't claim best_checkpoint_path.

        The best step may no longer exist on disk (retention pruned it, an
        operator cleaned it, or its files went corrupt); recover from the
        metrics of the steps that DO remain rather than erroring or
        pointing best_checkpoint_path at a ghost.
        """
        if manager is None or not cfg.resume:
            return None, None
        return self._best_from_manager(manager, cfg)

    def _best_from_manager(
        self, manager, cfg: TrainerConfig
    ) -> tuple[float | None, int | None]:
        """Best (value, step) among the steps the manager still holds."""
        sign = 1.0 if cfg.best_mode == "max" else -1.0
        try:
            steps = set(manager.all_steps())
            best_step = manager.best_step()
            if best_step is not None and best_step in steps:
                all_metrics = manager.metrics(best_step)
                return (all_metrics or {}).get(cfg.best_metric), best_step
            candidates = []
            for s in steps:
                try:
                    m = (manager.metrics(s) or {}).get(cfg.best_metric)
                except Exception:
                    continue  # unreadable per-step metrics: skip that step
                if m is not None and math.isfinite(m):
                    candidates.append((sign * m, s))
            if not candidates:
                return None, None
            _, s = max(candidates)
            return (manager.metrics(s) or {}).get(cfg.best_metric), s
        except Exception:
            return None, None

    def _save(self, manager, cfg: TrainerConfig, state: TrainState,
              step: int, *, metric_val, use_best: bool,
              synchronous: bool = False,
              trace: tracecontext.Handoff | None = None) -> None:
        """One checkpoint step + its integrity manifest.

        The manifest must checksum the COMMITTED files, which means
        waiting out orbax's async write before hashing — but neither
        belongs on the training thread (that would forfeit the
        async-save/next-epoch overlap). The wait + hash run on a
        background finalizer thread; the next save joins the previous
        finalizer (long done by then), and ``fit`` joins the last one
        before returning. ``synchronous=True`` (preemption) does it all
        inline — the process is about to exit.
        """
        if use_best:
            # With best-tracking on, every epoch save needs the metric or
            # orbax retention stops pruning; a missing value ranks worst
            # so it never wins "best". (Preemption saves pass
            # use_best=False instead: a -inf-ranked step would be pruned
            # at save time, losing the preserved work.)
            sign = 1.0 if cfg.best_mode == "max" else -1.0
            save_metrics = {
                cfg.best_metric: metric_val
                if metric_val is not None
                else sign * float("-inf")
            }
        else:
            save_metrics = None
        # Join the previous step's finalizer BEFORE driving the manager
        # again: its wait_until_finished() must not run concurrently with
        # this save (orbax's async internals aren't documented
        # thread-safe). By now it is long done — an epoch has passed.
        self._join_manifest_writer()
        # The save runs under the committing step's trace (the feeder's
        # step_id): checkpoint dispatch, the async finalizer below, and
        # the train step that produced the weights share one timeline.
        handoff = trace if trace is not None else tracecontext.Handoff(None)
        with handoff.activate(), telemetry.span("checkpoint", step=step):
            maybe_fail("checkpoint.save")
            manager.save(
                step,
                args=_ocp().args.StandardSave(_to_pytree(state)),
                metrics=save_metrics,
            )

        def finalize() -> None:
            # The finalizer thread adopts the step's handoff: its begin
            # event means a SIGKILL inside the save window leaves an
            # open checkpoint.finalize span naming the torn step.
            with handoff.activate(), telemetry.span(
                "checkpoint.finalize", step=step
            ):
                try:
                    manager.wait_until_finished()
                    # Process 0 only — the manifest is one file per
                    # step, not per host.
                    if self.topology.process_index == 0:
                        step_dir = Path(str(manager.directory)) / str(step)
                        if step_dir.is_dir():
                            integrity.write_manifest(step_dir)
                            # Manifest landed => the step is durably
                            # committed: record it in the run journal so
                            # a fresh process (doctor, --resume-auto,
                            # the arbiter) knows the last committed step
                            # without walking the checkpoint dir.
                            self._journal(
                                "checkpoint", step=step,
                                checkpoint_dir=str(manager.directory),
                            )
                except Exception:
                    # A failed manifest leaves the step "unverified"
                    # (still restorable), never a crashed training run.
                    log.exception(
                        "manifest write failed for step %d", step
                    )

        if synchronous:
            finalize()
        else:
            self._manifest_thread = threading.Thread(
                target=finalize, daemon=True, name=f"ckpt-manifest-{step}"
            )
            self._manifest_thread.start()

    def _join_manifest_writer(self) -> None:
        thread = getattr(self, "_manifest_thread", None)
        if thread is not None:
            thread.join()
            self._manifest_thread = None

    def _restore(self, manager, state: TrainState) -> TrainState:
        restored, _ = _restore_with_fallback(manager, _to_pytree(state))
        return TrainState(**restored)

    def _repair_manifest(self, cfg: TrainerConfig, step: int) -> None:
        """Recovery repairs proof: a restored step with no manifest (its
        writer was killed inside the save window) just demonstrated its
        bytes load — hash them NOW so the step verifies "intact" from
        here on instead of staying "unverified" forever. Journaled as
        ``manifest_repair`` (not ``checkpoint``: nothing new was
        committed)."""
        if self.topology.process_index != 0:
            return
        step_dir = Path(cfg.checkpoint_dir) / str(step)
        if not step_dir.is_dir() or (
            step_dir / integrity.MANIFEST_NAME
        ).exists():
            return
        try:
            integrity.write_manifest(step_dir)
        except Exception:
            log.exception("manifest repair failed for step %d", step)
            return
        self._journal(
            "manifest_repair", step=step,
            checkpoint_dir=str(Path(cfg.checkpoint_dir).absolute()),
        )

    def _drop_stale_steps(self, manager, cfg: TrainerConfig,
                          restored_step: int, *, use_best: bool):
        """Quarantine checkpoint steps newer than ``restored_step``.

        After a fallback restore (corrupt latest on resume, or a health
        rollback) the run will re-reach those step numbers, and
        ``manager.save`` would crash on "step already exists" (and the
        preemption-save gate would compare against a corrupt latest).
        Rename them aside (``<step>.corrupt``) and rebuild the manager so
        its step cache forgets them. Returns the (possibly rebuilt)
        manager. (Process 0 renames, same discipline as manifest writes;
        single-host in CI.)
        """
        stale = [s for s in manager.all_steps() if s > restored_step]
        if not stale:
            return manager
        if self.topology.process_index == 0:
            for s in stale:
                integrity.quarantine_step(Path(cfg.checkpoint_dir) / str(s))
        # Multi-host: no collective barrier here — instead every process
        # waits (bounded) until process 0's renames are VISIBLE on the
        # shared checkpoint FS before rebuilding its manager, so no
        # rebuilt manager can still list a stale step. Single-host: the
        # renames already happened synchronously above and the loop
        # exits immediately.
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and any(
            (Path(cfg.checkpoint_dir) / str(s)).exists() for s in stale
        ):
            time.sleep(0.2)
        leftover = [
            s for s in stale
            if (Path(cfg.checkpoint_dir) / str(s)).exists()
        ]
        if leftover:
            log.warning(
                "stale checkpoint steps still visible after quarantine "
                "wait: %s — a later save of those step numbers may fail",
                leftover,
            )
        return self._checkpoint_manager(cfg, use_best=use_best)

    def _health_rollback(self, manager, cfg: TrainerConfig,
                         state: TrainState, h_shardings,
                         supervisor, at_step: int, *, use_best: bool):
        """Policy-ladder rollback: restore the newest manifest-intact
        checkpoint, reset the spike detector, free the rolled-over step
        numbers. Returns ``(state, hstate, manager, step)``; escalates to
        the supervisor's abort when no checkpoint can be restored."""
        if manager is None:
            raise supervisor.abort(
                at_step,
                "rollback requested but no checkpoint_dir is configured",
                None,
            )
        t0_wall = time.time()
        t0 = time.perf_counter()
        # The in-flight manifest finalizer owns manager.wait_until_
        # finished(); join it before driving the manager again.
        self._join_manifest_writer()
        manager.wait_until_finished()
        try:
            restored, rstep = _restore_with_fallback(
                manager, _to_pytree(state)
            )
        except FileNotFoundError as e:
            raise supervisor.abort(
                at_step,
                f"rollback found no intact checkpoint: {e}",
                cfg.checkpoint_dir,
            ) from e
        state = TrainState(**restored)
        # Fresh detector: the restored trajectory's loss level may differ
        # from the EWMA the poisoned span accumulated.
        hstate = jax.device_put(health.HealthState.create(), h_shardings)
        manager = self._drop_stale_steps(
            manager, cfg, rstep, use_best=use_best
        )
        supervisor.record_rollback(
            at_step, rstep, t0_wall, time.perf_counter() - t0
        )
        return state, hstate, manager, rstep

    def _log(self, metrics: dict, step: int) -> None:
        if self.tracker is not None:
            self.tracker.log_metrics(metrics, step)

    def _journal(self, event: str, **fields) -> None:
        """Append to the tracker's run journal, if the tracker keeps one
        (RunStore does; foreign trackers may not — duck-typed so the
        Trainer stays tracker-agnostic)."""
        if event == "checkpoint":
            hook = getattr(self.tracker, "journal_checkpoint", None)
            if hook is not None:
                hook(fields["step"], fields["checkpoint_dir"])
            return
        hook = getattr(self.tracker, "journal_event", None)
        if hook is not None:
            hook(event, **fields)


def _zero1_shardings(opt_state, mesh: Mesh, axis: str):
    """ZeRO-1 sharding tree for an optimizer state.

    Each array leaf is split along its largest dimension divisible by the
    mesh axis size (Adam moments mirror param shapes, so conv kernels
    split along their channel dims); indivisible leaves (scalars, odd
    shapes) stay replicated. Because the update is elementwise per leaf,
    GSPMD keeps the math identical — only the layout (and the memory)
    changes.
    """
    if axis not in mesh.shape:
        raise ValueError(
            f"shard_opt_state: shard_axis {axis!r} is not an axis of the "
            f"mesh {dict(mesh.shape)}; set TrainerConfig.shard_axis to one "
            f"of {list(mesh.shape)}"
        )
    n = mesh.shape[axis]

    def leaf(l):
        shape = getattr(l, "shape", ())
        best = None  # (size, dim)
        for dim, size in enumerate(shape):
            if size % n == 0 and size > 0 and (best is None or size > best[0]):
                best = (size, dim)
        if best is None:
            return NamedSharding(mesh, P())
        spec = [None] * len(shape)
        spec[best[1]] = axis
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(leaf, opt_state)


def _restore_with_fallback(manager, template, *, steps=None):
    """Restore the newest usable step, walking past corrupt ones.

    ``steps`` (default: all steps, newest first) is the preference
    order. Each candidate is verified against its integrity manifest
    first; corrupt steps — and steps whose restore raises anyway (damage
    a manifest can't see, or a pre-manifest step gone bad) — are skipped
    with a ``checkpoint_fallback_total`` count and a warning, exactly
    the behavior that turns "latest checkpoint truncated by the
    preemption" from a crashed run into a one-step rollback. Returns
    ``(restored_pytree, step)``.
    """
    ocp = _ocp()
    directory = Path(str(manager.directory))
    if steps is None:
        steps = sorted(manager.all_steps(), reverse=True)
    last_exc = None
    for step in steps:
        status, problems = integrity.verify_step(directory / str(step))
        if status == "corrupt":
            integrity.record_fallback(step, "; ".join(problems))
            continue
        try:
            maybe_fail("checkpoint.restore")
            restored = manager.restore(
                step, args=ocp.args.StandardRestore(template)
            )
        except Exception as e:
            integrity.record_fallback(
                step, f"restore raised {type(e).__name__}: {e}"
            )
            last_exc = e
            continue
        return restored, int(step)
    raise FileNotFoundError(
        f"no intact checkpoint step under {directory} "
        f"(candidates: {list(steps)})"
    ) from last_exc


def restore_state(
    task,
    sample_batch: Batch,
    checkpoint_dir: str,
    *,
    step: int | None = None,
    prefer: str = "best",
    best_metric: str | None = None,
    best_mode: str | None = None,
    rng: jax.Array | None = None,
) -> tuple[TrainState, int]:
    """Restore a Trainer checkpoint outside the Trainer (inference/export).

    ``prefer="best"`` picks the best step by the tracked metric (task
    defaults apply) and falls back to the latest step when no metrics
    were saved; ``step=`` pins an explicit step. Returns
    ``(state, step_restored)``.

    Steps are verified against their integrity manifests: the preferred
    step being corrupt falls back to the newest intact one (same walk as
    ``Trainer`` resume), while an explicitly pinned ``step=`` that fails
    verification raises — the caller asked for that step by name, and
    silently serving different weights would be worse than an error.

    The restore is structure-matched against the task's full TrainState,
    optimizer state included (orbax restores whole templates) — callers
    that only infer should drop ``state.opt_state`` right away to free
    the extra ~2x-params memory.
    """
    if prefer not in ("best", "latest"):
        raise ValueError(f"prefer must be 'best' or 'latest', got {prefer!r}")
    ocp = _ocp()
    metric = best_metric or getattr(task, "default_best_metric", "val_acc")
    mode = best_mode or getattr(task, "default_best_mode", "max")
    manager = ocp.CheckpointManager(
        Path(checkpoint_dir).absolute(),
        options=ocp.CheckpointManagerOptions(
            best_fn=lambda m: m[metric], best_mode=mode,
            # Read-only usage: never prune on restore.
            max_to_keep=None,
        ),
    )
    state = task.init_state(
        rng if rng is not None else jax.random.key(0), sample_batch
    )
    if step is not None:
        status, problems = integrity.verify_step(
            Path(checkpoint_dir).absolute() / str(step)
        )
        if status == "corrupt":
            raise ValueError(
                f"pinned checkpoint step {step} under {checkpoint_dir} "
                f"fails integrity verification: {'; '.join(problems)}"
            )
        restored = manager.restore(
            step, args=ocp.args.StandardRestore(_to_pytree(state))
        )
        return TrainState(**restored), int(step)
    all_steps = sorted(manager.all_steps(), reverse=True)
    if not all_steps:
        raise FileNotFoundError(f"no checkpoints under {checkpoint_dir}")
    preferred = manager.best_step() if prefer == "best" else None
    order = (
        [preferred] if preferred is not None else []
    ) + [s for s in all_steps if s != preferred]
    restored, used = _restore_with_fallback(
        manager, _to_pytree(state), steps=order
    )
    return TrainState(**restored), used


def _ocp():
    import orbax.checkpoint as ocp

    return ocp


def _to_pytree(state: TrainState) -> dict:
    return {
        "step": state.step,
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
    }
