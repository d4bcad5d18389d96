"""Shared benchmark scaffolding for bench.py / bench_scaling.py.

One place for the model/task construction, synthetic batches, and the
timing methodology: a timing window ends in ``jax.block_until_ready`` on
a value that data-depends on the last step.
"""

from __future__ import annotations

import time


def build_resnet_task(num_classes: int, on_accel: bool,
                      learning_rate: float = 1e-5, fused_bn: bool = True):
    """Benchmark ResNet-50: full-size bf16 on accelerators, a small f32
    stand-in on CPU (where the number is a harness check, not a result).

    ``fused_bn`` (default on) selects the minimal-residual fused
    BN+relu(+residual) path (ops/fused_norm.py) — the HBM byte cut that
    BASELINE.md identifies as the throughput lever on v5e."""
    import jax.numpy as jnp
    import optax

    from ..models import ResNet50
    from ..parallel import ClassifierTask

    model = (
        ResNet50(num_classes=num_classes, fused_bn=fused_bn)
        if on_accel
        else ResNet50(
            num_classes=num_classes, num_filters=8, dtype=jnp.float32,
            fused_bn=fused_bn,
        )
    )
    return ClassifierTask(model=model, tx=optax.adam(learning_rate))


def dp_sharded_step(task, n_devices: int, batch_per_device: int, image: int,
                    num_classes: int, donate: bool = True):
    """(jitted step, placed state, placed batch) for a pure-DP mesh.

    The one DP sharding scaffold shared by the throughput harness
    (bench_scaling.py) and the collective-bytes model (scaling_model.py),
    so the program they measure is the same program."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..runtime import make_mesh

    mesh = make_mesh({"data": n_devices}, devices=jax.devices()[:n_devices])
    batch = synthetic_image_batch(
        batch_per_device * n_devices, image, num_classes=num_classes
    )
    state = task.init_state(jax.random.key(0), batch)
    replicated = NamedSharding(mesh, P())
    state = jax.device_put(state, replicated)
    batch = {
        "image": jax.device_put(
            batch["image"], NamedSharding(mesh, P("data", None, None, None))
        ),
        "label": jax.device_put(batch["label"], NamedSharding(mesh, P("data"))),
    }
    step = jax.jit(
        task.train_step,
        donate_argnums=(0,) if donate else (),
        out_shardings=(replicated, replicated),
    )
    return step, state, batch


def synthetic_image_batch(batch: int, image: int, num_classes: int,
                          seed: int = 0) -> dict:
    import numpy as np

    rng = np.random.default_rng(seed)
    return {
        "image": rng.normal(size=(batch, image, image, 3)).astype(np.float32),
        "label": rng.integers(0, num_classes, batch).astype(np.int32),
    }


def synthetic_image_batch_device(batch: int, image: int, num_classes: int,
                                 seed: int = 0) -> dict:
    """Device-resident synthetic batch, generated ON the device.

    The host-numpy path (``synthetic_image_batch`` + ``device_put``)
    ships ~127 MB host->device at batch 212. Generating the batch with
    on-device PRNG removes bulk transfer from the compute-path benchmark
    entirely, which is the honest shape of that metric: it measures the
    chip, not the host link.
    """
    import jax
    import jax.numpy as jnp

    # Eager (un-jitted) on purpose: a fresh jit closure per call would
    # guarantee a cache-miss compile per sweep point; eager PRNG ops
    # compile nothing extra and still run on the default device.
    ki, kl = jax.random.split(jax.random.key(seed))
    out = {
        "image": jax.random.normal(ki, (batch, image, image, 3),
                                   jnp.float32),
        "label": jax.random.randint(kl, (batch,), 0, num_classes,
                                    jnp.int32),
    }
    jax.block_until_ready(out)
    return out


def timed_train_steps(step_fn, state, batch, steps: int,
                      loss_key: str = "train_loss", warmup: int = 2):
    """(state, seconds) for ``steps`` chained calls after ``warmup``.

    The window ends in ``block_until_ready`` on the final step's loss.
    """
    import jax

    for _ in range(warmup):
        state, metrics = step_fn(state, batch)
    if warmup:
        jax.block_until_ready(metrics[loss_key])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, batch)
    jax.block_until_ready(metrics[loss_key])
    return state, time.perf_counter() - t0
