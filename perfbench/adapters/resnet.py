"""The program's side of the `resnet` family: what ``dsst train`` wires.

Only what defines a cell is passed; every other option is the program's
own default, read from ``register_train``'s parser when the run starts.
"""

from __future__ import annotations

import argparse


def program_defaults(table_path: str = "unused") -> argparse.Namespace:
    """``dsst train``'s own defaults, from its parser."""
    from dss_ml_at_scale_tpu.config.commands import register_train

    parser = argparse.ArgumentParser()
    register_train(parser.add_subparsers(dest="cmd"))
    return parser.parse_args(["train", "--data", table_path])


def build_task(config: dict, defaults: argparse.Namespace):
    """``ClassifierTask`` as ``_cmd_train`` builds it (Adam at the
    program's default rate, no augmentation)."""
    import optax

    from dss_ml_at_scale_tpu.config.checkpoints import build_classifier_model
    from dss_ml_at_scale_tpu.parallel import ClassifierTask

    model = build_classifier_model(
        config["program_model"], num_classes=config["num_classes"],
        torch_padding=False, fused_bn=defaults.fused_bn)
    return ClassifierTask(model=model, tx=optax.adam(defaults.learning_rate))


def variable_shapes(task, crop: int) -> dict:
    """Path -> shape of the program's variables, without running it."""
    import jax
    import jax.numpy as jnp

    from weights import flatten

    tree = jax.eval_shape(
        lambda: task.model.init(jax.random.key(0),
                                jnp.zeros((1, crop, crop, 3), jnp.float32),
                                train=False))
    return {p: tuple(s.shape) for p, s in flatten(tree).items()}


def initial_state(task, flat_weights: dict):
    from weights import nest

    return task.state_from_variables(nest(flat_weights))


def make_trainer(defaults: argparse.Namespace, mesh):
    """``Trainer`` as ``_cmd_train`` builds it, without validation,
    checkpoints, tracker or health supervision, and with one epoch that
    no window outlasts (the feed ends the run)."""
    from dss_ml_at_scale_tpu.parallel import Trainer, TrainerConfig

    return Trainer(
        TrainerConfig(
            max_epochs=1,
            total_train_rows=1 << 40,
            limit_val_batches=defaults.limit_val_batches,
            feeder_depth=defaults.feeder_depth,
        ),
        mesh=mesh,
    )


def table_reader(table_dir: str, defaults: argparse.Namespace, *,
                 batch: int, crop: int):
    """``batch_loader`` over a Delta table as ``_cmd_train`` opens it: a
    context manager that yields the reader."""
    from dss_ml_at_scale_tpu.data import DeltaTable, batch_loader
    from dss_ml_at_scale_tpu.data.transform import imagenet_transform_spec

    spec = imagenet_transform_spec(
        crop=crop, backend=defaults.decode_backend,
        output_dtype=defaults.image_dtype,
        on_error=defaults.on_decode_error, fast_decode=defaults.fast_decode)
    return batch_loader(
        DeltaTable(table_dir), batch_size=batch, num_epochs=None,
        workers_count=defaults.workers,
        results_queue_size=defaults.queue_size, transform_spec=spec,
        shuffle_row_groups=defaults.shuffle, cur_shard=0, shard_count=1)


def write_table(table_dir: str, jpegs: list, labels) -> None:
    import numpy as np
    import pyarrow as pa

    from dss_ml_at_scale_tpu.data import write_delta

    table = pa.table({
        "content": pa.array(jpegs, type=pa.binary()),
        "label_index": pa.array(np.asarray(labels, np.int64)),
    })
    write_delta(table, table_dir, max_rows_per_file=256, mode="error")
