"""The plain references against the program, at a small size on the CPU:
with the program's model switched to float32 they agree closely, which
is what makes the gap on the chip a reading of the program's precision."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import weights
from references import lowprec
from references import resnet as resnet_ref
from references import transformer_lm as lm_ref

RESNET = {"stage_sizes": [2, 3], "num_filters": 8, "num_classes": 10,
          "crop": 32}


def _resnet_program(dtype):
    from dss_ml_at_scale_tpu.models.resnet import BottleneckBlock, ResNet

    return ResNet(stage_sizes=RESNET["stage_sizes"], block_cls=BottleneckBlock,
                  num_classes=10, num_filters=8, fused_bn=True, dtype=dtype)


def test_resnet_reference_names_the_programs_variables():
    model = _resnet_program(jnp.float32)
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=False))
    mine = resnet_ref.param_shapes(RESNET)
    assert {p: tuple(s.shape) for p, s in weights.flatten(tree).items()} == mine


def test_resnet_reference_agrees_with_the_program_in_float32():
    import optax

    from dss_ml_at_scale_tpu.parallel import ClassifierTask

    flat = weights.make(resnet_ref.param_shapes(RESNET), 5)
    task = ClassifierTask(model=_resnet_program(jnp.float32),
                          tx=optax.adam(1e-3))
    state = task.state_from_variables(weights.nest(flat))
    rng = np.random.default_rng(0)
    batches = [(rng.standard_normal((8, 32, 32, 3), dtype=np.float32),
                rng.integers(0, 10, 8).astype(np.int32)) for _ in range(2)]
    losses = []
    with jax.default_matmul_precision("highest"):
        for img, lab in batches:
            state, metrics = jax.jit(task.train_step)(
                state, {"image": img, "label": lab})
            losses.append(float(metrics["train_loss"]))
    params = {k[len("params/"):]: v for k, v in flat.items()
              if k.startswith("params/")}
    ref = resnet_ref.follow(params, batches, cfg=RESNET, lr=1e-3)
    assert ref["loss"] == pytest.approx(losses, rel=2e-5)
    after = weights.flatten(jax.device_get(state.params))
    change = {k: float(np.linalg.norm(after[k] - np.asarray(params[k])))
              for k in params}
    for k, v in ref["change"].items():
        assert change[k] == pytest.approx(v, rel=2e-2, abs=1e-6), k


def test_fp8_control_moves_the_resnet_reference():
    flat = weights.make(resnet_ref.param_shapes(RESNET), 5)
    params = {k[len("params/"):]: v for k, v in flat.items()
              if k.startswith("params/")}
    rng = np.random.default_rng(0)
    batches = [(rng.standard_normal((8, 32, 32, 3), dtype=np.float32),
                rng.integers(0, 10, 8).astype(np.int32))]
    exact = resnet_ref.follow(params, batches, cfg=RESNET, lr=1e-3)
    low = resnet_ref.follow(params, batches, cfg=RESNET, lr=1e-3,
                            quant=lowprec.fp8)
    assert abs(low["loss"][0] - exact["loss"][0]) > 1e-4


LM = {"vocab_size": 512, "hidden_size": 64, "num_attention_heads": 2,
      "num_hidden_layers": 2, "intermediate_size": 256,
      "max_position_embeddings": 128}


def test_lm_reference_agrees_with_the_program_in_float32():
    from dss_ml_at_scale_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=512, dim=64, num_heads=2, num_layers=2,
                          max_seq=128, mlp_ratio=4, attention="reference",
                          dtype=jnp.float32)
    shapes = lm_ref.param_shapes(LM)
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32)))
    assert {p: tuple(s.shape)
            for p, s in weights.flatten(tree).items()} == shapes
    variables = weights.nest(weights.make(shapes, 9))
    tokens = list(np.random.default_rng(1).integers(0, 512, 37))
    with jax.default_matmul_precision("highest"):
        want = model.apply(variables, jnp.asarray([tokens], jnp.int32))[0]
    got = lm_ref.logits(tokens, 9, LM, pad_to=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_fp8_control_moves_the_lm_reference():
    tokens = list(np.random.default_rng(1).integers(0, 512, 37))
    exact = np.asarray(lm_ref.logits(tokens, 9, LM, pad_to=64))
    low = np.asarray(lm_ref.logits(tokens, 9, LM, lowprec.fp8, pad_to=64))
    assert np.max(np.abs(exact - low)) > 1e-2


def test_weights_take_any_seed_and_repeat():
    shapes = {"params/a/kernel": (4, 8), "params/a/bias": (8,),
              "params/n/scale": (8,), "batch_stats/n/var": (8,)}
    big = 2 ** 31 + 12345
    a, b = weights.make(shapes, big), weights.make(shapes, big)
    c = weights.make(shapes, big + 1)
    assert all(np.array_equal(a[k], b[k]) for k in shapes)
    assert not np.array_equal(a["params/a/kernel"], c["params/a/kernel"])
    assert np.array_equal(a["batch_stats/n/var"], np.ones(8))
    one = weights.leaf(weights.key_for(big), "params/a/kernel", (4, 8),
                       jnp.int32(weights.salt("params/a/kernel")))
    assert np.array_equal(one, a["params/a/kernel"])
