"""Fused BN+act(+residual) parity with the unfused model.

The fused path (ops/fused_norm.py) must be a drop-in: identical
parameter trees (checkpoint/pretrained-converter compatibility),
identical forward values, identical gradients, identical running-stat
updates — in both train and eval mode. Gradient checks run in float32 so
tolerances are tight; what the fused path buys in time has not been
measured on the chip (ROADMAP A4) and is not tested here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dss_ml_at_scale_tpu.models.resnet import ResNet, BottleneckBlock, ResNetBlock
from dss_ml_at_scale_tpu.ops.fused_norm import bn_act


def _tiny(fused, block=BottleneckBlock, dtype=jnp.float32):
    return ResNet(
        stage_sizes=[1, 1], block_cls=block, num_classes=5, num_filters=8,
        dtype=dtype, fused_bn=fused,
    )


def _paths(tree):
    return {
        "/".join(str(k.key) for k in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


@pytest.mark.parametrize("block", [BottleneckBlock, ResNetBlock])
def test_param_tree_identical(block):
    x = jnp.ones((2, 32, 32, 3))
    v_plain = _tiny(False, block).init(jax.random.key(0), x)
    v_fused = _tiny(True, block).init(jax.random.key(0), x)
    assert _paths(v_plain["params"]) == _paths(v_fused["params"])
    assert _paths(v_plain["batch_stats"]) == _paths(v_fused["batch_stats"])
    # Same initializers too (zero-init final BN scale included).
    for a, b in zip(
        jax.tree_util.tree_leaves(v_plain), jax.tree_util.tree_leaves(v_fused)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("block", [BottleneckBlock, ResNetBlock])
def test_train_forward_and_stats_parity(block):
    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    variables = _tiny(False, block).init(jax.random.key(0), x)
    out_p, upd_p = _tiny(False, block).apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    out_f, upd_f = _tiny(True, block).apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    np.testing.assert_allclose(out_f, out_p, rtol=0, atol=2e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=0, atol=2e-4),
        upd_p["batch_stats"], upd_f["batch_stats"],
    )


def test_eval_forward_parity():
    x = jax.random.normal(jax.random.key(2), (3, 32, 32, 3))
    variables = _tiny(False).init(jax.random.key(0), x)
    # Perturb running stats away from init so eval actually uses them.
    variables = jax.tree_util.tree_map(lambda a: a + 0.1, variables)
    out_p = _tiny(False).apply(variables, x, train=False)
    out_f = _tiny(True).apply(variables, x, train=False)
    np.testing.assert_allclose(out_f, out_p, rtol=0, atol=2e-4)


@pytest.mark.parametrize("block", [BottleneckBlock, ResNetBlock])
def test_grad_parity_through_training_loss(block):
    x = jax.random.normal(jax.random.key(3), (4, 32, 32, 3))
    y = jnp.array([0, 1, 2, 3])
    variables = _tiny(False, block).init(jax.random.key(0), x)

    def loss(params, model):
        logits, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        onehot = jax.nn.one_hot(y, logits.shape[-1])
        return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1))

    g_p = jax.grad(loss)(variables["params"], _tiny(False, block))
    g_f = jax.grad(loss)(variables["params"], _tiny(True, block))
    flat_p = jax.tree_util.tree_leaves_with_path(g_p)
    flat_f = dict(
        ("/".join(map(str, p)), v)
        for p, v in jax.tree_util.tree_leaves_with_path(g_f)
    )
    for path, v in flat_p:
        key = "/".join(map(str, path))
        np.testing.assert_allclose(
            flat_f[key], v, rtol=0, atol=5e-5, err_msg=key
        )


def test_bn_act_matches_autodiff_reference():
    """Unit check: hand-written VJP == autodiff of the reference math,
    for every (relu, residual) configuration, including bf16 inputs."""
    key = jax.random.key(4)
    x = jax.random.normal(key, (2, 4, 4, 6), jnp.float32)
    res = jax.random.normal(jax.random.key(5), x.shape, jnp.float32)
    scale = jax.random.normal(jax.random.key(6), (6,)) + 1.0
    bias = jax.random.normal(jax.random.key(7), (6,))

    def reference(x, scale, bias, residual, relu):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, (0, 1, 2))
        var = jnp.mean(jnp.square(x32), (0, 1, 2)) - jnp.square(mean)
        pre = (x32 - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias
        if residual is not None:
            pre = pre + residual.astype(jnp.float32)
        out = jnp.maximum(pre, 0.0) if relu else pre
        return out.astype(x.dtype)

    for relu in (False, True):
        for with_res in (False, True):
            r = res if with_res else None
            out, mean, var = bn_act(
                x, scale, bias, eps=1e-5, relu=relu, residual=r
            )
            ref_out = reference(x, scale, bias, r, relu)
            np.testing.assert_allclose(out, ref_out, rtol=0, atol=1e-5)
            np.testing.assert_allclose(mean, jnp.mean(x, (0, 1, 2)), atol=1e-6)

            def f_loss(args, fused):
                weights = jax.random.normal(jax.random.key(8), x.shape)
                if fused:
                    o, _, _ = bn_act(
                        args[0], args[1], args[2], eps=1e-5, relu=relu,
                        residual=args[3] if with_res else None,
                    )
                else:
                    o = reference(
                        args[0], args[1], args[2],
                        args[3] if with_res else None, relu,
                    )
                return jnp.sum(o * weights)  # non-uniform cotangent

            args = (x, scale, bias, res)
            g_fused = jax.grad(lambda a: f_loss(a, True))(args)
            g_ref = jax.grad(lambda a: f_loss(a, False))(args)
            for gf, gr, name in zip(
                g_fused, g_ref, ("dx", "dscale", "dbias", "dres")
            ):
                if name == "dres" and not with_res:
                    continue
                np.testing.assert_allclose(
                    gf, gr, rtol=0, atol=1e-4,
                    err_msg=f"relu={relu} res={with_res} {name}",
                )


def test_bn_act_bf16_io():
    x = jax.random.normal(jax.random.key(9), (2, 8, 8, 4)).astype(jnp.bfloat16)
    scale = jnp.ones((4,))
    bias = jnp.zeros((4,))
    out, mean, var = bn_act(x, scale, bias, relu=True)
    assert out.dtype == jnp.bfloat16
    assert mean.dtype == jnp.float32 and var.dtype == jnp.float32
    assert (np.asarray(out, jnp.float32) >= 0).all()
    dx = jax.grad(
        lambda x: jnp.sum(bn_act(x, scale, bias, relu=True)[0].astype(jnp.float32))
    )(x)
    assert dx.dtype == jnp.bfloat16


def test_bn_act_global_stats_under_batch_sharding(devices8):
    """Sync-BN falls out of GSPMD: bn_act over a batch-sharded mesh must
    compute GLOBAL batch statistics (cross-shard reduction inserted by
    XLA), matching the unsharded run exactly — the property that makes
    the fused path a drop-in for multi-chip DP training."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dss_ml_at_scale_tpu.runtime import make_mesh

    x = jax.random.normal(jax.random.key(0), (16, 8, 8, 4), jnp.float32)
    scale = jnp.ones((4,)) * 1.3
    bias = jnp.ones((4,)) * 0.2

    fn = jax.jit(lambda x: bn_act(x, scale, bias, relu=True))
    out_ref, mean_ref, var_ref = fn(x)

    mesh = make_mesh({"data": 8})
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None, None)))
    out_sh, mean_sh, var_sh = fn(xs)
    # Per-shard stats would differ wildly from global ones; equality here
    # proves the reduction spans the whole batch.
    np.testing.assert_allclose(np.asarray(mean_sh), np.asarray(mean_ref),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(var_sh), np.asarray(var_ref),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_sh), np.asarray(out_ref),
                               rtol=0, atol=1e-5)

    # ...and through the gradient too (the hand-written VJP's reductions
    # must also be global).
    def loss(x):
        out, _, _ = bn_act(x, scale, bias, relu=True)
        return jnp.sum(out * out)

    g_ref = jax.jit(jax.grad(loss))(x)
    g_sh = jax.jit(jax.grad(loss))(xs)
    np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_ref),
                               rtol=0, atol=1e-5)
