"""Plain reference of the ResNet the `resnet` configurations run.

ResNet v1.5 bottleneck network in straightforward ``jax.numpy``, float32,
matmul precision ``highest``: stride on the 3x3 convolution, projection
shortcut where the shape changes, train-mode batch normalisation over
the whole batch (biased variance, eps 1e-5), SAME padding as XLA pads it
(the program's default; torchvision pads stride-2 convolutions
symmetrically), mean softmax cross-entropy, Adam (0.9, 0.999, 1e-8) as
optax writes it.  It imports nothing of the program.  Each block is
rematerialised in the backward pass so that float32 activations of the
timed batch fit beside nothing else on one chip.

``quant`` is where the low-precision control enters: it is applied to
both operands of every convolution and of the classifier's product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BN_EPS = 1e-5
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


def param_shapes(cfg: dict) -> dict[str, tuple]:
    """Path -> shape of every variable, from the configuration's sizes."""
    width = cfg["num_filters"]
    shapes: dict[str, tuple] = {}

    def bn(prefix, c):
        shapes[f"params/{prefix}/scale"] = (c,)
        shapes[f"params/{prefix}/bias"] = (c,)
        shapes[f"batch_stats/{prefix}/mean"] = (c,)
        shapes[f"batch_stats/{prefix}/var"] = (c,)

    shapes["params/conv_init/kernel"] = (7, 7, 3, width)
    bn("norm_init", width)
    c_in, n = width, 0
    for i, count in enumerate(cfg["stage_sizes"]):
        f = width * 2 ** i
        for j in range(count):
            b = f"BottleneckBlock_{n}"
            stride = 2 if i > 0 and j == 0 else 1
            shapes[f"params/{b}/Conv_0/kernel"] = (1, 1, c_in, f)
            bn(f"{b}/BatchNorm_0", f)
            shapes[f"params/{b}/Conv_1/kernel"] = (3, 3, f, f)
            bn(f"{b}/BatchNorm_1", f)
            shapes[f"params/{b}/Conv_2/kernel"] = (1, 1, f, 4 * f)
            bn(f"{b}/BatchNorm_2", 4 * f)
            if c_in != 4 * f or stride != 1:
                shapes[f"params/{b}/conv_proj/kernel"] = (1, 1, c_in, 4 * f)
                bn(f"{b}/norm_proj", 4 * f)
            c_in, n = 4 * f, n + 1
    shapes["params/Dense_0/kernel"] = (c_in, cfg["num_classes"])
    shapes["params/Dense_0/bias"] = (cfg["num_classes"],)
    return shapes


def _conv(x, w, stride, quant):
    return lax.conv_general_dilated(
        quant(x), quant(w), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    )


def _bn(x, p, prefix):
    """Train-mode batch norm over the whole batch; ``p`` holds
    ``<prefix>/scale`` and ``<prefix>/bias``."""
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return ((x - mean) * lax.rsqrt(var + BN_EPS) * p[f"{prefix}/scale"]
            + p[f"{prefix}/bias"])


def _stem(p, x, quant):
    x = _conv(x, p["conv_init/kernel"], 2, quant)
    x = jax.nn.relu(_bn(x, p, "norm_init"))
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "SAME")


BLOCK_LEAVES = ("Conv_0/kernel", "BatchNorm_0/scale", "BatchNorm_0/bias",
                "Conv_1/kernel", "BatchNorm_1/scale", "BatchNorm_1/bias",
                "Conv_2/kernel", "BatchNorm_2/scale", "BatchNorm_2/bias")
PROJ_LEAVES = ("conv_proj/kernel", "norm_proj/scale", "norm_proj/bias")


def _block(w, x, stride, quant):
    """One bottleneck block; ``w`` holds its leaves by their local names."""
    y = _conv(x, w["Conv_0/kernel"], 1, quant)
    y = jax.nn.relu(_bn(y, w, "BatchNorm_0"))
    y = _conv(y, w["Conv_1/kernel"], stride, quant)
    y = jax.nn.relu(_bn(y, w, "BatchNorm_1"))
    y = _bn(_conv(y, w["Conv_2/kernel"], 1, quant), w, "BatchNorm_2")
    if "conv_proj/kernel" in w:
        x = _bn(_conv(x, w["conv_proj/kernel"], stride, quant), w,
                "norm_proj")
    return jax.nn.relu(x + y)


def logits_fn(p: dict, images, cfg: dict, quant=lambda a: a):
    """Train-mode forward pass: images NHWC float32 -> logits float32.

    The blocks of a stage after its first have the same shapes, so they
    run as one ``lax.scan`` over their stacked weights: the same
    arithmetic block after block, and a program a third the size, which
    the machine's compile cache can hold beside the program's own step.
    """
    x = jax.checkpoint(functools.partial(_stem, quant=quant))(p, images)
    n = 0
    for i, count in enumerate(cfg["stage_sizes"]):
        stride = 2 if i > 0 else 1
        first = {k: p[f"BottleneckBlock_{n}/{k}"]
                 for k in BLOCK_LEAVES + PROJ_LEAVES
                 if f"BottleneckBlock_{n}/{k}" in p}
        x = jax.checkpoint(functools.partial(
            _block, stride=stride, quant=quant))(first, x)
        rest = range(n + 1, n + count)
        if len(rest):
            stacked = {k: jnp.stack([p[f"BottleneckBlock_{m}/{k}"]
                                     for m in rest]) for k in BLOCK_LEAVES}
            body = jax.checkpoint(functools.partial(
                _block, stride=1, quant=quant))
            x, _ = lax.scan(lambda h, w: (body(w, h), None), x, stacked)
        n += count
    x = jnp.mean(x, (1, 2))
    return (jnp.dot(quant(x), quant(p["Dense_0/kernel"]),
                    precision=lax.Precision.HIGHEST) + p["Dense_0/bias"])


def loss_fn(p, images, labels, cfg, quant=lambda a: a):
    logits = logits_fn(p, images, cfg, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def _leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def train_step(p, m, v, t, images, labels, *, cfg, lr, quant):
    """One Adam step.  Returns the new (p, m, v), the loss and the
    gradient."""
    loss, g = jax.value_and_grad(loss_fn)(p, images, labels, cfg, quant)
    m = {k: B1 * m[k] + (1 - B1) * g[k] for k in p}
    v = {k: B2 * v[k] + (1 - B2) * jnp.square(g[k]) for k in p}
    t = t + 1
    mhat = {k: m[k] / (1 - B1 ** t) for k in p}
    vhat = {k: v[k] / (1 - B2 ** t) for k in p}
    p = {k: p[k] - lr * mhat[k] / (jnp.sqrt(vhat[k]) + ADAM_EPS) for k in p}
    return p, m, v, loss, g


def follow(params: dict, batches, *, cfg, lr, quant=lambda a: a,
           batch_sharding=None, replicated=None):
    """Follow the program's first steps.

    ``params`` are the trainable leaves ('params/' stripped), ``batches``
    a list of (images, labels) as the program was fed them.  Returns the
    losses, the per-leaf norms of the first gradient and the per-leaf
    norms of the parameters' change after the last step, as Python
    floats.  With ``batch_sharding`` the batch is spread over the chips
    of its mesh and the parameters replicated; the arithmetic is the
    same whole-batch arithmetic.
    """
    step = jax.jit(
        functools.partial(train_step, cfg=cfg, lr=lr, quant=quant),
        donate_argnums=(0, 1, 2),
    )
    p0 = params
    p = {k: jnp.copy(a) for k, a in p0.items()}
    m = {k: jnp.zeros_like(a) for k, a in p0.items()}
    v = {k: jnp.zeros_like(a) for k, a in p0.items()}
    losses, first_grad, first_grad_full = [], None, None
    with jax.default_matmul_precision("highest"):
        for t, (images, labels) in enumerate(batches):
            images = jnp.asarray(images, jnp.float32)
            labels = jnp.asarray(labels, jnp.int32)
            if batch_sharding is not None:
                images = jax.device_put(images, batch_sharding)
                labels = jax.device_put(labels, batch_sharding)
            p, m, v, loss, g = step(p, m, v, jnp.float32(t), images, labels)
            losses.append(float(loss))
            if first_grad is None:
                first_grad = {k: float(x) for k, x in
                              jax.jit(_leaf_norms)(g).items()}
                first_grad_full = {k: np.asarray(x) for k, x in g.items()}
            del g
    change = jax.jit(lambda a, b: _leaf_norms({k: a[k] - b[k] for k in a}))(
        p, p0)
    return {
        "loss": losses,
        "grad": first_grad,
        "grad_full": first_grad_full,
        "change": {k: float(x) for k, x in change.items()},
    }
