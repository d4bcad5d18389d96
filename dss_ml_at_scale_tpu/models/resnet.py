"""ResNet in Flax, TPU-first.

Capability parity with the reference's torchvision ``resnet50`` wrapped in
``ImageNetClassificationModel`` (reference
``deep_learning/2.distributed-data-loading-petastorm.py:135-165``). Built
natively rather than ported:

- NHWC layout (TPU's native conv layout; torchvision is NCHW).
- bfloat16 compute / float32 params by default — the MXU's preferred mix.
- BatchNorm batch statistics are computed inside the jitted, batch-sharded
  program, so under a ``data``-sharded mesh the reduction is *global*
  (XLA inserts the cross-chip collective): sync-BN falls out of SPMD for
  free, where DDP needs a separate SyncBatchNorm wrapper.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


def _norm_relu(norm, act, fused, y, **kw):
    """norm-then-relu, fused into one op when the fused path is on.

    The single site encoding the fused-vs-unfused activation decision —
    the stem and both block classes all route through it, so the two
    configurations cannot drift apart.
    """
    if fused:
        return norm(act="relu", **kw)(y)
    return act(norm(**kw)(y))


class _Conv1x1Kernel(nn.Module):
    """Parameter-only stand-in for an ``nn.Conv`` whose matmul executes
    inside the fused Pallas kernel (ops/fused_matmul.py).  Same param
    name, shape, dtype, and initializer as ``nn.Conv`` — checkpoints
    and the pretrained-weights converter see an identical tree."""

    features: int

    @nn.compact
    def __call__(self, in_features: int):
        return self.param(
            "kernel", nn.initializers.lecun_normal(),
            (1, 1, in_features, self.features), jnp.float32,
        )


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with projection shortcut."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable = nn.relu
    # Fused path: relu (and the final residual add) execute INSIDE the
    # norm (ops/fused_norm.py) so backward saves no extra activations.
    # "pallas" additionally fuses the middle BN's APPLY into the third
    # (1x1) conv as a Pallas matmul prologue (ops/fused_matmul.py), so
    # that site's normalized activation never exists in HBM.
    fused: bool | str = False
    # Batch-sharded SPMD form of the pallas site: when a mesh is given,
    # the kernel runs per-shard inside shard_map over `pallas_axis`
    # (stats stay global HLO; the op psums its backward sums — see
    # ops/fused_matmul.py).  None = single-device pallas_call.
    pallas_mesh: Any = None
    pallas_axis: str = "data"

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = _norm_relu(self.norm, self.act, self.fused, y)
        y = self.conv(self.filters, (3, 3), (self.strides, self.strides))(y)
        if self.fused == "pallas":
            from ..ops.fused_matmul import bn_relu_matmul

            # Stats in HLO (module auto-named BatchNorm_1, same tree as
            # the other paths), apply + matmul in the Pallas kernel.
            scale, bias, mean, var = self.norm()(y, stats_only=True)
            kernel = _Conv1x1Kernel(
                self.filters * 4, name="Conv_2"
            )(y.shape[-1])
            eps, running = 1e-5, False
            if hasattr(self.norm, "keywords"):
                eps = self.norm.keywords.get("epsilon", eps)
                running = self.norm.keywords.get(
                    "use_running_average", running
                )
            kernel = kernel.astype(y.dtype)
            # Init traces the body with a tiny (often 1-sample) batch
            # that cannot satisfy shard_map's divisibility; the
            # single-device path is math-identical, so init always
            # takes it.
            if self.pallas_mesh is not None and not self.is_initializing():
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                axis = self.pallas_axis
                m_global = y.shape[0] * y.shape[1] * y.shape[2]

                def per_shard(y_s, scale, bias, mean, var, kernel):
                    return bn_relu_matmul(
                        y_s, scale, bias, mean, var, kernel, eps=eps,
                        batch_stats=not running, axis_name=axis,
                        global_count=m_global,
                    )

                # check_vma=False: the varying-mesh-axes checker cannot
                # see through pallas_call.
                y = shard_map(
                    per_shard, mesh=self.pallas_mesh,
                    in_specs=(P(axis, None, None, None),
                              P(), P(), P(), P(), P()),
                    out_specs=P(axis, None, None, None),
                    check_vma=False,
                )(y, scale, bias, mean, var, kernel)
            else:
                y = bn_relu_matmul(
                    y, scale, bias, mean, var, kernel,
                    eps=eps,
                    # Eval/frozen BN: stats are constants; the
                    # backward's statistics correction must not apply.
                    batch_stats=not running,
                )
        else:
            y = _norm_relu(self.norm, self.act, self.fused, y)
            y = self.conv(self.filters * 4, (1, 1))(y)
        if residual.shape[-1] != self.filters * 4 or self.strides != 1:
            residual = self.conv(
                self.filters * 4, (1, 1), (self.strides, self.strides),
                name="conv_proj",
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        # Zero-init the last BN scale so each block starts as identity —
        # standard ResNet-v1.5 training recipe.
        if self.fused:
            return self.norm(scale_init=nn.initializers.zeros_init(),
                             act="relu")(y, residual=residual)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        return self.act(residual + y)


class ResNetBlock(nn.Module):
    """Basic 3x3 -> 3x3 block (ResNet-18/34)."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable = nn.relu
    fused: bool = False

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), (self.strides, self.strides))(x)
        y = _norm_relu(self.norm, self.act, self.fused, y)
        y = self.conv(self.filters, (3, 3))(y)
        if residual.shape[-1] != self.filters or self.strides != 1:
            residual = self.conv(
                self.filters, (1, 1), (self.strides, self.strides), name="conv_proj"
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        if self.fused:
            return self.norm(scale_init=nn.initializers.zeros_init(),
                             act="relu")(y, residual=residual)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        return self.act(residual + y)


class ResNet(nn.Module):
    """Configurable ResNet; ``stage_sizes=[3,4,6,3]`` + bottleneck = ResNet-50."""

    stage_sizes: Sequence[int]
    block_cls: ModuleDef = BottleneckBlock
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    act: Callable = nn.relu
    # torchvision pads stride-2 convs symmetrically ((k-1)//2 each side)
    # where XLA's SAME pads asymmetrically on even inputs. Irrelevant when
    # training from scratch; REQUIRED for numerical parity when loading
    # torchvision-layout pretrained weights (models/pretrained.py).
    torch_padding: bool = False
    # Fused BN+relu(+residual) with a minimal-residual custom VJP
    # (ops/fused_norm.py) — saves fewer activation-sized residuals for
    # the backward pass. Parameter paths are IDENTICAL to the unfused model,
    # so checkpoints and pretrained weights port both ways.
    # "pallas" (bottleneck blocks only) additionally fuses the middle
    # BN's apply into the third 1x1 conv as a Pallas matmul prologue
    # (ops/fused_matmul.py) — the second HBM byte cut.  Single-device
    # by default; pass pallas_mesh (+ pallas_axis) for the
    # batch-sharded shard_map form under a mesh.
    fused_bn: bool | str = False
    pallas_mesh: Any = None
    pallas_axis: str = "data"

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.torch_padding:
            def conv(features, kernel_size, strides=(1, 1), **kw):
                pad = tuple(((k - 1) // 2, (k - 1) // 2) for k in kernel_size)
                return nn.Conv(
                    features, kernel_size, strides, padding=pad,
                    use_bias=False, dtype=self.dtype, **kw,
                )
        else:
            conv = functools.partial(
                nn.Conv, use_bias=False, dtype=self.dtype, padding="SAME"
            )
        if self.fused_bn:
            if self.act is not nn.relu:
                raise ValueError("fused_bn supports act=nn.relu only")
            if (self.fused_bn == "pallas"
                    and self.block_cls is not BottleneckBlock):
                # Only the bottleneck block has the 1x1-conv site the
                # Pallas prologue fusion targets; silently running the
                # plain fused path would benchmark the wrong program.
                raise ValueError(
                    "fused_bn='pallas' requires block_cls=BottleneckBlock "
                    "(ResNet-50/101); use fused_bn=True for basic-block "
                    "models"
                )
        if self.pallas_mesh is not None and self.fused_bn != "pallas":
            # Same silent-wrong-program hazard in the other direction.
            raise ValueError(
                "pallas_mesh= requires fused_bn='pallas' (a mesh with "
                "the HLO fused path would be silently ignored)"
            )
        if self.fused_bn:
            from ..ops.fused_norm import BatchNorm as FusedBatchNorm

            norm_cls = FusedBatchNorm
        else:
            norm_cls = nn.BatchNorm
        norm = functools.partial(
            norm_cls,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
        )
        # The named scopes put "stem", "stage1".., "head" into every
        # operation's name in the compiled step (forward and backward),
        # which is where a device trace can be read by layer.
        with jax.named_scope("stem"):
            x = x.astype(self.dtype)
            x = conv(self.num_filters, (7, 7), (2, 2), name="conv_init")(x)
            x = _norm_relu(norm, self.act, self.fused_bn, x, name="norm_init")
            x = nn.max_pool(
                x, (3, 3), strides=(2, 2),
                padding=((1, 1), (1, 1)) if self.torch_padding else "SAME",
            )
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                # (pallas implies BottleneckBlock — validated above.)
                block_kw = (
                    {"pallas_mesh": self.pallas_mesh,
                     "pallas_axis": self.pallas_axis}
                    if self.fused_bn == "pallas" else {}
                )
                with jax.named_scope(f"stage{i + 1}"):
                    x = self.block_cls(
                        filters=self.num_filters * 2**i,
                        strides=strides,
                        conv=conv,
                        norm=norm,
                        act=self.act,
                        fused=self.fused_bn,
                        **block_kw,
                    )(x)
        with jax.named_scope("head"):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
            return x.astype(jnp.float32)


ResNet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=ResNetBlock)
ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock)
