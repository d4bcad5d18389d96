"""Operations of a ResNet (v1.5 bottleneck) from the configuration's shapes."""

from __future__ import annotations


def _same(n: int, stride: int) -> int:
    return -(-n // stride)


def forward_macs(cfg: dict) -> int:
    """Multiply-adds of one image's forward pass: convolutions and the
    classifier (normalisation, activations and pooling are not MACs)."""
    crop, width = cfg["crop"], cfg["num_filters"]
    hw = _same(crop, 2)
    macs = hw * hw * 7 * 7 * 3 * width           # stem
    hw = _same(hw, 2)                            # max pool
    c_in = width
    for i, count in enumerate(cfg["stage_sizes"]):
        f = width * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out = _same(hw, stride)
            macs += hw * hw * c_in * f           # 1x1 at input resolution
            macs += out * out * 9 * f * f        # 3x3, carries the stride
            macs += out * out * f * 4 * f        # 1x1 expand
            if c_in != 4 * f or stride != 1:
                macs += out * out * c_in * 4 * f  # projection shortcut
            hw, c_in = out, 4 * f
    return macs + c_in * cfg["num_classes"]


def train_flops_per_sample(cfg: dict) -> int:
    """Forward + backward: 3 x forward, 2 FLOPs a multiply-add."""
    return 3 * 2 * forward_macs(cfg)
