"""Inputs from the seed: images, labels, the table's JPEGs.

Each row has a generator stream of its own (seed, row), so rows all
differ and any subset can be made again.
"""

from __future__ import annotations

import io
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8


def _rng(seed: int, *stream) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(int, stream)])


def labels(seed: int, n: int, classes: int) -> np.ndarray:
    return _rng(seed, 1).integers(0, classes, n).astype(np.int32)


def image_batches(seed: int, n_batches: int, batch: int, crop: int) -> list:
    """``n_batches`` host batches of normalised float32 NHWC images (unit
    normal pixels: what a normalised photograph's are, roughly)."""
    def one(i):
        return _rng(seed, 2, i).standard_normal(
            (batch, crop, crop, 3), dtype=np.float32)

    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(one, range(n_batches)))


def grating_jpeg(seed: int, row: int, label: int, classes: int, size: int,
                 quality: int) -> bytes:
    """One JPEG as the program's ``datagen images`` draws them: a grating
    whose angle and frequency follow the label, with phase, contrast and
    pixel noise of its own."""
    from PIL import Image

    rng = _rng(seed, 3, row)
    yy, xx = np.mgrid[0:size, 0:size] / size
    angle = label * np.pi / classes
    freq = 3.0 + 1.5 * (label % 5)
    g = np.sin(2 * np.pi * freq * (xx * np.cos(angle) + yy * np.sin(angle))
               + rng.uniform(0, 2 * np.pi))
    base = 0.5 + 0.4 * rng.uniform(0.5, 1.0) * g
    img = base[..., None] + rng.normal(0, 0.08, (size, size, 3))
    buf = io.BytesIO()
    Image.fromarray((img.clip(0, 1) * 255).astype(np.uint8)).save(
        buf, format="JPEG", quality=quality)
    return buf.getvalue()


def table_rows(seed: int, n: int, classes: int, size: int,
               quality: int) -> tuple:
    """(jpegs, labels) of the table's ``n`` rows."""
    lab = labels(seed, n, classes)
    with ThreadPoolExecutor(THREADS) as pool:
        jpegs = list(pool.map(
            lambda i: grating_jpeg(seed, i, int(lab[i]), classes, size,
                                   quality), range(n)))
    return jpegs, lab
