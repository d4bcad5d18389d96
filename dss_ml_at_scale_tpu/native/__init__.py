"""ctypes binding for the native C++ image pipeline.

The shared library is built lazily from the bundled source with the system
``g++`` (no pybind11 — plain ``extern "C"`` + ctypes, per this repo's
toolchain constraints) and cached next to the source. The public surface is
:func:`native_available` and :func:`decode_jpeg_batch`; callers that want
per-image fallback (e.g. exotic colorspaces) read the returned status mask.

Replaces the host hot loop of the reference's Petastorm reader workers
(``deep_learning/2.distributed-data-loading-petastorm.py:282-296``) with a
GIL-free C++ decode pool.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

_SRC = Path(__file__).with_name("image_pipeline.cpp")
_LIB = Path(__file__).with_name("libdsst_image.so")
_HASH = Path(__file__).with_name("libdsst_image.srchash")
_ABI = 3

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_error: str | None = None


def _src_hash() -> str:
    """Cache key: source content + host ISA identity.

    The .so is built with ``-march=native`` and is not tracked by git,
    but a copy of the working tree (a baked image, the chip tool's copy
    of the disk) carries it to other hosts, where a binary from a newer
    CPU would SIGILL. So the host's CPU model and feature flags are part
    of the staleness key: on any other CPU the stamp does not match and
    ``_load`` rebuilds instead of loading.
    """
    import hashlib
    import platform

    isa = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            wanted = {"model name", "flags", "Features"}
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in wanted:
                    isa += line
                    wanted.discard(key)
                    if key != "model name":
                        break
    except OSError:
        pass
    return hashlib.sha256(_SRC.read_bytes() + isa.encode()).hexdigest()


def _build() -> None:
    # Compile to a temp path and durably publish into place: atomic for
    # other processes racing to load the same .so (the in-process lock
    # cannot cover multi-process launches / pytest-xdist), and fsynced
    # so a host dying right after the build can't leave a torn .so that
    # every later import would dlopen-crash on. Publish order matters:
    # the hash stamp lands only after the .so it vouches for.
    from ..resilience.durability import durable_replace, durable_write_text

    tmp = _LIB.with_name(f".{_LIB.name}.{os.getpid()}.tmp")
    cmd = [
        "g++", "-O3", "-march=native", "-std=c++17", "-fPIC", "-shared",
        str(_SRC), "-o", str(tmp), "-ljpeg", "-lpthread",
    ]
    try:
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except subprocess.CalledProcessError:
            # Some toolchains lack -march=native; retry plain.
            cmd.remove("-march=native")
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        durable_replace(tmp, _LIB, kind="native")
        durable_write_text(_HASH, _src_hash(), kind="native")
    finally:
        tmp.unlink(missing_ok=True)


def _load() -> ctypes.CDLL | None:
    global _lib, _load_error
    if _lib is not None or _load_error is not None:
        return _lib
    with _lock:
        if _lib is not None or _load_error is not None:
            return _lib
        try:
            # Content-hash staleness (mtime is meaningless after a fresh
            # checkout, and the .so is -march=native, i.e. host-specific).
            stale = (
                not _LIB.exists()
                or not _HASH.exists()
                or _HASH.read_text().strip() != _src_hash()
            )
            if stale:
                _build()
            lib = ctypes.CDLL(str(_LIB))
            lib.dsst_abi_version.restype = ctypes.c_int
            if lib.dsst_abi_version() != _ABI:
                raise RuntimeError("native ABI mismatch; rebuild required")
            lib.dsst_decode_batch.restype = ctypes.c_int
            lib.dsst_decode_batch.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_ulong),
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float),
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_void_p,
                ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
            ]
            _lib = lib
        except (OSError, subprocess.CalledProcessError, RuntimeError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            _load_error = f"native image pipeline unavailable: {detail}"
        return _lib


def native_available() -> bool:
    """True if the C++ pipeline compiled/loaded on this host."""
    return _load() is not None


def load_error() -> str | None:
    _load()
    return _load_error


def decode_jpeg_batch(
    jpegs: list[bytes],
    *,
    resize: int = 256,
    crop: int = 224,
    mean: np.ndarray | None = None,
    std: np.ndarray | None = None,
    chw: bool = True,
    dtype: str = "float32",
    fast_scale: bool = False,
    num_threads: int | None = None,  # default: one pool of cpu_count threads;
    # callers running several decode batches concurrently should divide the
    # host's cores among themselves to avoid oversubscription
) -> tuple[np.ndarray, np.ndarray]:
    """Decode a batch of JPEG byte strings into an image tensor.

    Returns ``(images, ok)`` where ``images`` has shape ``[n,3,crop,crop]``
    (or HWC with ``chw=False``) and ``ok`` is a boolean mask; failed rows
    are zero-filled and should be re-decoded by the caller's fallback.

    ``dtype="float32"``: values in [0, 1], or normalized when
    ``mean``/``std`` (3-vectors) are given — the torchvision-parity path.
    ``dtype="uint8"``: the raw quantized [0, 255] bytes, 4x less memory
    per image; normalization then belongs to the device program
    (``mean``/``std`` must be None).

    ``fast_scale=True`` decodes big sources directly at the largest
    DCT-domain m/8 scale covering ``resize`` (PIL draft-mode equivalent):
    much less IDCT work per image, pixel values slightly different from
    the full-decode path (the antialiased resize still runs).
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(_load_error or "native pipeline unavailable")
    if dtype not in ("float32", "uint8"):
        raise ValueError(f"dtype must be 'float32' or 'uint8', got {dtype!r}")
    out_u8 = dtype == "uint8"
    if out_u8 and (mean is not None or std is not None):
        raise ValueError(
            "uint8 output is raw [0,255]; normalize on device, not here"
        )
    n = len(jpegs)
    shape = (n, 3, crop, crop) if chw else (n, crop, crop, 3)
    out = np.zeros(shape, np.uint8 if out_u8 else np.float32)
    if n == 0:
        return out, np.zeros(0, bool)

    do_norm = mean is not None or std is not None
    mean_a = np.ascontiguousarray(
        mean if mean is not None else np.zeros(3), np.float32
    )
    std_a = np.ascontiguousarray(std if std is not None else np.ones(3), np.float32)

    ptrs = (ctypes.c_char_p * n)(*jpegs)
    sizes = (ctypes.c_ulong * n)(*[len(b) for b in jpegs])
    statuses = np.zeros(n, np.int32)
    if num_threads is None:
        num_threads = min(n, os.cpu_count() or 1)
    lib.dsst_decode_batch(
        ctypes.cast(ptrs, ctypes.POINTER(ctypes.c_char_p)),
        sizes, n, resize, crop, int(do_norm),
        mean_a.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std_a.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        int(chw),
        int(out_u8),
        int(fast_scale),
        out.ctypes.data_as(ctypes.c_void_p),
        int(num_threads),
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
    )
    return out, statuses == 0
