"""The one rule for Pallas interpret mode, shared by every kernel in ops/."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → interpret on a CPU backend only (tests, dry runs);
    compiled everywhere else. Interpret mode on a ``tpu`` backend is an
    error, never a choice: it would run a kernel's Python emulation
    under the chip's name.

    An explicit ``False`` on a CPU backend is allowed — that is how a
    kernel is compiled for a described (not attached) TPU topology.
    """
    backend = jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError(
            "Pallas interpret mode requested on a tpu backend; the kernels "
            "in ops/ only run compiled there"
        )
    return bool(interpret)
