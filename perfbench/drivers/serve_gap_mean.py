"""The serve driver (``drivers/serve.py``) with one more number held to
a limit: ``logit_gap_mean``, the mean over the checked tokens of how far
the served token's logit lies below the reference's best.

``serve.py`` already computes that mean for the low-precision control,
and logs it for the program, but gives ``harness.compare`` the program's
largest gap only.  Where one routed expert changing for one token moves
the largest gap as far as a lower precision does (a model whose router
picks many experts among many), the largest gap cannot tell the program
from the control and the mean can.  Everything else is ``serve.run``'s
own: this module records the program's gaps as ``serve`` computes them
and adds their mean to the numbers it compares, for the program only (the
control's numbers carry their own mean already).
"""

from __future__ import annotations

from drivers import serve


class _Harness:
    """``harness`` as ``serve.run`` sees it, its ``compare`` given the
    program's mean gap where its numbers lack one."""

    def __init__(self, harness, means: list):
        self._harness, self._means = harness, means

    def __getattr__(self, name):
        return getattr(self._harness, name)

    def compare(self, numbers: dict, limits: dict, quiet: bool = False):
        if "logit_gap_mean" not in numbers:
            numbers["logit_gap_mean"] = self._means[-1]
        return self._harness.compare(numbers, limits, quiet)


def run(cell, **kwargs):
    means: list = []
    gaps, harness = serve._gaps, serve.harness

    def recorded(sample, *args):
        served, control = gaps(sample, *args)
        means.append(sum(served) / len(served) if served else float("inf"))
        return served, control

    serve._gaps, serve.harness = recorded, _Harness(harness, means)
    try:
        return serve.run(cell, **kwargs)
    finally:
        serve._gaps, serve.harness = gaps, harness
