"""The five IR rules of ``dsst audit``.

Each rule reads the shared :class:`~.core.EntrypointContext` — one
trace/lower/compile per entrypoint no matter how many rules run — and
emits :class:`~.core.AuditFinding`s whose ``ident`` is chosen to be
stable under message rewording (the baseline keys hash idents, not
prose).
"""

from __future__ import annotations

import math
import re
from typing import Iterable

from .core import (
    COST_TOLERANCE,
    AuditFinding,
    AuditRule,
    EntrypointContext,
    _TraceFailed,
    register_rule,
)

# -- donation -----------------------------------------------------------------

_ALIAS_ATTR = "tf.aliasing_output"


def _main_signature(stablehlo: str) -> str | None:
    """The balanced-paren argument list of the public @main func."""
    marker = "func.func public @main("
    start = stablehlo.find(marker)
    if start < 0:
        return None
    i = start + len(marker)
    depth = 1
    j = i
    while j < len(stablehlo) and depth:
        c = stablehlo[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        j += 1
    return stablehlo[i:j - 1]


def _main_params(sig: str) -> list[tuple[int, str]]:
    """(argnum, type+attrs chunk) per @main parameter. Attribute dicts
    nest braces inside quoted sharding strings, so split on the %argN
    markers instead of trying to brace-match."""
    parts = re.split(r"%arg(\d+):", sig)
    return [
        (int(parts[k]), parts[k + 1])
        for k in range(1, len(parts) - 1, 2)
    ]


@register_rule
class DonationRule(AuditRule):
    name = "donation"
    description = (
        "args the registry expects donated (train step: params+"
        "opt_state) carry tf.aliasing_output in the lowered IR — a "
        "dropped donate_argnums or an un-aliasable output doubles "
        "peak HBM for the step"
    )

    def check(self, ctx: EntrypointContext) -> Iterable[AuditFinding]:
        if not ctx.spec.expect_donated:
            return
        sig = _main_signature(ctx.stablehlo)
        if sig is None:
            yield self.finding(
                ctx, "no-main",
                "lowered module has no public @main — cannot verify "
                "donation",
            )
            return
        params = _main_params(sig)
        aliased = {
            num for num, chunk in params if _ALIAS_ATTR in chunk
        }
        leaves = ctx.flat_avals()
        if len(params) != len(leaves):
            # keep_unused=False dropped some inputs — positional
            # mapping is unreliable, and a donated-but-unused arg is
            # itself suspicious enough to surface.
            yield self.finding(
                ctx, "arg-count-mismatch",
                f"lowered main has {len(params)} parameters but the "
                f"call signature flattens to {len(leaves)} leaves "
                "(unused args dropped?) — donation audit cannot map "
                "leaves to parameters",
            )
            return
        expected = set(ctx.spec.expect_donated)
        for pos, (argnum, leaf) in enumerate(leaves):
            if argnum not in expected or pos in aliased:
                continue
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = getattr(leaf, "dtype", "?")
            yield self.finding(
                ctx, f"arg{argnum}.leaf{pos}",
                f"arg {argnum} leaf #{pos} ({dtype}{list(shape)}) is "
                "expected donated but carries no tf.aliasing_output in "
                "the lowered IR — the buffer will be copied, not "
                "reused",
            )


# -- dtype discipline ---------------------------------------------------------

_WIDE = {"float64", "complex128"}


@register_rule
class DtypeDisciplineRule(AuditRule):
    name = "dtype-discipline"
    description = (
        "no tensor-sized f64/c128 minted under the x64 lens (latent "
        "promotions the f32 config silently canonicalizes away), and "
        "same-dtype convert churn stays under the entrypoint's budget"
    )

    def check(self, ctx: EntrypointContext) -> Iterable[AuditFinding]:
        # (a) latent wide-float promotions, visible only with x64 on.
        # A program that cannot even TRACE under x64 has a dtype-split
        # bug (mixed f32/f64 carries) — that is this rule's finding,
        # not an infrastructure error.
        try:
            x64_jaxpr = ctx.jaxpr_x64
        except _TraceFailed as e:
            yield self.finding(
                ctx, "x64-untraceable",
                f"program does not trace under the x64 lens — a "
                f"dtype-split bug (f32 state meeting f64 values): "
                f"{e.detail}",
            )
            x64_jaxpr = None
        seen: dict[tuple[str, str, tuple], int] = {}
        for eqn in ([] if x64_jaxpr is None
                    else ctx.all_eqns(x64_jaxpr)):
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                dtype = str(getattr(aval, "dtype", ""))
                if dtype not in _WIDE:
                    continue
                shape = tuple(getattr(aval, "shape", ()))
                if math.prod(shape) <= 1:
                    # Scalar f64 (optax bias-correction arithmetic,
                    # loop counters) costs nothing and cannot reach an
                    # activation-sized tensor without showing up here
                    # as a tensor itself.
                    continue
                key = (eqn.primitive.name, dtype, shape)
                seen[key] = seen.get(key, 0) + 1
        for (prim, dtype, shape), count in sorted(seen.items()):
            yield self.finding(
                ctx, f"wide:{prim}:{dtype}:{list(shape)}",
                f"{prim} produces tensor-sized {dtype}{list(shape)} "
                f"({count}x) under the x64 lens — a latent f64 "
                "promotion that doubles bytes the day x64 is enabled; "
                "pin the dtype explicitly",
            )
        # (b) weak-type churn: converts that change nothing but the
        # weak flag. A handful is idiomatic; a flood means scalars are
        # being re-canonicalized inside the hot loop.
        churn = 0
        for eqn in ctx.all_eqns(ctx.jaxpr):
            if eqn.primitive.name != "convert_element_type":
                continue
            try:
                src = eqn.invars[0].aval.dtype
                dst = eqn.outvars[0].aval.dtype
            except AttributeError:
                continue
            if src == dst:
                churn += 1
        budget = ctx.spec.weak_churn_budget
        if churn > budget:
            yield self.finding(
                ctx, "weak-churn",
                f"{churn} same-dtype convert_element_type eqns (budget "
                f"{budget}) — weak-type churn; hoist scalar "
                "canonicalization out of the traced body",
            )


# -- sharding / collectives ---------------------------------------------------

# `%all-gather.3 = f32[64,128]{1,0} all-gather(...)` in optimized HLO.
# The shape expression may also be a TUPLE — XLA's collective combiner
# and every async `-start` op emit e.g.
# `%all-reduce.1 = (f32[1048576]{0}, f32[524288]{0}) all-reduce(...)` —
# and those combined ops are exactly the largest collectives, so the
# pattern must capture the whole expression and sum every element.
# `-done` ops deliberately don't match (no `(` right after the op
# name): their payload was already counted at the matching `-start`.
_COLLECTIVE_RE = re.compile(
    r"=[ \t]*(\([^)\n]*\)|\S+)[ \t]*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_TOKEN_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# Default per-op byte ceilings. All-reduce is the collective
# data-parallel training is MADE of (gradient averaging), so it gets
# headroom; all-gather / all-to-all above 1 MiB in a program that
# declared its shardings is almost always GSPMD failing to propagate a
# spec (the "surprise all-gather" ROADMAP item 1 bans).
_DEFAULT_LIMITS = {
    "all-reduce": 64 << 20,
    "reduce-scatter": 64 << 20,
    "collective-permute": 64 << 20,
    "all-gather": 1 << 20,
    "all-to-all": 1 << 20,
}
_DEFAULT_REPLICATED_LIMIT = 32 << 20


@register_rule
class ShardingCollectivesRule(AuditRule):
    name = "sharding-collectives"
    description = (
        "optimized SPMD HLO contains no collective moving more bytes "
        "than the entrypoint's ceiling (surprise all-gathers fail "
        "small), and no large input is fully replicated"
    )

    def check(self, ctx: EntrypointContext) -> Iterable[AuditFinding]:
        limits = dict(_DEFAULT_LIMITS)
        if ctx.spec.collective_limits:
            limits.update(ctx.spec.collective_limits)
        counts: dict[tuple[str, str, int], int] = {}
        for shape_expr, op in _COLLECTIVE_RE.findall(ctx.optimized_hlo):
            nbytes = 0
            # Layout annotations ({1,0}) are stripped from the
            # normalized shape so the finding ident (the baseline key)
            # survives layout-only recompiles.
            parts = []
            for dtype, dims in _SHAPE_TOKEN_RE.findall(shape_expr):
                b = _DTYPE_BYTES.get(dtype, 4)
                for d in dims.split(","):
                    if d:
                        b *= int(d)
                nbytes += b
                parts.append(f"{dtype}[{dims}]")
            if not parts:
                continue  # no array shape before the op name: not an eqn
            if nbytes <= limits.get(op, _DEFAULT_LIMITS["all-gather"]):
                continue
            key = (op, "+".join(parts), nbytes)
            counts[key] = counts.get(key, 0) + 1
        for (op, shape_s, nbytes), n in sorted(counts.items()):
            yield self.finding(
                ctx, f"{op}:{shape_s}",
                f"{op} of {shape_s} ({nbytes} bytes, {n}x) exceeds the "
                f"{limits.get(op, 0)}-byte ceiling — an unplanned "
                "cross-chip materialization under the abstract mesh",
            )
        # Large fully-replicated inputs: every chip holds a full copy.
        limit = (
            ctx.spec.replicated_bytes_limit
            if ctx.spec.replicated_bytes_limit is not None
            else _DEFAULT_REPLICATED_LIMIT
        )
        import numpy as np

        for pos, (argnum, leaf) in enumerate(ctx.flat_avals()):
            sharding = getattr(leaf, "sharding", None)
            if sharding is None or not getattr(
                sharding, "is_fully_replicated", False
            ):
                continue
            shape = tuple(getattr(leaf, "shape", ()))
            try:
                nbytes = int(
                    np.dtype(leaf.dtype).itemsize * math.prod(shape)
                )
            except TypeError:
                continue
            if nbytes <= limit:
                continue
            yield self.finding(
                ctx, f"replicated:arg{argnum}.leaf{pos}",
                f"arg {argnum} leaf #{pos} ({leaf.dtype}{list(shape)}, "
                f"{nbytes} bytes) is fully replicated over the mesh — "
                "above the ceiling; shard it or raise "
                "replicated_bytes_limit with a reason",
            )


# -- host interop -------------------------------------------------------------

# jax 0.9.0 names: ``jax.debug.print`` lowers to its own ``debug_print``
# primitive, ``jax.debug.callback`` to ``debug_callback``.
_CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
}


@register_rule
class HostInteropRule(AuditRule):
    name = "host-interop"
    description = (
        "no pure_callback/io_callback/debug.print inside compiled hot "
        "paths — each one fences the program on a host round-trip"
    )

    def check(self, ctx: EntrypointContext) -> Iterable[AuditFinding]:
        if not ctx.spec.hotpath:
            return
        counts: dict[str, int] = {}
        for eqn in ctx.all_eqns(ctx.jaxpr):
            prim = eqn.primitive.name
            if prim in _CALLBACK_PRIMS:
                counts[prim] = counts.get(prim, 0) + 1
        for prim, n in sorted(counts.items()):
            yield self.finding(
                ctx, f"callback:{prim}",
                f"{n} {prim} eqn(s) inside the compiled program — a "
                "host sync per step on a hot path; move it out of the "
                "jit or mark the entrypoint hotpath=False with a "
                "reason",
            )


# -- program baseline ---------------------------------------------------------


@register_rule
class ProgramBaselineRule(AuditRule):
    name = "program-baseline"
    description = (
        "the entrypoint's abstract signature+jaxpr hash and its "
        "FLOPs/bytes cost stay pinned to AUDIT_BASELINE.json — "
        "unintended program changes and cost regressions fail until "
        "re-baselined with a reason"
    )

    def check(self, ctx: EntrypointContext) -> Iterable[AuditFinding]:
        baseline = getattr(ctx, "baseline_programs", None)
        if baseline is None:
            return
        rec = baseline.get(ctx.name)
        if rec is None:
            yield self.finding(
                ctx, "unbaselined",
                "entrypoint has no program baseline — pin it with "
                "`dsst audit --update-baseline --reason '...'`",
            )
            return
        current = ctx.program_hash()
        if current != rec.get("hash"):
            yield self.finding(
                ctx, "hash",
                f"program changed: jaxpr/signature hash {current} != "
                f"baselined {rec.get('hash')} — re-pin with "
                "--update-baseline --reason if intended",
            )
        cost = ctx.cost
        if cost is None:
            return
        for kind in ("flops", "bytes"):
            budget = rec.get(kind)
            if budget is None:
                continue
            if cost[kind] > budget * (1.0 + COST_TOLERANCE):
                yield self.finding(
                    ctx, kind,
                    f"{kind} regression: {cost[kind]:.4g} > budget "
                    f"{budget:.4g} (+{COST_TOLERANCE:.0%} tolerance) — "
                    "the compiled program got more expensive; fix or "
                    "re-pin with --update-baseline --reason",
                )
