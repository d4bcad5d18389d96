"""IR-level program auditor: jaxpr/HLO contracts over real entrypoints.

``dsst lint`` (the first analysis tier) stops at the Python AST — it
can prove a ``jit`` body never branches on a traced value, but it
cannot see what XLA actually receives. This second tier abstractly
traces a registry of the package's REAL compiled entrypoints (the
train/eval steps, the serving scorer, the LM decode step, the fused
ops, the batched SARIMAX fitter — see :mod:`.entrypoints`) with
``jax.eval_shape``-style abstract inputs on a simulated ≥8-device mesh
and runs rules over the lowered IR:

- **donation**: args the program declares donated are actually aliased
  in the lowered StableHLO (the train step donates params+opt_state);
- **dtype-discipline**: no tensor-sized f64/c128 silently minted under
  an x64 lens, no weak-type convert churn beyond budget;
- **sharding-collectives**: no oversized all-gather/reduce-scatter in
  the optimized SPMD HLO, no large fully-replicated inputs where the
  registry expects sharding;
- **host-interop**: no ``pure_callback``/``io_callback``/``debug``
  callbacks inside compiled hot paths;
- **program-baseline**: a content-addressed hash of each entrypoint's
  abstract signature + jaxpr, plus FLOPs/bytes budgets, committed in
  ``AUDIT_BASELINE.json`` — an unintended program change or cost
  regression fails CI until explicitly re-baselined with a reason.

The framework mirrors :mod:`..core` deliberately: one shared
trace/lower/compile per entrypoint (:class:`EntrypointContext` is the
``FileContext`` of this tier), per-entrypoint suppressions with
MANDATORY reasons (declared in the registry, where the entrypoint is
defined), baseline add/expire/reopen semantics, text/JSON renderers,
and exit codes 0/1/2.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import re
from pathlib import Path
from typing import Any, Callable, Iterable, Mapping, Sequence

from ..core import Finding, LintUsageError, REPO_ROOT

DEFAULT_AUDIT_BASELINE = REPO_ROOT / "AUDIT_BASELINE.json"
AUDIT_SCHEMA_VERSION = 1

# Fraction by which flops/bytes may exceed their committed budget before
# the program-baseline rule calls it a regression. Compiler noise on
# identical programs is zero (the hash would catch any change first);
# the headroom exists for cost-model jitter across jaxlib patch levels.
COST_TOLERANCE = 0.05

# Memory addresses in jaxpr params (`<function f at 0x7f..>`,
# partial reprs) churn per process; scrub them so the program hash is
# stable across runs of the same code.
_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")
# A set prints in hash-seed order (shard_map's ``manual_axes=frozenset(
# {'pipe', 'data'})``): sorted, so the program hash is the same in every
# process.
_SET_RE = re.compile(r"frozenset\(\{([^{}]*)\}\)")


def _sorted_set(m: "re.Match") -> str:
    items = sorted(x.strip() for x in m.group(1).split(","))
    return "frozenset({" + ", ".join(items) + "})"


class AuditUsageError(LintUsageError):
    """Bad invocation (unknown entrypoint/rule, missing --reason): exit 2."""


@dataclasses.dataclass(frozen=True)
class AuditFinding(Finding):
    """One audit diagnostic. ``path`` holds the entrypoint name and
    ``ident`` the stable within-entrypoint identity the baseline key
    hashes (so message rewording never churns the baseline)."""

    ident: str = ""

    def text(self) -> str:
        return f"{self.path}: [{self.rule}] {self.message}"

    def to_json(self) -> dict:
        out = super().to_json()
        out["entrypoint"] = self.path
        out["ident"] = self.ident
        return out


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One registered entrypoint, built and ready to lower.

    ``fn`` is the REAL production callable (not a test twin); ``args``
    are abstract or tiny concrete inputs already carrying their
    production shardings; ``jit_kwargs`` are the exact keywords the
    production jit passes (``donate_argnums``, ``out_shardings``,
    ``static_argnums`` ...). ``expect_donated`` lists argnums whose
    every leaf must alias an output in the lowered IR. ``suppress``
    maps rule name -> mandatory reason for per-entrypoint suppressions.
    """

    name: str
    fn: Callable
    args: tuple
    jit_kwargs: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    # The production-built jit object, when the registry has one (e.g.
    # trainer.make_train_step) — the audit then lowers EXACTLY what
    # production compiles; ``jit_kwargs`` stays descriptive (signature
    # hashing) and as the fallback constructor.
    jitted: Any = None
    expect_donated: tuple[int, ...] = ()
    hotpath: bool = True
    # sharding-collectives knobs (bytes). ``None`` = rule defaults.
    collective_limits: Mapping[str, int] | None = None
    replicated_bytes_limit: int | None = None
    # dtype-discipline: tolerated same-dtype convert_element_type count.
    weak_churn_budget: int = 8
    suppress: Mapping[str, str] = dataclasses.field(default_factory=dict)


class EntrypointContext:
    """Everything rules need about ONE entrypoint, computed at most once.

    The trace artifacts are lazy: a rule subset (``--rules donation``)
    pays for lowering only, never for compilation; the dtype rule's x64
    lens re-traces the jaxpr without touching the lowered program. A
    failure in any stage is captured as ``trace_error`` — the runner
    reports it as a finding instead of aborting the whole audit.
    """

    def __init__(self, spec: ProgramSpec, mesh):
        self.spec = spec
        self.mesh = mesh
        self.name = spec.name
        self._jitted = None
        self._jaxpr = None
        self._jaxpr_x64 = None
        self._lowered = None
        self._stablehlo = None
        self._compiled = None
        self._optimized_hlo = None
        self._cost = _UNSET
        self.trace_error: str | None = None

    def _capture(self, stage: str, fn):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - reported as a finding
            self.trace_error = f"{stage}: {type(e).__name__}: {e}"
            raise _TraceFailed(self.name, self.trace_error) from e

    @property
    def jitted(self):
        if self._jitted is None:
            if self.spec.jitted is not None:
                self._jitted = self.spec.jitted
            else:
                import jax

                self._jitted = self._capture(
                    "jit",
                    lambda: jax.jit(self.spec.fn, **self.spec.jit_kwargs),
                )
        return self._jitted

    @property
    def jaxpr(self):
        """ClosedJaxpr of the raw fn under the production config."""
        if self._jaxpr is None:
            import jax

            static = _static_argnums(self.spec)
            self._jaxpr = self._capture(
                "trace",
                lambda: jax.make_jaxpr(
                    self.spec.fn, static_argnums=static
                )(*self.spec.args),
            )
        return self._jaxpr

    @property
    def jaxpr_x64(self):
        """Re-trace under the x64 lens: latent f64 promotions that the
        production config silently canonicalizes away become visible."""
        if self._jaxpr_x64 is None:
            import jax

            static = _static_argnums(self.spec)

            def trace():
                with jax.enable_x64(True):
                    return jax.make_jaxpr(
                        self.spec.fn, static_argnums=static
                    )(*self.spec.args)

            self._jaxpr_x64 = self._capture("trace-x64", trace)
        return self._jaxpr_x64

    @property
    def lowered(self):
        if self._lowered is None:
            self._lowered = self._capture(
                "lower", lambda: self.jitted.lower(*self.spec.args)
            )
        return self._lowered

    @property
    def stablehlo(self) -> str:
        if self._stablehlo is None:
            self._stablehlo = self._capture(
                "stablehlo", lambda: self.lowered.as_text()
            )
        return self._stablehlo

    @property
    def compiled(self):
        if self._compiled is None:
            self._compiled = self._capture(
                "compile", lambda: self.lowered.compile()
            )
        return self._compiled

    @property
    def optimized_hlo(self) -> str:
        if self._optimized_hlo is None:
            self._optimized_hlo = self._capture(
                "hlo", lambda: self.compiled.as_text()
            )
        return self._optimized_hlo

    @property
    def cost(self) -> dict | None:
        """Normalized ``{"flops": .., "bytes": ..}`` or None when the
        backend's cost model declines to answer."""
        if self._cost is _UNSET:
            try:
                raw = self.compiled.cost_analysis()
            except Exception:  # noqa: BLE001 - cost model is best-effort
                raw = None
            if isinstance(raw, (list, tuple)):
                raw = raw[0] if raw else None
            if isinstance(raw, dict):
                self._cost = {
                    "flops": float(raw.get("flops", 0.0)),
                    "bytes": float(raw.get("bytes accessed", 0.0)),
                }
            else:
                self._cost = None
        return self._cost

    # -- derived views -----------------------------------------------------

    def flat_avals(self) -> list[tuple[int, Any]]:
        """(argnum, aval-like leaf) in jit flattening order, static
        argnums excluded (they are not HLO parameters)."""
        import jax

        static = set(_static_argnums(self.spec))
        out = []
        for i, a in enumerate(self.spec.args):
            if i in static:
                continue
            for leaf in jax.tree_util.tree_leaves(a):
                out.append((i, leaf))
        return out

    def all_eqns(self, jaxpr=None) -> list:
        """Every eqn of the (closed) jaxpr, recursing into sub-jaxprs
        (cond/scan/while/pjit/custom_vjp bodies)."""
        import jax

        if jaxpr is None:
            jaxpr = self.jaxpr
        root = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
        out: list = []

        def walk(jx):
            for eqn in jx.eqns:
                out.append(eqn)
                for v in eqn.params.values():
                    for sub in _subjaxprs(v, jax):
                        walk(sub)

        walk(root)
        return out

    def signature(self) -> str:
        """Canonical abstract signature: per-arg shape/dtype/sharding
        plus the donation declaration — the part of the program hash
        that catches interface drift even when the body is unchanged."""
        parts = []
        for argnum, leaf in self.flat_avals():
            sharding = getattr(leaf, "sharding", None)
            spec = getattr(sharding, "spec", None)
            parts.append(
                f"arg{argnum}:{getattr(leaf, 'dtype', '?')}"
                f"{list(getattr(leaf, 'shape', ()))}:{spec}"
            )
        donate = self.spec.jit_kwargs.get(
            "donate_argnums", self.spec.jit_kwargs.get("donate_argnames", ())
        )
        parts.append(f"donate={donate}")
        out_avals = [
            f"{v.aval.dtype}{list(v.aval.shape)}"
            for v in (self.jaxpr.jaxpr.outvars)
            if hasattr(v, "aval")
        ]
        parts.append("out=" + ",".join(out_avals))
        return ";".join(parts)

    def program_hash(self) -> str:
        """Content-addressed identity of the abstract program: the
        signature plus the address-scrubbed jaxpr text. Stable across
        processes for identical code; any semantic edit reopens it."""
        body = _SET_RE.sub(_sorted_set, _ADDR_RE.sub("0x", str(self.jaxpr)))
        digest = hashlib.blake2s(
            (self.signature() + "\n" + body).encode(), digest_size=10
        ).hexdigest()
        return digest


_UNSET = object()


class _TraceFailed(Exception):
    """Internal: one entrypoint's trace stage failed; the runner turns
    it into a ``trace-error`` finding and moves on."""

    def __init__(self, name: str, detail: str):
        super().__init__(f"{name}: {detail}")
        self.name = name
        self.detail = detail


def _static_argnums(spec: ProgramSpec) -> tuple[int, ...]:
    v = spec.jit_kwargs.get("static_argnums", ())
    if isinstance(v, int):
        return (v,)
    return tuple(v)


def _subjaxprs(v, jax) -> Iterable:
    from jax.extend import core as jex_core

    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for vv in v:
            yield from _subjaxprs(vv, jax)


# -- rules -------------------------------------------------------------------


class AuditRule:
    """Base audit rule: one pass over a shared :class:`EntrypointContext`."""

    name: str = ""
    description: str = ""

    def check(self, ctx: EntrypointContext) -> Iterable[AuditFinding]:
        raise NotImplementedError

    def finding(self, ctx: EntrypointContext, ident: str,
                message: str) -> AuditFinding:
        return AuditFinding(
            rule=self.name, path=ctx.name, line=0, message=message,
            ident=ident,
        )


_RULES: dict[str, type[AuditRule]] = {}


def register_rule(cls: type[AuditRule]) -> type[AuditRule]:
    if not cls.name:
        raise ValueError(f"audit rule {cls.__name__} has no name")
    if cls.name in _RULES:
        raise ValueError(f"duplicate audit rule {cls.name!r}")
    _RULES[cls.name] = cls
    return cls


def rule_names() -> list[str]:
    _load_rules()
    return sorted(_RULES)


def rule_catalog() -> list[tuple[str, str]]:
    _load_rules()
    return [(n, _RULES[n].description) for n in sorted(_RULES)]


def _load_rules() -> None:
    from . import rules  # noqa: F401 - import registers the classes


# -- keys and baseline -------------------------------------------------------


def _finding_keys(findings: list[AuditFinding]) -> list[AuditFinding]:
    """Content-addressed keys over (rule, entrypoint, ident,
    occurrence). Idents are chosen by rules to survive message
    rewording (e.g. a collective's op+dtype+shape, a donated arg's
    leaf path) — editing the PROGRAM re-opens findings, editing
    diagnostics prose does not."""
    seen: dict[tuple[str, str, str], int] = {}
    out = []
    for f in findings:
        ident = f.ident or f.message
        trip = (f.rule, f.path, ident)
        n = seen.get(trip, 0)
        seen[trip] = n + 1
        digest = hashlib.blake2s(
            f"{f.rule}\0{f.path}\0{ident}\0{n}".encode(), digest_size=8
        ).hexdigest()
        out.append(dataclasses.replace(f, key=f"{f.rule}:{digest}"))
    return out


def load_audit_baseline(path: Path) -> dict:
    """{"entries": {...}, "programs": {...}} (both possibly empty)."""
    if not path.exists():
        return {"entries": {}, "programs": {}}
    try:
        data = json.loads(path.read_text(encoding="utf-8"))
    except json.JSONDecodeError as e:
        raise AuditUsageError(f"baseline {path} is not valid JSON: {e}")
    if not isinstance(data, dict):
        raise AuditUsageError(f"baseline {path}: top level must be an object")
    entries = data.get("entries", {})
    programs = data.get("programs", {})
    if not isinstance(entries, dict) or not isinstance(programs, dict):
        raise AuditUsageError(
            f"baseline {path}: 'entries' and 'programs' must be objects"
        )
    return {"entries": entries, "programs": programs}


def write_audit_baseline(
    path: Path,
    result: "AuditResult",
    old: dict,
    new_reason: str | None,
) -> int:
    """Rewrite the baseline: programs get the CURRENT hash/costs
    (keeping their authored reason where one exists), accepted findings
    keep old reasons or take ``new_reason`` (required for new keys),
    stale keys don't survive."""
    old_entries = old.get("entries", {})
    old_programs = old.get("programs", {})
    entries: dict[str, dict] = {}
    added = 0
    # An entrypoint that failed to build/trace has no program record —
    # rewriting now would silently drop its committed pin and budgets,
    # and the fixed-up entrypoint would later re-pin fresh, defeating
    # drift detection. Broken registry → no baseline writes.
    broken = sorted({
        f.path for f in result.findings + result.baselined
        if f.rule == "trace-error"
    })
    if broken:
        raise AuditUsageError(
            "refusing --update-baseline: trace errors on "
            f"{', '.join(broken)} — their program pins would be "
            "dropped from the baseline; fix the registry first"
        )
    # program-baseline drift is resolved by re-pinning 'programs' (done
    # below), and a trace-error means the registry itself is broken —
    # neither may be laundered into an accepted 'entries' record.
    acceptable = [
        f for f in result.findings + result.baselined
        if f.rule not in ("program-baseline", "trace-error")
    ]
    for f in sorted(acceptable, key=lambda f: (f.path, f.rule, f.ident)):
        prev = old_entries.get(f.key)
        if prev is not None and str(prev.get("reason", "")).strip():
            reason = prev["reason"]
        else:
            if not (new_reason and new_reason.strip()):
                raise AuditUsageError(
                    f"new finding {f.key} ({f.path}) needs --reason TEXT "
                    "to enter the audit baseline"
                )
            reason = new_reason.strip()
            added += 1
        entries[f.key] = {
            "reason": reason,
            "rule": f.rule,
            "entrypoint": f.path,
            "ident": f.ident,
            "message": f.message,
        }
    programs: dict[str, dict] = {}
    for name, prog in sorted(result.programs.items()):
        prev = old_programs.get(name, {})
        rec = {
            "hash": prog["hash"],
            "flops": prog.get("flops"),
            "bytes": prog.get("bytes"),
        }
        # Pinning IS the program record (the update itself is the
        # authorization); a reason rides along only when one was
        # authored on the previous pin.
        if str(prev.get("reason", "")).strip():
            rec["reason"] = prev["reason"]
        programs[name] = rec
    payload = {
        "_comment": (
            "dsst audit baseline. 'programs' pins each registry "
            "entrypoint's abstract program (signature+jaxpr hash) and "
            "its FLOPs/bytes budgets — a hash change or a cost "
            "regression beyond tolerance fails the audit until "
            "`dsst audit --update-baseline --reason '...'` re-pins it. "
            "'entries' are accepted findings, each with a mandatory "
            "reason; entries whose finding disappeared go stale and "
            "FAIL the audit until the baseline is regenerated."
        ),
        "version": AUDIT_SCHEMA_VERSION,
        "programs": programs,
        "entries": entries,
    }
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n",
                    encoding="utf-8")
    return added


# -- the runner --------------------------------------------------------------


@dataclasses.dataclass
class AuditResult:
    rules: list[str]
    entrypoints: list[str]
    findings: list[AuditFinding]          # active
    baselined: list[AuditFinding]
    suppressed: list[AuditFinding]
    stale_baseline: list[dict]
    programs: dict[str, dict]             # name -> {hash, flops, bytes, ...}

    @property
    def ok(self) -> bool:
        return not self.findings and not self.stale_baseline

    @property
    def exit_code(self) -> int:
        return 0 if self.ok else 1

    def render_text(self) -> str:
        lines = [f.text() for f in self.findings]
        for entry in self.stale_baseline:
            what = entry.get("kind", "entry")
            lines.append(
                f"{entry.get('entrypoint', '?')}: [baseline] stale "
                f"{what} {entry['key']} — no longer produced; "
                "regenerate (dsst audit --update-baseline)"
            )
        for name in sorted(self.programs):
            prog = self.programs[name]
            lines.append(
                f"  {name}: hash {prog['hash']}"
                + (
                    f" flops={prog['flops']:.3g} bytes={prog['bytes']:.3g}"
                    if prog.get("flops") is not None else ""
                )
            )
        lines.append(
            f"{len(self.findings)} finding(s), "
            f"{len(self.baselined)} baselined, "
            f"{len(self.suppressed)} suppressed, "
            f"{len(self.stale_baseline)} stale baseline entr(y/ies) "
            f"[{len(self.entrypoints)} entrypoint(s); "
            f"rules: {', '.join(self.rules)}]"
        )
        return "\n".join(lines)

    def render_json(self) -> str:
        return json.dumps({
            "version": AUDIT_SCHEMA_VERSION,
            "rules": self.rules,
            "entrypoints": self.entrypoints,
            "counts": {
                "active": len(self.findings),
                "baselined": len(self.baselined),
                "suppressed": len(self.suppressed),
                "stale_baseline": len(self.stale_baseline),
            },
            "ok": self.ok,
            "findings": [f.to_json() for f in self.findings],
            "baselined": [f.to_json() for f in self.baselined],
            "stale_baseline": self.stale_baseline,
            "programs": self.programs,
        }, indent=2)


def run_audit(
    entrypoints: Sequence[str] | None = None,
    *,
    rules: Sequence[str] | None = None,
    baseline_path: Path | None = None,
    mesh=None,
    specs: Mapping[str, Callable] | None = None,
) -> AuditResult:
    """Run the audit; the single entry point the CLI and tier-1 share.

    ``entrypoints``/``rules`` select subsets. ``specs`` overrides the
    registry entirely (fixture tests inject synthetic entrypoints);
    each value is a ``build(mesh) -> ProgramSpec`` callable. Baseline
    staleness is judged only against the selected entrypoints and
    rules — a subset run must not declare the rest of the world stale.
    """
    _load_rules()
    from . import entrypoints as registry

    if mesh is None:
        mesh = default_audit_mesh()

    builders = dict(specs) if specs is not None else registry.builders()
    names = list(entrypoints) if entrypoints else sorted(builders)
    unknown = [n for n in names if n not in builders]
    if unknown:
        raise AuditUsageError(
            f"unknown entrypoint(s) {', '.join(sorted(unknown))}; "
            f"known: {', '.join(sorted(builders))}"
        )
    rule_list = list(rules) if rules else sorted(_RULES)
    unknown = [n for n in rule_list if n not in _RULES]
    if unknown:
        raise AuditUsageError(
            f"unknown audit rule(s) {', '.join(sorted(unknown))}; "
            f"known: {', '.join(sorted(_RULES))}"
        )
    checkers = [_RULES[n]() for n in rule_list]

    from ... import telemetry

    entrypoints_total = telemetry.counter(
        "audit_entrypoints_total", "entrypoints traced by dsst audit"
    )
    findings_total = telemetry.counter(
        "audit_findings_total", "active findings reported by dsst audit"
    )

    bl_path = (
        DEFAULT_AUDIT_BASELINE if baseline_path is None else baseline_path
    )
    baseline = load_audit_baseline(bl_path)
    entries = baseline["entries"]
    bl_programs = baseline["programs"]

    raw: list[AuditFinding] = []
    suppressed: list[AuditFinding] = []
    programs: dict[str, dict] = {}
    audited: list[str] = []
    for name in names:
        try:
            spec = builders[name](mesh)
        except Exception as e:  # noqa: BLE001 - builder bugs are findings
            raw.append(AuditFinding(
                rule="trace-error", path=name, line=0, ident="build",
                message=f"entrypoint builder failed: "
                        f"{type(e).__name__}: {e}",
            ))
            continue
        _validate_suppressions(spec)
        ctx = EntrypointContext(spec, mesh)
        ctx.baseline_programs = bl_programs
        audited.append(name)
        for checker in checkers:
            try:
                found = list(checker.check(ctx))
            except _TraceFailed as e:
                raw.append(AuditFinding(
                    rule="trace-error", path=name, line=0,
                    ident=f"trace:{checker.name}",
                    message=f"could not trace for rule "
                            f"{checker.name}: {e.detail}",
                ))
                continue
            for f in found:
                reason = spec.suppress.get(f.rule)
                if reason:
                    suppressed.append(f)
                else:
                    raw.append(f)
        # Program identity for the baseline rule + report, even when
        # the program-baseline rule is deselected (the report is how
        # --update-baseline learns the hashes).
        try:
            prog = {"hash": ctx.program_hash()}
            cost = ctx.cost if _wants_cost(rule_list) else None
            prog["flops"] = None if cost is None else cost["flops"]
            prog["bytes"] = None if cost is None else cost["bytes"]
            programs[name] = prog
        except _TraceFailed as e:
            raw.append(AuditFinding(
                rule="trace-error", path=name, line=0, ident="hash",
                message=f"could not hash program: {e.detail}",
            ))

    keyed = _finding_keys(raw)

    active: list[AuditFinding] = []
    baselined: list[AuditFinding] = []
    matched: set[str] = set()
    for f in keyed:
        entry = entries.get(f.key)
        if entry is not None and str(entry.get("reason", "")).strip():
            baselined.append(f)
            matched.add(f.key)
        else:
            active.append(f)

    rule_set = set(rule_list) | {"trace-error"}
    ep_set = set(names)
    stale = [
        {"key": k, "kind": "entry", **entry}
        for k, entry in sorted(entries.items())
        if k not in matched
        and entry.get("rule") in rule_set
        and entry.get("entrypoint") in ep_set
    ]
    # Program-baseline comparison lives in the rule (reopen/cost), but
    # EXPIRY is the runner's: a baselined program whose entrypoint left
    # the registry is stale ballast exactly like a fixed lint finding.
    if specs is None and not entrypoints:
        stale.extend(
            {"key": f"program:{name}", "kind": "program",
             "entrypoint": name, **rec}
            for name, rec in sorted(bl_programs.items())
            if name not in builders
        )

    active.sort(key=lambda f: (f.path, f.rule, f.ident))
    entrypoints_total.inc(len(audited))
    findings_total.inc(len(active))
    return AuditResult(
        rules=rule_list,
        entrypoints=names,
        findings=active,
        baselined=baselined,
        suppressed=suppressed,
        stale_baseline=stale,
        programs=programs,
    )


def _wants_cost(rule_list: Sequence[str]) -> bool:
    return "program-baseline" in rule_list


def _validate_suppressions(spec: ProgramSpec) -> None:
    for rule, reason in spec.suppress.items():
        if not str(reason).strip():
            raise AuditUsageError(
                f"entrypoint {spec.name}: suppression for rule "
                f"{rule!r} has no reason — every silenced diagnostic "
                "carries its audit trail in the registry"
            )


def default_audit_mesh():
    """The abstract audit mesh: ≥8 devices on the "data" axis.

    Under ``JAX_PLATFORMS=cpu`` the host platform must be multiplexed
    (``--xla_force_host_platform_device_count=8``) BEFORE backend init;
    the CLI does that, tests inherit it from conftest. Fewer than 8
    devices can't express the sharding contracts, so it's a usage
    error, not a silent single-device audit.
    """
    import jax

    devices = jax.devices()
    if len(devices) < 8:
        raise AuditUsageError(
            f"audit needs >=8 devices for the abstract mesh, have "
            f"{len(devices)} — run under JAX_PLATFORMS=cpu with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(dsst audit sets this up when invoked before backend init)"
        )
    from ...runtime.mesh import make_mesh

    return make_mesh({"data": 8}, devices=devices[:8])
