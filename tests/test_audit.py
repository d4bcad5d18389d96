"""Tier-1 face of the ``dsst audit`` IR-level program auditor.

Three layers, mirroring ``test_lint.py``:

- **the real gate**: the full rule suite over the LIVE entrypoint
  registry must be clean against the committed ``AUDIT_BASELINE.json``
  (zero active findings, zero stale entries, every accepted entry
  justified) — this is ROADMAP item 1's "partitioned, donated,
  no-hidden-allgather" exit gate, enforced before any TPU exists;
- **per-rule fixtures**: live positive/negative entrypoint twins under
  ``tests/fixtures/audit/`` prove each IR rule bites the violation it
  claims (an un-donated train-step twin, a latent-f64 op, a callback
  in a jit, a surprise all-gather) and spares the clean idiom;
- **framework semantics**: per-entrypoint suppressions (reason
  mandatory), trace failures surfacing as findings, and baseline
  pin / reopen-on-hash-change / reopen-on-cost-regression / expire.

The audit compiles every registry entrypoint on the 8-device CPU mesh
(conftest multiplexes the host platform), so the registry gate is the
most expensive single test in tier-1 — it runs ONCE via the shared
cache below.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

import pytest

from dss_ml_at_scale_tpu.analysis.audit import (
    DEFAULT_AUDIT_BASELINE,
    AuditUsageError,
    default_audit_mesh,
    entrypoint_names,
    load_audit_baseline,
    rule_names,
    run_audit,
    write_audit_baseline,
)
from dss_ml_at_scale_tpu.config.cli import main

FIXTURES = Path(__file__).resolve().parent / "fixtures" / "audit"

# A path that never exists: run_audit sees an empty baseline.
NO_BASELINE = FIXTURES / "_never_written.json"


@functools.lru_cache(maxsize=8)
def _fixture(name: str):
    spec = importlib.util.spec_from_file_location(
        f"_audit_fixture_{name}", FIXTURES / f"{name}_fixture.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=1)
def _mesh():
    return default_audit_mesh()


def _audit(builders: dict, rules: list[str], baseline=NO_BASELINE):
    return run_audit(
        specs=builders, rules=rules, baseline_path=baseline, mesh=_mesh()
    )


def test_program_hash_ignores_set_print_order():
    """A set's repr follows the process's hash seed (shard_map prints
    ``manual_axes=frozenset({...})``); the program hash must not."""
    from dss_ml_at_scale_tpu.analysis.audit import core

    a = "shard_map[manual_axes=frozenset({'pipe', 'data'}) x=1]"
    b = "shard_map[manual_axes=frozenset({'data', 'pipe'}) x=1]"
    assert a != b
    assert core._SET_RE.sub(core._sorted_set, a) == b
    assert core._SET_RE.sub(core._sorted_set, b) == b


# -- the real gate: the live registry is clean against the baseline ----------


@functools.lru_cache(maxsize=1)
def _registry_result():
    """ONE full-registry audit shared by every gate below — each
    entrypoint traces/lowers/compiles exactly once per tier-1 run."""
    return run_audit()


def test_registry_clean_against_committed_baseline():
    res = _registry_result()
    assert res.findings == [], "\n".join(f.text() for f in res.findings)
    assert res.stale_baseline == [], (
        "stale audit baseline entries (programs or accepted findings "
        "no longer produced): "
        + ", ".join(e["key"] for e in res.stale_baseline)
    )
    assert res.exit_code == 0


def test_registry_covers_the_contracted_entrypoints():
    """The ROADMAP-item-1 contract surface: losing one of these from
    the registry silently un-audits a production program."""
    expected = {
        "train_step.classifier",
        "train_step.classifier.health",
        "eval_step.classifier",
        "train_step.lm",
        "train_step.pipelined_lm",
        "decode_step.lm",
        "serving.score",
        "ops.fused_matmul.grad",
        "ops.fused_norm.grad",
        "ops.flash_attention.grad",
        "sarimax.batched_fit",
    }
    assert expected <= set(entrypoint_names())
    assert expected <= set(_registry_result().programs)


def test_every_audit_baseline_entry_has_a_reason():
    baseline = load_audit_baseline(DEFAULT_AUDIT_BASELINE)
    assert baseline["programs"], "committed audit baseline pins nothing"
    for key, entry in baseline["entries"].items():
        assert str(entry.get("reason", "")).strip(), (
            f"audit baseline entry {key} has no reason"
        )


def test_audit_emits_registered_telemetry():
    from dss_ml_at_scale_tpu import telemetry

    def val(name: str) -> float:
        for m in telemetry.snapshot()["metrics"]:
            if m["name"] == name and not m["labels"]:
                return m["value"]
        return 0.0

    before = val("audit_entrypoints_total")
    _registry_result()  # cached: inc'd once, on whichever test ran first
    assert val("audit_entrypoints_total") >= before
    assert val("audit_entrypoints_total") >= len(entrypoint_names())


# -- per-rule fixtures --------------------------------------------------------


def test_donation_flags_undonated_twin():
    fx = _fixture("donation")
    res = _audit({"fixture.donation.pos": fx.build_positive}, ["donation"])
    assert [f.rule for f in res.findings] == ["donation"], [
        f.text() for f in res.findings
    ]
    assert res.findings[0].ident == "arg0.leaf0"
    assert res.exit_code == 1


def test_donation_spares_donated_twin():
    fx = _fixture("donation")
    res = _audit({"fixture.donation.neg": fx.build_negative}, ["donation"])
    assert res.findings == [], [f.text() for f in res.findings]


def test_dtype_flags_latent_f64():
    fx = _fixture("dtype")
    res = _audit(
        {"fixture.dtype.wide.pos": fx.build_positive_wide},
        ["dtype-discipline"],
    )
    assert res.findings, "latent f64 promotion not flagged"
    assert all(f.ident.startswith("wide:") for f in res.findings), [
        f.text() for f in res.findings
    ]


def test_dtype_flags_weak_type_churn():
    fx = _fixture("dtype")
    res = _audit(
        {"fixture.dtype.churn.pos": fx.build_positive_churn},
        ["dtype-discipline"],
    )
    assert [f.ident for f in res.findings] == ["weak-churn"], [
        f.text() for f in res.findings
    ]


def test_dtype_spares_pinned_twin():
    fx = _fixture("dtype")
    res = _audit(
        {"fixture.dtype.neg": fx.build_negative}, ["dtype-discipline"]
    )
    assert res.findings == [], [f.text() for f in res.findings]


def test_host_interop_flags_callback_in_jit():
    fx = _fixture("host_interop")
    res = _audit(
        {"fixture.host_interop.pos": fx.build_positive}, ["host-interop"]
    )
    assert [f.ident for f in res.findings] == [
        "callback:debug_print"
    ], [f.text() for f in res.findings]


def test_host_interop_spares_declared_coldpath():
    fx = _fixture("host_interop")
    res = _audit(
        {"fixture.host_interop.neg": fx.build_negative}, ["host-interop"]
    )
    assert res.findings == []


def test_sharding_flags_surprise_allgather():
    fx = _fixture("sharding")
    res = _audit(
        {"fixture.sharding.gather.pos": fx.build_positive_gather},
        ["sharding-collectives"],
    )
    idents = [f.ident for f in res.findings]
    assert any(i.startswith("all-gather:") for i in idents), [
        f.text() for f in res.findings
    ]


def test_sharding_flags_oversized_replicated_input():
    fx = _fixture("sharding")
    res = _audit(
        {"fixture.sharding.replicated.pos": fx.build_positive_replicated},
        ["sharding-collectives"],
    )
    assert [f.ident for f in res.findings] == ["replicated:arg0.leaf0"], [
        f.text() for f in res.findings
    ]


def test_sharding_sums_tuple_shaped_combined_collectives():
    """XLA's collective combiner and async `-start` ops emit
    TUPLE-shaped collectives — exactly the largest ones. The rule must
    sum every tuple element (here 64 MiB + 32 MiB, each alone at or
    under the 64 MiB all-reduce ceiling) and must not double-count the
    `-done` half of an async pair."""
    from dss_ml_at_scale_tpu.analysis.audit.rules import (
        ShardingCollectivesRule,
    )

    class _Spec:
        collective_limits = None
        replicated_bytes_limit = None

    class _Ctx:
        spec = _Spec()
        name = "fixture.tuple_collective"
        optimized_hlo = (
            "  %all-reduce.1 = (f32[16777216]{0}, f32[8388608]{0})"
            " all-reduce(f32[16777216]{0} %a, f32[8388608]{0} %b),"
            " replica_groups={}\n"
            "  %ag-start = (f32[262144]{0}, f32[2097152]{0})"
            " all-gather-start(f32[262144]{0} %c), dimensions={0}\n"
            "  %ag-done = f32[2097152]{0}"
            " all-gather-done((f32[262144]{0}, f32[2097152]{0})"
            " %ag-start)\n"
        )

        def flat_avals(self):
            return []

    findings = list(ShardingCollectivesRule().check(_Ctx()))
    by_op = {f.ident.split(":")[0]: f for f in findings}
    assert set(by_op) == {"all-reduce", "all-gather"}, [
        f.text() for f in findings
    ]
    assert "100663296 bytes" in by_op["all-reduce"].message
    # ONE all-gather finding: the -start counted, the -done skipped.
    assert sum(1 for f in findings if f.ident.startswith("all-gather")) == 1


def test_sharding_spares_sharded_elementwise():
    fx = _fixture("sharding")
    res = _audit(
        {"fixture.sharding.neg": fx.build_negative},
        ["sharding-collectives"],
    )
    assert res.findings == [], [f.text() for f in res.findings]


# -- framework: suppressions and trace failures -------------------------------


def test_suppression_with_reason_silences_and_is_reported():
    fx = _fixture("host_interop")
    res = _audit(
        {"fixture.host_interop.suppressed": fx.build_suppressed},
        ["host-interop"],
    )
    assert res.findings == []
    assert len(res.suppressed) == 1
    assert res.exit_code == 0


def test_suppression_without_reason_is_a_usage_error():
    from dss_ml_at_scale_tpu.analysis.audit import ProgramSpec

    def build(mesh):
        import jax.numpy as jnp

        return ProgramSpec(
            name="fixture.bad_suppress",
            fn=lambda x: x,
            args=(jnp.zeros((4,), jnp.float32),),
            suppress={"host-interop": "  "},
        )

    with pytest.raises(AuditUsageError):
        _audit({"fixture.bad_suppress": build}, ["host-interop"])


def test_builder_failure_is_a_trace_error_finding():
    def build(mesh):
        raise ValueError("fixture builder exploded")

    res = _audit({"fixture.broken_builder": build}, ["host-interop"])
    assert [(f.rule, f.ident) for f in res.findings] == [
        ("trace-error", "build")
    ]
    assert res.exit_code == 1


def test_untraceable_fn_is_a_trace_error_finding():
    from dss_ml_at_scale_tpu.analysis.audit import ProgramSpec

    def build(mesh):
        import jax.numpy as jnp

        def f(x):
            if x.sum() > 0:  # concretization error under tracing
                return x
            return -x

        return ProgramSpec(
            name="fixture.untraceable", fn=f,
            args=(jnp.zeros((4,), jnp.float32),),
        )

    res = _audit({"fixture.untraceable": build}, ["host-interop"])
    assert res.findings and all(
        f.rule == "trace-error" for f in res.findings
    ), [f.text() for f in res.findings]


def test_unknown_entrypoint_and_rule_are_usage_errors():
    with pytest.raises(AuditUsageError):
        run_audit(["no.such.entrypoint"], mesh=_mesh())
    with pytest.raises(AuditUsageError):
        run_audit(rules=["no-such-rule"], mesh=_mesh())


# -- baseline: pin, reopen on hash change, reopen on cost regression ---------


def test_program_baseline_pin_and_reopen(tmp_path):
    fx = _fixture("baseline")
    bl = tmp_path / "audit_baseline.json"
    name = fx.NAME
    rules = rule_names()

    # 1. Unpinned program: the rule demands a baseline.
    res = _audit({name: fx.build_v1}, rules, baseline=bl)
    assert [f.ident for f in res.findings] == ["unbaselined"]

    # 2. Pin it; the same program is now clean.
    write_audit_baseline(bl, res, load_audit_baseline(bl), None)
    res2 = _audit({name: fx.build_v1}, rules, baseline=bl)
    assert res2.findings == [], [f.text() for f in res2.findings]
    assert res2.exit_code == 0

    # 3. A semantic edit under the same name reopens on the hash (the
    # extra add also moves the byte cost, which may reopen too — the
    # hash is the guaranteed signal).
    res3 = _audit({name: fx.build_v2}, rules, baseline=bl)
    idents = [f.ident for f in res3.findings]
    assert "hash" in idents, [f.text() for f in res3.findings]
    assert all(f.rule == "program-baseline" for f in res3.findings)
    assert res3.exit_code == 1


def test_program_baseline_reopens_on_cost_regression(tmp_path):
    fx = _fixture("baseline")
    bl = tmp_path / "audit_baseline.json"
    name = fx.NAME
    rules = rule_names()

    res = _audit({name: fx.build_v1}, rules, baseline=bl)
    write_audit_baseline(bl, res, load_audit_baseline(bl), None)
    flops = res.programs[name]["flops"]
    if flops is None or flops <= 0:
        pytest.skip("backend cost model reports no flops on this host")

    # Shrink the committed budget below measured cost: same program,
    # now over budget — the regression arm must fire.
    data = json.loads(bl.read_text())
    data["programs"][name]["flops"] = flops / 2.0
    bl.write_text(json.dumps(data))
    res2 = _audit({name: fx.build_v1}, rules, baseline=bl)
    assert [f.ident for f in res2.findings] == ["flops"], [
        f.text() for f in res2.findings
    ]


def test_accepted_finding_expires_when_fixed(tmp_path):
    """A baselined finding whose program got fixed is stale ballast and
    FAILS the audit until the baseline is regenerated."""
    fx = _fixture("host_interop")
    bl = tmp_path / "audit_baseline.json"

    def dirty(mesh):
        spec = fx.build_positive(mesh)
        import dataclasses

        return dataclasses.replace(spec, name="fixture.hi")

    def clean(mesh):
        spec = fx.build_negative(mesh)
        import dataclasses

        return dataclasses.replace(spec, name="fixture.hi")

    res = _audit({"fixture.hi": dirty}, ["host-interop"], baseline=bl)
    assert len(res.findings) == 1
    write_audit_baseline(
        bl, res, load_audit_baseline(bl), "accepted for the fixture"
    )
    res2 = _audit({"fixture.hi": dirty}, ["host-interop"], baseline=bl)
    assert res2.findings == [] and len(res2.baselined) == 1

    res3 = _audit({"fixture.hi": clean}, ["host-interop"], baseline=bl)
    assert res3.findings == []
    assert len(res3.stale_baseline) == 1
    assert res3.exit_code == 1


def test_update_baseline_refuses_a_broken_registry(tmp_path):
    """A trace-errored entrypoint has no program record this run — a
    rewrite would silently drop its committed pin, and the fixed-up
    entrypoint would later re-pin fresh, defeating drift detection."""
    bl = tmp_path / "audit_baseline.json"
    bl.write_text(json.dumps({
        "entries": {},
        "programs": {"fixture.broken_builder": {"hash": "cafe",
                                                "flops": 1, "bytes": 1}},
    }))

    def build(mesh):
        raise ValueError("fixture builder exploded")

    res = _audit({"fixture.broken_builder": build}, ["host-interop"],
                 baseline=bl)
    before = bl.read_text()
    with pytest.raises(AuditUsageError, match="trace errors"):
        write_audit_baseline(bl, res, load_audit_baseline(bl), "r")
    assert bl.read_text() == before  # pin survives untouched


def test_new_baseline_entry_requires_reason(tmp_path):
    fx = _fixture("host_interop")
    bl = tmp_path / "audit_baseline.json"
    res = _audit(
        {"fixture.host_interop.pos": fx.build_positive},
        ["host-interop"], baseline=bl,
    )
    assert res.findings
    with pytest.raises(AuditUsageError):
        write_audit_baseline(bl, res, load_audit_baseline(bl), None)


# -- CLI ----------------------------------------------------------------------


def test_cli_list_rules(capsys):
    assert main(["audit", "--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in (
        "donation", "dtype-discipline", "sharding-collectives",
        "host-interop", "program-baseline",
    ):
        assert rule in out


def test_cli_list_entrypoints(capsys):
    assert main(["audit", "--list-entrypoints"]) == 0
    out = capsys.readouterr().out
    assert "train_step.classifier" in out
    assert "sarimax.batched_fit" in out


def test_cli_unknown_entrypoint_exits_2():
    assert main(["audit", "--entrypoints", "no.such.ep"]) == 2


def test_cli_update_baseline_rejects_subset_runs():
    """Mirror of `lint --changed --update-baseline`: a subset run must
    never rewrite the whole-registry baseline (it would drop every pin
    it didn't re-check). Guarded BEFORE tracing, so this is cheap."""
    for subset in (["--entrypoints", "ops.fused_norm.grad"],
                   ["--rules", "donation"]):
        assert main([
            "audit", *subset, "--update-baseline", "--reason", "nope",
        ]) == 2


def test_cli_single_entrypoint_json(capsys):
    rc = main([
        "audit", "--entrypoints", "ops.fused_norm.grad", "--json",
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    payload = json.loads(out)
    assert payload["ok"] is True
    assert payload["entrypoints"] == ["ops.fused_norm.grad"]
    assert "ops.fused_norm.grad" in payload["programs"]
    assert set(payload["counts"]) == {
        "active", "baselined", "suppressed", "stale_baseline"
    }
