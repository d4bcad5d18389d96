"""`dsst bench` — the performance-observability tier.

Four layers under test, cheapest first:

- **stats core**: synthetic timing distributions through warmup
  discard, median/MAD, dispersion-derived tolerance, and the
  regression/improvement/within-noise verdict vocabulary — no workload.
- **baseline**: fingerprint-keyed add/expire/reopen round-trips, the
  reason-mandatory contract, foreign-fingerprint isolation.
- **the registry + runner**: framework-owned repetition loop with
  durable partials, child JSON protocol, registry coverage, and the
  synthetic-regression exit-1 acceptance gate through the real CLI.
- **integrations**: the feeder_e2e attribution cross-check (self-
  verifying harness), achieved-FLOPs/s gauges priced by the audit
  baseline, and the profile merge (flight-recorder spans + jax.profiler
  events in ONE Perfetto file).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from dss_ml_at_scale_tpu import telemetry
from dss_ml_at_scale_tpu.bench import (
    BenchUsageError,
    Metric,
    Scenario,
    environment_fingerprint,
    fingerprint_key,
    get_scenario,
    load_bench_baseline,
    measure_scenario,
    run_bench,
    scenario_names,
    write_bench_baseline,
)
from dss_ml_at_scale_tpu.bench import core as bench_core
from dss_ml_at_scale_tpu.bench import stats
from dss_ml_at_scale_tpu.config.cli import main

REPO = Path(__file__).resolve().parents[1]


# -- stats core ---------------------------------------------------------------


def test_warmup_discard():
    assert stats.discard_warmup([9.0, 1.0, 1.1, 1.2], 1) == [1.0, 1.1, 1.2]
    assert stats.discard_warmup([1.0], 0) == [1.0]
    with pytest.raises(ValueError):
        stats.discard_warmup([1.0], -1)


def test_median_and_mad_robust_to_outlier():
    # One stalled repetition must not move the summary the way it moves
    # a mean/stddev: that is the whole reason the harness uses
    # median/MAD.
    clean = [100.0, 101.0, 99.0, 100.5, 99.5]
    stalled = clean + [400.0]
    s_clean = stats.summarize(clean)
    s_stalled = stats.summarize(stalled)
    assert abs(s_clean.median - 100.0) <= 0.5
    assert abs(s_stalled.median - s_clean.median) <= 1.0
    assert s_stalled.mad < 5.0
    assert stats.median([1.0, 3.0]) == 2.0  # even-length interpolation


def test_tolerance_derives_from_dispersion():
    quiet = stats.Summary(median=100.0, mad=0.5, n=5)
    noisy = stats.Summary(median=100.0, mad=20.0, n=5)
    # Quiet on both sides: the floor rules.
    assert stats.tolerance(quiet, quiet, floor=0.25) == 0.25
    # A noisy side widens the band beyond the floor (4 * 20/100 = 0.8).
    assert stats.tolerance(quiet, noisy, floor=0.25) == pytest.approx(0.8)
    assert stats.tolerance(noisy, quiet, floor=0.25) == pytest.approx(0.8)


def test_large_regression_cannot_inflate_its_own_tolerance():
    """Each side's MAD normalizes by its OWN median: a 10x lower-is-
    better regression whose absolute noise scaled with the regressed
    value (MAD 100 on median 1000 = 10% relative) must not widen the
    band past the change it is being judged for."""
    base = stats.Summary(median=100.0, mad=1.0, n=5)
    regressed = stats.Summary(median=1000.0, mad=100.0, n=5)
    tol = stats.tolerance(regressed, base, floor=0.25)
    assert tol == pytest.approx(0.4)  # 4 * (100/1000), NOT 4 * (100/100)
    out = stats.classify("lower", regressed, base, floor=0.25)
    assert out["verdict"] == "regression"


@pytest.mark.parametrize("direction,cur,verdict", [
    ("higher", 30.0, "regression"),
    ("higher", 170.0, "improvement"),
    ("higher", 95.0, "within-noise"),
    ("lower", 170.0, "regression"),
    ("lower", 30.0, "improvement"),
    ("lower", 105.0, "within-noise"),
])
def test_classify_verdicts(direction, cur, verdict):
    base = stats.Summary(median=100.0, mad=1.0, n=5)
    out = stats.classify(
        direction, stats.Summary(median=cur, mad=1.0, n=5), base,
        floor=0.35,
    )
    assert out["verdict"] == verdict
    assert out["tolerance"] == pytest.approx(0.35)


def test_classify_edges():
    cur = stats.Summary(median=50.0, mad=1.0, n=5)
    assert stats.classify("higher", cur, None)["verdict"] == "no-baseline"
    zero = stats.Summary(median=0.0, mad=0.0, n=5)
    assert stats.classify("higher", cur, zero)["verdict"] == "no-baseline"
    base = stats.Summary(median=100.0, mad=0.0, n=5)
    assert stats.classify(
        "higher", cur, base, gate=False
    )["verdict"] == "informational"
    with pytest.raises(ValueError):
        stats.classify("sideways", cur, base)


# -- synthetic scenarios (framework loop, baseline round-trips) ---------------


def _synth_scenario(values, name="synth", warmup=1, extra=None):
    it = iter(values)

    def measure(_ctx):
        out = {"synth_metric": next(it)}
        if extra is not None:
            out["_extra"] = extra
        return out

    return Scenario(
        name=name,
        description="synthetic",
        tier="tier1",
        metrics=(Metric("synth_metric", "units", "higher", floor=0.25),),
        measure=measure,
        repetitions=len(values) - warmup,
        warmup=warmup,
    )


@pytest.fixture
def synth_registry(monkeypatch):
    """Injects synthetic scenarios into the live registry (restored
    after the test) and returns a register(sc) helper."""
    bench_core._load_scenarios()

    def register(sc):
        monkeypatch.setitem(bench_core._SCENARIOS, sc.name, sc)
        return sc

    return register


def test_measure_scenario_discards_warmup_and_checkpoints(tmp_path):
    sc = _synth_scenario([999.0, 10.0, 11.0, 12.0], warmup=1)
    partial = tmp_path / "partial.json"
    record = measure_scenario(sc, partial_path=partial, env={})
    assert record["samples"]["synth_metric"] == [10.0, 11.0, 12.0]
    assert record["completed"] == 3
    # The durable partial holds the same post-warmup record (salvage
    # input for a watchdog-killed child).
    assert json.loads(partial.read_text()) == record


def test_measure_scenario_rejects_undeclared_metric():
    sc = Scenario(
        name="synth", description="", tier="tier1",
        metrics=(Metric("declared", "u"),),
        measure=lambda ctx: {"undeclared": 1.0},
        repetitions=1, warmup=0,
    )
    with pytest.raises(BenchUsageError, match="undeclared"):
        measure_scenario(sc, env={})


def test_scenario_schema_validation():
    with pytest.raises(ValueError, match="direction"):
        Metric("m", "u", "sideways")
    with pytest.raises(ValueError, match="tier"):
        Scenario(name="x", description="", tier="warp",
                 metrics=(), measure=lambda c: {})
    with pytest.raises(ValueError, match="steps_metric"):
        Scenario(name="x", description="", tier="tier1",
                 metrics=(Metric("m", "u"),), measure=lambda c: {},
                 steps_metric="absent")


def test_run_bench_judges_against_fingerprinted_baseline(
    tmp_path, synth_registry,
):
    register = synth_registry
    env = environment_fingerprint()
    fp = fingerprint_key(env)
    bl = tmp_path / "BENCH_BASELINE.json"

    # Round 1: no baseline -> no-baseline verdict, exit 0.
    register(_synth_scenario([100.0, 100.0, 101.0, 99.0]))
    res = run_bench(["synth"], baseline_path=bl, isolation=False)
    m = res.results["synth"]["metrics"]["synth_metric"]
    assert m["verdict"] == "no-baseline"
    assert res.exit_code == 0

    # Record it (new entry needs --reason).
    with pytest.raises(BenchUsageError, match="reason"):
        write_bench_baseline(bl, res, load_bench_baseline(bl), None)
    write_bench_baseline(bl, res, load_bench_baseline(bl), "initial")
    data = json.loads(bl.read_text())
    entry = data["entries"][fp]["scenarios"]["synth"]
    assert entry["reason"] == "initial"
    assert entry["metrics"]["synth_metric"]["median"] == 100.0

    # Round 2: same numbers -> within-noise, exit 0.
    register(_synth_scenario([100.0, 100.0, 101.0, 99.0]))
    res = run_bench(["synth"], baseline_path=bl, isolation=False)
    assert res.results["synth"]["metrics"]["synth_metric"]["verdict"] \
        == "within-noise"
    assert res.exit_code == 0

    # Round 3: collapse -> regression, exit 1 (the acceptance contract).
    register(_synth_scenario([50.0, 50.0, 51.0, 49.0]))
    res = run_bench(["synth"], baseline_path=bl, isolation=False)
    assert res.results["synth"]["metrics"]["synth_metric"]["verdict"] \
        == "regression"
    assert res.exit_code == 1
    assert any(f["kind"] == "regression" for f in res.findings)

    # Round 4: a re-baseline keeps the authored reason and reopens the
    # gate at the new level.
    write_bench_baseline(bl, res, load_bench_baseline(bl), None)
    data = json.loads(bl.read_text())
    entry = data["entries"][fp]["scenarios"]["synth"]
    assert entry["reason"] == "initial"  # kept, not re-required
    assert entry["metrics"]["synth_metric"]["median"] == 50.0


def test_foreign_fingerprint_entries_never_gate_or_expire(
    tmp_path, synth_registry,
):
    register = synth_registry
    bl = tmp_path / "BENCH_BASELINE.json"
    foreign = {
        "env": {"platform": "tpu"},
        "scenarios": {
            "long_gone_scenario": {"reason": "tpu box truth",
                                   "metrics": {"x": {"median": 1.0}}},
        },
    }
    bl.write_text(json.dumps({
        "version": 1, "entries": {"tpu:v5:8dev:jax9:py3:64cpu": foreign},
    }))
    register(_synth_scenario([5.0, 5.0], warmup=1))
    res = run_bench(["synth"], baseline_path=bl, isolation=False)
    # The foreign entry names an unregistered scenario — but it belongs
    # to another environment, so it neither gates nor goes stale here.
    assert res.exit_code == 0
    write_bench_baseline(bl, res, load_bench_baseline(bl), "r")
    data = json.loads(bl.read_text())
    assert data["entries"]["tpu:v5:8dev:jax9:py3:64cpu"] == foreign


def test_stale_baseline_entries_fail(tmp_path, synth_registry):
    register = synth_registry
    env = environment_fingerprint()
    fp = fingerprint_key(env)
    bl = tmp_path / "BENCH_BASELINE.json"
    bl.write_text(json.dumps({
        "version": 1,
        "entries": {fp: {"env": env, "scenarios": {
            "unregistered_scenario": {
                "reason": "r", "metrics": {"x": {"median": 1.0}}},
            "synth": {"reason": "r", "metrics": {
                "synth_metric": {"median": 5.0, "mad": 0.0, "n": 3},
                "dropped_metric": {"median": 2.0, "mad": 0.0, "n": 3},
            }},
        }}},
    }))
    register(_synth_scenario([5.0, 5.0], warmup=1))
    res = run_bench(["synth"], baseline_path=bl, isolation=False)
    stale = [f for f in res.findings if f["kind"] == "stale"]
    assert res.exit_code == 1
    assert {f.get("scenario") for f in stale} == {
        "unregistered_scenario", "synth",
    }
    # --update-baseline sheds both kinds of ballast.
    write_bench_baseline(bl, res, load_bench_baseline(bl), "r")
    data = json.loads(bl.read_text())
    scen = data["entries"][fp]["scenarios"]
    assert "unregistered_scenario" not in scen
    assert "dropped_metric" not in scen["synth"]["metrics"]
    register(_synth_scenario([5.0, 5.0], warmup=1))
    assert run_bench(["synth"], baseline_path=bl,
                     isolation=False).exit_code == 0


def test_extra_block_carried_into_report(synth_registry, tmp_path):
    register = synth_registry
    register(_synth_scenario([1.0, 1.0], warmup=1,
                             extra={"detail": {"k": "v"}}))
    res = run_bench(["synth"], baseline_path=tmp_path / "b.json",
                    isolation=False)
    assert res.results["synth"]["extra"] == {"detail": {"k": "v"}}


def test_update_baseline_refuses_salvaged_results(
    tmp_path, synth_registry,
):
    """A record salvaged from a killed child is reportable but must not
    become the committed truth — a median-of-one from a wedged host
    would silently weaken the gate for every future run."""
    register = synth_registry
    register(_synth_scenario([5.0, 5.0], warmup=1))
    res = run_bench(["synth"], baseline_path=tmp_path / "b.json",
                    isolation=False)
    res.results["synth"]["salvaged"] = True
    with pytest.raises(BenchUsageError, match="salvaged"):
        write_bench_baseline(tmp_path / "b.json", res,
                             {"entries": {}}, "r")


def test_profile_repetitions_flag_reaches_the_profile(monkeypatch):
    """`dsst bench --repetitions 5 profile X` and `dsst bench profile X
    --repetitions 3` must both reach profile_scenario (a shared
    argparse dest let the subparser default clobber the parent value)."""
    from dss_ml_at_scale_tpu.bench import profile as profile_mod

    seen = {}

    def fake_profile(name, out, *, repetitions, min_profiler_dur_us):
        seen["reps"] = repetitions
        return {"out": str(out), "spans": 0, "flows": 0,
                "profiler_events": 0, "profiler_events_dropped": 0,
                "mfu": None}

    monkeypatch.setattr(profile_mod, "profile_scenario", fake_profile)
    assert main(["bench", "--repetitions", "5", "profile", "feeder_e2e",
                 "--out", "/tmp/x.json"]) == 0
    assert seen["reps"] == 5
    assert main(["bench", "profile", "feeder_e2e", "--repetitions", "3",
                 "--out", "/tmp/x.json"]) == 0
    assert seen["reps"] == 3
    assert main(["bench", "profile", "feeder_e2e",
                 "--out", "/tmp/x.json"]) == 0
    assert seen["reps"] == 1


def test_require_baseline_fails_ungated_host(tmp_path, synth_registry):
    register = synth_registry
    register(_synth_scenario([5.0, 5.0], warmup=1))
    bl = tmp_path / "empty.json"
    res = run_bench(["synth"], baseline_path=bl, isolation=False)
    assert res.exit_code == 0  # default: no-baseline passes
    register(_synth_scenario([5.0, 5.0], warmup=1))
    res = run_bench(["synth"], baseline_path=bl, isolation=False,
                    require_baseline=True)
    assert res.exit_code == 1
    assert any(f["kind"] == "no-baseline" for f in res.findings)


def test_in_process_scenario_defect_is_finding_not_usage_error(
    tmp_path, synth_registry,
):
    """A broken scenario must judge identically in-process and in child
    isolation: an error finding with exit 1, never a whole-run abort."""
    register = synth_registry
    register(Scenario(
        name="synth", description="", tier="tier1",
        metrics=(Metric("declared", "u"),),
        measure=lambda ctx: {"undeclared": 1.0},
        repetitions=1, warmup=0,
    ))
    res = run_bench(["synth"], baseline_path=tmp_path / "b.json",
                    isolation=False)
    assert res.exit_code == 1
    assert any(f["kind"] == "error" and "undeclared" in f["message"]
               for f in res.findings)
    # Pre-run flag errors stay usage errors in both modes.
    with pytest.raises(BenchUsageError, match="repetitions"):
        run_bench(["synth"], baseline_path=tmp_path / "b.json",
                  isolation=False, repetitions=0)


def test_recorder_scenario_parks_and_restores_live_recorder(tmp_path):
    """recorder_overhead must own the recorder for both halves of its
    comparison and hand back whatever tail was live before (a tracked
    run or `dsst bench profile` must not lose its recorder, nor absorb
    the scenario's synthetic events)."""
    from dss_ml_at_scale_tpu.telemetry import flightrec

    sc = get_scenario("recorder_overhead")
    outer = tmp_path / "outer_tail.jsonl"
    flightrec.enable(outer)
    try:
        ctx = sc.setup()
        try:
            out = sc.measure(ctx)
        finally:
            sc.teardown(ctx)
        assert flightrec.get_recorder().path == outer.absolute()
        assert out["recorder_emit_tail_us"] > 0
        # No synthetic bench event leaked into the parked outer tail.
        assert not any(
            e.get("thread") == "bench"
            for e in flightrec.read_events(outer)
        )
    finally:
        flightrec.disable(outer)


def test_salvage_partial_contract(tmp_path):
    p = tmp_path / "partial.json"
    assert bench_core._salvage_partial(p) is None  # missing
    p.write_text(json.dumps({"scenario": "x", "completed": 0}))
    assert bench_core._salvage_partial(p) is None  # nothing measured
    p.write_text(json.dumps({"scenario": "x", "completed": 2,
                             "samples": {"m": [1, 2]}}))
    assert bench_core._salvage_partial(p)["completed"] == 2


# -- registry + catalog reconciliation (runtime side of the lint) -------------


def test_registry_matches_catalog_and_spans():
    from dss_ml_at_scale_tpu.telemetry.catalog import (
        KNOWN_BENCH_METRICS,
        KNOWN_SPANS,
        SPAN_ATTRIBUTION,
    )

    names = scenario_names()
    assert set(names) == set(KNOWN_BENCH_METRICS)
    for name in names:
        sc = get_scenario(name)
        assert tuple(m.name for m in sc.metrics) == tuple(
            KNOWN_BENCH_METRICS[name]
        ), name
    # The attribution mapping buckets only declared spans — the
    # single-sourcing fix this PR exists to pin.
    assert set(SPAN_ATTRIBUTION) <= set(KNOWN_SPANS)
    assert set(SPAN_ATTRIBUTION.values()) <= {
        "data_wait", "transfer", "compute", "host",
    }


# -- child protocol -----------------------------------------------------------


def _run_child(args, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "dss_ml_at_scale_tpu.bench", *args],
        capture_output=True, text=True, timeout=timeout, cwd=str(REPO),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def test_child_protocol_success_and_partial(tmp_path):
    partial = tmp_path / "p.json"
    proc = _run_child([
        "--scenario", "sanitizer_overhead", "--partial", str(partial),
        "--repetitions", "2",
    ])
    assert proc.returncode == 0, proc.stderr[-2000:]
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["scenario"] == "sanitizer_overhead"
    assert record["completed"] == 2
    assert len(record["samples"]["sanitizer_overhead_ratio"]) == 2
    # The durable partial mirrors the final record — what a watchdog
    # kill would salvage.
    assert json.loads(partial.read_text())["completed"] == 2


def test_child_protocol_failure_is_json_not_crash():
    proc = _run_child(["--scenario", "no_such_scenario"], timeout=120)
    assert proc.returncode == 0
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["failed"] is True
    assert "no_such_scenario" in record["error"]


# -- CLI ----------------------------------------------------------------------


def test_cli_usage_errors():
    assert main(["bench", "--scenarios", "decode", "--tier", "tier1"]) == 2
    assert main(["bench", "--scenarios", "no_such"]) == 2
    assert main(["bench", "--tier", "warp"]) == 2


def test_cli_list_scenarios(capsys):
    assert main(["bench", "--list-scenarios"]) == 0
    out = capsys.readouterr().out
    for name in scenario_names():
        assert name in out


def test_cli_synthetic_regression_exits_nonzero(tmp_path, capsys):
    """Acceptance gate: a committed baseline whose numbers this host
    cannot meet must fail `dsst bench` with exit 1 — through the real
    CLI, the real child, and the real verdict path."""
    env = environment_fingerprint()
    fp = fingerprint_key(env)
    bl = tmp_path / "BENCH_BASELINE.json"
    bl.write_text(json.dumps({
        "version": 1,
        "entries": {fp: {"env": env, "scenarios": {
            "sanitizer_overhead": {
                "reason": "synthetic: impossible ratio",
                "metrics": {
                    # lower-is-better with an unreachable baseline: any
                    # real measurement is a regression beyond tolerance.
                    "sanitizer_overhead_ratio": {
                        "median": 0.001, "mad": 0.0, "n": 5},
                },
            },
        }}},
    }))
    rc = main(["bench", "--scenarios", "sanitizer_overhead", "--json",
               "--baseline", str(bl)])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["ok"] is False
    assert report["counts"]["regressions"] == 1
    m = report["results"]["sanitizer_overhead"]["metrics"][
        "sanitizer_overhead_ratio"]
    assert m["verdict"] == "regression"


def test_cli_parent_never_imports_jax():
    """The isolating parent of `dsst bench` only starts children (the
    fingerprint child, then one per scenario): a parent that touched JAX
    would hold the chip its children need."""
    import subprocess
    import sys

    code = (
        "import sys, json\n"
        "from dss_ml_at_scale_tpu.config.cli import main\n"
        "rc = main(['bench', '--scenarios', 'sanitizer_overhead',"
        " '--repetitions', '1', '--json'])\n"
        "print(json.dumps({'rc': rc, 'jax_in_parent': 'jax' in sys.modules}))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=str(Path(__file__).parent.parent),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    verdict = json.loads(lines[-1])
    report = json.loads("\n".join(lines[:-1]))
    assert verdict["jax_in_parent"] is False
    # ...while the children did run: a fingerprint and a measured record.
    assert report["fingerprint"]["platform"] == "cpu"
    assert "sanitizer_overhead" in report["results"]


def test_cli_tier1_smoke_gate(capsys):
    """The CI gate: the full tier-1 subset runs in isolated children
    against the committed BENCH_BASELINE.json with registry coverage —
    a scenario silently dropping out of the run is a finding, and the
    exit code is the report's verdict."""
    registered_tier1 = {
        n for n in scenario_names() if get_scenario(n).tier == "tier1"
    }
    rc = main(["bench", "--tier", "tier1", "--json"])
    report = json.loads(capsys.readouterr().out)
    # Coverage: every registered tier-1 scenario both selected AND
    # measured (a child crash surfaces as an error finding + rc 1).
    assert set(report["scenarios"]) == registered_tier1
    assert set(report["results"]) == registered_tier1
    bad = [f for f in report["findings"]
           if f["kind"] in ("error", "timeout", "no-samples", "stale")]
    assert bad == [], bad
    assert rc == (0 if report["ok"] else 1)
    # The committed baseline speaks for this fingerprint: every gated
    # tier-1 metric must have found a baseline to be judged against.
    for name in registered_tier1:
        for mname, m in report["results"][name]["metrics"].items():
            if get_scenario(name).metric(mname).gate:
                assert m["verdict"] != "no-baseline", (name, mname)
    # The achieved-FLOPs/s block priced by the audit pin rode along.
    assert "train_step.classifier" in report["mfu"]
    assert report["mfu"]["train_step.classifier"][
        "achieved_flops_per_sec"] > 0


# -- feeder_e2e cross-check + MFU + profile -----------------------------------


@pytest.fixture(scope="module")
def feeder_ctx():
    sc = get_scenario("feeder_e2e")
    ctx = sc.setup()
    yield sc, ctx
    sc.teardown(ctx)


def test_feeder_e2e_crosscheck_passes(feeder_ctx):
    sc, ctx = feeder_ctx
    out = sc.measure(ctx)
    assert out["e2e_images_per_sec"] > 0
    # The loop is fully span-covered: reader.next/feeder.place/
    # train_step account for (nearly) all of the measured wall time.
    assert out["e2e_unexplained_fraction"] < 0.5


def test_feeder_e2e_crosscheck_fails_on_attribution_gap(
    feeder_ctx, monkeypatch,
):
    """The self-verification: if the attribution buckets stop seeing
    the loop's spans (renamed span, broken handoff, mapping rot), the
    scenario must fail rather than emit unattributable numbers."""
    from dss_ml_at_scale_tpu.bench import scenarios as scen_mod

    sc, ctx = feeder_ctx
    monkeypatch.setattr(
        scen_mod, "_attribution_buckets",
        lambda tail, since: {"data_wait": 0.0, "transfer": 0.0,
                             "compute": 0.0, "host": 0.0},
    )
    with pytest.raises(RuntimeError, match="unexplained"):
        sc.measure(ctx)


def test_mfu_gauges_priced_by_audit_pin():
    from dss_ml_at_scale_tpu.bench import mfu

    flops = mfu.pinned_flops("train_step.classifier")
    assert flops and flops > 0  # the audit baseline pins this program
    assert mfu.pinned_flops("no.such.entrypoint") is None

    block = mfu.publish_achieved(
        "train_step.classifier", 10.0, device_kind="TPU v5 lite",
    )
    assert block["achieved_flops_per_sec"] == pytest.approx(flops * 10.0)
    assert block["utilization"] == pytest.approx(
        flops * 10.0 / mfu.PEAK_BF16_FLOPS["TPU v5 lite"]
    )
    text = telemetry.render_prometheus()
    assert "entrypoint_achieved_flops_per_sec" in text
    assert "entrypoint_flops_utilization" in text
    assert mfu.publish_achieved("no.such.entrypoint", 10.0) is None


@pytest.mark.parametrize("kind,peak_note", [
    ("cpu", None),
    ("TPU v4", "unknown device kind TPU v4"),
    ("TPU v5e", "unknown device kind TPU v5e"),
])
def test_mfu_never_assumes_another_chips_peak(kind, peak_note):
    """A device kind without a peak in the table gets no utilization and
    says so by name (the CPU has none to report); the lookup itself
    raises for anything but the CPU."""
    from dss_ml_at_scale_tpu.bench import mfu

    block = mfu.publish_achieved(
        "train_step.classifier", 10.0, device_kind=kind,
    )
    assert block["utilization"] is None
    assert block.get("peak") == peak_note
    if peak_note is None:
        assert mfu.peak_for(mfu.PEAK_BF16_FLOPS, kind) is None
    else:
        with pytest.raises(mfu.UnknownDeviceKind, match=kind):
            mfu.peak_for(mfu.PEAK_HBM_BYTES, kind)


def test_mfu_publish_from_trace(tmp_path):
    from dss_ml_at_scale_tpu.bench import mfu

    def _tail(path, period):
        events = []
        for i in range(4):
            base = {"name": "train_step", "ts": i * period, "pid": 1,
                    "tid": 1, "trace": "t1", "span": f"{i:08x}",
                    "kind": "step"}
            events.append({**base, "ph": "B"})
            events.append({**base, "ph": "E", "dur": 0.5})
        path.write_text("\n".join(json.dumps(e) for e in events) + "\n")
        return path

    # Back-to-back spans: 4 steps over 2.0s of wall -> 2 steps/sec.
    block = mfu.publish_from_trace(
        _tail(tmp_path / "busy.jsonl", 0.5), "train_step.classifier"
    )
    assert block["steps_per_sec"] == pytest.approx(2.0)
    # Stalled run: same 0.5s spans arriving every 1.0s — the gaps ARE
    # wall time, so the rate halves (1/mean(dur) would still say 2.0
    # and inflate utilization exactly on the stalled runs).
    stalled = mfu.publish_from_trace(
        _tail(tmp_path / "stalled.jsonl", 1.0), "train_step.classifier"
    )
    assert stalled["steps_per_sec"] == pytest.approx(4 / 3.5, rel=1e-3)
    assert mfu.publish_from_trace(tmp_path / "empty.jsonl",
                                  "train_step.classifier") is None


def test_profile_merges_spans_and_profiler_events(tmp_path):
    """Acceptance gate: ONE Perfetto file holding both the
    flight-recorder spans (flow arrows intact) and the jax.profiler
    events of the same run."""
    from dss_ml_at_scale_tpu.bench.profile import (
        PROFILER_PID_OFFSET,
        profile_scenario,
    )

    out = tmp_path / "merged.json"
    report = profile_scenario("feeder_e2e", out, repetitions=1)
    assert report["spans"] > 0
    trace = json.loads(out.read_text())
    evs = trace["traceEvents"]
    dsst = [e for e in evs if e.get("pid", 0) < PROFILER_PID_OFFSET]
    prof = [e for e in evs if e.get("pid", 0) >= PROFILER_PID_OFFSET]
    # Host side: the runtime spans with their cross-thread flow arrows.
    names = {e["name"] for e in dsst if e.get("ph") == "X"}
    assert {"reader.next", "feeder.place", "train_step"} <= names
    assert any(e.get("ph") in ("s", "f") for e in dsst)
    # Device/profiler side: events present, pid-offset into their own
    # lanes, metadata labeled as jax.
    assert report["profiler_events"] == len(prof) > 0
    jax_lanes = [e for e in prof if e.get("ph") == "M"
                 and e.get("name") == "process_name"]
    assert jax_lanes and all(
        e["args"]["name"].startswith("jax: ") for e in jax_lanes
    )
    # Same timeline: profiler span timestamps overlap the host spans'
    # wall-clock window (epoch microseconds).
    host_ts = [e["ts"] for e in dsst if e.get("ph") == "X"]
    prof_ts = [e["ts"] for e in prof
               if e.get("ph") == "X" and e.get("ts")]
    assert prof_ts and host_ts
    assert min(prof_ts) < max(host_ts) and max(prof_ts) > min(host_ts)
    # The volume cap is explicit, never silent.
    assert "profiler_events_dropped" in report
