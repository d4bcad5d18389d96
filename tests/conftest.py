"""Test rig: simulate an 8-device TPU slice on host CPU.

The reference has no fake backend; its closest move is single-node
multi-process DDP (SURVEY.md §4.5). The TPU-native analogue is XLA's
host-platform device multiplexing: 8 virtual CPU devices behave like an
8-chip slice for sharding/collective semantics (not performance).

This must run before any test triggers JAX backend init, hence conftest
import time: XLA_FLAGS via env, platform via jax.config (so the suite is
on the CPU even when the caller forgot ``JAX_PLATFORMS=cpu``).
"""

import os

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# No persistent compilation cache under the suite:
# runtime.enable_compile_cache is a no-op while jax_platforms is "cpu"
# (tests/test_compile_cache.py). An older jaxlib's CPU backend crashed
# on reading back a cached executable; jaxlib 0.9.0 does not (re-checked
# in PR 22: a second process read its entries and ran them), but it logs
# a machine-feature mismatch error per hit, and a cached CPU executable
# is tied to the host CPU it was built on — nothing the suite wants.

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# DSST_SANITIZE=1 arms the runtime thread sanitizer for the WHOLE
# session: every lock/thread the package creates during the suite is
# instrumented, and unbaselined findings fail the run (exit 1) even
# when every test passed. Opt-in (it adds per-acquire bookkeeping);
# the always-on tier-1 coverage is tests/test_sanitize.py's gate,
# which arms the named workloads inside the normal suite.
if os.environ.get("DSST_SANITIZE"):
    _san_state = {}

    def pytest_configure(config):
        from dss_ml_at_scale_tpu.analysis.sanitize import sanitize_scope

        cm = sanitize_scope()
        _san_state["cm"] = cm
        _san_state["scope"] = cm.__enter__()

    def pytest_sessionfinish(session, exitstatus):
        from dss_ml_at_scale_tpu.analysis.sanitize import build_result

        cm = _san_state.pop("cm", None)
        scope = _san_state.pop("scope", None)
        if cm is None:
            return
        cm.__exit__(None, None, None)
        res = build_result(scope, ["<pytest session>"], full_run=False)
        # The suite deliberately seeds hazards via
        # tests/fixtures/sanitize/ (loaded under the sanfix_ prefix);
        # the session gate judges PACKAGE code, not the fixtures'
        # staged crimes.
        res.findings = [
            f for f in res.findings
            if "tests/fixtures/sanitize/" not in f.path
        ]
        if res.findings:
            print("\n=== dsst sanitize (DSST_SANITIZE=1 session) ===")
            print(res.render_text())
            if session.exitstatus == 0:
                session.exitstatus = 1


@pytest.fixture(autouse=True)
def _isolated_tracking_root(tmp_path, monkeypatch):
    """CLI autologging defaults ON (dsst_runs/ in cwd); redirect every
    test's default root — including subprocess pipelines, which inherit
    the env — under tmp_path so suite runs never litter the repo.
    Tests that pass an explicit --tracking-root are unaffected."""
    monkeypatch.setenv("DSST_TRACKING_ROOT", str(tmp_path / "dsst_runs"))


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
