import numpy as np
import pytest

from dss_ml_at_scale_tpu.hpo import (
    STATUS_FAIL,
    STATUS_OK,
    TPE,
    Trials,
    fmin,
    hp,
    random_suggest,
    sample_space,
    space_eval,
    tpe_suggest,
)
from dss_ml_at_scale_tpu.hpo.hp import scope
from dss_ml_at_scale_tpu.hpo.shipping import (
    Broadcast,
    broadcast,
    load_shared,
    save_shared,
)


# -- spaces ------------------------------------------------------------------


def test_space_sampling_ranges():
    rng = np.random.default_rng(0)
    space = {
        "u": hp.uniform("u", -1, 1),
        "lu": hp.loguniform("lu", 1e-3, 1e2),
        "ln": hp.lognormal("ln", 0, 1),
        "q": scope.int(hp.quniform("q", 0, 4, 1)),
        "c": hp.choice("c", ["a", "b", "c"]),
    }
    for _ in range(200):
        pt = sample_space(space, rng)
        assert -1 <= pt["u"] <= 1
        assert 1e-3 <= pt["lu"] <= 1e2
        assert pt["ln"] > 0
        assert pt["q"] in (0, 1, 2, 3, 4) and isinstance(pt["q"], int)
        assert pt["c"] in (0, 1, 2)


def test_space_eval_structure():
    space = {
        "order": (
            scope.int(hp.quniform("p", 0, 4, 1)),
            scope.int(hp.quniform("d", 0, 2, 1)),
            scope.int(hp.quniform("q", 0, 4, 1)),
        ),
        "trend": hp.choice("trend", ["n", "c", "t"]),
        "fixed": 42,
    }
    point = {"p": 2, "d": 1, "q": 3, "trend": 1}
    out = space_eval(space, point)
    assert out == {"order": (2, 1, 3), "trend": "c", "fixed": 42}


def test_duplicate_labels_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        sample_space(
            [hp.uniform("x", 0, 1), hp.uniform("x", 5, 6)], np.random.default_rng(0)
        )


def test_seeded_sampling_deterministic():
    space = {"x": hp.uniform("x", 0, 1), "c": hp.choice("c", [1, 2, 3])}
    a = [sample_space(space, np.random.default_rng(42)) for _ in range(3)]
    assert a[0] == a[1] == a[2]


# -- fmin / Trials -----------------------------------------------------------


def test_fmin_sequential_quadratic():
    best = fmin(
        lambda p: (p["x"] - 3.0) ** 2,
        {"x": hp.uniform("x", -10, 10)},
        max_evals=60,
        rstate=0,
    )
    assert abs(best["x"] - 3.0) < 0.5


def test_fmin_reproducible_with_seed():
    space = {"x": hp.uniform("x", -5, 5)}
    obj = lambda p: (p["x"] + 1) ** 2
    b1 = fmin(obj, space, max_evals=25, rstate=7)
    b2 = fmin(obj, space, max_evals=25, rstate=7)
    assert b1 == b2


def test_tpe_beats_random_on_quadratic():
    space = {"x": hp.uniform("x", -10, 10), "y": hp.uniform("y", -10, 10)}
    obj = lambda p: (p["x"] - 2) ** 2 + (p["y"] + 4) ** 2

    def best_loss(algo, seed):
        t = fmin(obj, space, algo=algo, max_evals=50, rstate=seed, return_argmin=False)
        return min(l for l in t.losses if l is not None)

    tpe_scores = [best_loss(tpe_suggest, s) for s in range(5)]
    rnd_scores = [best_loss(random_suggest, s) for s in range(5)]
    assert np.mean(tpe_scores) < np.mean(rnd_scores)


def test_failed_trials_are_isolated():
    calls = {"n": 0}

    def flaky(p):
        calls["n"] += 1
        if p["x"] < 0:
            raise RuntimeError("negative!")
        return p["x"] ** 2

    trials = fmin(
        flaky,
        {"x": hp.uniform("x", -1, 1)},
        max_evals=30,
        rstate=3,
        return_argmin=False,
    )
    statuses = {t["result"]["status"] for t in trials.trials}
    assert STATUS_FAIL in statuses and STATUS_OK in statuses
    assert len(trials.trials) == 30  # sweep completed despite failures
    assert calls["n"] == 30
    assert trials.best_trial["result"]["loss"] >= 0
    fail = next(t for t in trials.trials if t["result"]["status"] == STATUS_FAIL)
    assert "negative!" in fail["result"]["error"]


def test_objective_dict_protocol():
    def obj(p):
        return {"loss": p["x"] ** 2, "status": STATUS_OK, "extra": "kept"}

    trials = fmin(
        obj, {"x": hp.uniform("x", -2, 2)}, max_evals=12, rstate=0, return_argmin=False
    )
    assert trials.best_trial["result"]["extra"] == "kept"


def test_choice_param_in_fmin():
    # minimum at kernel="b"
    table = {"a": 3.0, "b": 0.5, "c": 2.0}
    best = fmin(
        lambda p: table[p["kernel"]],
        {"kernel": hp.choice("kernel", ["a", "b", "c"])},
        max_evals=25,
        rstate=0,
    )
    assert best["kernel"] == 1  # index, like hyperopt argmin


# -- distributed executor ----------------------------------------------------


def test_device_trials_parallel_sweep(devices8):
    from dss_ml_at_scale_tpu.parallel import DeviceTrials

    seen = []
    lock = __import__("threading").Lock()

    def obj(p):
        import jax.numpy as jnp

        val = float(jnp.asarray(p["x"]) ** 2)  # touches the pinned device
        with lock:
            seen.append(p["x"])
        return val

    trials = DeviceTrials(parallelism=4)
    best = fmin(obj, {"x": hp.uniform("x", -3, 3)}, max_evals=20,
                trials=trials, rstate=0)
    assert len(trials.trials) == 20
    assert len(seen) == 20
    assert [t["tid"] for t in trials.trials] == list(range(20))
    assert abs(best["x"]) < 1.5


def test_device_trials_failure_isolation(devices8):
    from dss_ml_at_scale_tpu.parallel import DeviceTrials

    def obj(p):
        if p["x"] > 0:
            raise ValueError("boom")
        return -p["x"]

    trials = DeviceTrials(parallelism=3)
    fmin(obj, {"x": hp.uniform("x", -1, 1)}, max_evals=15, trials=trials, rstate=1)
    assert len(trials.trials) == 15
    assert any(t["result"]["status"] == STATUS_FAIL for t in trials.trials)
    assert trials.best_trial["result"]["loss"] >= 0


def test_device_trials_max_concurrency(devices8):
    import threading

    from dss_ml_at_scale_tpu.parallel import DeviceTrials

    state = {"now": 0, "peak": 0}
    lock = threading.Lock()

    def obj(p):
        with lock:
            state["now"] += 1
            state["peak"] = max(state["peak"], state["now"])
        import time

        time.sleep(0.02)
        with lock:
            state["now"] -= 1
        return p["x"] ** 2

    fmin(
        obj,
        {"x": hp.uniform("x", -1, 1)},
        max_evals=12,
        trials=DeviceTrials(parallelism=3, pin_devices=False),
        rstate=0,
    )
    assert state["peak"] <= 3


# -- data shipping -----------------------------------------------------------


def test_broadcast_lazy_and_shared():
    builds = {"n": 0}

    def factory():
        builds["n"] += 1
        return np.arange(10)

    b = Broadcast(factory=factory)
    assert builds["n"] == 0
    np.testing.assert_array_equal(b.value, np.arange(10))
    b.value
    assert builds["n"] == 1
    assert broadcast([1, 2]).value == [1, 2]
    with pytest.raises(ValueError):
        Broadcast()


def test_shared_fs_roundtrip(tmp_path):
    x = np.random.default_rng(0).normal(size=(100, 5))
    y = np.arange(100)
    path = save_shared(tmp_path / "data.npz", X=x, y=y)
    out = load_shared(path)
    np.testing.assert_array_equal(out["X"], x)
    np.testing.assert_array_equal(out["y"], y)
    # cached: same dict object back
    assert load_shared(path) is out


def test_loguniform_bounds_validated():
    with pytest.raises(ValueError, match="low > 0"):
        hp.loguniform("x", 0, 10)


def test_malformed_result_fails_trial_not_sweep():
    out = fmin(
        lambda p: {"loss": "bad", "status": STATUS_OK},
        {"x": hp.uniform("x", 0, 1)},
        max_evals=3,
        rstate=0,
        return_argmin=False,
    )
    assert all(t["result"]["status"] == STATUS_FAIL for t in out.trials)


def test_randint_uniform_endpoints():
    rng = np.random.default_rng(0)
    draws = [sample_space({"k": hp.randint("k", 3)}, rng)["k"] for _ in range(3000)]
    counts = np.bincount(draws, minlength=3) / 3000
    assert np.all(np.abs(counts - 1 / 3) < 0.05), counts


def test_device_trials_resume_keeps_pinning(devices8):
    from dss_ml_at_scale_tpu.parallel import DeviceTrials

    dt = DeviceTrials(parallelism=2)
    fmin(lambda p: p["x"] ** 2, {"x": hp.uniform("x", -1, 1)}, max_evals=4,
         trials=dt, rstate=0)
    fmin(lambda p: p["x"] ** 2, {"x": hp.uniform("x", -1, 1)}, max_evals=10,
         trials=dt, rstate=1)
    assert [t["tid"] for t in dt.trials] == list(range(10))


def test_unpersist_semantics():
    with pytest.raises(ValueError, match="value-backed"):
        broadcast([1]).unpersist()
    b = Broadcast(factory=lambda: [1, 2])
    assert b.value == [1, 2]
    b.unpersist()
    assert b.value == [1, 2]  # rebuilt


def test_fmin_nonfinite_loss_is_isolated():
    # A diverged trial (NaN loss) must fail that trial, not win argmin.
    from itertools import count

    calls = count()

    def obj(p):
        return float("nan") if next(calls) == 0 else (p["x"] - 2.0) ** 2

    from dss_ml_at_scale_tpu.hpo import Trials, fmin, hp

    trials = Trials()
    best = fmin(obj, {"x": hp.uniform("x", 0, 5)}, max_evals=15, trials=trials, rstate=0)
    assert abs(best["x"] - 2.0) < 1.5
    assert sum(r["status"] == "fail" for r in trials.results) == 1


def test_two_device_trials_smoke_logic(devices8):
    # DeviceTrials' pinning/concurrency contract on the simulated slice:
    # two pinned trials must use distinct devices and genuinely overlap.
    import threading
    import time

    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.hpo import fmin, hp
    from dss_ml_at_scale_tpu.parallel import DeviceTrials

    seen: set[str] = set()
    concurrent = {"now": 0, "max": 0}
    lock = threading.Lock()

    def objective(x):
        with lock:
            concurrent["now"] += 1
            concurrent["max"] = max(concurrent["max"], concurrent["now"])
        try:
            arr = jnp.ones((256, 256)) * x
            val = float(jnp.sum(arr * arr).block_until_ready())
            with lock:
                seen.add(str(next(iter(arr.devices()))))
            time.sleep(0.3)  # hold the device so trials genuinely overlap
            return {"loss": abs(val), "status": "ok"}
        finally:
            with lock:
                concurrent["now"] -= 1

    trials = DeviceTrials(devices=devices8[:2], parallelism=2)
    fmin(objective, hp.uniform("x", -1, 1), max_evals=8, trials=trials,
         rstate=np.random.default_rng(0), return_argmin=False)

    assert sum(t["result"]["status"] == "ok" for t in trials.trials) == 8
    assert len(seen) >= 2
    assert concurrent["max"] >= 2
