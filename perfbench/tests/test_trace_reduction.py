"""The trace reduction: interval arithmetic on made-up tables, and every
reduction on a trace recorded on the chip (``data/``)."""

from pathlib import Path

import pytest

import trace as tracemod
from trace import Tables

DATA = Path(__file__).resolve().parent / "data"


def test_union_measure_subtract():
    merged = tracemod.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert merged == [[0, 3], [5, 8]]
    assert tracemod.measure(merged) == 6
    assert tracemod.subtract([[0, 10]], merged) == [[3, 5], [8, 10]]
    assert tracemod.subtract(merged, [[1, 6]]) == [[0, 1], [6, 8]]
    assert tracemod.subtract(merged, []) == merged


def synthetic() -> Tables:
    us = 1000
    ops = [("%fusion.1 = f32[8] fusion(...)", 0, 100 * us),
           ("%all-reduce.3 = f32[8] all-reduce(...)", 100 * us, 50 * us),
           ("%fusion.2 = f32[8] fusion(...)", 120 * us, 60 * us),
           ("%fusion.1 = f32[8] fusion(...)", 400 * us, 100 * us)]
    modules = [("jit_train_step(123)", 0, 200 * us),
               ("jit_train_step(123)", 400 * us, 100 * us)]
    host = [("reader.next", 150 * us, 300 * us), ("train_step", 390 * us, 5 * us)]
    return Tables({0: {"modules": modules, "ops": ops, "async": []},
                   1: {"modules": [], "ops": [ops[0]], "async": []}}, host)


def test_busy_is_the_union_and_the_mean_over_devices():
    t = synthetic()
    assert tracemod.measure(tracemod.busy(t.devices[0])) == 280_000
    assert tracemod.busy_seconds(t) == pytest.approx((280e-6 + 100e-6) / 2)


def test_per_program_time_is_clipped_to_each_execution():
    prog = tracemod.programs(synthetic())["jit_train_step"]
    assert prog["count"] == 2
    assert prog["busy_s"] == pytest.approx(280e-6)
    assert prog["span_s"] == pytest.approx(300e-6)


def test_exposed_collective_time_is_what_no_compute_covers():
    # the all-reduce runs 100..150 us; fusion.2 covers 120..150
    assert tracemod.exposed_collective_seconds(synthetic()) == pytest.approx(
        20e-6)


def test_gaps_go_to_the_host_span_that_overlaps_them_most():
    gaps = dict(tracemod.idle_gaps(synthetic()))
    # one gap, 180..400 us: reader.next covers it, train_step only 10 us
    assert gaps == {"reader.next": pytest.approx(220e-6)}


def test_top_ops_sum_by_name():
    top = tracemod.top_ops(synthetic(), n=2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(200e-6)
    assert top[1][0] == "fusion.2"


def test_tables_round_trip(tmp_path):
    path = str(tmp_path / "t.json.gz")
    synthetic().write(path)
    back = Tables.read(path)
    assert back.devices == synthetic().devices and back.host == synthetic().host


RECORDED = sorted(DATA.glob("*.json.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_chip_trace_reduces(path):
    t = Tables.read(str(path))
    lo, hi = tracemod.extent(t)
    busy = tracemod.busy_seconds(t)
    assert 0 < busy <= (hi - lo) / 1e9
    progs = tracemod.programs(t)
    assert progs and all(p["count"] > 0 and 0 < p["busy_s"] <= p["span_s"] * 1.001
                         for p in progs.values())
    idle = (hi - lo) / 1e9 - tracemod.measure(tracemod.busy(t.devices[0])) / 1e9
    gaps = tracemod.idle_gaps(t)
    assert sum(s for _, s in gaps) <= idle * 1.001
    assert len(tracemod.top_ops(t)) <= 10


def test_a_recorded_trace_is_checked_in():
    assert RECORDED, "no recorded chip trace under perfbench/tests/data"
