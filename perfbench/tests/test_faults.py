"""``correct`` has to come out false when the timed path is broken, and
when the control stands in for the program.

Each test skips the harness's look for a chip and drives the rest of a
run (the drivers, the feed, the program's own ``Trainer.fit`` or served
engine, the reference, the comparison) on the tiny cells under
``rehearsal/``, with one fault planted underneath."""

import time
from pathlib import Path

import pytest

import harness

BENCH_FILE = Path(__file__).resolve().parent / "rehearsal" / "BENCHMARK.json"


def drive(cell, seed=77, seconds=0.5, **kw):
    return harness.run_cell(cell, seed, seconds, False,
                            t_start=time.perf_counter(), require_chip=False,
                            bench_file=BENCH_FILE, **kw)


def failed(result):
    return [k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]]


def test_sound_training_run_is_correct():
    result = drive("resnet_tiny_train_predecoded")
    assert result["correct"] and not failed(result), result["compared"]
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


def test_a_step_that_returns_its_state_unchanged_is_caught():
    result = drive("resnet_tiny_train_predecoded",
                   faults={"state_unchanged": True})
    assert not result["correct"]
    assert "change_after" in failed(result)
    assert result["compared"]["change_after"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_caught():
    result = drive("resnet_tiny_train_predecoded",
                   faults={"keep_rows_fraction": 0.5})
    assert not result["correct"] and failed(result)


def test_the_exchange_between_chips_left_out_is_caught():
    # every chip training on its own rows alone: a quarter of the batch
    sound = drive("resnet_tiny_train_dp4")
    assert sound["correct"], sound["compared"]
    assert sound["device"]["count"] == 4
    result = drive("resnet_tiny_train_dp4",
                   faults={"keep_rows_fraction": 0.25})
    assert not result["correct"] and failed(result)


def test_table_rows_are_matched_and_decoded_by_the_reference():
    result = drive("resnet_tiny_train_parquet")
    assert result["compared"]["pixel_gap"]["value"] < 0.1


@pytest.mark.parametrize("variant", ["control_fp8", "fault_rows_2"])
def test_a_stand_in_comes_out_not_correct(variant):
    # the reference in fp8, or fed half of every batch, put in the
    # program's place and held to the cell's limits by harness.compare
    result = drive("resnet_tiny_train_predecoded", variants=(variant,))
    assert result["correct"], result["compared"]
    stood = result["readings"][variant]
    assert not stood["correct"], stood["compared"]
    assert "grad_first_direction_least" in failed(stood)


def test_sound_serving_run_is_correct():
    result = drive("lm_tiny_serve_chat", seconds=1.0)
    assert result["correct"], result["compared"]
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


def test_the_open_loop_drives_the_same_server():
    # Poisson arrivals with bursts, timed from when each was due
    result = drive("lm_tiny_serve_open", seconds=1.5)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 6 and result["failed"] == 0
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_a_token_altered_where_it_is_produced_is_caught():
    result = drive("lm_tiny_serve_chat", seconds=1.0,
                   faults={"alter_token_every": 5})
    assert not result["correct"]
    assert "logit_gap_max" in failed(result)


def test_the_lm_control_comes_out_not_correct():
    result = drive("lm_tiny_serve_chat", seconds=1.0,
                   variants=("control_fp8",))
    assert result["correct"], result["compared"]
    stood = result["readings"]["control_fp8"]
    assert not stood["correct"] and failed(stood) == ["logit_gap_max"]
