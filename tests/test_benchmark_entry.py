"""The benchmark as the driver runs it (``BENCHMARK.json``), held to one
rule without a chip: a CPU run prints no result.

The cells are read from the file, so a cell added later is covered the
day it is added.
"""

import json
import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_without_a_chip_exits_2_and_prints_nothing(cell):
    """Exit code 2 and an empty stdout: no number of a CPU run can
    appear under a device metric's name. (A usage error exits 2 as
    well, hence the message.)"""
    proc = subprocess.run(
        [*BENCHMARK["command"], "--workload", cell, "--seed", "1",
         "--seconds", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout == ""
    assert "no chip" in proc.stderr


def test_benchmark_names_files_that_exist():
    scripts = [a for a in BENCHMARK["command"] if a.endswith(".py")]
    assert scripts and CELLS
    files = scripts + [c["file"] for c in BENCHMARK["configs"]]
    assert [p for p in files
            if not os.path.isfile(os.path.join(REPO, p))] == []
    assert [d for d in BENCHMARK["paths"]
            if not os.path.isdir(os.path.join(REPO, d))] == []
