"""``BENCHMARK.json`` against the contract it is checked by, and against
the files the harness finds by its names."""

import json
import re
import subprocess
import sys
from pathlib import Path


import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(BENCH["command"]) <= 32 and all(map(line, BENCH["command"]))
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    # the whole check has to fit with the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) <= 24
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert len(c["reduced"]) <= 16 and c["reduced"] == body["reduced"]
        for key in c["reduced"]:
            assert NAME.fullmatch(key)
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head_size)",
                                 key)


def test_workloads():
    names = [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and NAME.fullmatch(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(names) // 4)


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert len(e2e) == len(BENCH["end_to_end"]) <= 16
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    layer_names = [m["name"] for m in BENCH["per_layer"]]
    assert len(set(layer_names)) == len(layer_names) <= 128
    assert not set(layer_names) & set(e2e)
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", moved)) <= moved
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_enough_and_every_file_is_there():
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        assert (harness.BENCH_DIR / "drivers"
                / f"{cell.traffic['driver']}.py").is_file()
        for kind in ("adapters", "references"):
            assert (harness.BENCH_DIR / kind
                    / f"{cell.config['family']}.py").is_file()
        for name in cell.per_layer:
            reader = name.replace(".", "_").replace("-", "_")
            assert (harness.BENCH_DIR / "layer_metrics"
                    / f"{reader}.py").is_file(), name
        # the rooflines that move a metric stand beside a whole-step mfu
        moves = {m["moves"] for m in BENCH["per_layer"]
                 if m["name"].endswith("_roofline")
                 and w["name"] in m.get("workloads", [w["name"]])}
        for metric in moves:
            assert any("mfu" in m["name"] and m["moves"] == metric
                       and w["name"] in m.get("workloads", [w["name"]])
                       for m in BENCH["per_layer"])
        assert set(cell.traffic["limits"]), "no limits for correct"


def test_files_under_paths_are_named_from_names():
    for p in BENCH["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts or not f.is_file():
                continue
            rel = str(f.relative_to(ROOT))
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_no_result_without_a_chip():
    # here JAX is held to the CPU: the run must fail and print no result
    proc = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "BENCH_RUN": "x"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_no_result_where_the_program_is_missing(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "perfbench" / "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"], capture_output=True, text=True,
        timeout=300, cwd=tmp_path, env={"JAX_PLATFORMS": "cpu",
                                        "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0 and proc.stdout.strip() == ""
