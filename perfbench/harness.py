"""What every cell's run shares: the files that define it, the look for
the chip, the window's record, and the result line.

A cell is found by name in ``BENCHMARK.json``; its configuration is the
file the entry names, its traffic ``traffic/<traffic>.json``, its driver
``drivers/<traffic.driver>.py``, the program's adapter
``adapters/<config.family>.py``, the plain reference
``references/<config.family>.py`` and each per-layer metric
``layer_metrics/<name>.py``.  Nothing here names a cell, a configuration
or a metric.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import statistics
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    """An earlier line: everything but the result goes to stderr."""
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file
    traffic_path: str     # ... and where it is
    end_to_end: list      # names of the end-to-end metrics this cell reports
    per_layer: list       # names of its per-layer metrics
    units: dict           # metric name -> unit


def load_cell(name: str, bench_file: Path | None = None) -> Cell:
    bench_file = bench_file or ROOT / "BENCHMARK.json"
    bench = json.loads(bench_file.read_text())
    base = bench_file.parent
    try:
        entry = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(f"no workload {name!r} in {bench_file}") from None
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = json.loads((base / cfg_entry["file"]).read_text())
    traffic_dir = (base / cfg_entry["file"]).parent.parent / "traffic"
    traffic_path = traffic_dir / f"{entry['traffic']}.json"
    traffic = json.loads(traffic_path.read_text())

    def reported(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if reported(m)]
    layer = [m for m in bench["per_layer"] if reported(m)]
    # A per-layer metric without its own list follows the end-to-end
    # metric it moves.
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in layer if m["moves"] in e2e_names]
    return Cell(
        name=name, chips=int(entry["chips"]), config=config, traffic=traffic,
        traffic_path=str(traffic_path),
        end_to_end=[m["name"] for m in e2e],
        per_layer=[m["name"] for m in layer],
        units={m["name"]: m["unit"] for m in e2e + layer},
    )


def find_devices(chips: int, require_chip: bool = True):
    """The devices the cell runs on, and the device record of the result."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_chip and dev.platform != "tpu":
        raise NoChip(f"JAX found platform {dev.platform!r}, not a tpu")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    record = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    return devices[:chips], record


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest device; 0 where the backend reports none.

    The TPU runtime counts buffers (``peak_bytes_in_use``) apart from what
    it reserves for the compiled programs' own temporaries
    (``peak_bytes_reserved``: the 8 GB of a ResNet-50 step's activations
    are there and not among the buffers), so the peak is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0


class CompileCounter:
    """What JAX compiled or read back, and when (host clock)."""

    def __init__(self):
        import jax

        self.compiles: list = []      # (perf_counter at end, seconds)
        self.events = {"requests": 0, "hits": 0, "misses": 0}
        names = {"/jax/compilation_cache/compile_requests_use_cache":
                 "requests",
                 "/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}

        def on_event(event, **_):
            key = names.get(event)
            if key:
                self.events[key] += 1

        def on_duration(event, seconds, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles.append((time.perf_counter(), seconds))

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def inside(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.compiles if t0 <= t <= t1)


def cache_report(cache_dir: str | None) -> dict:
    """Entries of the persistent compile cache: how many, how large."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return {"dir": cache_dir, "entries": 0, "bytes": 0}
    sizes = [os.path.getsize(os.path.join(cache_dir, f))
             for f in os.listdir(cache_dir)
             if os.path.isfile(os.path.join(cache_dir, f))]
    return {"dir": cache_dir, "entries": len(sizes), "bytes": sum(sizes),
            "largest": sorted(sizes)[-4:]}


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation on all values."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class Window:
    """The measured window, as the per-layer readers see it."""

    cell: Cell
    t0: float                  # perf_counter at the window's start
    t1: float                  # ... and at its end
    wall0: float               # time.time() at t0 (the spans' clock)
    spans: list                # the program's spans that began inside it
    counters0: dict            # the program's counters at t0
    counters1: dict            # ... and at t1
    stats: dict                # the driver's counts (steps, tokens, ...)
    device_kind: str
    tables: object = None      # trace.Tables of the traced part, or None
    traced: tuple = None       # (perf_counter start, end) of the traced part

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def span_durations(self, name: str) -> list:
        return [e["dur"] for e in self.spans if e["name"] == name]

    def counter_delta(self, name: str, **labels) -> float | None:
        def value(snapshot):
            for m in snapshot["metrics"]:
                if m["name"] == name and all(
                        m["labels"].get(k) == v for k, v in labels.items()):
                    return m.get("value")
            return None

        a, b = value(self.counters0), value(self.counters1)
        if b is None:
            return None
        return b - (a or 0.0)

    def spans_in_trace(self, name: str) -> list:
        """Spans that began inside the traced part of the window."""
        if self.traced is None:
            return []
        lo = self.wall0 + (self.traced[0] - self.t0)
        hi = self.wall0 + (self.traced[1] - self.t0)
        return [e for e in self.spans
                if e["name"] == name and lo <= e["ts"] <= hi]


def program_spans(wall_lo: float, wall_hi: float) -> list:
    from dss_ml_at_scale_tpu import telemetry

    return [e for e in telemetry.get_span_log().events()
            if wall_lo <= e["ts"] <= wall_hi]


def program_counters() -> dict:
    from dss_ml_at_scale_tpu import telemetry

    return telemetry.snapshot()


def read_layer_metrics(window: Window) -> dict:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for name in window.cell.per_layer:
        module = importlib.import_module(
            "layer_metrics." + name.replace(".", "_").replace("-", "_"))
        value = module.read(window)
        if value is not None:
            out[name] = {"value": float(value),
                         "unit": window.cell.units[name]}
    return out


def compare(numbers: dict, limits: dict, quiet: bool = False) -> tuple:
    """Hold each number that has a limit in the traffic file to it.  A
    limit without its number is an error, not a pass; a number without a
    limit is one that PERF.md names as not compared, and is only logged
    (not even that for a stand-in of ``check.py``: ``quiet``).
    Returns (correct, {name: {"value", "limit"}})."""
    table, ok = {}, True
    for name, limit in limits.items():
        if name not in numbers:
            raise KeyError(f"no number for the limit {name!r}")
        value = numbers[name]
        good = value is not None and value == value and value <= limit
        ok = ok and good
        table[name] = {"value": value, "limit": limit}
    rest = {k: v for k, v in numbers.items() if k not in limits}
    if rest and not quiet:
        log("not compared: " + json.dumps(rest))
    return ok, table


def emit(result: dict) -> None:
    """The compared numbers as the last lines of stderr, the result as the
    last line of stdout."""
    for name, row in result.get("compared", {}).items():
        log(f"compared {name}: {row['value']} (limit {row['limit']})")
    log(f"correct: {result['correct']}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             bench_file: Path | None = None, faults: dict | None = None,
             variants: tuple = ()):
    """Drive one run of one cell; returns the result object.

    ``faults`` (tests only) breaks the timed path underneath the driver;
    ``variants`` (``check.py`` only) adds the readings of the control and
    of planted faults, each put in the program's place.
    """
    cell = load_cell(name, bench_file)
    driver = importlib.import_module(f"drivers.{cell.traffic['driver']}")
    return driver.run(cell, seed=int(seed), seconds=float(seconds),
                      trace=bool(trace), t_start=t_start,
                      require_chip=require_chip, faults=faults or {},
                      variants=tuple(variants))


def gaps_of_norms(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf, |‖prog‖ - ‖ref‖| over max(‖ref‖ of the leaf, of the
    median leaf)."""
    names = [k for k in ref if keep is None or k in keep]
    floor = statistics.median(ref[k] for k in names)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor) for k in names}


def process_age(t_start: float) -> float:
    """Seconds since this process was started (from /proc where it is
    there, else since ``run.py`` began)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        started = ticks / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return time.perf_counter() - t_start


def finish(cell: Cell, *, trace: bool, correct: bool, compared: dict,
           attempted: int, failed: int, end_to_end: dict, window: Window,
           device: dict, tables=None, busy_window=None, extra=None) -> dict:
    """The result object: end-to-end metrics without a trace, per-layer
    metrics with one; ``compared`` comes last."""
    if trace:
        metrics = read_layer_metrics(window)
    else:
        metrics = {k: {"value": float(v), "unit": cell.units[k]}
                   for k, v in end_to_end.items() if k in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace and tables is not None:
        import trace as tracemod

        device["busy_s"] = tracemod.busy_seconds(tables)
        device["window_s"] = busy_window
        result["breakdown"] = {
            "device_ops": tracemod.top_ops(tables),
            "idle_gaps": tracemod.idle_gaps(tables),
        }
    if extra:
        result.update(extra)
    result["compared"] = compared
    return result
