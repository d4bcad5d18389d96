"""From a profiler trace to numbers: the benchmark's own reduction.

``start``/``stop`` wrap ``jax.profiler`` with the Python tracer and the
host tracer off: with either on, every host-to-device copy of a batch
writes some hundred thousand host events, a traced step takes five times
as long and the trace of ten steps is 290 MB (my chip runs, PR 23).  The
program's spans are put on the trace's clock instead, from the span log
and the trace's own ``profile_start_time``.  ``load_xplane`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` into plain tables; everything after that is
arithmetic on lists of ``(name, start_ns, duration_ns)`` and is what the
tests check on the recorded trace in ``tests/data``.

Layout of a TPU trace (jax 0.9, libtpu 0.0.34, looked at by hand, PR 23):
plane ``/device:TPU:<n>`` with lines ``XLA Modules`` (one event per
execution of a compiled program, named ``jit_<fn>(<hash>)``), ``XLA Ops``
(one event per HLO operation, named by its HLO text ``%name = ...``) and
``Async XLA Ops`` (copies and collectives in flight); plane ``/host:CPU``
with one line per thread, where, if the host tracer is on,
``TraceAnnotation`` events (the program's spans) sit on lines named
``python``; plane ``Task Environment`` with ``profile_start_time`` in
nanoseconds since the epoch.  Event times are nanoseconds since then.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

# Gaps shorter than this are the device's own pauses between operations;
# they are summed under one name instead of being looked up on the host.
SHORT_GAP_NS = 10_000

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


@dataclasses.dataclass
class Tables:
    """devices: {index: {"modules"|"ops"|"async": [(name, start, dur)]}};
    host: [(name, start, dur)] of the program's spans."""

    devices: dict
    host: list

    def to_json(self) -> dict:
        return {"devices": {str(k): v for k, v in self.devices.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, obj: dict) -> "Tables":
        devs = {int(k): {line: [tuple(e) for e in evs]
                         for line, evs in v.items()}
                for k, v in obj["devices"].items()}
        return cls(devs, [tuple(e) for e in obj["host"]])

    @classmethod
    def read(cls, path: str) -> "Tables":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))

    def write(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)


def op_name(hlo_text: str) -> str:
    """'%fusion.12 = bf16[..] fusion(..)' -> 'fusion.12'."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def start(logdir: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=options)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load_xplane(path: str, *, spans: list) -> Tables:
    """Tables from an ``.xplane.pb``.

    ``spans``: the program's span log (dicts with ``name``, ``ts`` in
    epoch seconds, ``dur`` in seconds); they become the host events, put
    on the trace's clock by its ``profile_start_time`` (the trace itself
    is taken with the host tracer off and holds none)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name == "Task Environment":
            start_ns = int(dict(plane.stats)["profile_start_time"])
            host = [(e["name"], int(e["ts"] * 1e9) - start_ns,
                     int(e["dur"] * 1e9)) for e in spans]
            continue
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = devices.setdefault(
                int(m.group(1)), {"modules": [], "ops": [], "async": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops",
                       "Async XLA Ops": "async"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
    return Tables(devices, host)


# -- interval arithmetic -----------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted [start, end) pairs of possibly overlapping ones."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def measure(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list:
    """Parts of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _spans(events):
    return [(s, s + d) for _, s, d in events]


def busy(dev: dict) -> list:
    """Merged intervals in which an operation ran on this device."""
    return union(_spans(dev["ops"]))


def busy_seconds(t: Tables) -> float:
    """Seconds an operation ran on the device, mean over the devices."""
    if not t.devices:
        return 0.0
    return sum(measure(busy(d)) for d in t.devices.values()) / (
        1e9 * len(t.devices))


def extent(t: Tables) -> tuple:
    """First start and last end of any device event, ns."""
    starts = [s for d in t.devices.values() for _, s, _ in d["ops"]]
    ends = [s + n for d in t.devices.values() for _, s, n in d["ops"]]
    return (min(starts), max(ends)) if starts else (0, 0)


def program_name(module_event: str) -> str:
    """'jit_train_step(123)' -> 'jit_train_step'."""
    return module_event.split("(", 1)[0]


def programs(t: Tables, device: int = 0) -> dict:
    """Per compiled program on one device: executions and the device-busy
    seconds inside them (operations' union clipped to each execution)."""
    dev = t.devices[device]
    merged = busy(dev)
    out: dict = {}
    for name, s, d in dev["modules"]:
        short = program_name(name)
        inside = measure(_clip(merged, s, s + d))
        rec = out.setdefault(short, {"count": 0, "busy_s": 0.0, "span_s": 0.0})
        rec["count"] += 1
        rec["busy_s"] += inside / 1e9
        rec["span_s"] += d / 1e9
    return out


def busy_per_execution(t: Tables, program: str, device: int = 0):
    """Device-busy seconds of one execution of ``program``, the mean over
    its executions in the trace; None where it never ran."""
    prog = programs(t, device).get(program)
    if not prog or not prog["count"]:
        return None
    return prog["busy_s"] / prog["count"]


def _clip(merged, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if e > lo and s < hi]


def top_ops(t: Tables, n: int = 10, device: int = 0) -> list:
    """The operations that took most device time, by the trace's names."""
    acc: dict = {}
    for name, _, d in t.devices[device]["ops"]:
        key = op_name(name)
        acc[key] = acc.get(key, 0) + d
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_gaps(t: Tables, n: int = 10, device: int = 0) -> list:
    """Idle time of one device by what the host was doing: each gap goes
    to the host span that overlaps it most ('no_span' where none does)."""
    merged = busy(t.devices[device])
    if not merged:
        return []
    gaps = subtract([[merged[0][0], merged[-1][1]]], merged)
    host = sorted(t.host, key=lambda e: e[1])
    acc: dict = {}
    for lo, hi in gaps:
        if hi - lo < SHORT_GAP_NS:
            acc["between_ops"] = acc.get("between_ops", 0) + (hi - lo)
            continue
        best, best_ov = "no_span", 0
        for name, s, d in host:
            if s >= hi:
                break
            ov = min(hi, s + d) - max(lo, s)
            if ov > best_ov:
                best, best_ov = name, ov
        acc[best] = acc.get(best, 0) + (hi - lo)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def exposed_collective_seconds(t: Tables, device: int = 0) -> float:
    """Time in collective operations (in flight or executing) during which
    no other operation ran on that device."""
    dev = t.devices[device]
    coll = union((s, s + d) for line in ("ops", "async")
                 for n, s, d in dev[line] if COLLECTIVE.search(op_name(n)))
    compute = union((s, s + d) for n, s, d in dev["ops"]
                    if not COLLECTIVE.search(op_name(n)))
    return measure(subtract(coll, compute)) / 1e9
