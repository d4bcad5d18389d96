"""Do the program's spans and the device trace tell the same time?

The spans start on ``time.time()``; the trace counts from its own
``profile_start_time``; ``trace.load_xplane`` puts the spans on the
trace's clock by that one number.  If the two clocks agree, a device
execution lies inside the host interval that caused and awaited it.
Both checks take ``trace.Tables`` (the spans are its ``host`` list) and
return ``{"executions", "worst_violation_ms", "median_slack_ms", ...}``,
or None where the tables hold nothing to pair.  A violation is how far
an execution reaches outside its interval (0 where none does); a slack
is how far inside it lies.

    python3 perfbench/clockcheck.py --workload <cell> --seed <n> --seconds <s>

is one ``--trace 1`` run of the cell as ``run.py`` makes it, with the
check of its driver's kind logged on standard error (``clock check:``)
before the result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

MS = 1e6    # nanoseconds


def _executions(tables, program, device=0):
    return sorted((s, s + d) for n, s, d in tables.devices[device]["modules"]
                  if n.split("(", 1)[0] == program)


def _host(tables, name):
    return sorted((s, s + d) for n, s, d in tables.host if n == name)


def _summary(begin_slack, end_slack):
    slacks = begin_slack + end_slack
    worst = max([0.0] + [-x for x in slacks])
    out = {"executions": len(begin_slack),
           "worst_violation_ms": worst / MS,
           "least_slack_ms": min(begin_slack) / MS,
           "median_slack_ms": statistics.median(begin_slack) / MS}
    if end_slack:
        out["least_end_slack_ms"] = min(end_slack) / MS
        out["median_end_slack_ms"] = statistics.median(end_slack) / MS
    return out


def serve(tables, device=0):
    """Every ``jit_slot_decode`` execution against the ``lm.dispatch``
    that began nearest to it (the engine has one step in flight and the
    steps are tens of milliseconds apart, so nearest is its own unless
    the clocks are off by half a step) and the ``lm.wait`` that follows
    that dispatch: it must begin after the dispatch began and end before
    the wait ended."""
    execs = _executions(tables, "jit_slot_decode", device)
    dispatches, waits = _host(tables, "lm.dispatch"), _host(tables, "lm.wait")
    if not execs or not dispatches or not waits:
        return None
    d_starts = [s for s, _ in dispatches]
    w_starts = [s for s, _ in waits]
    begin, end = [], []
    for lo, hi in execs:
        k = bisect.bisect_left(d_starts, lo)
        i = min((c for c in (k - 1, k) if 0 <= c < len(d_starts)),
                key=lambda c: abs(d_starts[c] - lo))
        j = bisect.bisect_left(w_starts, d_starts[i])
        if j == len(waits):
            continue
        begin.append(lo - d_starts[i])
        end.append(waits[j][1] - hi)
    return _summary(begin, end) if begin else None


def train(tables, device=0):
    """The k-th ``jit_train_step`` execution of the trace against the
    k-th ``train_step`` span that began in it (the driver starts the
    trace where the device is idle and every earlier step is done, so the
    orders agree): it must begin after the span that dispatched it
    began."""
    execs = _executions(tables, "jit_train_step", device)
    spans = [iv for iv in _host(tables, "train_step") if iv[0] >= 0]
    if not execs or not spans:
        return None
    begin = [lo - s for (lo, _), (s, _) in zip(execs, spans)]
    return _summary(begin, [])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench_dir = Path(__file__).resolve().parent
    sys.path.insert(0, str(bench_dir))
    sys.path.insert(1, str(bench_dir.parent))

    import harness

    finish = harness.finish

    def checked(cell, **kw):
        tables = kw.get("tables")
        if tables is not None:
            check = {"serve": serve, "train": train}[cell.traffic["driver"]]
            harness.log("clock check: " + json.dumps(check(tables)))
        return finish(cell, **kw)

    harness.finish = checked
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  True, t_start=T_START)
    except harness.NoChip as e:
        harness.log(f"no chip: {e}")
        return 2
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
