"""Readings for the limits of ``correct``: many seeds in one process.

    python3 perfbench/check.py --workload <cell> --seeds 11,12,13 --seconds 6 \
        [--variants control_fp8,fault_rows_2] [--trace 1] [--out FILE]

Each seed is one full run of the cell through the same driver as
``run.py`` (set-up, a short window at the cell's own load, the reference
afterwards).  ``--variants`` adds, for every seed, the readings of the
low-precision control and of planted faults, each computed with the
reference put in the program's place.  One JSON line per seed goes to
``--out`` (and to standard output).  The benchmark's own runs never run
this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(1, str(BENCH_DIR.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--variants", default="")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--bench-file", default=None)
    ap.add_argument("--no-chip", action="store_true",
                    help="rehearsal on the CPU: skip the look for a chip")
    args = ap.parse_args(argv)

    import harness

    variants = tuple(v for v in args.variants.split(",") if v)
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            result = harness.run_cell(
                args.workload, seed, args.seconds, bool(args.trace),
                t_start=t, require_chip=not args.no_chip, variants=variants,
                bench_file=Path(args.bench_file) if args.bench_file else None)
            result["seed"] = seed
            result["run_seconds_total"] = time.perf_counter() - t
            line = json.dumps(result)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
