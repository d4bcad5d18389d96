"""CPU rehearsal of a cell's control flow at a tiny size.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py --workload resnet_tiny_train_predecoded [--chips 4]

Runs the same drivers as ``run.py`` on the files under
``tests/rehearsal``, without the look for a chip.  What it prints is a
count of control flow, under names no device metric uses
(``rehearsal.<name>``): a number from a CPU run is never a speed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(1, str(BENCH_DIR.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3_000_000_019)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    if args.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import harness

    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, require_chip=False,
        bench_file=BENCH_DIR / "tests" / "rehearsal" / "BENCHMARK.json")
    result["metrics"] = {f"rehearsal.{k}": v
                         for k, v in result["metrics"].items()}
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
