"""`dsst` command-line entry point.

Replaces the reference's three config surfaces — ``dbutils.widgets``,
module-level constant cells, and the RUNME job JSON (SURVEY.md §5.6) —
with ordinary subcommands. Subcommands register here as workloads land.
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="dsst",
        description="dss_ml_at_scale_tpu: TPU-native scale-out ML framework",
    )
    parser.add_argument(
        "--platform", default=None, metavar="NAME",
        help="force the jax platform (e.g. cpu) for this invocation, "
        "before any backend use (the in-process equivalent of "
        "JAX_PLATFORMS); an error if a backend is already up",
    )
    # Site list generated from the one registry the tier-1 lint
    # (scripts/check_fault_sites.py) holds the code to, so this help
    # text cannot drift from the actual injection surface.
    from ..resilience.faults import KNOWN_SITES

    parser.add_argument(
        "--fault-plan", default=None, metavar="SPEC",
        help="arm deterministic fault injection for this invocation, e.g. "
        "'rpc.send=2;grads.nonfinite=1@5;reader.next=p0.1;seed=7' "
        f"(sites: {', '.join(sorted(KNOWN_SITES))}; N = fail the first N "
        "hits, N@K = skip K hits then fail N, pX = seeded per-hit "
        "probability, kN/kN@K = SIGKILL the process at the hit — the "
        "power-cut mode dsst chaos arms at the fs.* sites: "
        "fs.torn_write leaves a truncated staged .tmp, "
        "fs.crash_after_tmp a complete .tmp that never publishes, "
        "fs.fsync an EIO-style fsync failure; suffix .<kind> scopes one "
        "publish family, e.g. fs.crash_after_tmp.manifest=k1). "
        "Default: env DSST_FAULT_PLAN; chaos testing only",
    )
    sub = parser.add_subparsers(dest="command")
    info = sub.add_parser("info", help="show runtime topology and devices")
    info.add_argument(
        "--probe", type=_positive_seconds, default=None, metavar="SECONDS",
        help="query devices in a subprocess with this timeout instead "
        "of in-process — reports a device query that does not return "
        "as a diagnostic (exit 3) instead of hanging",
    )
    info.set_defaults(fn=_cmd_info)

    from .commands import register_all

    register_all(sub)
    return parser


def _positive_seconds(s: str) -> float:
    v = float(s)
    if v <= 0:
        raise argparse.ArgumentTypeError(
            f"must be a positive number of seconds, got {s!r}"
        )
    return v


def _cmd_info(args: argparse.Namespace) -> int:
    if getattr(args, "probe", None) is not None:
        import subprocess

        try:
            # The child is this same CLI without --probe, so both paths
            # print identical output by construction.
            proc = subprocess.run(
                [sys.executable, "-m", "dss_ml_at_scale_tpu.config.cli",
                 "info"],
                timeout=args.probe, capture_output=True, text=True,
            )
        except subprocess.TimeoutExpired:
            print(
                f"accelerator unreachable: device query did not return "
                f"within {args.probe:g}s"
            )
            return 3
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        return proc.returncode

    import jax

    from ..runtime import local_topology

    topo = local_topology()
    print(f"process {topo.process_index}/{topo.process_count}")
    print(f"devices {topo.local_device_count} local / {topo.global_device_count} global")
    for d in jax.local_devices():
        print(f"  {d}")
    return 0


def main(argv: list[str] | None = None) -> int:
    import os

    parser = build_parser()
    args = parser.parse_args(argv)
    # Stash the exact invocation for the run journal: what `dsst runs
    # doctor --resume` re-executes (with --resume-auto) to revive a run
    # this process may leave interrupted.
    from .commands import set_invocation_argv

    set_invocation_argv(argv if argv is not None else sys.argv[1:])
    fault_spec = args.fault_plan or os.environ.get("DSST_FAULT_PLAN")
    if fault_spec:
        # Armed before any subcommand work, and exported so subprocess
        # workers (which inherit the env and re-enter main here) arm the
        # same plan — a --fault-plan chaos run must not silently test
        # only the driver process.
        os.environ["DSST_FAULT_PLAN"] = fault_spec
        from ..resilience.faults import install_from_spec

        install_from_spec(fault_spec)
    if os.environ.get("DSST_SANITIZE") and args.command != "sanitize":
        # Armed before any subcommand constructs its locks/threads (and
        # exported to subprocess workers via the inherited env): the
        # runtime thread sanitizer rides ANY dsst command in
        # observation mode — findings to stderr at exit, exit code
        # untouched. `dsst sanitize` itself manages its own scope.
        from ..analysis.sanitize import arm_observation_mode

        arm_observation_mode()
    if args.platform:
        import jax
        from jax._src import xla_bridge

        # backends_are_initialized() reads a flag and claims no device
        # (jax 0.9.0 has no public reader for it). After initialization
        # jax ignores a jax_platforms update, so a --platform that can
        # no longer be honoured is an error, not a warning: a caller
        # that asked for cpu must not keep running on the accelerator
        # unawares.
        if xla_bridge.backends_are_initialized():
            print(
                f"error: --platform {args.platform} cannot be honoured — "
                f"a JAX backend is already initialized as "
                f"{jax.default_backend()!r} in this process",
                file=sys.stderr,
            )
            return 2
        jax.config.update("jax_platforms", args.platform)
    if args.command != "bench":
        # After --platform: the cache function reads the configured
        # platform (and is a no-op on cpu). No backend is initialised
        # here. `dsst bench` is left out: its parent only starts
        # children and never imports jax (the in-process modes switch
        # the cache on themselves).
        from ..runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
    if not getattr(args, "fn", None):
        parser.print_help()
        return 2
    try:
        return args.fn(args)
    except BaseException:
        # A crashed command must not leave its (default-on) tracking run
        # in RUNNING state — close it as FAILED before propagating.
        from .commands import fail_active_tracker

        fail_active_tracker()
        raise


if __name__ == "__main__":
    sys.exit(main())
