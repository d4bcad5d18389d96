"""Group-apply engine — the ``groupBy().applyInPandas()`` replacement.

Reference contract (SURVEY.md §2.2 X3, §3.3): hash-partition rows so each
(Product, SKU) group lands in its own Spark task, run an arbitrary
pandas→pandas function per group, union the results
(``group_apply/02_Fine_Grained_Demand_Forecasting.py:516-528``). Two
TPU-native execution paths replace that:

1. :func:`group_apply` — the **host path**: groups hash-sharded across
   processes (multi-host) and a worker pool within each process. Runs
   any Python function per group, exactly like ``applyInPandas``; this
   is the compatibility surface. ``executor="process"`` runs each group
   in a subprocess pool — the reference's actual execution shape (one
   Python worker process per Spark task) and the right choice for
   GIL-bound pure-Python group functions; it requires ``fn`` to be
   importable by reference, the same contract as remote HPO objectives.
2. :func:`pad_groups` + :func:`make_grid_fit` / :func:`grid_fit_panel`
   — the **device path**: groups padded to a rectangle, stacked, sharded
   over a ``Mesh`` axis, and fit-tune-scored by a bounded family of
   grid-fused XLA launches. The discrete HPO space (75 ``(p, d, q)``
   orders) is enumerated INSIDE the program — ``vmap`` over the
   flattened (group x order) plane, per-group argmin reduced on device
   — so thousands of per-SKU tuned fits cost a handful of launches
   instead of thousands of Python processes or one launch per TPE
   round. :func:`batched_fmin` + :func:`device_put_groups` remain as
   the per-round TPE compatibility path (same search semantics as the
   reference's nested Hyperopt, one launch per round).
"""

from __future__ import annotations

import functools
import hashlib
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from typing import Callable, NamedTuple, Sequence

import numpy as np
import pandas as pd

from .. import telemetry
from ..hpo.tpe import TPE


def stable_group_hash(key: tuple) -> int:
    """Deterministic cross-process hash of a group key (Spark-shuffle-like)."""
    digest = hashlib.md5(repr(key).encode()).digest()
    return int.from_bytes(digest[:8], "little")


def shard_of(key: tuple, process_count: int) -> int:
    return stable_group_hash(key) % process_count


def _run_group_by_ref(args):
    """Subprocess worker: resolve ``fn`` by module:qualname and run it.

    Module-level so it pickles by reference into pool workers; the group
    frame ships pickled, the function ships as a name — the moral
    equivalent of Spark sending Arrow batches to Python worker processes.
    The ref resolves with a plain importlib lookup (not
    ``trials.resolve_objective``) so spawn workers don't also pay the
    jax-importing ``trials``/``hpo.fmin`` module chain.
    """
    ref, group, on_error = args
    import importlib

    module, _, qualname = ref.partition(":")
    fn = importlib.import_module(module)
    for part in qualname.split("."):
        fn = getattr(fn, part)
    try:
        return fn(group)
    except Exception:
        if on_error == "raise":
            raise
        return None


def group_apply(
    df: pd.DataFrame,
    keys: str | Sequence[str],
    fn: Callable[[pd.DataFrame], pd.DataFrame],
    *,
    num_workers: int | None = None,
    process_index: int = 0,
    process_count: int = 1,
    on_error: str = "raise",
    executor: str = "thread",
) -> pd.DataFrame:
    """Apply ``fn`` to each key-group of ``df``; concat the results.

    Multi-host: each process computes the same deterministic key→shard
    hash and runs only its own groups; callers concatenate per-host
    outputs (or write them to a common Parquet dataset, the usual sink).
    ``on_error='skip'`` gives SparkTrials-style per-group failure
    isolation: a failing group is dropped, the rest proceed.

    ``executor``: ``"thread"`` (default — right for fns that release the
    GIL, e.g. anything calling jitted kernels or numpy), ``"process"``
    (one subprocess per worker — right for GIL-bound pure-Python fns;
    requires ``fn`` importable by reference, like remote HPO objectives),
    or ``"inline"`` (sequential, for debugging).
    """
    if on_error not in ("raise", "skip"):
        raise ValueError(f"on_error must be 'raise' or 'skip', got {on_error!r}")
    if executor not in ("thread", "process", "inline"):
        raise ValueError(
            f"executor must be 'thread', 'process', or 'inline', got {executor!r}"
        )
    keys = [keys] if isinstance(keys, str) else list(keys)
    groups = [
        (k if isinstance(k, tuple) else (k,), g)
        for k, g in df.groupby(keys, sort=True)
    ]
    mine = [(k, g) for k, g in groups if shard_of(k, process_count) == process_index]

    def run(item):
        key, g = item
        try:
            return fn(g.reset_index(drop=True))
        except Exception:
            if on_error == "raise":
                raise
            return None

    if executor == "process":
        import multiprocessing

        from .trials import objective_ref

        ref = objective_ref(fn)  # raises early on closures/lambdas
        work = [(ref, g.reset_index(drop=True), on_error) for _, g in mine]
        # spawn, not fork: the caller has usually initialized JAX/XLA by
        # now, and forking a process whose runtime threads may hold locks
        # can deadlock the child. Spawned workers persist across groups,
        # amortizing their interpreter startup.
        with ProcessPoolExecutor(
            max_workers=num_workers,
            mp_context=multiprocessing.get_context("spawn"),
        ) as pool:
            outs = list(pool.map(_run_group_by_ref, work))
    elif executor == "thread" and (num_workers is None or num_workers > 1):
        with ThreadPoolExecutor(max_workers=num_workers) as pool:
            outs = list(pool.map(run, mine))
    else:
        outs = [run(item) for item in mine]
    outs = [o for o in outs if o is not None]
    if not outs:
        return pd.DataFrame()
    return pd.concat(outs, ignore_index=True)


# -- device path: pad → stack → shard → vmap ---------------------------------


class PaddedGroups(NamedTuple):
    """A rectangularized group panel ready for a vmapped fit."""

    values: dict[str, np.ndarray]  # column -> (G, L) float32, zero-padded
    n_valid: np.ndarray  # (G,) true length per group
    keys: pd.DataFrame  # (G, len(keys)) group keys, row i = group i
    n_groups: int  # true group count (before any mesh padding)


def pad_groups(
    df: pd.DataFrame,
    keys: str | Sequence[str],
    columns: Sequence[str],
    sort_by: str | None = None,
    max_len: int | None = None,
) -> PaddedGroups:
    """Stack per-group columns into (G, L) arrays with validity lengths.

    The tail is zero-padded; consumers use ``n_valid`` masks (the ops
    kernels take ``n_valid`` directly). ``sort_by`` orders rows within a
    group first (stably) — the reference sorts by Date (``02...py:422``).

    The build is one vectorized scatter per column — group codes +
    within-group positions computed once for the whole frame — rather
    than a Python loop over G x len(columns) slices, so assembling a
    10k-SKU panel is pandas/numpy-bound, not interpreter-bound.
    """
    keys = [keys] if isinstance(keys, str) else list(keys)
    with telemetry.span("panel.build"):
        codes = df.groupby(keys, sort=True).ngroup().to_numpy()
        if codes.dtype.kind == "f":
            # Null group keys: groupby drops those groups, so ngroup()
            # marks their rows NaN — exclude the rows before the
            # scatter, mirroring the per-group iteration this replaced.
            keep = ~np.isnan(codes)
            df = df.loc[keep]
            codes = codes[keep]
        codes = codes.astype(np.int64)
        n = len(codes)
        if n == 0:
            raise ValueError("pad_groups: empty frame has no groups")
        G = int(codes.max()) + 1
        if sort_by is not None:
            order = np.lexsort((df[sort_by].to_numpy(), codes))
        else:
            order = np.lexsort((np.arange(n), codes))
        codes_s = codes[order]
        lengths = np.bincount(codes_s, minlength=G)
        L = int(max_len or lengths.max())
        if (lengths > L).any():
            raise ValueError(
                f"group length {lengths.max()} exceeds max_len {L}"
            )
        starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
        pos = np.arange(n) - starts[codes_s]
        values = {}
        for c in columns:
            buf = np.zeros((G, L), np.float32)
            buf[codes_s, pos] = df[c].to_numpy(np.float32)[order]
            values[c] = buf
        key_frame = df.iloc[order[starts]][keys].reset_index(drop=True)
    return PaddedGroups(values, lengths, key_frame, G)


def pad_to_multiple(arr: np.ndarray, multiple: int) -> np.ndarray:
    """Pad axis 0 with copies of row 0 so G divides the mesh axis evenly.

    Dummy groups are real (duplicate) work discarded by the caller via
    ``PaddedGroups.n_groups`` — simpler and cheaper than masking inside
    the compiled fit.
    """
    g = arr.shape[0]
    pad = (-g) % multiple
    if pad == 0:
        return arr
    return np.concatenate([arr, np.repeat(arr[:1], pad, axis=0)], axis=0)


def device_put_groups(tree, mesh, axis_name: str = "data"):
    """Shard a pytree of (G, ...) arrays over one mesh axis (group-parallel).

    Pads G to a multiple of the axis size (duplicating group 0), then
    ``device_put``s with ``NamedSharding(P(axis_name))`` so a following
    ``jit(vmap(fit))`` runs SPMD across the slice — the pjit-across-pod
    execution SURVEY.md §2.3 assigns to group parallelism.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[axis_name]
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(pad_to_multiple(np.asarray(a), n), sharding), tree
    )


# -- grid-fused group fit: chunk → shard → one launch per chunk --------------

# Bound on groups per launch and per device: caps live panel + fit-plane
# memory on device (a chunk holds chunk_size x K simultaneous fits) and
# keeps the launch family at ONE compiled shape — every chunk, including
# the ragged tail, is padded to exactly this many rows. Sized for the
# 16 GB of one TPU v5e chip at `dsst forecast`'s default order bounds
# (75 orders, state dim 25, ~157 weeks): compiled for that chip, 64
# groups need 12.45 GiB of temporaries and 128 are refused (23.93G of
# 15.75G hbm); the former 1024 was refused outright for one 44 GB
# buffer, f32[1024,75,3,12,25,25].
GRID_CHUNK_PER_DEVICE = 64


class GridPanelResult(NamedTuple):
    """Host-side (G, ...) results of a chunked grid-fused panel fit."""

    order: np.ndarray  # (G, 3) winning (p, d, q) per group
    params: np.ndarray  # (G, n_params) packed params at the winner
    loss: np.ndarray  # (G,) selection score at the winner
    loglike: np.ndarray  # (G,) exact loglike of the winning fit
    pred: np.ndarray  # (G, L) full-range predictions at the winner
    n_iter: np.ndarray  # (G,) NM iterations summed over the grid
    converged: np.ndarray  # (G,) winning fit convergence
    chunks: int  # launches it took (the whole launch family)


# One jitted program per (cfg, select, mesh, axis_name, donate) — the
# handful of grid-fit configurations a process runs, each reused for
# every chunk of every panel; bounded by construction like the fused-op
# caches.
@functools.lru_cache(maxsize=None)
# dsst: ignore[retrace-hazard] config-keyed program cache: a process uses a handful of grid-fit configs and every chunk of every panel reuses its entry
def make_grid_fit(
    cfg,
    select: str = "mse",
    mesh=None,
    axis_name: str = "data",
    donate: bool = True,
):
    """The grid-fused group-fit program: ONE jitted launch fitting the
    full order grid for a whole chunk of groups.

    ``vmap`` over the group axis of :func:`..ops.sarimax.sarimax_fit_grid`
    (itself ``vmap`` over the order axis) flattens the (group x order)
    fit plane into one batched program; the per-group argmin is reduced
    on device, so the launch returns winners only. With ``mesh`` the
    group axis is sharded ``P(axis_name)`` (in AND out — pinned
    ``out_shardings`` keep donation intact under committed inputs, the
    decode-step lesson) and the audit's sharding-collectives rule proves
    the groups stay independent in the lowered HLO. ``donate`` donates
    the demand panel ``y``, which XLA aliases to the like-shaped
    predictions output — the chunk's dominant round-trip buffer is
    reused in place. (``exog`` has no like-shaped output to alias, so
    donating it would only buy a warning.)

    Signature of the returned callable:
    ``(y (G, L), exog (G, L, E), n_train (G,), n_valid (G,),
    orders (K, 3)) -> SarimaxGridResult`` with a leading G axis on every
    field. Cached per configuration: the audit registry pins EXACTLY
    this program (``sarimax.batched_fit``), so the certified IR and the
    production launches cannot drift apart.
    """
    import jax

    from ..ops.sarimax import sarimax_fit_grid

    def fit_chunk(y, exog, n_train, n_valid, orders):
        return jax.vmap(
            lambda yg, eg, ntg, nvg: sarimax_fit_grid(
                cfg, yg, eg, orders, ntg, nvg, select=select
            ),
        )(y, exog, n_train, n_valid)

    kwargs: dict = {}
    if donate:
        kwargs["donate_argnums"] = (0,)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        groups = NamedSharding(mesh, P(axis_name))
        replicated = NamedSharding(mesh, P())
        kwargs["in_shardings"] = (groups, groups, groups, groups,
                                  replicated)
        from ..ops.sarimax import SarimaxGridResult

        kwargs["out_shardings"] = SarimaxGridResult(
            order=groups, params=groups, loss=groups, loglike=groups,
            pred=groups, n_iter=groups, converged=groups,
        )
    return jax.jit(fit_chunk, **kwargs)


def grid_fit_panel(
    cfg,
    y: np.ndarray,
    exog: np.ndarray,
    n_train: np.ndarray,
    n_valid: np.ndarray,
    *,
    orders: np.ndarray | None = None,
    select: str = "mse",
    mesh=None,
    axis_name: str = "data",
    chunk_size: int | None = None,
    donate: bool = True,
) -> GridPanelResult:
    """Fit-tune-score every group over the full order grid in bounded
    chunked launches — the host driver of the grid-fused engine.

    Replaces the per-round HPO shape (10 TPE rounds = 10 ``eval_batch``
    launches + a host-side per-group TPE loop + a fresh ``device_put``
    of orders per round, then a refit launch) with
    ``ceil(G / chunk_size)`` launches total: each chunk is padded to the
    one compiled shape (duplicating group 0 — discarded work, no masking
    inside the program), placed sharded over ``axis_name`` when ``mesh``
    is given, and fitted by :func:`make_grid_fit`'s program with the
    demand panel donated. Orders default to the full
    :func:`..ops.sarimax.grid_orders` grid of ``cfg``.
    """
    import jax

    from ..ops.sarimax import grid_orders

    G = int(y.shape[0])
    if not (len(exog) == len(n_train) == len(n_valid) == G):
        raise ValueError(
            f"group-axis mismatch: y {G}, exog {len(exog)}, "
            f"n_train {len(n_train)}, n_valid {len(n_valid)}"
        )
    n_shards = int(mesh.shape[axis_name]) if mesh is not None else 1
    C = int(chunk_size or min(G, GRID_CHUNK_PER_DEVICE * n_shards))
    C = max(-(-C // n_shards) * n_shards, n_shards)
    order_grid = np.asarray(
        grid_orders(cfg) if orders is None else orders, np.int32
    )

    fit = make_grid_fit(
        cfg, select=select, mesh=mesh, axis_name=axis_name, donate=donate
    )
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        chunk_sharding = NamedSharding(mesh, P(axis_name))
        orders_dev = jax.device_put(
            order_grid, NamedSharding(mesh, P())
        )
    else:
        chunk_sharding = None
        orders_dev = order_grid

    fitted_counter = telemetry.counter(
        "skus_fitted_total", "groups fitted by the grid-fused engine"
    )
    outs: list[tuple] = []
    n_chunks = 0
    for lo in range(0, G, C):
        hi = min(lo + C, G)
        chunk = tuple(
            pad_to_multiple(a[lo:hi], C)
            for a in (y, exog, n_train, n_valid)
        )
        with telemetry.span("grid.chunk", groups=hi - lo, orders=len(order_grid)):
            if chunk_sharding is not None:
                chunk = tuple(
                    jax.device_put(a, chunk_sharding) for a in chunk
                )
            res = fit(*chunk, orders_dev)
            outs.append(tuple(
                np.asarray(leaf)[: hi - lo] for leaf in res
            ))
        fitted_counter.inc(hi - lo)
        n_chunks += 1
    return GridPanelResult(
        *(np.concatenate(parts) for parts in zip(*outs)),
        chunks=n_chunks,
    )


# -- nested HPO, batched ------------------------------------------------------


def batched_fmin(
    evaluate_batch: Callable[[list[dict]], np.ndarray],
    space,
    max_evals: int,
    n_groups: int,
    rstate: int | np.random.Generator | Sequence = 123,
    algo: TPE | None = None,
) -> tuple[list[dict], list[list[tuple[dict, float]]]]:
    """Run ``n_groups`` independent TPE searches with batched evaluation.

    The reference nests a sequential ``fmin(max_evals=10)`` inside every
    SKU's pandas UDF (``02...py:461-469``). Here each round proposes one
    point per group (host-side TPE, cheap) and ``evaluate_batch`` scores
    ALL groups at once — built to be one vmapped SARIMAX fit per round.
    Search semantics per group are unchanged: each group keeps its own
    history and proposal stream (the reference even seeds every SKU with
    the same rstate=123, reproduced by the scalar-``rstate`` default).

    Returns per-group best points and full histories. Groups whose
    evaluation returns a non-finite loss record it as a failed trial
    (excluded from history), preserving trial-failure isolation.
    """
    algo = algo or TPE()
    if isinstance(rstate, (int, np.integer)):
        rngs = [np.random.default_rng(rstate) for _ in range(n_groups)]
    elif isinstance(rstate, np.random.Generator):
        # One shared generator would entangle the groups' proposal
        # streams; spawn independent children instead.
        rngs = rstate.spawn(n_groups)
    else:
        rngs = list(rstate)
        if len(rngs) != n_groups:
            raise ValueError(f"need {n_groups} rstates, got {len(rngs)}")

    histories: list[list[tuple[dict, float]]] = [[] for _ in range(n_groups)]
    for _ in range(max_evals):
        points = [algo.suggest(space, histories[g], rngs[g]) for g in range(n_groups)]
        losses = np.asarray(evaluate_batch(points), float)
        if losses.shape != (n_groups,):
            raise ValueError(f"evaluate_batch returned {losses.shape}, want ({n_groups},)")
        for g in range(n_groups):
            if np.isfinite(losses[g]):
                histories[g].append((points[g], float(losses[g])))

    best = []
    for g in range(n_groups):
        if not histories[g]:
            raise ValueError(f"group {g}: no successful trials")
        best.append(min(histories[g], key=lambda pl: pl[1])[0])
    return best, histories
