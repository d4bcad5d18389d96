"""Fine-grained demand forecasting: per-SKU SARIMAX fit-tune-score.

TPU-native rebuild of the reference's scaled forecasting track
(``group_apply/02_Fine_Grained_Demand_Forecasting.py:341-556``):

- :func:`add_exo_variables` — covid / christmas / new-year exogenous
  enrichment with the reference's exact breakpoints (``:343-370``).
- :func:`split_train_score_data` — 40-week holdout (``:372-380``).
- :func:`build_tune_and_score_model` — per-group fit-tune-score
  (``:417-494``), runnable under :func:`..parallel.group_apply` for the
  applyInPandas-style host path.
- :func:`tune_and_forecast_panel` — the TPU path. Default
  ``search="grid"``: the discrete 5x3x5 = 75-order space the reference's
  Hyperopt samples (``:461-469``) is **enumerated inside the compiled
  program** — bounded chunks of groups, each chunk one XLA launch
  ``vmap``-ing the flattened (group x order) fit plane with the
  per-group argmin reduced on device (strictly better optima than
  TPE-with-max_evals=10, exact argmin over the same grid, and a handful
  of launches instead of one per round). ``search="tpe"`` keeps the
  per-round batched-TPE execution shape as the compatibility path: same
  proposal streams as the reference's nested ``fmin``, one vmapped
  launch per round.
"""

from __future__ import annotations

import datetime as dt

import numpy as np
import pandas as pd

from ..hpo import hp
from ..hpo.hp import scope
from ..ops import SarimaxConfig, sarimax_fit, sarimax_predict
from ..parallel.group_apply import (
    batched_fmin,
    device_put_groups,
    grid_fit_panel,
    pad_groups,
)

EXO_FIELDS = ["covid", "christmas", "new_year"]
FORECAST_HORIZON = 40  # weeks, reference :341

# p in [0,4], d in [0,2], q in [0,4] — reference :462-464. The TPE path
# samples this space; the grid path enumerates exactly it
# (``ops.grid_orders`` of the same bounds).
SEARCH_SPACE = {
    "p": scope.int(hp.quniform("p", 0, 4, 1)),
    "d": scope.int(hp.quniform("d", 0, 2, 1)),
    "q": scope.int(hp.quniform("q", 0, 4, 1)),
}

# The benchmark/audit geometry of the grid-fused group-fit chunk: the
# `dsst bench` `group_fit` tier-1 gate and the audited
# `sarimax.batched_fit` entrypoint (32 groups x 40 weeks, reduced order
# bounds) both describe THIS program, so the pinned FLOPs budget prices
# the measured launches.
# bfgs_iter=0: the vmapped BFGS line search serializes the fit plane on
# CPU hosts and the f64 polish is a host-side step (ops/polish.py), not
# part of the batched launch.
GROUP_FIT_BENCH_GROUPS = 32
GROUP_FIT_BENCH_WEEKS = 40
GROUP_FIT_BENCH_HORIZON = 20
GROUP_FIT_BENCH_CFG = SarimaxConfig(
    k_exog=len(EXO_FIELDS), max_p=1, max_d=1, max_q=1, max_iter=40,
    bfgs_iter=0,
)

_COVID_BREAKPOINT = dt.datetime(2020, 3, 1)


def add_exo_variables(pdf: pd.DataFrame) -> pd.DataFrame:
    """Business-knowledge exogenous flags (reference ``:343-370``).

    Vectorized over the whole frame — the reference runs this per-Product
    group purely for Spark parallelism; there is no cross-row dependency.
    """
    ts = pd.to_datetime(pdf["Date"])
    week = ts.dt.isocalendar().week
    out = pdf.assign(
        covid=(ts >= _COVID_BREAKPOINT).astype(np.float32),
        christmas=((week >= 51) & (week <= 52)).astype(np.float32),
        new_year=((week >= 1) & (week <= 4)).astype(np.float32),
    )
    return out[["Date", "Product", "SKU", "Demand", *EXO_FIELDS]]


def split_train_score_data(data: pd.DataFrame, forecast_horizon: int = FORECAST_HORIZON):
    """Last ``forecast_horizon`` rows are the scoring window (``:372-380``)."""
    return data.iloc[: len(data) - forecast_horizon], data.iloc[len(data) - forecast_horizon :]


def _fit_predict_mse_fn(cfg: SarimaxConfig):
    """(y, exog, order, n_train, n_valid) -> holdout MSE; vmap target."""
    import jax.numpy as jnp

    def one(y, exog, order, n_train, n_valid):
        fit = sarimax_fit(cfg, y, exog, order, n_train)
        pred = sarimax_predict(cfg, fit.params, y, exog, order, n_train)
        t = jnp.arange(y.shape[0])
        score_mask = (t >= n_train) & (t < n_valid)
        err = jnp.where(score_mask, y - pred, 0.0)
        return jnp.sum(err**2) / jnp.maximum(score_mask.sum(), 1)

    return one


def _final_fit_predict_fn(cfg: SarimaxConfig):
    import jax.numpy as jnp  # noqa: F401

    def one(y, exog, order, n_train):
        fit = sarimax_fit(cfg, y, exog, order, n_train)
        return sarimax_predict(cfg, fit.params, y, exog, order, n_train)

    return one


def tune_and_forecast_panel(
    df: pd.DataFrame,
    keys=("Product", "SKU"),
    max_evals: int = 10,
    forecast_horizon: int = FORECAST_HORIZON,
    rstate: int = 123,
    mesh=None,
    cfg: SarimaxConfig | None = None,
    search: str = "grid",
    chunk_size: int | None = None,
    axis_name: str = "data",
) -> pd.DataFrame:
    """Tune + fit + full-range-predict every group; one launch family,
    all SKUs.

    Output schema matches the reference's ``tuning_schema`` (``:498-506``):
    Product, SKU, Date, Demand, Demand_Fitted. Pass ``mesh`` to shard the
    group axis across devices (group parallelism per SURVEY.md §2.3);
    ``axis_name`` names the mesh axis the groups shard over.

    ``search="grid"`` (default) runs the grid-fused engine: the full
    discrete order grid of ``cfg`` is fitted inside
    ``ceil(G / chunk_size)`` launches with the per-group argmin (by
    holdout MSE, the reference's tuning objective) reduced on device —
    an exact argmin over the space TPE only samples, with no refit
    launch (the winning eval fit IS the final fit). ``max_evals`` and
    ``rstate`` apply to ``search="tpe"`` only, which preserves the
    reference's per-round TPE semantics as the compatibility path.
    """
    if search not in ("grid", "tpe"):
        raise ValueError(f"search must be 'grid' or 'tpe', got {search!r}")
    cfg = cfg or SarimaxConfig(k_exog=len(EXO_FIELDS))
    # pad_groups drops null-key rows (groupby semantics); drop them
    # HERE too so the reassembly below indexes the same row set.
    if df[list(keys)].isna().any().any():
        df = df.dropna(subset=list(keys))
    padded = pad_groups(
        df, list(keys), ["Demand", *EXO_FIELDS], sort_by="Date"
    )
    G = padded.n_groups
    y = padded.values["Demand"]
    exog = np.stack([padded.values[f] for f in EXO_FIELDS], axis=-1)
    n_valid = padded.n_valid.astype(np.int32)
    n_train = np.maximum(n_valid - forecast_horizon, 1).astype(np.int32)

    chunks = 0
    if search == "grid":
        res = grid_fit_panel(
            cfg, y, exog, n_train, n_valid,
            mesh=mesh, axis_name=axis_name, chunk_size=chunk_size,
        )
        preds = res.pred
        chunks = res.chunks
    else:
        preds = _tpe_tune_predict(
            cfg, y, exog, n_train, n_valid, G,
            max_evals=max_evals, rstate=rstate, mesh=mesh,
            axis_name=axis_name,
        )

    # Reassemble the long frame: one row per (group, valid timestep).
    sorted_df = df.sort_values([*keys, "Date"])
    out = sorted_df[[*keys, "Date", "Demand"]].copy()
    fitted = np.concatenate(
        [preds[i, : padded.n_valid[i]] for i in range(G)]
    )
    out["Demand_Fitted"] = fitted.astype(np.float32)
    out = out.reset_index(drop=True)
    # Observability side channel for harnesses (the bench scenarios
    # verify "bounded launches, no host loop" against the REAL count).
    out.attrs["grid_chunks"] = chunks
    out.attrs["groups_fitted"] = G
    return out


def _tpe_tune_predict(
    cfg, y, exog, n_train, n_valid, G, *, max_evals, rstate, mesh,
    axis_name,
):
    """The per-round batched-TPE compatibility path: one vmapped eval
    launch per TPE round, then a final refit+predict launch."""
    import jax

    if mesh is not None:
        y, exog, n_valid_d, n_train_d = device_put_groups(
            (y, exog, n_valid, n_train), mesh, axis_name=axis_name
        )
    else:
        n_valid_d, n_train_d = n_valid, n_train

    eval_one = _fit_predict_mse_fn(cfg)
    eval_batch = jax.jit(jax.vmap(eval_one))

    def put_orders(orders):
        if mesh is None:
            return orders
        from ..parallel.group_apply import pad_to_multiple

        return jax.device_put(
            pad_to_multiple(orders, mesh.shape[axis_name]),
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(axis_name)
            ),
        )

    def evaluate(points):
        orders = np.array([[pt["p"], pt["d"], pt["q"]] for pt in points], np.int32)
        losses = np.asarray(eval_batch(y, exog, put_orders(orders), n_train_d, n_valid_d))
        return losses[:G]

    best, _ = batched_fmin(evaluate, SEARCH_SPACE, max_evals, G, rstate=rstate)

    final_orders = np.array([[b["p"], b["d"], b["q"]] for b in best], np.int32)
    final_one = _final_fit_predict_fn(cfg)
    final_batch = jax.jit(jax.vmap(final_one))
    return np.asarray(final_batch(y, exog, put_orders(final_orders), n_train_d))[:G]


def build_tune_and_score_model(
    sku_pdf: pd.DataFrame,
    max_evals: int = 10,
    forecast_horizon: int = FORECAST_HORIZON,
    rstate: int = 123,
    cfg: SarimaxConfig | None = None,
    search: str = "grid",
) -> pd.DataFrame:
    """Single-group fit-tune-score (reference ``:417-494``), for the host
    path: ``group_apply(df, ["Product","SKU"], build_tune_and_score_model)``.

    Uses the same jitted kernels as the batched path (a 1-group batch), so
    host-path and device-path results agree.
    """
    one = tune_and_forecast_panel(
        sku_pdf,
        max_evals=max_evals,
        forecast_horizon=forecast_horizon,
        rstate=rstate,
        cfg=cfg,
        search=search,
    )
    return one[["Product", "SKU", "Date", "Demand", "Demand_Fitted"]]
