"""JAX numerical kernels: time-series fits + the hot deep-learning ops.

TPU-native replacement for the statsmodels surface the reference
exercises (SURVEY.md §2.2 X10): SARIMAX state-space ML fit, Holt-Winters
exponential smoothing, ARMA sample generation, plus the vmappable
Nelder-Mead optimizer that statsmodels' ``fit(method='nm')`` maps to.
The deep-learning hot path adds the Pallas flash-attention kernel, the
length-aware decode attention over a slot arena (``decode_attention``,
imported from its own module) and the fused BN+act custom VJP
(``fused_norm``) that cuts ResNet HBM bytes.

Everything here is pure JAX (``lax.scan`` / ``lax.while_loop``), built to
``vmap`` across thousands of SKU groups at once — one sharded batched fit
replaces the reference's one-Spark-task-per-group Python processes
(``group_apply/02_Fine_Grained_Demand_Forecasting.py:516-528``).
"""

from .arma import arma_generate_sample, lfilter
from .flash_attention import attention_reference, flash_attention
from .fused_norm import bn_act
from .holt_winters import HoltWintersResult, holt_winters_fit, holt_winters_forecast
from .kalman import kalman_filter, kalman_forecast
from .neldermead import NelderMeadResult, nelder_mead
from .polish import sarimax_polish
from .sarimax import (
    SarimaxConfig,
    SarimaxGridResult,
    SarimaxResult,
    grid_orders,
    sarimax_fit,
    sarimax_fit_grid,
    sarimax_loglike,
    sarimax_predict,
)

__all__ = [
    "arma_generate_sample",
    "lfilter",
    "attention_reference",
    "flash_attention",
    "bn_act",
    "HoltWintersResult",
    "holt_winters_fit",
    "holt_winters_forecast",
    "kalman_filter",
    "kalman_forecast",
    "NelderMeadResult",
    "nelder_mead",
    "SarimaxConfig",
    "SarimaxGridResult",
    "SarimaxResult",
    "grid_orders",
    "sarimax_fit",
    "sarimax_fit_grid",
    "sarimax_loglike",
    "sarimax_polish",
    "sarimax_predict",
]
