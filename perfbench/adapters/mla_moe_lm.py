"""The program's side of the `mla_moe_lm` family: what ``dsst serve-lm
--model-config FILE`` builds (``MlaMoeLM.from_config`` ->
``TransformerDecoder`` -> ``LMEngine`` -> ``serve_lm_in_thread``), from
the same kind of file."""

from __future__ import annotations


def build_model(config: dict, server: dict):
    from dss_ml_at_scale_tpu.models.mla_moe import MlaMoeLM

    return MlaMoeLM.from_config(config, attention=server["attention"])


def variable_shapes(model, bucket: int) -> dict:
    return {p: tuple(s) for p, s in model.variable_shapes().items()}


def start_server(model, variables, server: dict):
    """The engine, started (its own warm-up compiles every shape), behind
    the HTTP front end on a free port.  Returns (engine, handle).

    The driver's float32 tree and the decoder's served one do not fit the
    chip together: each leaf is cast on its own and its float32 buffer
    deleted once the narrow copy exists (the driver reads ``variables``
    no more after this call), as ``dsst serve-lm`` drops its tree."""
    from dss_ml_at_scale_tpu.serving.lm import (LMConfig, LMEngine,
                                                TransformerDecoder)
    from dss_ml_at_scale_tpu.workloads.serving import serve_lm_in_thread

    config = LMConfig(
        slots=server["slots"], max_len=server["max_len"],
        prefill_buckets=tuple(server["prefill_buckets"]),
        queue_depth=server["queue_depth"], deadline_ms=0.0)
    decoder = TransformerDecoder(
        model, model.serving_variables(variables, release=True),
        slots=config.slots, max_len=config.max_len,
        buckets=config.prefill_buckets)
    engine = LMEngine(decoder, config).start()
    return engine, serve_lm_in_thread(engine, "127.0.0.1", 0)
