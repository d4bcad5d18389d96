"""The program's side of the `transformer_lm` family: what ``dsst
serve-lm`` builds (``TransformerLM`` -> ``TransformerDecoder`` ->
``LMEngine`` -> ``serve_lm_in_thread``), at the configuration's sizes."""

from __future__ import annotations


def build_model(config: dict, server: dict):
    from dss_ml_at_scale_tpu.models import TransformerLM

    if config["intermediate_size"] % config["hidden_size"]:
        raise ValueError("the program's block takes a whole mlp_ratio")
    return TransformerLM(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        max_seq=server["max_len"],
        mlp_ratio=config["intermediate_size"] // config["hidden_size"],
        attention=server["attention"])


def variable_shapes(model, bucket: int) -> dict:
    import jax
    import jax.numpy as jnp

    from weights import flatten

    tree = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, bucket), jnp.int32)))
    return {p: tuple(s.shape) for p, s in flatten(tree).items()}


def start_server(model, variables, server: dict):
    """The engine, started (its own warm-up compiles every shape), behind
    the HTTP front end on a free port.  Returns (engine, handle)."""
    from dss_ml_at_scale_tpu.serving.lm import (LMConfig, LMEngine,
                                                TransformerDecoder)
    from dss_ml_at_scale_tpu.workloads.serving import serve_lm_in_thread

    config = LMConfig(
        slots=server["slots"], max_len=server["max_len"],
        prefill_buckets=tuple(server["prefill_buckets"]),
        queue_depth=server["queue_depth"], deadline_ms=0.0)
    decoder = TransformerDecoder(
        model, variables, slots=config.slots, max_len=config.max_len,
        buckets=config.prefill_buckets)
    engine = LMEngine(decoder, config).start()
    return engine, serve_lm_in_thread(engine, "127.0.0.1", 0)
