"""A served ``TransformerLM(ffn="moe")``: a slot's stream is its own.

The decode step is one batched call over every slot, idle slots
included. Top-1 routing with a capacity queues tokens along the batch
axis, so a step that routed the slots together would let an idle slot
(token 0 at position 0: every idle slot picks the same expert) take a
live slot's place in an expert, and the live slot's token would lose
its FFN output. A one-token decode keeps every token instead
(``MoEMLP(keep_all=True)``), as each slot decoded alone always did.

Prompts here are whole prefill buckets: the prefill is a parallel pass
whose capacity goes by the tokens of the pass, padding included, so a
padded prompt is not the prompt ``generate`` prefills (the train/infer
discrepancy ``tests/test_generate.py::test_moe_lm_generates`` names).
"""

from __future__ import annotations

import numpy as np
import pytest

from dss_ml_at_scale_tpu import telemetry
from dss_ml_at_scale_tpu.serving.lm import LMConfig, LMEngine

SLOTS, MAX_LEN, BUCKETS = 8, 48, (8, 16)


def _moe_lm():
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=64, dim=32, num_heads=4, num_layers=2,
                          max_seq=64, dtype=jnp.float32,
                          attention="reference", ffn="moe", num_experts=4)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    # The fresh model says one token over and over, whatever its FFN
    # gives: weights three times the size make a stream that a lost
    # FFN output turns.
    variables = jax.tree_util.tree_map(
        lambda a: a if a.ndim == 1 else 3.0 * a, variables)
    return model, variables


def _slot_decode(model, variables, tokens, arena, pos):
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.serving.lm import kvcache

    logits, _, _, arena = jax.jit(kvcache.slot_decode, static_argnums=0)(
        model, variables, jnp.asarray(tokens, jnp.int32), arena,
        jnp.asarray(pos, jnp.int32))
    return np.asarray(logits), arena


@pytest.mark.parametrize("live", [
    pytest.param((5, 6, 7), id="live_behind_idle"),
    pytest.param((0, 3, 6), id="live_among_idle"),
    pytest.param(tuple(range(8)), id="all_live"),
])
def test_a_slots_logits_are_those_of_the_slot_decoded_alone(live, devices8):
    """Every live slot of a step over 8 slots against the same slot in
    an arena of its own. The idle slots are as the engine leaves them
    (token 0, position 0) and sit ahead of live slots in the batch,
    where a shared queue would serve them first. The logits agree to
    the rounding of a matmul over 8 rows against one over 1 (the dense
    model's too); a token dropped from its expert moves them by tenths.
    The cache rows are bitwise."""
    import jax
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.serving.lm import kvcache

    model, variables = _moe_lm()
    rng = np.random.default_rng(3)
    rows = jax.tree_util.tree_map(
        lambda a: rng.standard_normal(a.shape).astype(a.dtype),
        kvcache.make_arena(model, SLOTS, MAX_LEN))
    tokens = np.zeros(SLOTS, np.int32)
    pos = np.zeros(SLOTS, np.int32)
    tokens[list(live)] = rng.integers(1, 64, len(live))
    pos[list(live)] = rng.integers(1, MAX_LEN, len(live))
    together, arena = _slot_decode(
        model, variables, tokens, jax.tree_util.tree_map(jnp.asarray, rows),
        pos)
    for slot in live:
        own = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a[slot:slot + 1]), rows)
        alone, own = _slot_decode(
            model, variables, tokens[slot:slot + 1], own, pos[slot:slot + 1])
        np.testing.assert_allclose(together[slot], alone[0], atol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(arena),
                        jax.tree_util.tree_leaves(own)):
            np.testing.assert_allclose(np.asarray(a)[slot],
                                       np.asarray(b)[0], atol=1e-5)


def _generate_expected(model, variables, prompt, n_new):
    import jax.numpy as jnp

    from dss_ml_at_scale_tpu.models.transformer import generate

    out = generate(model, variables, jnp.asarray([prompt], jnp.int32), n_new)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def _collect(gen, timeout=60.0):
    tokens = []
    while True:
        event = gen.next_event(timeout=timeout)
        if event[0] == "token":
            tokens.append(event[1])
        else:
            return tokens, event


def test_moe_parity_churn_vs_solo_vs_generate(devices8):
    """``test_lm_serving.py::test_parity_churn_vs_solo_vs_generate``
    for the expert model, with idle slots ahead of the live ones: all
    eight are submitted at once, the five short ones take slots 0-4
    (lowest first) and retire, and the three long ones decode on in
    slots 5-7 behind them. Engine == solo == ``generate``, token for
    token."""
    from dss_ml_at_scale_tpu.serving.lm import TransformerDecoder

    model, variables = _moe_lm()
    rng = np.random.default_rng(3)
    lengths = (8, 8, 16, 8, 8, 16, 8, 8)
    n_new = (8, 8, 8, 8, 8, 20, 20, 20)
    prompts = [list(rng.integers(1, 64, n)) for n in lengths]
    expected = [_generate_expected(model, variables, p, n)
                for p, n in zip(prompts, n_new)]

    def engine(slots):
        return LMEngine(
            TransformerDecoder(model, variables, slots=slots,
                               max_len=MAX_LEN, buckets=BUCKETS),
            LMConfig(slots=slots, max_len=MAX_LEN, prefill_buckets=BUCKETS),
        ).start()

    solo = engine(1)
    try:
        for prompt, n, want in zip(prompts, n_new, expected):
            tokens, terminal = _collect(solo.submit(prompt, n))
            assert terminal == ("done", "max_tokens")
            assert tokens == want
    finally:
        solo.drain(10.0)

    telemetry.reset()
    churn = engine(SLOTS)
    try:
        gens = [churn.submit(prompt, n) for prompt, n in zip(prompts, n_new)]
        for want, gen in zip(expected, gens):
            tokens, terminal = _collect(gen)
            assert terminal == ("done", "max_tokens")
            assert tokens == want
    finally:
        churn.drain(10.0)


# -- prefill in chunks ---------------------------------------------------------
#
# A model that prefills a chunk at a start position (``MlaMoeLM``) is
# prefilled in chunks of the smallest bucket; the others keep one program a
# bucket. Tiny models with the weights their ``init`` makes, float32.

MLA_CFG = {
    "vocab_size": 64, "hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 2, "q_lora_rank": 16, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "moe_intermediate_size": 16, "n_routed_experts": 4, "router_width": 4,
    "num_experts_per_tok": 2,
}
JAMBA_CFG = {
    "model_type": "jamba", "vocab_size": 64, "hidden_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 1, "intermediate_size": 48, "mamba_d_state": 8,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
    "attn_layer_period": 2, "attn_layer_offset": 1, "num_experts": 1,
    "tie_word_embeddings": True,
}
CHUNK_BUCKETS = (8, 32)


def _served(kind):
    """(model, variables) of one of the three served families."""
    import jax
    import jax.numpy as jnp

    if kind == "moe":
        return _moe_lm()
    if kind == "mla":
        from dss_ml_at_scale_tpu.models.mla_moe import MlaMoeLM

        model = MlaMoeLM.from_config(MLA_CFG, attention="reference",
                                     dtype=jnp.float32)
        return model, model.init(jax.random.key(4))
    from dss_ml_at_scale_tpu.models.jamba import JambaLM

    model = JambaLM.from_config(JAMBA_CFG, dtype=jnp.float32,
                                attention="reference")
    return model, model.init(jax.random.key(4))


class _CompileCount:
    """Backend compiles in this process while ``on``."""

    def __init__(self):
        import jax

        self.on, self.n = False, 0

        def count(event, seconds, **_):
            if self.on and event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(count)


@pytest.mark.parametrize("kind, chunk, programs", [
    # one program a bucket, the whole prompt, as before
    ("moe", None, [(8, None), (32, None)]),
    ("jamba", None, [(8, None), (32, None)]),
    # one a start, a chunk of the smallest bucket each
    ("mla", 8, [(8, 0), (8, 8), (8, 16), (8, 24)]),
])
def test_warmup_compiles_the_programs_the_model_prefills_with(
        devices8, kind, chunk, programs):
    """The prefill programs warm-up runs, by their static shapes: the
    tokens' length and ``start``."""
    from dss_ml_at_scale_tpu.serving.lm import TransformerDecoder

    model, variables = _served(kind)
    decoder = TransformerDecoder(model, variables, slots=4, max_len=MAX_LEN,
                                 buckets=CHUNK_BUCKETS)
    assert decoder.chunk == chunk
    ran, prefill = [], decoder._prefill_fn

    def recorded(model, variables, tokens, cache, n_real, start=None):
        ran.append((tokens.shape[1], start))
        if start is None:
            return prefill(model, variables, tokens, cache, n_real)
        return prefill(model, variables, tokens, cache, n_real, start=start)

    decoder._prefill_fn = recorded
    decoder.warmup()
    assert ran == programs


def test_a_chunked_engine_streams_the_whole_prompt_engines_tokens(devices8):
    """Greedy streams of the latent-attention model through the engine,
    its prompts prefilled in chunks of 8 (1 to 4 of them), are the
    streams of the same engine prefilling each prompt in one program;
    and nothing compiles once the engine has warmed up."""
    from dss_ml_at_scale_tpu.serving.lm import TransformerDecoder

    model, variables = _served("mla")
    rng = np.random.default_rng(8)
    prompts = [[int(t) for t in rng.integers(1, 64, n)]
               for n in (3, 8, 9, 17, 30, 32, 12)]
    compiles = _CompileCount()

    def streams(chunked):
        decoder = TransformerDecoder(model, variables, slots=4,
                                     max_len=MAX_LEN, buckets=CHUNK_BUCKETS)
        if not chunked:          # the test steers the decoder, no option
            decoder.chunk = None
        engine = LMEngine(decoder, LMConfig(
            slots=4, max_len=MAX_LEN, prefill_buckets=CHUNK_BUCKETS)).start()
        compiles.n, compiles.on = 0, True
        try:
            gens = [engine.submit(p, 6) for p in prompts]
            out = [_collect(gen) for gen in gens]
        finally:
            compiles.on = False
            engine.drain(10.0)
        assert compiles.n == 0
        return out

    chunked = streams(True)
    assert all(end == ("done", "max_tokens") for _, end in chunked)
    assert chunked == streams(False)
