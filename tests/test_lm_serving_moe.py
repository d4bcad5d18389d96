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
