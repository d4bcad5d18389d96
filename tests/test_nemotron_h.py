"""The Mamba-2 / latent-expert / attention LM (``models/nemotron_h.py``)
and its SSD kernel (``ops/ssd_scan.py``) against the benchmark's plain
reference (``perfbench/references/nemotron_h_lm.py``, loaded by path:
float32 ``jax.numpy``, the recurrence a ``lax.scan`` over positions, the
held experts a loop, no cache), at a small size on the CPU.

Sizes: the first six layers of the pattern ``MEM*EMEM*E`` (three Mamba-2
mixers, two expert layers, one attention layer), hidden 64; Mamba-2 with
8 heads of 8 in 2 groups, state 16, conv 4, SSD blocks of 16; attention
of 4 query heads over 2 key/value heads of 16; experts 4..11 held of the
16 a sigmoid router scores, top-4 at scale 2.5, latent 32, width 24, a
shared expert of 48; vocabulary 128. Weights are the model's own
``init`` at Mamba-2's values (``A`` in [1, 16] a head, steps of
0.001-0.1), so the state remembers for many blocks of 16 and a wrong
carry between blocks or chunks shows far from where it was made.

Tolerances. A float32 model on the CPU differs from the reference by
summation order only: logits of size 1-4 agree to about 1e-6 (read:
1.4e-6 full forward, 1.9e-6 chunked prefill then decode), held to
``F32_TOL`` 2e-4. The same model in bfloat16 misses by 4e-2 or more
(``test_bfloat16_fails_the_float32_tolerance``), over 100 times
``F32_TOL``. The SSD kernel against the step-by-step recurrence agrees to
1e-6 on outputs and 4e-6 on the state, relative to their largest value
(read), held to ``SCAN_TOL`` 2e-5: a recurrence whose state is rounded to
bfloat16 each position misses by 3.3e-3 (read), over 100 times that, and
``test_a_bfloat16_state_fails_the_scan_tolerance`` keeps it so.
Where the program is compared with itself (chunks against one piece,
together against alone) the two sides run different shapes through the
same float32 arithmetic, held to ``SAME_TOL`` 1e-5.
"""

from __future__ import annotations

import importlib.util
import json
import os
import signal
import subprocess
import sys
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import lax

from dss_ml_at_scale_tpu import telemetry
from dss_ml_at_scale_tpu.models.nemotron_h import NemotronHLM, layer_kinds
from dss_ml_at_scale_tpu.ops.ssd_scan import ssd_scan, ssd_scan_reference
from dss_ml_at_scale_tpu.serving.lm import (
    LMConfig,
    LMEngine,
    TransformerDecoder,
    kvcache,
)

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "perfbench"
F32_TOL = 2e-4
SCAN_TOL = 2e-5
SAME_TOL = 1e-5

CFG = {
    "model_type": "nemotron_h", "vocab_size": 128, "hidden_size": 64,
    "hybrid_override_pattern": "MEM*EMEM*E", "num_hidden_layers": 10,
    "num_layers": 6, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "mamba_num_heads": 8, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4, "expand": 1,
    "chunk_size": 16, "moe_intermediate_size": 24, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 8,
    "router_width": 16, "expert_offset": 4, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
    "n_shared_experts": 1, "layer_norm_epsilon": 1e-5,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "tie_word_embeddings": False, "use_conv_bias": True,
}
BUCKETS = (16, 32)


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    """The benchmark's reference, by path (it imports ``weights`` and
    ``weight_map`` by those names)."""
    load("weights", BENCH / "weights.py")
    load("weight_map", BENCH / "weight_map.py")
    return load("nemotron_h_lm_reference",
                BENCH / "references" / "nemotron_h_lm.py")


def model_of(dtype=jnp.float32, cfg=None, **kw):
    return NemotronHLM.from_config(cfg or CFG, dtype=dtype, **kw)


@pytest.fixture(scope="module")
def variables():
    return model_of().init(jax.random.key(7))


@pytest.fixture(scope="module")
def served(variables):
    return model_of().serving_variables(variables)


def flat(tree, prefix=""):
    out = {}
    for name, value in tree.items():
        path = f"{prefix}/{name}" if prefix else name
        out.update(flat(value, path) if isinstance(value, dict)
                   else {path: value})
    return out


def tokens_of(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, CFG["vocab_size"], n)]


def series(name, **labels):
    for m in telemetry.snapshot()["metrics"]:
        if m["name"] == name and m["labels"] == labels:
            return m["value"]
    return None


# -- the model against the reference ------------------------------------------


def test_the_layer_kinds_are_read_from_the_pattern():
    assert layer_kinds("ME*") == ("mamba", "moe", "attn")
    assert model_of().layer_kinds == (
        "mamba", "moe", "mamba", "attn", "moe", "mamba")
    with pytest.raises(ValueError, match="not served"):
        layer_kinds("M-E")


def test_the_program_and_the_reference_name_the_same_leaves(reference):
    assert reference.param_shapes(CFG) == {
        p: tuple(s) for p, s in model_of().variable_shapes().items()}


def test_leaves_are_named_by_the_benchmarks_rules():
    """Every leaf's last path part is one the benchmark's weight rules
    make (``perfbench/weights.py``) without a configuration's overrides:
    each expert is a leaf of its own until ``serving_variables`` stacks
    them."""
    for path, shape in model_of().variable_shapes().items():
        last = path.rsplit("/", 1)[-1]
        assert last in ("scale", "bias", "embedding") or (
            last == "kernel" and len(shape) == 2), path


def test_the_served_tree_stacks_the_held_experts(served):
    moe = served["params"]["layer_1"]["moe"]
    assert "expert_0" not in moe
    assert moe["experts"]["up"].shape == (8, 32, 24)
    assert moe["experts"]["down"].shape == (8, 24, 32)
    again = model_of().serving_variables(served)
    assert again["params"]["layer_1"]["moe"]["experts"]["up"] is \
        moe["experts"]["up"]


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_full_forward_logits_match_the_reference(reference, variables,
                                                 served, attention):
    """160 positions (ten SSD blocks of 16) through all six layers: the
    plain paths, and the flash and SSD kernels (interpreted on the
    CPU)."""
    toks = tokens_of(160)
    model = model_of(attention=attention)
    got, stats = jax.jit(model.logits)(served, jnp.asarray(toks))
    want = reference.logits_of(toks, flat(variables), CFG)
    assert float(jnp.max(jnp.abs(want))) > 0.5       # logits of size
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL
    # two expert layers, 160 tokens, 4 choices each, all valid
    assert int(stats[0]) + int(stats[1]) == 2 * 160 * 4
    assert int(stats[0]) == int(jnp.sum(stats[3:])) > 0


def test_bfloat16_fails_the_float32_tolerance(reference, variables):
    toks = tokens_of(64)
    model = model_of(dtype=jnp.bfloat16, attention="reference")
    got, _ = jax.jit(model.logits)(model.serving_variables(variables),
                                   jnp.asarray(toks))
    gap = float(jnp.max(jnp.abs(
        got - reference.logits_of(toks, flat(variables), CFG))))
    assert gap > 100 * F32_TOL, gap


def test_the_seeded_weights_match_the_reference(reference):
    """The benchmark's own weights from a seed past 2**31, as its driver
    makes them, with a ``serve_weight_map`` applied as the family's
    adapter applies it, and the reference's layer-by-layer weights (the
    experts made inside its loop) from the same seed."""
    weights = sys.modules["weights"]
    weight_map = sys.modules["weight_map"]
    cfg = {**CFG, "serve_weight_map": {
        "mamba/A_log/scale": [-1.3862944, 2.7725887],
        "mamba/dt_bias/bias": [-4.6, 24.0]}}
    seed = 3_000_000_019
    variables = weight_map.apply(
        weights.nest(weights.make(reference.param_shapes(cfg), seed)),
        weight_map.maps(cfg))
    toks = tokens_of(200, 3)          # padded to one of the reference's
    model = model_of(attention="reference")
    got, _ = jax.jit(model.logits)(model.serving_variables(variables),
                                   jnp.asarray(toks))
    want = reference.logits(toks, seed, cfg, pad_to=256)
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


def test_the_expert_shares_add_up_to_the_uncut_layer(reference):
    """Eight chips' shares of one expert layer (two of the router's 16
    experts each): the program's outputs of the eight, with the shared
    expert (which every share computes) counted once, add up to the
    uncut reference layer over all 16."""
    uncut = {**CFG, "hybrid_override_pattern": "E", "num_hidden_layers": 1,
             "num_layers": 1, "n_routed_experts": 16, "expert_offset": 0}
    leaves = flat(model_of(cfg=uncut).init(jax.random.key(3)))
    x = 0.5 * jax.random.normal(jax.random.key(4), (40, 64))
    w = {name: leaves[f"params/layer_0/{name}"]
         for name in reference.layer_shapes(uncut, "moe")}
    with jax.default_matmul_precision("highest"):
        want = reference.layer(x, w, uncut, "moe") - x
    shared = None
    total = jnp.zeros_like(x)
    for chip in range(8):
        share = {**uncut, "n_routed_experts": 2, "expert_offset": 2 * chip}
        model = model_of(cfg=share)
        tree = {name: leaves[f"params/layer_0/{name}"]
                for name in reference.layer_shapes(share, "moe",
                                                   experts=False)}
        for e in range(2):
            for part in ("up", "down"):
                tree[f"moe/expert_{e}/{part}/kernel"] = leaves[
                    f"params/layer_0/moe/expert_{2 * chip + e}/{part}/kernel"]
        params = model.serving_variables(
            {"params": {"layer_0": _nest(tree)}})["params"]["layer_0"]
        out, _ = model._moe(x, params, jnp.ones(40, bool))
        total = total + out
        if shared is None:
            h = reference._rms(x, w["norm/scale"], 1e-5)
            with jax.default_matmul_precision("highest"):
                shared = reference._relu2(
                    h, w["moe/shared/up/kernel"], w["moe/shared/down/kernel"],
                    reference._exact)
    got = total - 7 * shared
    assert float(jnp.max(jnp.abs(want))) > 0.5
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


def _nest(flat_tree):
    out: dict = {}
    for path, value in flat_tree.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
    return out


# -- chunks -------------------------------------------------------------------


@pytest.mark.parametrize("n", [5, 16, 23, 32])
def test_a_prompt_in_chunks_gives_the_logits_and_state_of_one_piece(
        served, n):
    """The same ``n`` real tokens as one 32-bucket prefill and as two
    chunks of 16 (the second resuming from the first's state, conv inputs
    and key/value rows; padding rows in whichever holds them): the last
    real row's logits, every Mamba layer's state and conv inputs, and the
    real key/value rows agree."""
    model = model_of(attention="flash")
    run = jax.jit(kvcache.prefill_bucket, static_argnums=0,
                  static_argnames=("start",))
    padded = np.zeros((1, 32), np.int32)
    padded[0, :n] = tokens_of(n, 5)
    whole, _, one = run(model, served, jnp.asarray(padded),
                        kvcache.make_arena(model, 1, 64), jnp.int32(n))
    cache = kvcache.make_arena(model, 1, 64)
    for start in (0, 16):
        logits, _, cache = run(model, served,
                               jnp.asarray(padded[:, start:start + 16]),
                               cache, jnp.int32(n), start=start)
        if start <= n - 1 < start + 16:
            chunked = logits
    assert np.max(np.abs(np.asarray(whole) - np.asarray(chunked))) \
        < SAME_TOL
    for kind, a_slabs, b_slabs in zip(model.layer_kinds, one, cache):
        for name in a_slabs:
            a, b = np.asarray(a_slabs[name]), np.asarray(b_slabs[name])
            if kind == "attn":
                a, b = a[:, :, :n], b[:, :, :n]
            scale = max(1.0, float(np.max(np.abs(a))))
            assert np.max(np.abs(a - b)) < SAME_TOL * scale, (kind, name)


def decoder_of(variables, slots=6, **kw):
    return TransformerDecoder(model_of(**kw), variables, slots=slots,
                              max_len=64, buckets=BUCKETS)


def prefill(decoder, seq, n, slot):
    """A prompt of ``n`` tokens through the decoder's chunks."""
    padded = np.zeros((1, BUCKETS[-1]), np.int32)
    padded[0, :n] = seq[:n]
    for start in range(0, -(-n // decoder.chunk) * decoder.chunk,
                       decoder.chunk):
        decoder.prefill_chunk(padded[:, start:start + decoder.chunk],
                              start, n)
    return decoder.finish_prefill(slot)


def step(decoder, feeds):
    """One decode step; ``feeds`` maps slot -> (token, pos). Returns every
    slot's logits row."""
    override = np.zeros(decoder.slots, np.int32)
    pos = np.zeros(decoder.slots, np.int32)
    for slot, (token, p) in feeds.items():
        override[slot], pos[slot] = token, p
    _, logits = decoder.fetch(decoder.dispatch(override, pos), logits=True)
    return logits


@pytest.mark.parametrize("attention", ["flash", "reference"])
def test_chunked_prefill_then_decode_match_the_full_forward(
        reference, variables, attention):
    """Six slots admitted with prompts of 1, 2, 5, 16, 17 and 32 real
    tokens (one or two chunks of 16), then decoded together, teacher-
    forced along their sequences: the prefill's row and every decoded
    row equal the reference's full forward of that slot's sequence."""
    decoder = decoder_of(variables, attention=attention)
    assert decoder.chunk == 16
    seqs = {s: tokens_of(44, 20 + s) for s in range(6)}
    prompts = dict(enumerate((1, 2, 5, 16, 17, 32)))
    want = {s: np.asarray(reference.logits_of(seqs[s], flat(variables), CFG))
            for s in seqs}
    for slot, n in prompts.items():
        row = prefill(decoder, seqs[slot], n, slot)
        assert np.max(np.abs(row - want[slot][n - 1])) < F32_TOL, (slot, n)
    pos = dict(prompts)
    for _ in range(10):
        logits = step(decoder, {s: (seqs[s][p], p) for s, p in pos.items()})
        for s, p in pos.items():
            gap = np.max(np.abs(logits[s] - want[s][p]))
            assert gap < F32_TOL, (s, p, gap)
            pos[s] += 1


def test_the_scratch_and_a_readmitted_slot_start_from_zero(reference,
                                                           variables):
    """A long prompt prefilled and decoded in slot 2 and retired, then a
    short one admitted there: the first chunk of the short one reads none
    of the state the long one left in the scratch, and its rows equal the
    reference's and a fresh decoder's."""
    long, short = tokens_of(40, 8), tokens_of(24, 9)
    want = np.asarray(reference.logits_of(short, flat(variables), CFG))
    used = decoder_of(variables, slots=3, attention="reference")
    prefill(used, long, 30, 2)
    for p in range(30, 36):
        step(used, {2: (long[p], p)})
    fresh = decoder_of(variables, slots=3, attention="reference")
    for decoder in (used, fresh):
        row = prefill(decoder, short, 3, 2)
        assert np.max(np.abs(row - want[2])) < F32_TOL
        for p in range(3, 12):
            logits = step(decoder, {2: (short[p], p)})
            assert np.max(np.abs(logits[2] - want[p])) < F32_TOL, p


def test_two_slots_decode_together_as_each_alone(variables):
    """The expert layer routes every slot's token together: a slot's rows
    are what it gets decoded alone."""
    seqs = {0: tokens_of(30, 11), 1: tokens_of(30, 12)}
    together = decoder_of(variables, slots=2, attention="reference")
    alone = {s: decoder_of(variables, slots=2, attention="reference")
             for s in seqs}
    for s, seq in seqs.items():
        prefill(together, seq, 4 + 3 * s, s)
        prefill(alone[s], seq, 4 + 3 * s, s)
    for k in range(8):
        feeds = {s: (seqs[s][4 + 3 * s + k], 4 + 3 * s + k) for s in seqs}
        both = step(together, feeds)
        for s in seqs:
            one = step(alone[s], {s: feeds[s]})
            assert np.max(np.abs(both[s] - one[s])) < SAME_TOL, (s, k)


def _loop_experts(self, u, r, experts):
    """The per-pass loop that the grouped kernel replaced, kept as its
    reference: one ``fori_loop`` over every pass of every held expert,
    each pass at most 256 rows of the expert's segment (and at most
    ``t``, and at most twice a held expert's expected share of the
    ``t x k`` assignments, in whole 128-row tiles), gathered by token,
    through the expert and added at their tokens by their weights."""
    t, k = u.shape[0], self.num_experts_per_tok
    tile = min(256, t, -(-2 * t * k // self.router_width // 128) * 128)
    step, lane = np.int32(tile), jnp.arange(tile, dtype=jnp.int32)
    passes = (r.counts + (step - 1)) // step
    ends = jnp.cumsum(passes, dtype=jnp.int32)
    order = jnp.concatenate([r.order, jnp.zeros(tile, jnp.int32)])

    def one_pass(i, acc):
        e = jnp.sum(ends <= i, dtype=jnp.int32)
        first = (i - (ends[e] - passes[e])) * step
        idx = lax.dynamic_slice(order, (r.starts[e] + first,), (tile,))
        token = idx // np.int32(k)
        y = self._relu2(u[token], experts["up"][e], experts["down"][e])
        scale = jnp.where(first + lane < r.counts[e], r.weight[idx],
                          np.float32(0))
        return acc.at[token].add(y * scale[:, None])

    return lax.fori_loop(np.int32(0), ends[-1], one_pass,
                         jnp.zeros(u.shape, jnp.float32))


def test_the_expert_kernel_gives_the_per_pass_loops_outputs(served,
                                                             monkeypatch):
    """A prompt's second chunk (rows 16-31, 27 real, resuming from the
    first) and a decode step over six slots (two idle), through the
    grouped kernel and again through the per-pass loop it replaced: the
    logits, the stats and every cache slab agree to ``SAME_TOL``."""
    model = model_of(attention="reference")
    padded = jnp.asarray([tokens_of(32, 31)])
    tokens = jnp.asarray(tokens_of(6, 32), jnp.int32)
    pos = jnp.asarray([0, 3, 5, 0, 7, 9], jnp.int32)

    def run():
        cache = kvcache.make_arena(model, 1, 64)
        for start in (0, 16):
            logits, stats, cache = jax.jit(
                lambda v, t, c, start=start: kvcache.prefill_bucket(
                    model, v, t, c, jnp.int32(27), start=start)
            )(served, padded[:, start:start + 16], cache)
        step = jax.jit(lambda v, t, a, p: model.decode_slots(v, t, a, p))(
            served, tokens, cache_of_slots(), pos)
        return (logits, stats, cache), step

    def cache_of_slots():
        arena = kvcache.make_arena(model, 6, 64)
        rng = np.random.default_rng(6)
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(0.1 * rng.standard_normal(a.shape),
                                  a.dtype), arena)

    kernel = run()
    monkeypatch.setattr(NemotronHLM, "_experts", _loop_experts)
    loop = run()
    for got, want in zip(jax.tree_util.tree_leaves(kernel),
                         jax.tree_util.tree_leaves(loop)):
        if jnp.issubdtype(got.dtype, jnp.integer):
            assert np.array_equal(np.asarray(got), np.asarray(want))
        else:
            assert np.max(np.abs(np.asarray(got) - np.asarray(want))) \
                < SAME_TOL
    chunk_stats, step_stats = kernel[0][1], kernel[1][1]
    assert int(chunk_stats[0]) > 0 and int(step_stats[0]) > 0


# -- the kernel ---------------------------------------------------------------


def ssd_inputs(t=300, heads=8, p=16, groups=2, n=32, seed=0):
    """At Mamba-2's own init: ``A`` in [1, 16] a head, steps log-uniform
    in [0.001, 0.1]; a nonzero initial state."""
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (t, heads, p))
    dt = jnp.exp(jax.random.uniform(k[1], (t, heads), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    a = -jax.random.uniform(k[2], (heads,), minval=1.0, maxval=16.0)
    b = jax.random.normal(k[3], (t, groups, n))
    c = jax.random.normal(k[4], (t, groups, n))
    d = jax.random.uniform(k[5], (heads,), minval=0.5, maxval=1.5)
    state = jax.random.normal(k[6], (heads, p, n))
    return x, dt, a, b, c, d, state


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("t,chunk", [(300, 128), (300, 64), (40, 16)])
def test_the_ssd_kernel_matches_the_recurrence_from_a_nonzero_state(t,
                                                                    chunk):
    """``t`` positions in blocks of ``chunk`` (the last block padded by
    the op) from a nonzero initial state: ``y`` and the final state are
    the step-by-step recurrence's."""
    args = ssd_inputs(t)
    y, state = ssd_scan(*args, chunk=chunk)
    with jax.default_matmul_precision("highest"):
        y_ref, state_ref = ssd_scan_reference(*args)
    assert float(jnp.max(jnp.abs(y_ref))) > 1.0
    assert _rel(y, y_ref) < SCAN_TOL
    assert _rel(state, state_ref) < SCAN_TOL


def test_the_ssd_kernel_resumes_where_it_stopped():
    """300 positions as one call, and as 128 + 172 with the second call
    started from the first's state: the same outputs and state."""
    x, dt, a, b, c, d, state = ssd_inputs()
    y, final = ssd_scan(x, dt, a, b, c, d, state)
    y1, mid = ssd_scan(x[:128], dt[:128], a, b[:128], c[:128], d, state)
    y2, end = ssd_scan(x[128:], dt[128:], a, b[128:], c[128:], d, mid)
    assert _rel(jnp.concatenate([y1, y2]), y) < SCAN_TOL
    assert _rel(end, final) < SCAN_TOL


def test_a_bfloat16_state_fails_the_scan_tolerance():
    x, dt, a, b, c, d, state = ssd_inputs()
    y_ref, _ = ssd_scan_reference(x, dt, a, b, c, d, state)
    group = np.arange(8) // 4

    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :])
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.sum(s * c_t[group][:, None, :], axis=-1)

    _, y = jax.lax.scan(step, state, (x, dt, b, c))
    assert _rel(y + d[None, :, None] * x, y_ref) > 100 * SCAN_TOL


def _sub_jaxprs(eqn):
    for param in eqn.params.values():
        for x in param if isinstance(param, (tuple, list)) else (param,):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def _kernel_jaxpr(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["jaxpr"]
        for sub in _sub_jaxprs(eqn):
            found = _kernel_jaxpr(sub)
            if found is not None:
                return found
    return None


def _equations(jaxpr):
    return sum(1 + sum(_equations(s) for s in _sub_jaxprs(e))
               for e in jaxpr.eqns)


def test_the_ssd_kernel_body_is_traced_once():
    """The blocks are the grid and the heads of a group one loop: the
    kernel's jaxpr holds as many equations over 2 blocks as over 8, and
    at 16 heads a group as at 4."""

    def size(t, heads, groups):
        f32 = jnp.float32
        shapes = (jax.ShapeDtypeStruct((t, heads, 64), f32),
                  jax.ShapeDtypeStruct((t, heads), f32),
                  jax.ShapeDtypeStruct((heads,), f32),
                  jax.ShapeDtypeStruct((t, groups, 128), f32),
                  jax.ShapeDtypeStruct((t, groups, 128), f32),
                  jax.ShapeDtypeStruct((heads,), f32),
                  jax.ShapeDtypeStruct((heads, 64, 128), f32))
        traced = jax.make_jaxpr(ssd_scan)(*shapes)
        kernel = _kernel_jaxpr(traced.jaxpr)
        assert kernel is not None
        return _equations(kernel)

    assert size(256, 128, 8) == size(1024, 128, 8) == size(256, 32, 8)


# -- the engine ---------------------------------------------------------------


def test_the_arena_gauge_reports_each_kind(served):
    telemetry.reset()
    decoder = decoder_of(served, slots=2)
    want = {"ssm": 0, "conv": 0, "kv": 0}
    for layer in decoder._arena:
        for name, slab in layer.items():
            want["kv" if name in ("k", "v") else name] += slab.nbytes
    for kind, nbytes in want.items():
        assert series("lm_cache_bytes", kind=kind) == nbytes > 0
    # three Mamba layers x two slots x a [8, 8, 16] float32 state
    assert want["ssm"] == 3 * 2 * 8 * 8 * 16 * 4


def test_the_engines_streams_lie_on_the_references_best(reference,
                                                        variables):
    """Greedy streams through ``LMEngine`` (prompts prefilled a chunk a
    turn, one step in flight, slots freed and re-admitted as requests
    end): every served token's reference logit is the reference's best at
    its position, up to ``F32_TOL`` twice; the expert layer's counters
    count every routed pair of the prompts and of the decode steps."""
    telemetry.reset()
    decoder = decoder_of(variables, slots=3, attention="reference")
    engine = LMEngine(decoder, LMConfig(
        slots=3, max_len=64, prefill_buckets=BUCKETS, queue_depth=8)).start()
    try:
        prompts = [tokens_of(n, 30 + n) for n in (3, 17, 26, 9, 1)]
        gens = [engine.submit(p, 8) for p in prompts]
        streams = []
        for gen in gens:
            out = []
            while True:
                event = gen.next_event(timeout=120.0)
                if event[0] != "token":
                    assert event == ("done", "max_tokens")
                    break
                out.append(event[1])
            streams.append(out)
    finally:
        engine.drain(10.0)
    for prompt, out in zip(prompts, streams):
        assert len(out) == 8
        seq = prompt + out[:-1]
        ref = np.asarray(reference.logits_of(seq, flat(variables), CFG))
        ref = ref[len(prompt) - 1:]
        gaps = ref.max(-1) - ref[np.arange(8), out]
        assert float(gaps.max()) < 2 * F32_TOL, gaps
    held = series("lm_moe_assignments_total", where="held")
    absent = series("lm_moe_assignments_total", where="absent")
    # every real prompt token and at least every decoded token but each
    # stream's last (a step dispatched ahead may add rows), in two expert
    # layers, four choices each
    routed = sum(len(p) for p in prompts) + 5 * 7
    assert held + absent >= 2 * 4 * routed
    assert (held + absent) % (2 * 4) == 0
    assert held == sum(series("lm_moe_expert_assignments_total",
                              expert=str(e)) or 0 for e in range(8)) > 0
    assert series("lm_moe_experts_touched_total", program="decode") > 0


# -- the command --------------------------------------------------------------


def test_serve_lm_model_config_builds_the_nemotron_h_model(tmp_path):
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(CFG))
    proc = subprocess.Popen(
        [sys.executable, "-m", "dss_ml_at_scale_tpu.config.cli", "serve-lm",
         "--model-config", str(path), "--port", "0", "--slots", "2",
         "--max-len", "48", "--prefill-buckets", "8,16"],
        stdout=subprocess.PIPE, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        boot = json.loads(proc.stdout.readline())
        assert boot["model"] == "NemotronHLM"
        assert boot["decoder"] == "TransformerDecoder"
        body = json.dumps({"tokens": [1, 2, 3], "max_new_tokens": 5,
                           "temperature": 0.0}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{boot['port']}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            rows = [json.loads(line) for line in resp.read().splitlines()]
        tokens = [r["token"] for r in rows if "token" in r]
        assert len(tokens) == 5 and all(0 <= t < 128 for t in tokens)
        assert rows[-1]["done"] == "max_tokens"
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0
