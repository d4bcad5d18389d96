"""The latent-attention expert LM (``models/mla_moe.py``) against the
benchmark's plain reference (``perfbench/references/mla_moe_lm.py``,
loaded by path: float32 ``jax.numpy``, no cache, every held expert
applied to every token and masked by the routing), at a small size on
the CPU with seeded weights from ``perfbench/weights.py``.

Sizes: hidden 64, 4 heads, ranks 32 and 16, heads of 8 + 8 and 16, a
router over 8 experts top-2 of which 4 are held, 2 layers, vocabulary
128, YaRN factor 4 over 16 original positions, so sequences of 48 pass
both the ramp of the frequencies and the threshold of the query scale.

Tolerances. A float32 model on the CPU differs from the reference by
summation order only: logits of magnitude 4 agree to about 6e-6 (read:
5.7e-6 expanded, 2.4e-6 absorbed against expanded), held to ``F32_TOL``
2e-4. The same model in bfloat16 reads 0.05 and more on the same
logits, so it fails ``F32_TOL`` by two orders of magnitude
(``test_bfloat16_fails_the_float32_tolerance`` keeps that true).
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

from dss_ml_at_scale_tpu import telemetry
from dss_ml_at_scale_tpu.models.mla_moe import MlaMoeLM
from dss_ml_at_scale_tpu.serving.lm import (
    LMConfig,
    LMEngine,
    TransformerDecoder,
    kvcache,
)

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "perfbench"
F32_TOL = 2e-4
SEED = 3_000_000_019          # past 2**31, as the driver's seeds are

CFG = {
    "vocab_size": 128, "hidden_size": 64, "num_layers": 2,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "moe_intermediate_size": 32, "n_routed_experts": 4, "router_width": 8,
    "expert_offset": 2, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "beta_fast": 32, "beta_slow": 1, "factor": 4,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "rope_theta": 10000},
}


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def bench():
    """The benchmark's weights and reference, by path (the reference
    imports ``weights`` by that name)."""
    weights = load("weights", BENCH / "weights.py")
    reference = load("mla_moe_lm_reference",
                     BENCH / "references" / "mla_moe_lm.py")
    return weights, reference


def model_of(cfg=CFG, dtype=jnp.float32):
    return MlaMoeLM.from_config(cfg, attention="reference", dtype=dtype)


def variables_of(bench, cfg=CFG, seed=SEED):
    weights, reference = bench
    return weights.nest(weights.make(reference.param_shapes(cfg), seed))


def tokens_of(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, CFG["vocab_size"], n)]


def series(name, **labels):
    for m in telemetry.snapshot()["metrics"]:
        if m["name"] == name and m["labels"] == labels:
            return m["value"]
    return None


# -- the model against the reference ------------------------------------------


def test_the_program_and_the_reference_name_the_same_leaves(bench):
    _, reference = bench
    assert reference.param_shapes(CFG) == {
        p: tuple(s) for p, s in model_of().variable_shapes().items()}


def test_full_forward_logits_match_the_reference(bench):
    _, reference = bench
    toks = tokens_of(48)
    got, _ = jax.jit(model_of().logits)(variables_of(bench),
                                        jnp.asarray(toks))
    want = reference.logits(toks, SEED, CFG)
    assert float(jnp.max(jnp.abs(want))) > 1.0       # logits of size
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


def test_bfloat16_fails_the_float32_tolerance(bench):
    _, reference = bench
    toks = tokens_of(48)
    model = model_of(dtype=jnp.bfloat16)
    got, _ = jax.jit(model.logits)(
        model.serving_variables(variables_of(bench)), jnp.asarray(toks))
    gap = float(jnp.max(jnp.abs(got - reference.logits(toks, SEED, CFG))))
    assert gap > 10 * F32_TOL, gap


def test_the_flash_prefill_matches_the_plain_one(bench):
    # the Pallas kernel (interpreted on the CPU) at head size 8 + 8 = 16
    toks = jnp.asarray(tokens_of(32))
    variables = variables_of(bench)
    plain, _ = jax.jit(model_of().logits)(variables, toks)
    flash = MlaMoeLM.from_config(CFG, attention="flash", dtype=jnp.float32)
    got, _ = jax.jit(flash.logits)(variables, toks)
    assert float(jnp.max(jnp.abs(got - plain))) < F32_TOL


def test_prefill_then_decode_through_the_latent_arena(bench):
    """Three slots at different ``pos``, one idle: the prefill's one row
    and every decoded row (the absorbed path over the latent rows) equal
    the reference's full forward of that slot's sequence."""
    _, reference = bench
    model, variables = model_of(), variables_of(bench)
    seqs = {0: tokens_of(48, 1), 2: tokens_of(40, 2), 3: tokens_of(30, 3)}
    prompts = {0: 20, 2: 33, 3: 9}
    want = {s: np.asarray(reference.logits(t, SEED, CFG))
            for s, t in seqs.items()}
    prefill = jax.jit(kvcache.prefill_bucket, static_argnums=0)
    write = jax.jit(kvcache.write_slot)
    step = jax.jit(kvcache.slot_decode, static_argnums=0)
    arena = kvcache.make_arena(model, 4, 64)
    assert {k: a.shape for k, a in arena[0].items()} == {
        "c_kv": (4, 64, 16), "k_r": (4, 64, 8)}
    scratch = kvcache.make_arena(model, 1, 64)
    for slot, n in prompts.items():
        bucket = 16 if n <= 16 else 48
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = seqs[slot][:n]
        row, stats, scratch = prefill(model, variables, jnp.asarray(padded),
                                      scratch, jnp.int32(n))
        assert row.shape == (1, CFG["vocab_size"])   # the last real row
        assert np.max(np.abs(np.asarray(row[0]) - want[slot][n - 1])) \
            < F32_TOL
        # padding is routed nowhere: pairs of real tokens only
        assert int(stats[0] + stats[1]) == n * 2 * CFG["num_layers"]
        arena = write(arena, scratch, jnp.int32(slot))
    pos = dict(prompts)
    for _ in range(15):
        live = [s for s in seqs if pos[s] < len(seqs[s])]
        tokens = np.zeros(4, np.int32)
        where = np.zeros(4, np.int32)          # 0: idle, as the engine's
        for s in live:
            tokens[s], where[s] = seqs[s][pos[s]], pos[s]
        logits, ids, stats, arena = step(model, variables,
                                         jnp.asarray(tokens), arena,
                                         jnp.asarray(where))
        for s in live:
            gap = np.max(np.abs(np.asarray(logits[s]) - want[s][pos[s]]))
            assert gap < F32_TOL, (s, pos[s], gap)
            assert int(ids[s]) == int(np.argmax(want[s][pos[s]]))
            pos[s] += 1
        # idle slots are counted nowhere
        assert int(stats[0] + stats[1]) == len(live) * 2 * CFG["num_layers"]
    assert max(pos.values()) > 32 > min(prompts.values())   # past 2 x 16


# -- the expert layer ---------------------------------------------------------


def layer_weights(bench, cfg, seed=SEED):
    weights, reference = bench
    key = weights.key_for(seed)
    return {name: weights.leaf(key, name, shape,
                               weights.salt(f"params/layer_0/{name}"))
            for name, shape in reference.layer_shapes(cfg).items()}


def nested(flat):
    tree = {}
    for path, value in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
    return tree


def test_every_token_to_one_expert_and_none_dropped(bench):
    """A router that sends every token to experts 2 and 3: expert 2
    (held, local 0) gets all 40 tokens, in passes of 16 rows, and the
    layer's output is the reference's."""
    _, reference = bench
    cfg = {**CFG, "num_layers": 1}
    flat = layer_weights(bench, cfg)
    router = np.zeros((64, 8), np.float32)
    router[:, 2], router[:, 3] = 0.5, 0.25
    x = jax.random.normal(jax.random.key(1), (40, 64), jnp.float32)
    # gains are positive and x's mean is not 0: the gates order alike
    x = jnp.abs(x)
    flat = {**flat, "router/kernel": jnp.asarray(router)}
    model = MlaMoeLM.from_config(cfg, attention="reference",
                                 dtype=jnp.float32)
    model = type(model)(**{**model.__dict__, "expert_tile": 16})
    hid = model._norm(x, flat["ffn_norm/scale"])
    routed, stats = jax.jit(model._experts)(hid, hid, jnp.ones(40, bool),
                                            nested(flat))
    counts = [int(n) for n in stats[3:]]
    assert counts == [40, 40, 0, 0] and int(stats[0]) == 80
    assert int(stats[1]) == 0 and int(stats[2]) == 2
    got, _ = jax.jit(model._ffn)(x, jnp.ones(40, bool), nested(flat))
    probs = jax.nn.softmax(hid @ flat["router/kernel"], -1)
    top = probs[:, 2:4] / jnp.sum(probs[:, 2:4], -1, keepdims=True)
    want = x + reference._gated(
        hid, flat["shared/gate/kernel"], flat["shared/up/kernel"],
        flat["shared/down/kernel"], reference._exact)
    for local, e in enumerate((0, 1)):
        want = want + top[:, local, None] * reference._gated(
            hid, flat[f"expert_{e}/gate/kernel"],
            flat[f"expert_{e}/up/kernel"], flat[f"expert_{e}/down/kernel"],
            reference._exact)
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


@pytest.mark.parametrize("chips", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(bench, chips):
    """The routed parts that the shares of ``chips`` chips give (each
    holding ``8 / chips`` of the router's 8 experts, under the weights
    the uncut layer has for them), with the shared expert counted once,
    are the uncut reference layer's expert output."""
    weights, reference = bench
    whole = {**CFG, "num_layers": 1, "n_routed_experts": 8,
             "expert_offset": 0}
    flat = layer_weights(bench, whole)
    x = jax.random.normal(jax.random.key(2), (48, 64), jnp.float32)
    uncut = reference._layer(x, flat, whole, reference._exact)
    per = 8 // chips
    routed = 0.0
    for chip in range(chips):
        cfg = {**whole, "n_routed_experts": per, "expert_offset": chip * per}
        mine = {k: v for k, v in flat.items() if not k.startswith("expert_")}
        for e in range(per):
            for m in ("gate", "up", "down"):
                mine[f"expert_{e}/{m}/kernel"] = flat[
                    f"expert_{chip * per + e}/{m}/kernel"]
        model = MlaMoeLM.from_config(cfg, attention="reference",
                                     dtype=jnp.float32)
        # the share's reference layer is the program's share, too
        share_ref = reference._layer(x, mine, cfg, reference._exact)
        tree = nested(mine)
        pos = jnp.arange(48, dtype=jnp.int32)
        q_nope, q_rope, row = model._qkv_latent(x, tree, pos)
        attn = model._attend_expanded(q_nope, q_rope, row, tree, pos)
        after_attn = x + attn @ tree["o"]["kernel"]
        got, _ = model._ffn(after_attn, jnp.ones(48, bool), tree)
        assert float(jnp.max(jnp.abs(got - share_ref))) < F32_TOL
        hid = model._norm(after_attn, tree["ffn_norm"]["scale"])
        part, _ = model._experts(hid, hid, jnp.ones(48, bool), tree)
        routed = routed + part
    shared = reference._gated(
        hid, flat["shared/gate/kernel"], flat["shared/up/kernel"],
        flat["shared/down/kernel"], reference._exact)
    assert float(jnp.max(jnp.abs(
        after_attn + routed + shared - uncut))) < F32_TOL


# -- through the engine -------------------------------------------------------


@pytest.mark.parametrize("depth", ["ahead", "lockstep"])
def test_the_engines_streams_lie_on_the_references_best(bench, depth):
    """Greedy streams through ``LMEngine`` (one step in flight; in
    lock-step, beside a request that samples on the host): every served
    token's reference logit is the reference's best at its position, up
    to ``F32_TOL`` twice (the program's row and the reference's)."""
    _, reference = bench
    telemetry.reset()
    model, variables = model_of(), variables_of(bench)
    decoder = TransformerDecoder(model, variables, slots=4, max_len=64,
                                 buckets=(16, 32))
    engine = LMEngine(decoder, LMConfig(
        slots=4, max_len=64, prefill_buckets=(16, 32),
        queue_depth=8)).start()
    warm = {k: series("lm_moe_assignments_total", where=k) or 0
            for k in ("held", "absent")}
    try:
        sampler = None
        if depth == "lockstep":
            sampler = engine.submit([5, 6], 60, temperature=1.0, seed=5)
        prompts = [tokens_of(n, 10 + n) for n in (3, 17, 26, 9, 30)]
        gens = [engine.submit(p, 12) for p in prompts]
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
        if sampler is not None:
            sampler.cancel()
            while sampler.next_event(timeout=120.0)[0] == "token":
                pass
    finally:
        engine.drain(10.0)
    for prompt, out in zip(prompts, streams):
        assert len(out) == 12
        seq = prompt + out[:-1]
        ref = np.asarray(reference.logits(seq, SEED, CFG))[len(prompt) - 1:]
        gaps = ref.max(-1) - ref[np.arange(12), out]
        assert float(gaps.max()) < 2 * F32_TOL, gaps
    mode = "lockstep" if depth == "lockstep" else "ahead"
    assert series("lm_decode_steps_total", mode=mode) > 0
    # held + absent = routed pairs: tokens x top-k x layers, over the
    # prompts prefilled and the tokens stepped (idle slots nowhere)
    held = series("lm_moe_assignments_total", where="held") - warm["held"]
    absent = (series("lm_moe_assignments_total", where="absent")
              - warm["absent"])
    stepped = sum(e["args"]["active"] for e in
                  telemetry.get_span_log().events() if e["name"] == "lm.step")
    real = series("lm_prefill_tokens_total", kind="real")
    assert held + absent == (real + stepped) * 2 * CFG["num_layers"]
    per_expert = sum(series("lm_moe_expert_assignments_total",
                            expert=str(e)) or 0 for e in range(4))
    assert per_expert - warm["held"] == held
    assert series("lm_cache_bytes", kind="latent") == sum(
        a.nbytes for layer in decoder._arena for a in layer.values())


def test_experts_touched_counts_the_held_experts_that_got_a_token(bench):
    telemetry.reset()
    model, variables = model_of(), variables_of(bench)
    decoder = TransformerDecoder(model, variables, slots=4, max_len=64,
                                 buckets=(16,))
    seq = tokens_of(12, 4)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :12] = seq
    decoder.prefill(padded, 12, 1)
    before = series("lm_moe_experts_touched_total", program="decode") or 0
    per_before = [series("lm_moe_expert_assignments_total",
                         expert=str(e)) or 0 for e in range(4)]
    override = np.array([0, 7, 0, 0], np.int32)
    pos = np.array([0, 12, 0, 0], np.int32)
    decoder.fetch(decoder.dispatch(override, pos))
    touched = series("lm_moe_experts_touched_total",
                     program="decode") - before
    per = [(series("lm_moe_expert_assignments_total", expert=str(e)) or 0)
           - b for e, b in enumerate(per_before)]
    # one token, top-2, two layers: at most 4 pairs, each its own expert
    # of its layer; an expert is touched a layer where it got the token
    assert touched == sum(per) <= 4
    assert all(n <= CFG["num_layers"] for n in per)


def test_release_frees_each_wide_leaf_once_it_is_cast(bench):
    model = model_of(dtype=jnp.bfloat16)
    variables = variables_of(bench)
    wide = jax.tree_util.tree_leaves(variables)
    served = model.serving_variables(variables, release=True)
    kept = {id(a) for a in jax.tree_util.tree_leaves(served)}
    for leaf in wide:
        assert leaf.is_deleted() != (id(leaf) in kept)
    dtypes = {str(a.dtype) for a in jax.tree_util.tree_leaves(served)}
    assert dtypes == {"bfloat16", "float32"}
    assert served["params"]["layer_0"]["router"]["kernel"].dtype == jnp.float32


# -- the command --------------------------------------------------------------


def test_serve_lm_model_config_boots_and_answers(tmp_path):
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
        assert boot["model"] == "MlaMoeLM"
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
