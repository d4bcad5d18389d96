"""The hybrid state-space LM (``models/jamba.py``) and its scan kernel
(``ops/selective_scan.py``) against the benchmark's plain reference
(``perfbench/references/jamba_lm.py``, loaded by path: float32
``jax.numpy``, a ``lax.scan`` over positions, no cache), at a small size
on the CPU.

Sizes: two periods of a pattern of four (layers 2 and 6 attention, the
other six Mamba), hidden 64, 4 query heads over 1 key/value head of 16,
``d_state`` 16, ``d_conv`` 4, ``dt_rank`` 8, ``d_inner`` 128, vocabulary
128. Weights are the model's own ``init`` at Mamba's values (``A_log =
log(1..16)``, steps of 0.001-0.1), so the state remembers for hundreds of
positions and a wrong carry shows far from where it was made.

Tolerances. A float32 model on the CPU differs from the reference by
summation order only: logits of size 0.7-2 agree to about 2e-6 (read:
1.9e-6 full forward, 1.3e-6 prefill then decode), held to ``F32_TOL``
2e-4. The same model in bfloat16 misses by 3.0e-2 (read), 150 times
``F32_TOL``. The kernel against its ``lax.scan`` agrees to 4.8e-7 on
outputs of size 5.8 (read), held to ``SCAN_TOL`` x 6 = 6e-5 (the state,
of size 1, to ``SCAN_TOL``): a scan whose state is rounded to bfloat16
each position misses by 2.1e-2 (read), 350 times that, and
``test_a_bfloat16_state_fails_the_scan_tolerance`` keeps it so. Where
the program is compared with itself (padded against unpadded, together
against alone) the two sides run different shapes through the same
float32 arithmetic, held to ``SAME_TOL`` 1e-5.
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
from dss_ml_at_scale_tpu.models import TransformerLM
from dss_ml_at_scale_tpu.models.jamba import JambaLM
from dss_ml_at_scale_tpu.ops.selective_scan import (
    selective_scan,
    selective_scan_reference,
)
from dss_ml_at_scale_tpu.serving.lm import (
    LMConfig,
    LMEngine,
    TransformerDecoder,
    kvcache,
)
from dss_ml_at_scale_tpu.serving.lm.engine import cache_bytes_by_kind

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "perfbench"
F32_TOL = 2e-4
SCAN_TOL = 1e-5
SAME_TOL = 1e-5

CFG = {
    "model_type": "jamba", "vocab_size": 128, "hidden_size": 64,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 1, "intermediate_size": 96, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
    "attn_layer_period": 4, "attn_layer_offset": 2, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "num_experts": 1, "tie_word_embeddings": True,
    "rms_norm_eps": 1e-6,
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
    return load("jamba_lm_reference", BENCH / "references" / "jamba_lm.py")


def model_of(dtype=jnp.float32, **kw):
    return JambaLM.from_config(CFG, dtype=dtype, **kw)


@pytest.fixture(scope="module")
def variables():
    return model_of().init(jax.random.key(7))


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


def test_the_layer_kinds_follow_the_period():
    assert model_of().layer_kinds == (
        "mamba", "mamba", "attn", "mamba", "mamba", "mamba", "attn", "mamba")


def test_the_program_and_the_reference_name_the_same_leaves(reference):
    assert reference.param_shapes(CFG) == {
        p: tuple(s) for p, s in model_of().variable_shapes().items()}


def test_leaves_are_named_by_the_benchmarks_rules():
    """Every leaf's last path part is one the benchmark's weight rules
    make (``perfbench/weights.py``) without a configuration's overrides."""
    for path, shape in model_of().variable_shapes().items():
        last = path.rsplit("/", 1)[-1]
        assert last in ("scale", "bias", "embedding") or (
            last == "kernel" and len(shape) == 2), path


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_full_forward_logits_match_the_reference(reference, variables,
                                                 attention):
    """512 positions (two of the reference's query blocks) through all
    eight layers: the plain paths, and the flash and scan kernels
    (interpreted on the CPU)."""
    toks = tokens_of(512)
    model = model_of(attention=attention)
    got = jax.jit(model.logits)(variables, jnp.asarray(toks))
    want = reference.logits_of(toks, flat(variables), CFG)
    assert float(jnp.max(jnp.abs(want))) > 0.5       # logits of size
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


def test_bfloat16_fails_the_float32_tolerance(reference, variables):
    toks = tokens_of(64)
    model = model_of(dtype=jnp.bfloat16, attention="reference")
    got = jax.jit(model.logits)(model.serving_variables(variables),
                                jnp.asarray(toks))
    gap = float(jnp.max(jnp.abs(
        got - reference.logits_of(toks, flat(variables), CFG))))
    assert gap > 10 * F32_TOL, gap


def test_the_seeded_weights_match_the_reference(reference):
    """The benchmark's own weights from a seed past 2**31, as its driver
    makes them, and the reference's layer-by-layer weights from it."""
    weights = sys.modules["weights"]
    seed = 3_000_000_019
    variables = weights.nest(weights.make(reference.param_shapes(CFG), seed))
    toks = tokens_of(300, 3)          # padded to the cell's one shape
    model = model_of(attention="reference")
    got = jax.jit(model.logits)(variables, jnp.asarray(toks))
    want = reference.logits(toks, seed, CFG)
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


# -- through the decoder ------------------------------------------------------


def decoder_of(variables, slots=6, **kw):
    return TransformerDecoder(model_of(**kw), variables, slots=slots,
                              max_len=64, buckets=BUCKETS)


def prefill(decoder, seq, n, slot):
    bucket = next(b for b in BUCKETS if b >= n)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = seq[:n]
    return decoder.prefill(padded, n, slot)


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
def test_prefill_then_decode_match_the_full_forward(reference, variables,
                                                    attention):
    """Six slots admitted with prompts of 1, 2, 3, 5, bucket - 1 and
    bucket real tokens, then decoded together, teacher-forced along their
    sequences: the prefill's row and every decoded row equal the
    reference's full forward of that slot's sequence."""
    decoder = decoder_of(variables, attention=attention)
    seqs = {s: tokens_of(44, 20 + s) for s in range(6)}
    prompts = dict(enumerate((1, 2, 3, 5, 15, 16)))
    want = {s: np.asarray(reference.logits_of(seqs[s], flat(variables), CFG))
            for s in seqs}
    for slot, n in prompts.items():
        row = prefill(decoder, seqs[slot], n, slot)
        assert np.max(np.abs(row - want[slot][n - 1])) < F32_TOL, (slot, n)
    pos = dict(prompts)
    for _ in range(26):
        logits = step(decoder, {s: (seqs[s][p], p) for s, p in pos.items()})
        for s, p in pos.items():
            gap = np.max(np.abs(logits[s] - want[s][p]))
            assert gap < F32_TOL, (s, p, gap)
            pos[s] += 1


@pytest.mark.parametrize("n", [5, 16])
def test_a_padded_prefill_leaves_the_unpadded_state(variables, n):
    """The same ``n`` real tokens in the 16 and the 32 bucket: every Mamba
    layer's state and conv inputs, and the real key/value rows, agree."""
    model = model_of()
    run = jax.jit(kvcache.prefill_bucket, static_argnums=0)
    seq = tokens_of(n, 5)
    caches = []
    for bucket in BUCKETS:
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = seq
        _, _, cache = run(model, variables, jnp.asarray(padded),
                          kvcache.make_arena(model, 1, 64), jnp.int32(n))
        caches.append(cache)
    for kind, short, long in zip(model.layer_kinds, *caches):
        for name in short:
            a, b = np.asarray(short[name]), np.asarray(long[name])
            if kind == "attn":
                a, b = a[:, :, :n], b[:, :, :n]
            assert np.max(np.abs(a - b)) < SAME_TOL, (kind, name)


def test_the_scratch_and_a_readmitted_slot_start_from_zero(reference,
                                                           variables):
    """A long prompt decoded in slot 2 and retired, then a short one
    admitted there; the scratch the long prompt's prefill left behind is
    the short one's: its rows and state are what a fresh decoder gives,
    and both equal the reference."""
    long, short = tokens_of(40, 8), tokens_of(24, 9)
    want = np.asarray(reference.logits_of(short, flat(variables), CFG))
    used = decoder_of(variables, slots=3)
    prefill(used, long, 30, 2)
    for p in range(30, 40):
        step(used, {2: (long[p], p)})
    fresh = decoder_of(variables, slots=3)
    for decoder in (used, fresh):
        row = prefill(decoder, short, 3, 2)
        assert np.max(np.abs(row - want[2])) < F32_TOL
        for p in range(3, 20):
            logits = step(decoder, {2: (short[p], p)})
            assert np.max(np.abs(logits[2] - want[p])) < F32_TOL, p
    for a, b in zip(jax.tree_util.tree_leaves(used._arena),
                    jax.tree_util.tree_leaves(fresh._arena)):
        if a.ndim == 3:                     # a Mamba layer's slot state
            assert np.max(np.abs(np.asarray(a[2]) - np.asarray(b[2]))) \
                < SAME_TOL


def test_two_slots_decode_together_as_each_alone(variables):
    seqs = {0: tokens_of(30, 11), 1: tokens_of(30, 12)}
    together = decoder_of(variables, slots=2)
    alone = {s: decoder_of(variables, slots=2) for s in seqs}
    for s, seq in seqs.items():
        prefill(together, seq, 4 + 3 * s, s)
        prefill(alone[s], seq, 4 + 3 * s, s)
    for k in range(12):
        feeds = {s: (seqs[s][4 + 3 * s + k], 4 + 3 * s + k) for s in seqs}
        both = step(together, feeds)
        for s in seqs:
            one = step(alone[s], {s: feeds[s]})
            assert np.max(np.abs(both[s] - one[s])) < SAME_TOL, (s, k)


# -- the kernel ---------------------------------------------------------------


def scan_inputs(t=300, channels=256, n_state=16, seed=0):
    """At Mamba's own init: ``A = -(1..16)`` on every channel, steps
    log-uniform in [0.001, 0.1]."""
    k = jax.random.split(jax.random.key(seed), 4)
    u = jax.random.normal(k[0], (t, channels))
    dt = jnp.exp(jax.random.uniform(k[1], (t, channels),
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    a = -jnp.broadcast_to(
        jnp.arange(1, n_state + 1, dtype=jnp.float32)[:, None],
        (n_state, channels))
    b = jax.random.normal(k[2], (t, n_state))
    c = jax.random.normal(k[3], (t, n_state))
    return u, dt, a, b, c, jnp.ones(channels)


@pytest.mark.parametrize("n_real", [300, 257, 130, 64, 1])
def test_the_kernel_matches_the_scan(n_real):
    """300 positions in chunks of 64 (the last chunk padded by the op),
    two channel blocks, the tail past ``n_real`` masked: ``y`` up to
    ``n_real`` and the state after it are the ``lax.scan``'s, the rows
    past it zeros."""
    args = scan_inputs()
    y, state = selective_scan(*args, n_real, chunk=64, block_c=128)
    y_ref, state_ref = selective_scan_reference(*args, n_real)
    assert float(jnp.max(jnp.abs(y_ref))) > 1.0
    assert float(jnp.max(jnp.abs(y - y_ref))) < SCAN_TOL * 6
    assert float(jnp.max(jnp.abs(state - state_ref))) < SCAN_TOL
    assert not np.any(np.asarray(y[n_real:]))


def test_a_bfloat16_state_fails_the_scan_tolerance():
    u, dt, a, b, c, d = scan_inputs()
    y_ref, _ = selective_scan_reference(u, dt, a, b, c, d, 300)

    def step(s, row):
        dt_t, u_t, b_t, c_t = row
        s = (jnp.exp(dt_t[None] * a) * s + b_t[:, None] * (dt_t * u_t)[None]
             ).astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.sum(s * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros(a.shape), (dt, u, b, c))
    gap = float(jnp.max(jnp.abs(y + d * u - y_ref)))
    assert gap > 100 * SCAN_TOL * 6, gap


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


def test_kernel_body_is_traced_once_whatever_the_chunk_count():
    """The walk over a chunk's positions is a loop, and the chunks are
    the grid: the kernel's jaxpr holds as many equations over 2 chunks
    as over 8, and at a chunk of 64 positions as at one of 256."""

    def size(t, chunk):
        x = jax.ShapeDtypeStruct((t, 512), jnp.float32)
        s = jax.ShapeDtypeStruct((t, 16), jnp.float32)
        a = jax.ShapeDtypeStruct((16, 512), jnp.float32)
        d = jax.ShapeDtypeStruct((512,), jnp.float32)
        traced = jax.make_jaxpr(lambda u, dt, a, b, c, d: selective_scan(
            u, dt, a, b, c, d, 7, chunk=chunk))(x, x, a, s, s, d)
        kernel = _kernel_jaxpr(traced.jaxpr)
        assert kernel is not None
        return _equations(kernel)

    assert size(2 * 256, 256) == size(8 * 256, 256) == size(8 * 256, 64)


# -- the engine ---------------------------------------------------------------


def test_the_arena_gauge_reports_each_kind(variables):
    """A hybrid arena reports its recurrent state and its keys and
    values apart; a transformer's one kind is reported whole, as before."""
    telemetry.reset()
    decoder = decoder_of(variables, slots=2)
    ssm = kv = 0
    for layer in decoder._arena:
        for name, slab in layer.items():
            if name in ("k", "v"):
                kv += slab.nbytes
            else:
                ssm += slab.nbytes
    assert series("lm_cache_bytes", kind="ssm") == ssm > 0
    assert series("lm_cache_bytes", kind="kv") == kv > 0
    assert ssm == 6 * 2 * (16 + 3) * 128 * 4        # layers x slots x rows
    lm = TransformerLM(vocab_size=64, dim=32, num_heads=2, num_layers=2,
                       max_seq=32, dtype=jnp.float32)
    lm_vars = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    chat = TransformerDecoder(lm, lm_vars, slots=2, max_len=32, buckets=(8,))
    assert series("lm_cache_bytes", kind="kv") == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(chat._arena))
    assert cache_bytes_by_kind(lm, chat._arena) == {
        "kv": series("lm_cache_bytes", kind="kv")}


def test_the_engines_streams_lie_on_the_references_best(reference,
                                                        variables):
    """Greedy streams through ``LMEngine`` (one step in flight, slots
    freed and re-admitted as requests end): every served token's
    reference logit is the reference's best at its position, up to
    ``F32_TOL`` twice (the program's row and the reference's)."""
    decoder = decoder_of(variables, slots=3)
    engine = LMEngine(decoder, LMConfig(
        slots=3, max_len=64, prefill_buckets=BUCKETS, queue_depth=8)).start()
    try:
        prompts = [tokens_of(n, 30 + n) for n in (3, 17, 26, 9, 1)]
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
    finally:
        engine.drain(10.0)
    for prompt, out in zip(prompts, streams):
        assert len(out) == 12
        seq = prompt + out[:-1]
        ref = np.asarray(reference.logits_of(seq, flat(variables), CFG))
        ref = ref[len(prompt) - 1:]
        gaps = ref.max(-1) - ref[np.arange(12), out]
        assert float(gaps.max()) < 2 * F32_TOL, gaps


# -- the command --------------------------------------------------------------


def test_serve_lm_model_config_builds_the_hybrid_model(tmp_path):
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(CFG))
    proc = subprocess.Popen(
        [sys.executable, "-m", "dss_ml_at_scale_tpu.config.cli", "serve-lm",
         "--model-config", str(path), "--attention", "flash", "--port", "0",
         "--slots", "2", "--max-len", "48", "--prefill-buckets", "8,16"],
        stdout=subprocess.PIPE, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        boot = json.loads(proc.stdout.readline())
        assert boot["model"] == "JambaLM"
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
