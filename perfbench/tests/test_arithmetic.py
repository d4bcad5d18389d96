"""FLOP and byte functions against hand counts; peaks; percentiles; the
generator's determinism."""

import json
from pathlib import Path

import pytest

import flops
import flops_resnet
import flops_transformer_lm as flops_lm
import harness
import loadgen

BENCH = Path(__file__).resolve().parents[1]
RESNET50 = json.loads((BENCH / "configs" / "resnet50.json").read_text())
GPT = json.loads((BENCH / "configs" / "cerebras_gpt_1p3b.json").read_text())


def test_resnet50_forward_is_4_09_gmac():
    # torchvision's count for resnet50 at 224: 4.09 GMAC (convolutions and
    # the classifier)
    assert flops_resnet.forward_macs(RESNET50) == pytest.approx(4.09e9,
                                                                rel=0.01)


def test_resnet50_stem_and_head_by_hand():
    tiny = dict(RESNET50, stage_sizes=[])
    # stem 112*112*7*7*3*64, classifier 64*1000 (no stages: width stays 64)
    assert flops_resnet.forward_macs(tiny) == 112 * 112 * 147 * 64 + 64 * 1000


def test_resnet50_train_is_three_forwards():
    assert flops_resnet.train_flops_per_sample(RESNET50) == (
        6 * flops_resnet.forward_macs(RESNET50))


def test_cerebras_token_is_1_21_g_macs_plus_attention_and_head():
    non_embedding = 24 * (4 * 2048 * 2048 + 2 * 2048 * 8192)
    assert flops_lm.params_nonembedding(GPT) == non_embedding
    assert non_embedding == pytest.approx(1.21e9, rel=0.005)
    ctx = 500
    assert flops_lm.token_macs(GPT, ctx) == (
        non_embedding + 24 * 2 * ctx * 2048 + 2048 * 50257)
    assert flops_lm.decode_flops(GPT, ctx) == 2 * flops_lm.token_macs(GPT, ctx)


def test_prefill_counts_the_causal_half_once():
    n = 512
    dense = n * (flops_lm.params_nonembedding(GPT) + 2048 * 50257)
    attn = 24 * 2 * 2048 * n * (n + 1) // 2
    assert flops_lm.prefill_flops(GPT, n) == 2 * (dense + attn)
    call = flops_lm.flash_prefill_call(GPT, n)
    assert call["flops"] == 4 * 2048 * n * (n + 1) // 2
    assert call["bytes"] == 4 * n * 2048 * 2


def test_roofline_names_its_bound():
    peak = flops.peaks("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 1.0, peak) == (1.0, "compute")
    t, bound = flops.roofline_seconds(1.0, 819e9, peak)
    assert (round(t, 9), bound) == (1.0, "memory")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(LookupError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(LookupError):
        flops.peaks("cpu")


def test_percentile_interpolates_over_all_values():
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    assert harness.percentile(range(101), 95) == 95
    assert harness.percentile([0, 10], 95) == pytest.approx(9.5)
    assert harness.percentile([1.0, float("inf")], 95) == float("inf")


TRAFFIC = json.loads((BENCH / "traffic" / "serve_chat.json").read_text())


def test_every_seed_gets_the_same_lengths_in_another_order():
    a = loadgen.schedule(TRAFFIC, 1)
    b = loadgen.schedule(TRAFFIC, 3_000_000_019)
    assert a == loadgen.schedule(TRAFFIC, 1)
    assert a != b and sorted(a) == sorted(b)
    assert len(a) == TRAFFIC["schedule_size"]
    assert min(p for p, _ in a) >= 64 and max(p for p, _ in a) <= 1024
    assert min(o for _, o in a) >= 32 and max(o for _, o in a) <= 256


def test_a_family_is_found_by_its_name():
    assert flops.of_family("resnet") is flops_resnet
    assert flops.of_family("transformer_lm") is flops_lm
    with pytest.raises(ImportError):
        flops.of_family("no_such_family")


def test_prompts_come_from_the_seed_alone():
    x = loadgen.prompt_ids(7, 3, 100, 50257)
    assert x == loadgen.prompt_ids(7, 3, 100, 50257)
    assert x != loadgen.prompt_ids(8, 3, 100, 50257)
    assert x != loadgen.prompt_ids(7, 4, 100, 50257)
    assert len(x) == 100 and all(0 <= t < 50257 for t in x)
    body = json.loads(loadgen.body_of(7, 3, 100, 40, 50257))
    assert body == {"tokens": x, "max_new_tokens": 40, "temperature": 0.0,
                    "seed": 3}


@pytest.mark.parametrize("lo,hi,u,want", [
    (64, 1024, 0.5, 256), (64, 1024, 1e-9, 64), (32, 256, 1 - 1e-9, 256),
    (4, 32, 1 / 3, 8),
])
def test_length_quantiles(lo, hi, u, want):
    dist = {"dist": "loguniform", "min": lo, "max": hi}
    assert loadgen.quantile_of(dist, u) == want


def test_an_unknown_length_distribution_is_an_error():
    with pytest.raises(ValueError):
        loadgen.quantile_of({"dist": "zipf", "min": 1, "max": 2}, 0.5)


def test_open_loop_arrivals_are_fixed_by_the_seed():
    tr = {"rate_per_s": 5.0, "arrivals": "poisson",
          "burst": {"every_s": 2.0, "size": 3}}
    a = loadgen.arrival_times(tr, 11, 0.0, 10.0)
    assert a == loadgen.arrival_times(tr, 11, 0.0, 10.0)
    assert a == sorted(a) and a.count(2.0) == 3
    assert 30 < len(a) < 90
