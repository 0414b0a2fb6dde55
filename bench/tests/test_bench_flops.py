"""Operation and byte counts against hand counts of both configurations."""
import json

import pytest

from bench import ROOT, flops


def _config(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)


# per layer: q and o projections (d x heads x 128 each), k and v
# (d x kv heads x 128 each), and gate, up and down (d x d_ff each)
QWEN_LAYER = 2 * 2048 * 16 * 128 + 2 * 2048 * 8 * 128 + 3 * 2048 * 6144
YI_LAYER = 2 * 4096 * 32 * 128 + 2 * 4096 * 4 * 128 + 3 * 4096 * 11008


def test_hand_counts_of_one_layer():
    assert QWEN_LAYER == 50_331_648
    assert YI_LAYER == 173_015_040
    assert flops.layer_weights(flops.dims(_config("qwen3-1.7b"))) == QWEN_LAYER
    assert flops.layer_weights(flops.dims(_config("yi-6b.l16"))) == YI_LAYER


@pytest.mark.parametrize("name,layers,layer,head,heads,kv", [
    # head: the tied 2048 x 151936 embedding / the untied 4096 x 64000 head
    ("qwen3-1.7b", 28, QWEN_LAYER, 2048 * 151936, 16, 28 * 2 * 8 * 128 * 2),
    ("yi-6b.l16", 16, YI_LAYER, 4096 * 64000, 32, 16 * 2 * 4 * 128 * 2),
])
def test_decode_and_prefill_counts(name, layers, layer, head, heads, kv):
    hf = _config(name)
    d = hf["hidden_size"]
    # a decode step writing position 700 attends to 701 positions: q.k and
    # p.v are 2 x 128 operations each per head and position
    pos = 700
    one_row = 2 * layers * layer + layers * heads * 4 * 128 * 701 + 2 * head
    assert flops.decode_flops(hf, 3, pos) == 3 * one_row
    assert flops.kv_bytes_per_token(hf) == kv
    norms = 2 * d + (2 * 128 if name == "qwen3-1.7b" else 0)
    weights = 2 * (layers * (layer + norms) + d + head)
    assert flops.weight_bytes(hf) == weights
    rows = 5
    assert flops.decode_bytes(hf, rows, pos) == (
        weights + rows * (kv * 701 + kv + 2 * d))
    # a 512-token prefill: every position through every layer, causal
    # attention over 1 + 2 + ... + 512 positions, the head once
    causal = 512 * 513 // 2
    prefill = (2 * layers * layer * 512 + layers * heads * 4 * 128 * causal
               + 2 * head)
    assert flops.prefill_flops(hf, 2, 512) == 2 * prefill


def test_least_time_is_the_bound_that_binds():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.min_seconds(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert flops.min_seconds(1.0, 819e9, peaks) == pytest.approx(1.0)
    hf = _config("qwen3-1.7b")
    # one decode step of 16 rows is bound by memory: 3.4 GB of weights
    f, b = flops.decode_flops(hf, 16, 600), flops.decode_bytes(hf, 16, 600)
    assert flops.min_seconds(f, b, peaks) == pytest.approx(b / 819e9)
