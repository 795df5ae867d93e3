"""Percentile arithmetic and the operation and byte counts of the
kernels, against values worked by hand."""
import math

import pytest

import flops
from conftest import with_family
from stats import percentile, ttft_ms

# 2 layers, d 8, 2 query heads of 4 sharing 1 KV head, d_ff 16, vocab 10
TOY = with_family({
    "num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
    "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10,
    "qkv_bias": True, "tie_word_embeddings": False,
    "reference": "dense_gqa"})


def test_percentile_nearest_rank_over_all_samples():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 90) == 90
    assert percentile(xs, 99) == 99
    assert percentile([5.0], 99) == 5.0
    # ten samples: p90 is the 9th, p99 the 10th (the maximum)
    ten = [float(i) for i in range(10)]
    assert percentile(ten, 90) == 8.0
    assert percentile(ten, 99) == 9.0


def test_failed_request_counts_as_missed():
    xs = [10.0] * 9 + [math.inf]
    assert percentile(xs, 90) == 10.0
    assert percentile(xs, 99) == math.inf
    assert percentile([1.0, math.inf], 50) == 1.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_refused_or_failed_request_is_infinitely_late():
    from run import Rec
    t1 = 100.0
    ok = [Rec(due=i, prompt=[1], max_new_tokens=1, greedy=True,
              first=i + 0.05) for i in range(8)]
    refused = Rec(due=8, prompt=[1], max_new_tokens=1, greedy=True,
                  error="rejected")
    failed = Rec(due=9, prompt=[1], max_new_tokens=1, greedy=True,
                 first=9.01, error="internal")
    later = Rec(due=t1 + 1, prompt=[1], max_new_tokens=1, greedy=True)
    xs = ttft_ms(ok + [refused, failed, later], t1)
    assert len(xs) == 10 and xs.count(math.inf) == 2
    assert percentile(xs, 50) == pytest.approx(50.0)
    assert percentile(xs, 90) == math.inf


def test_decode_attention_counts():
    # one sequence of 5 positions: QK^T and PV over 5 keys for 2 heads of 4
    f, b = flops.decode_attn_call(TOY, [5])
    assert f == 4 * 2 * 4 * 5
    # K and V rows of 1 head x 4 in bf16, plus q in and o out
    assert b == 5 * 2 * 1 * 4 * 2 + 2 * 2 * 4 * 2
    f2, b2 = flops.decode_attn_call(TOY, [5, 3])
    assert f2 == f + 4 * 2 * 4 * 3 and b2 == b + 3 * 16 + 32


def test_prefill_attention_counts_causal_positions():
    # 3 queries after 2 cached positions see 3, 4, 5 positions
    f, b = flops.prefill_attn_call(TOY, [(2, 3), (7, 0)])
    assert f == 4 * 2 * 4 * (3 + 4 + 5)
    assert b == 5 * 2 * 1 * 4 * 2 + 2 * 3 * 2 * 4 * 2


def test_model_counts():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, three 8x16 MLP matrices
    assert flops.layer_matmul_params(TOY) == 64 + 32 + 32 + 64 + 384
    per_layer = 576 + 2 * 8 + (8 + 4 + 4)
    assert flops.param_count(TOY) == 2 * per_layer + 2 * 10 * 8 + 8
    # one decode row at 5 positions and one prefill row of 3 after 2
    want = (2 * 2 * 576 + 2 * 8 * 10 + 2 * 4 * 2 * 4 * 5) \
        + (3 * 2 * 2 * 576 + 2 * 8 * 10 + 2 * 4 * 2 * 4 * 12)
    assert flops.step_flops(TOY, [5], [(2, 3)]) == want


def test_published_sizes():
    import json
    import os
    here = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "configs")
    with open(os.path.join(here, "phi3-mini-3.8b.json")) as f:
        phi3 = with_family(json.load(f))
    with open(os.path.join(here, "qwen2.5-32b-l8.json")) as f:
        qwen = with_family(json.load(f))
    assert flops.param_count(phi3) * 2 == 7_642_159_104
    assert flops.kv_bytes_per_token(phi3) == 393_216
    assert flops.kv_bytes_per_token(qwen) == 32_768
    # 8 layers of 487.6M and an embedding and head of 778.6M each
    assert round(flops.param_count(qwen) / 1e6, 1) == 5458.0


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(200, 10, peak) == 2.0
    assert flops.roofline_seconds(100, 50, peak) == 5.0
