"""The yardstick's arithmetic against hand-worked cases."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import arith, reference  # noqa: E402

# a model small enough to count by hand
M = dict(hidden_size=8, intermediate_size=16, head_dim=4,
         num_attention_heads=2, num_key_value_heads=1, num_hidden_layers=3,
         vocab_size=10, tie_word_embeddings=False)


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_is_numpys(q):
    v = np.random.default_rng(0).lognormal(size=37)
    assert arith.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_percentile_by_hand():
    assert arith.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    assert arith.percentile([7], 90) == 7
    assert arith.median([3, 1, 2, 10]) == 2.5
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_layer_params_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8 = 192; mlp 3 x 8 x 16 = 384
    assert arith.layer_matmul_params(M) == 576


def test_train_flops_by_hand():
    # matmul weights: 3 layers x 576 + head 8 x 10 = 1808 -> 6 x 1808
    # attention forward per token at seq 32: 3 layers x 2 products x
    # 2 FLOP x dh 4 x 2 heads x 32/2 keys = 1536; x3 with the backward pass
    assert arith.train_flops_per_token(M, 32) == 6 * 1808 + 3 * 1536


def test_decode_bytes_by_hand():
    # weights: 3 x (576 + 2 x 8) + head 80 + final norm 8 = 1864 values
    # KV per cached token: 2 x 3 layers x 1 head x 4 = 24 values
    assert arith.decode_step_bytes(M, 0) == 2 * 1864
    assert arith.decode_step_bytes(M, 100) == 2 * 1864 + 2 * 2400
    assert arith.kv_bytes_per_token(M) == 48
    assert arith.serve_weight_bytes(M) == 2 * (1864 + 80)


def test_mistral_sizes_are_the_known_ones():
    m = _cfg("mistral-7b.json")
    assert arith.layer_matmul_params(m) == 218_103_808
    full = dict(m, num_hidden_layers=32)
    # 7.25 B parameters published
    assert arith.serve_weight_bytes(full) / 2 == pytest.approx(7.248e9,
                                                               rel=1e-3)
    # 4 KiB of bf16 KV a token and layer
    assert arith.kv_bytes_per_token(m) == 4096 * m["num_hidden_layers"]


def test_dscoder_flops_against_6n():
    m = _cfg("deepseek-coder-1.3b.json")
    full = dict(m, num_hidden_layers=24)
    n = 24 * (arith.layer_matmul_params(m) + 2 * 2048) \
        + 2 * 2048 * 32256 + 2048
    assert n == pytest.approx(1.346e9, rel=2e-3)     # published 1.3 B
    f = arith.train_flops_per_token(full, 2048)
    # 6N counts the embedding rows and no attention: at 2k tokens the two
    # nearly cancel, the exact count stays within a tenth of it
    assert f == pytest.approx(6 * n, rel=0.1)
    assert f > 6 * (n - 2048 * 32256)


def test_peaks_table():
    p = arith.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="no peaks"):
        arith.peaks("cpu")


def test_serve_depth_is_what_the_config_runs():
    m = _cfg("mistral-7b.json")
    dep = m["deployment"]
    fits = reference.serve_depth(m, int(15.75 * 2**30),
                                 dep["kv_pool_tokens"])
    assert m["num_hidden_layers"] == fits == 21
    assert reference.serve_depth(m, 2**30, dep["kv_pool_tokens"]) < 1
    longest = dep["max_prompt_len"] + dep["max_new_tokens"]
    assert dep["kv_pool_tokens"] >= 2 * longest
