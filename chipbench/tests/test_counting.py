"""The operation and byte counts against hand counts at GPT-J-6B's shapes."""
import json
from pathlib import Path

from chipbench import counting as c

GPTJ = json.loads((Path(__file__).resolve().parents[1] / "configs"
                   / "gpt-j-6b.json").read_text())["model"]

# one layer: q, k, v, o are 4096 x 4096 (16 heads of 256, MHA), the MLP
# 4096 x 16384 in and out
LAYER = 4 * 4096 * 4096 + 2 * 4096 * 16384
HEAD = 4096 * 50400


def test_params_and_weight_bytes():
    assert c.layer_matmul_params(GPTJ) == LAYER == 201_326_592
    assert c.head_params(GPTJ) == HEAD
    # every layer's matmuls and its one norm (parallel block), the final
    # norm and the LM head, at 2 bytes
    assert c.weight_bytes(GPTJ) == 2 * (28 * (LAYER + 4096) + 4096 + HEAD)
    assert c.weight_bytes(GPTJ) == 11_687_403_520


def test_kv_bytes_per_token():
    # k and v, 16 heads of 256, 28 layers, bf16
    assert c.kv_bytes_per_token(GPTJ) == 2 * 16 * 256 * 28 * 2 == 458_752


def test_attention_flops_causal():
    # 3 queries seeing 1, 2 and 3 keys: 6 pairs, 4 operations per pair and
    # head dimension (scores and values), per head and layer
    per = 4 * 28 * 16 * 256
    assert c.attention_flops(GPTJ, 3, 3) == per * 6
    # one decode token seeing 100 keys
    assert c.attention_flops(GPTJ, 1, 100) == per * 100


def test_prefill_flops():
    S = 192
    want = (2 * 28 * LAYER * S + 2 * HEAD
            + 4 * 28 * 16 * 256 * S * (S + 1) // 2)
    assert c.prefill_flops(GPTJ, S) == want


def test_decode_flops_and_bytes():
    ctx = [10, 300]
    per_tok = 2 * (28 * LAYER + HEAD)
    att = 4 * 28 * 16 * 256 * (10 + 300)
    assert c.decode_flops(GPTJ, ctx) == 2 * per_tok + att
    kv = 458_752
    assert c.decode_bytes(GPTJ, ctx) == (11_687_403_520 + kv * (9 + 299)
                                         + kv * 2)


def test_flash_attention_cost():
    S = 256
    flops, nbytes = c.flash_attention_cost(GPTJ, S)
    assert flops == 4 * 28 * 16 * 256 * S * (S + 1) // 2
    # q and o over 16 heads, k and v over 16 KV heads, 2 bytes, 28 layers
    assert nbytes == 2 * S * 256 * (16 + 16 + 16 + 16) * 28


def test_roofline_seconds_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert c.roofline_seconds(1000, 50, peaks) == 10.0
    assert c.roofline_seconds(100, 50, peaks) == 5.0
