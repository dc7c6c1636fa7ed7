"""The latent-attention expert block (``blocks/mla_moe.py``): its drawn
tree is the program's, the check passes through the harness on the CPU at
a small width, and its counts agree with hand counts."""
import sys
import time

import jax
import pytest

from chipbench import harness
from chipbench.blocks import mla_moe as B

SEED = 2**41 + 77

# a small model of the block's shape: 1 dense + 2 MoE layers, 8 routed
# experts of which 4 are held (the second half), 2 per token
M = dict(num_layers=2, dense_layers=1, d_model=64, num_heads=4,
         num_kv_heads=1, kv_lora_rank=32, qk_nope_head_dim=16,
         qk_rope_head_dim=8, v_head_dim=16, d_ff=96, moe_d_ff=24,
         shared_d_ff=48, num_experts=8, experts_per_token=2, experts_held=4,
         experts_first=4, routed_scale=2.446, vocab=300, activation="swiglu",
         gated=True, parallel_block=False, tie_embeddings=False,
         rope_theta=50000.0, norm_eps=1e-5, embed_scale=0.02,
         router_bias_scale=0.05, dtype="bfloat16")


def small_cell():
    """``moonlight-chat`` at a small width, served on the CPU."""
    from repro.configs.base import get_config

    bench = harness.load_bench()
    cell = harness.load_cell(bench, "moonlight-chat")
    cfg = get_config("moonlight-16b-a3b").replace(
        num_layers=3, d_model=64, num_heads=4, d_ff=96, vocab_size=300,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_d_ff=24, num_experts=8, experts_per_token=2,
        experts_held=4, experts_first=4, dtype="bfloat16")
    cell.update(bench=bench, rate_per_s=4.0,
                engine={"max_slots": 4, "block_size": 16,
                        "max_blocks_per_seq": 8, "num_blocks": 40},
                check=dict(cell["check"], min_tokens=20))
    cell["mix"] = dict(cell["mix"],
                       prompt={"dist": "uniform", "min": 17, "max": 60},
                       output={"dist": "uniform", "min": 8, "max": 24})
    return cell, cfg


def test_sizes_are_the_small_model():
    _, cfg = small_cell()
    assert B.serves(cfg)
    assert {k: v for k, v in M.items()
            if k not in ("embed_scale", "router_bias_scale")} == B.sizes_of(cfg)


def test_the_drawn_tree_is_the_programs():
    from repro.models import registry

    cell, cfg = small_cell()
    engine, m = harness.build(cell, SEED, cfg)  # raises on any mismatch
    params = engine.model.params
    want = registry.param_shapes(cfg)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), params) == jax.tree.map(
        lambda x: (x.shape, x.dtype), want)
    assert params["layers"]["moe_wi"].shape == (2, 4, 64, 24)
    assert params["dense_wi"].shape == (1, 64, 96)
    assert engine.model.cache.v_pool is None


def test_the_check_passes_through_the_harness():
    cell, cfg = small_cell()
    out = harness.run_cell(
        cell, SEED, 3.0, True, t_start=time.perf_counter(),
        devices=jax.devices(), peaks=harness.load_peaks("TPU v5 lite"),
        cfg=cfg, log=lambda s: print(s, file=sys.stderr))
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_compared"]["value"] >= 20
    assert out["metrics"]["mfu"]["value"] > 0
    # the device readers need a TPU plane in the trace; the CPU has none
    assert "mla_decode_roofline" not in out["metrics"]


def test_the_dense_block_refuses_it():
    from chipbench.blocks import dense

    _, cfg = small_cell()
    assert not dense.serves(cfg)


# -- counts ------------------------------------------------------------------

# one layer's attention: W_q 64 x (4 x 24), W_kva 64 x (32 + 8), W_kvb
# 32 x (4 x 32), wo (4 x 16) x 64
ATTN = 64 * 96 + 64 * 40 + 32 * 128 + 64 * 64
EXPERT = 3 * 64 * 24
MOE_FIXED = ATTN + 64 * 8 + 3 * 64 * 48  # + router, shared
DENSE_FIXED = ATTN + 3 * 64 * 96
HEAD = 64 * 300


def test_params_and_weight_bytes():
    assert B.attn_params(M) == ATTN == 16896
    # per layer: attention, router + its bias, 4 held experts; norms
    # (2 x 64 + 32 per layer, 64 final); the head
    want = 2 * (DENSE_FIXED + 2 * (MOE_FIXED + 8 + 4 * EXPERT)
                + 3 * (2 * 64 + 32) + 64 + HEAD)
    assert B.weight_bytes(M) == want


def test_expectations_under_uniform_routing():
    # k n / E = 2 x 4 / 8 held experts a token
    assert B.held_per_token(M) == 1.0
    # 3 rows miss a given expert with probability (6/8)^3
    assert B.held_reached(M, 3) == pytest.approx(4 * (1 - 0.75 ** 3))


def test_kv_and_latent_attention_cost():
    # 32 + 8 values of 2 bytes, 3 layers
    assert B.kv_bytes_per_token(M) == 240
    flops, nbytes = B.latent_attention_cost(M, [5, 10])
    assert nbytes == 240 * 15
    assert flops == 2 * 4 * (32 + 8 + 32) * 15 * 3


def test_prefill_and_decode_flops():
    tok = 2 * (DENSE_FIXED + 2 * (MOE_FIXED + 1.0 * EXPERT))
    assert B.token_flops(M) == tok
    S = 20
    att = 2 * 3 * 4 * (24 + 16) * S * (S + 1) // 2
    assert B.attention_flops(M, S, S) == att
    assert B.prefill_flops(M, S) == tok * S + 2 * HEAD + att
    ctx = [5, 10]
    lat = 2 * 4 * 72 * 15 * 3
    assert B.decode_flops(M, ctx) == 2 * (tok + 2 * HEAD) + lat


def test_decode_bytes_count_the_reached_experts():
    ctx = [5, 10]
    reached = 4 * (1 - 0.75 ** 2)
    skipped = 2 * 2 * (4 - reached) * EXPERT
    want = B.weight_bytes(M) - skipped + 240 * (4 + 9) + 240 * 2
    assert B.decode_bytes(M, ctx) == pytest.approx(want)


def test_flash_attention_cost():
    S = 32
    flops, nbytes = B.flash_attention_cost(M, S)
    assert flops == B.attention_flops(M, S, S)
    # q and k 24 wide, v and o 16, 4 heads, 2 bytes, 3 layers
    assert nbytes == 2 * S * 4 * (24 + 24 + 16 + 16) * 3
