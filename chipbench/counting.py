"""Operations and bytes the served work needs, from the model's sizes.

These count the work the algorithm needs, not what today's programs do:
attention over the live context only (not the width of a block table), the
LM head at the one position a prefill samples from, and each weight read
once per decode step. So a kernel or program that drops waste is measured
against the same yardstick as the one it replaces.

Sizes come from the configuration's ``model`` block (see ``weights``). A
multiply-add counts as two operations.
"""
from __future__ import annotations


def _itemsize(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["dtype"]]


def layer_matmul_params(m: dict) -> int:
    """Weights of one layer's matmuls (attention projections and MLP)."""
    d, f, hd = m["d_model"], m["d_ff"], m["head_dim"]
    H, K = m["num_heads"], m["num_kv_heads"]
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    mlp = (3 if m["gated"] else 2) * d * f
    return attn + mlp


def head_params(m: dict) -> int:
    """The LM head over the served vocabulary."""
    return m["d_model"] * m["vocab"]


def weight_bytes(m: dict) -> int:
    """Bytes of every weight a decode step reads once: each layer's
    matmuls and norms, the final norm and the LM head. The embedding is
    read only at the batch's tokens, which is negligible, except where it
    is tied to the head, and then it is the head."""
    d = m["d_model"]
    norms = (1 if m["parallel_block"] else 2) * d
    per_layer = layer_matmul_params(m) + norms
    return _itemsize(m) * (m["num_layers"] * per_layer + d
                           + head_params(m))


def kv_bytes_per_token(m: dict) -> int:
    """Keys and values of one token over all layers."""
    return (_itemsize(m) * 2 * m["num_kv_heads"] * m["head_dim"]
            * m["num_layers"])


def attention_flops(m: dict, q_rows: int, context: int) -> int:
    """Causal attention of ``q_rows`` queries whose last one sees
    ``context`` keys (the ones before see one fewer each), all layers:
    scores and the weighted sum of values."""
    first = context - q_rows + 1
    pairs = (first + context) * q_rows // 2
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * pairs


def prefill_flops(m: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens: every layer at every position, the LM
    head at the last one."""
    return (2 * m["num_layers"] * layer_matmul_params(m) * prompt
            + 2 * head_params(m) + attention_flops(m, prompt, prompt))


def decode_flops(m: dict, contexts) -> int:
    """One decode step over live sequences whose new token sees ``contexts``
    keys each (itself included)."""
    per_token = 2 * (m["num_layers"] * layer_matmul_params(m)
                     + head_params(m))
    return sum(per_token + attention_flops(m, 1, c) for c in contexts)


def decode_bytes(m: dict, contexts) -> int:
    """One decode step: every weight once, the cached KV of each live
    token read once, and the new token's KV written."""
    kv = kv_bytes_per_token(m)
    return (weight_bytes(m) + sum(kv * (c - 1) for c in contexts)
            + kv * len(contexts))


def flash_attention_cost(m: dict, prompt: int) -> tuple[int, int]:
    """(operations, bytes) of the prefill attention kernel over one
    ``prompt``-token sequence, all layers: q, k, v read and o written
    once, causal scores and values."""
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    io = _itemsize(m) * prompt * hd * (2 * H + 2 * K) * m["num_layers"]
    return attention_flops(m, prompt, prompt), io


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
