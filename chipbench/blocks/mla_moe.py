"""DeepSeek-V3's decoder block (Moonlight-16B-A3B), as one chip of an
expert-parallel group serves it: latent attention (MLA), leading dense
layers, and MoE layers of which this chip holds a contiguous range of the
routed experts, beside the shared experts.

What is specific to the block lives here (the interface of
``blocks/dense.py``): ``sizes_of`` and ``serves``, ``leaf_specs``, the
float32 reference ``logits_at`` with its fp8 control, and the counts.

The weights: ``weights.make_tree`` stacks only ``layers/`` leaves, over
the file's ``num_layers``, so the MoE layers are ``layers/`` and each leaf
of the leading dense layers is a top-level ``dense_<leaf>`` with a leading
axis of ``dense_layers`` (the program's tree, ``transformer.dense_layers``).
The file's ``num_layers`` counts the MoE layers alone.

The equations (a sequential pre-norm residual, RMSNorm):

- attention: ``q = x W_q`` per head as ``[q_nope, q_pe]``; ``[c, k_pe] =
  x W_kva``; ``c = RMSNorm(c)``; ``[k_nope, v] = c W_kvb`` per head; the
  rotary embedding (half-split pairing) on ``q_pe`` and on ``k_pe``, which
  every head shares; causal softmax at ``1 / sqrt(qk_nope + qk_rope)``;
  then ``wo``;
- router: ``s = sigmoid(x W_r)`` in f32, the top ``k`` of ``s + b``,
  weights ``s`` at the chosen experts over their sum, times
  ``routed_scale``;
- MoE output: the weighted held experts' SwiGLUs (experts held elsewhere
  add nothing here, in the program and in the reference alike) plus the
  shared SwiGLU.

The counts take the routed experts' share as its expectation under uniform
routing: a token chooses each expert with probability ``k / E``, so it
reaches ``k n / E`` of the ``n`` held experts (1.5 for Moonlight's rank),
and ``B`` rows reach ``n (1 - (1 - k / E)^B)`` of them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counting as C
from chipbench import reference as R
from chipbench import weights as W

DENSE = "dense_"

# -- the program --------------------------------------------------------------


def sizes_of(cfg) -> dict:
    """A ``ModelConfig`` in the vocabulary of a configuration file's
    ``model`` block."""
    first, held = cfg.held_experts
    return {"num_layers": cfg.num_layers - cfg.first_dense_layers,
            "dense_layers": cfg.first_dense_layers,
            "d_model": cfg.d_model, "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "d_ff": cfg.d_ff,
            "moe_d_ff": cfg.resolved_moe_d_ff(),
            "shared_d_ff": cfg.num_shared_experts * cfg.resolved_moe_d_ff(),
            "num_experts": cfg.num_experts,
            "experts_per_token": cfg.experts_per_token,
            "experts_held": held, "experts_first": first,
            "routed_scale": cfg.routed_scale, "vocab": cfg.vocab_size,
            "activation": cfg.activation,
            "gated": cfg.activation in ("swiglu", "geglu"),
            "parallel_block": cfg.parallel_block,
            "tie_embeddings": cfg.tie_embeddings,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
            "dtype": cfg.dtype}


def serves(cfg) -> bool:
    """Latent attention, a grouped MoE with the sigmoid/bias router and
    shared experts, a sequential residual; no qk-norm, bias or window."""
    return bool(cfg.mla and cfg.grouped_moe and cfg.num_shared_experts
                and cfg.activation == "swiglu" and not cfg.parallel_block
                and not (cfg.qkv_bias or cfg.qk_norm or cfg.sliding_window))


# -- the weights --------------------------------------------------------------


def _attn_specs(m: dict) -> dict:
    d, H, r = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return {"attn_norm": ((d,), "norm", None),
            "wq": ((d, H * qk), "dense", None),
            "wkv_a": ((d, r + m["qk_rope_head_dim"]), "dense", None),
            "kv_norm": ((r,), "norm", None),
            "wkv_b": ((r, H * (m["qk_nope_head_dim"] + m["v_head_dim"])),
                      "dense", None),
            "wo": ((H * m["v_head_dim"], d), "dense", None),
            "mlp_norm": ((d,), "norm", None)}


def leaf_specs(m: dict) -> dict:
    """name -> (shape of one layer or of the leaf, stacked, kind, scale)
    (``weights``). ``m`` holds a configuration file's ``model`` sizes."""
    d, f, fe, fs = m["d_model"], m["d_ff"], m["moe_d_ff"], m["shared_d_ff"]
    n, E = m["experts_held"], m["num_experts"]
    vp = W.padded_vocab(m["vocab"])
    moe = dict(_attn_specs(m), **{
        "router": ((d, E), "dense", None),
        "router_bias": ((E,), "dense", m["router_bias_scale"]),
        "moe_wi": ((n, d, fe), "dense", None),
        "moe_wg": ((n, d, fe), "dense", None),
        "moe_wo": ((n, fe, d), "dense", None),
        "shared_wi": ((d, fs), "dense", None),
        "shared_wg": ((d, fs), "dense", None),
        "shared_wo": ((fs, d), "dense", None)})
    lead = dict(_attn_specs(m), **{"wi": ((d, f), "dense", None),
                                   "wg": ((d, f), "dense", None),
                                   "wo_mlp": ((f, d), "dense", None)})
    nd = m["dense_layers"]
    specs = {f"layers/{k}": (shape, True, kind, scale)
             for k, (shape, kind, scale) in moe.items()}
    specs.update({DENSE + k: ((nd, *shape), False, kind, scale)
                  for k, (shape, kind, scale) in lead.items()})
    specs.update({"embed": ((vp, d), False, "dense", m["embed_scale"]),
                  "final_norm": ((d,), False, "norm", None),
                  "lm_head": ((d, vp), False, "dense", None)})
    return specs


# -- the reference ------------------------------------------------------------


def _fp8_weights(p: dict) -> dict:
    """The control's weights: every matrix rounded per output column (over
    its input dimension, also in a stack of experts), norms and the
    router's bias kept."""
    return {k: (w if w.ndim == 1 else R.fp8(w, w.ndim - 2))
            for k, w in p.items()}


def _attention(q, k, v, scale, low):
    """Causal attention of one sequence, q/k (L, H, dqk), v (L, H, dv), in
    blocks of query rows; (L, H * dv)."""
    L, H, _ = q.shape
    if low:
        q, k, v = R.fp8(q, -1), R.fp8(k, -1), R.fp8(v, -1)
    cols = jnp.arange(L)
    outs = []
    for r0 in range(0, L, R.Q_BLOCK):
        qb = q[r0:r0 + R.Q_BLOCK] * scale
        rows = r0 + jnp.arange(qb.shape[0])
        s = jnp.einsum("qhd,shd->hqs", qb, k, precision=R.HI)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if low:
            p = R.fp8(p, -1)
        outs.append(jnp.einsum("hqs,shd->qhd", p, v, precision=R.HI))
    return jnp.concatenate(outs, 0).reshape(L, -1)


def _mla(p, x, m, low):
    """MLA of one sequence (L, d) -> (L, d)."""
    L = x.shape[0]
    H, r = m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q = R.mm(x, p["wq"], low).reshape(L, H, dn + dr)
    kva = R.mm(x, p["wkv_a"], low)
    c = R.rms(kva[:, :r], p["kv_norm"], m["norm_eps"])
    kv = R.mm(c, p["wkv_b"], low).reshape(L, H, dn + dv)
    q_pe = R.rope(q[..., dn:], m["rope_theta"])
    k_pe = R.rope(kva[:, None, r:], m["rope_theta"])
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (L, H, dr))],
                        -1)
    o = _attention(q, k, kv[..., dn:], 1.0 / np.sqrt(dn + dr), low)
    return R.mm(o, p["wo"], low)


def _swiglu(x, wi, wg, wo, low):
    return R.mm(jax.nn.silu(R.mm(x, wg, low)) * R.mm(x, wi, low), wo, low)


def _moe(p, x, m, low):
    """The held experts' routed part plus the shared experts, (L, d)."""
    s = jax.nn.sigmoid(R.mm(x, p["router"], low))
    _, top = jax.lax.top_k(s + p["router_bias"], m["experts_per_token"])
    w = jnp.take_along_axis(s, top, -1)
    w = w / w.sum(-1, keepdims=True) * m["routed_scale"]
    out = _swiglu(x, p["shared_wi"], p["shared_wg"], p["shared_wo"], low)
    for j in range(m["experts_held"]):
        gate = (w * (top == m["experts_first"] + j)).sum(-1, keepdims=True)
        out = out + gate * _swiglu(x, p["moe_wi"][j], p["moe_wg"][j],
                                   p["moe_wo"][j], low)
    return out


def _block(p, h, m, low, ffn):
    x = R.rms(h, p["attn_norm"], m["norm_eps"])
    h = h + _mla(p, x, m, low)
    return h + ffn(p, R.rms(h, p["mlp_norm"], m["norm_eps"]))


@functools.partial(jax.jit, static_argnames=("mkey", "fp8"))
def _moe_layer(h, lo, hi, layer, *, mkey, fp8):
    """One MoE layer over (n, L, d), one sequence at a time."""
    m = dict(mkey)
    p = W.layer_of(leaf_specs(m), W.root_of(lo, hi), layer, m["dtype"])
    if fp8:
        p = _fp8_weights(p)
    return jax.lax.map(
        lambda h: _block(p, h, m, fp8, lambda p, x: _moe(p, x, m, fp8)), h)


@functools.partial(jax.jit, static_argnames=("mkey", "fp8"))
def _dense_layer(h, lo, hi, layer, *, mkey, fp8):
    """Leading dense layer ``layer`` over (n, L, d)."""
    m = dict(mkey)
    specs, root = leaf_specs(m), W.root_of(lo, hi)
    p = {k[len(DENSE):]: W.top_of(specs, root, k, m["dtype"])[layer]
         for k in specs if k.startswith(DENSE)}
    if fp8:
        p = _fp8_weights(p)

    def mlp(p, x):
        return _swiglu(x, p["wi"], p["wg"], p["wo_mlp"], fp8)

    return jax.lax.map(lambda h: _block(p, h, m, fp8, mlp), h)


@functools.partial(jax.jit, static_argnames=("mkey",))
def _embed(tokens, lo, hi, *, mkey):
    m = dict(mkey)
    return W.top_of(leaf_specs(m), W.root_of(lo, hi), "embed",
                    m["dtype"])[tokens]


@functools.partial(jax.jit, static_argnames=("mkey", "fp8"))
def _head(rows, lo, hi, *, mkey, fp8):
    m = dict(mkey)
    specs, root = leaf_specs(m), W.root_of(lo, hi)
    x = R.rms(rows, W.top_of(specs, root, "final_norm", m["dtype"]),
              m["norm_eps"])
    head = W.top_of(specs, root, "lm_head", m["dtype"])
    if fp8:
        head = R.fp8(head, 0)
    return R.mm(x, head, fp8)[:, : m["vocab"]]


def logits_at(m: dict, seed, tokens, where, *, fp8=False):
    """Reference logits (len(where), vocab) float32, as a numpy array.

    ``tokens``: (n, L) int32, each row a whole sequence padded at its end
    (causal attention keeps the padding out of every real position);
    ``where``: list of (row, position) pairs to read. Each held expert is
    computed for every token and weighted where the router chose it."""
    mk = R.model_key(m)
    lo, hi = W.seed_key(seed)
    h = _embed(jnp.asarray(tokens), lo, hi, mkey=mk)
    for layer in range(m["dense_layers"]):
        h = _dense_layer(h, lo, hi, jnp.int32(layer), mkey=mk, fp8=fp8)
    for layer in range(m["num_layers"]):
        h = _moe_layer(h, lo, hi, jnp.int32(layer), mkey=mk, fp8=fp8)
    picked = R.pick(h, where)
    del h
    return jax.device_get(_head(picked, lo, hi, mkey=mk, fp8=fp8))


# -- the counts ---------------------------------------------------------------


def attn_params(m: dict) -> int:
    """One layer's attention matrices (each used once per token, in the
    expanded and in the absorbed form alike)."""
    d, H, r = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def expert_params(m: dict) -> int:
    return 3 * m["d_model"] * m["moe_d_ff"]


def held_per_token(m: dict) -> float:
    """Held experts one token reaches, in expectation under uniform
    routing: ``k n / E``."""
    return (m["experts_per_token"] * m["experts_held"]
            / m["num_experts"])


def held_reached(m: dict, rows: int) -> float:
    """Held experts that ``rows`` tokens reach together, in expectation
    under uniform routing: ``n (1 - (1 - k / E)^rows)``."""
    miss = 1.0 - m["experts_per_token"] / m["num_experts"]
    return m["experts_held"] * (1.0 - miss ** rows)


def _moe_fixed(m: dict) -> int:
    """An MoE layer's matrices every token uses: attention, router, shared
    experts."""
    d = m["d_model"]
    return attn_params(m) + d * m["num_experts"] + 3 * d * m["shared_d_ff"]


def _dense_fixed(m: dict) -> int:
    return attn_params(m) + 3 * m["d_model"] * m["d_ff"]


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab"]


def token_flops(m: dict) -> float:
    """Matmul operations of one token through every layer (the routed
    experts at their expectation), without attention and the head."""
    moe = _moe_fixed(m) + held_per_token(m) * expert_params(m)
    return 2 * (m["dense_layers"] * _dense_fixed(m) + m["num_layers"] * moe)


def _layers(m: dict) -> int:
    return m["dense_layers"] + m["num_layers"]


def _norms(m: dict) -> int:
    return (2 * m["d_model"] + m["kv_lora_rank"]) * _layers(m) + m["d_model"]


def weight_bytes(m: dict) -> int:
    """Bytes of every weight a decode step may read: each layer's matrices
    and norms, the router's bias, every held expert, the final norm and
    the LM head (the embedding is read only at the batch's tokens)."""
    per_moe = (_moe_fixed(m) + m["num_experts"]
               + m["experts_held"] * expert_params(m))
    return C.itemsize(m) * (m["dense_layers"] * _dense_fixed(m)
                            + m["num_layers"] * per_moe + _norms(m)
                            + head_params(m))


def kv_bytes_per_token(m: dict) -> int:
    """The latent row of one token over all layers."""
    return (C.itemsize(m) * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            * _layers(m))


def attention_flops(m: dict, q_rows: int, context: int) -> int:
    """Expanded causal attention (prefill) of ``q_rows`` queries whose last
    one sees ``context`` keys, all layers: scores over the q/k width and
    the weighted sum over v's."""
    first = context - q_rows + 1
    pairs = (first + context) * q_rows // 2
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return 2 * _layers(m) * m["num_heads"] * (qk + m["v_head_dim"]) * pairs


def latent_attention_cost(m: dict, contexts) -> tuple[int, int]:
    """(operations, bytes) of the absorbed latent decode attention of one
    step, all layers: each live token's latent row read once, and against
    it every head's scores over the row (latent and rotary) and weighted
    sum over the latent."""
    r, dr = m["kv_lora_rank"], m["qk_rope_head_dim"]
    live = sum(contexts)
    flops = 2 * m["num_heads"] * (r + dr + r) * live * _layers(m)
    return flops, kv_bytes_per_token(m) * live


def prefill_flops(m: dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens: every layer at every position, the LM
    head at the last one."""
    return (token_flops(m) * prompt + 2 * head_params(m)
            + attention_flops(m, prompt, prompt))


def decode_flops(m: dict, contexts, keys=None) -> float:
    """One decode step over live sequences whose new token sees
    ``contexts`` latent rows each (itself included). One chip only:
    ``keys`` must be None."""
    assert keys is None, "the latent pool is served on one chip"
    per_token = token_flops(m) + 2 * head_params(m)
    return (per_token * len(contexts)
            + latent_attention_cost(m, contexts)[0])


def decode_bytes(m: dict, contexts, keys=None) -> float:
    """One decode step: every weight but the held experts once, the held
    experts the batch reaches (in expectation), each live token's latent
    row read once and the new rows written."""
    assert keys is None, "the latent pool is served on one chip"
    unreached = (m["experts_held"] - held_reached(m, len(contexts)))
    skipped = C.itemsize(m) * m["num_layers"] * unreached * expert_params(m)
    kv = kv_bytes_per_token(m)
    return (weight_bytes(m) - skipped
            + kv * sum(c - 1 for c in contexts) + kv * len(contexts))


def flash_attention_cost(m: dict, prompt: int) -> tuple[int, int]:
    """(operations, bytes) of the prefill attention kernel over one
    ``prompt``-token sequence, all layers: q and k (qk wide), v and o (v
    wide) read or written once, causal scores and values."""
    H = m["num_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    io = (C.itemsize(m) * prompt * H * (2 * qk + 2 * m["v_head_dim"])
          * _layers(m))
    return attention_flops(m, prompt, prompt), io
