"""Decoder-only transformer LM: dense, MoE, and VLM families.

One config-driven scaffold covers GQA/MQA, qk-norm, QKV biases, gated/plain
MLPs, parallel-residual blocks, MoE layers, latent attention (MLA), and
multimodal prefix embeddings. Layers are stacked on a leading axis and
executed with lax.scan (+ remat), which keeps compiled HLO size O(1) in
depth — essential for the 512-device dry-run compiles.

Leading dense layers (``cfg.first_dense_layers``, DeepSeek-V3's
``first_k_dense_replace``) are top-level leaves named ``dense_<leaf>``, each
stacked over those layers, and run by a scan of their own before the scan
over ``params["layers"]``.

MLA (``cfg.mla``) projects each head's query whole; keys and values come
from a per-token latent ``c`` (RMS-normed) and one rotary key ``k_pe``
shared by every head. Prefill expands them per head and runs the
registered ``flash_attention`` (v narrower than q and k). Paged decode is
absorbed: it caches only ``[c, k_pe]`` per token and layer, moves each
head's query into latent space (``q_nope W_uk``), attends over the latent
rows with ``latent_decode_attention``, and maps the result back through
``W_uv`` before ``wo``; no per-head key or value is ever made.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import layers as L
from repro.models import moe as M
from repro.parallel.sharding import constrain


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _attn_params(kg, cfg, nl, dtype):
    d, H = cfg.d_model, cfg.num_heads
    if cfg.mla:
        r, dv = cfg.kv_lora_rank, cfg.v_head_dim
        return {
            "attn_norm": jnp.ones((nl, d), dtype),
            "wq": L.dense_init(kg(), (nl, d, H * cfg.qk_head_dim),
                               dtype=dtype),
            "wkv_a": L.dense_init(kg(), (nl, d, cfg.latent_dim), dtype=dtype),
            "kv_norm": jnp.ones((nl, r), dtype),
            "wkv_b": L.dense_init(
                kg(), (nl, r, H * (cfg.qk_nope_head_dim + dv)), dtype=dtype),
            "wo": L.dense_init(kg(), (nl, H * dv, d),
                               scale=1.0 / math.sqrt(H * dv), dtype=dtype),
        }
    hd, K = cfg.resolved_head_dim(), cfg.num_kv_heads
    layers = {
        "attn_norm": jnp.ones((nl, d), dtype),
        "wq": L.dense_init(kg(), (nl, d, H * hd), dtype=dtype),
        "wk": L.dense_init(kg(), (nl, d, K * hd), dtype=dtype),
        "wv": L.dense_init(kg(), (nl, d, K * hd), dtype=dtype),
        "wo": L.dense_init(
            kg(), (nl, H * hd, d), scale=1.0 / math.sqrt(H * hd), dtype=dtype
        ),
    }
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((nl, H * hd), dtype)
        layers["bk"] = jnp.zeros((nl, K * hd), dtype)
        layers["bv"] = jnp.zeros((nl, K * hd), dtype)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((nl, hd), dtype)
        layers["k_norm"] = jnp.ones((nl, hd), dtype)
    return layers


def _mlp_params(kg, cfg, nl, f, dtype):
    d = cfg.d_model
    p = {"wi": L.dense_init(kg(), (nl, d, f), dtype=dtype)}
    if L.is_gated(cfg.activation):
        p["wg"] = L.dense_init(kg(), (nl, d, f), dtype=dtype)
    p["wo_mlp"] = L.dense_init(
        kg(), (nl, f, d), scale=1.0 / math.sqrt(f), dtype=dtype
    )
    return p


def init_params(cfg, rng):
    kg = L.KeyGen(rng)
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    nd = cfg.first_dense_layers
    nl = cfg.num_layers - nd
    vp = L.padded_vocab(cfg.vocab_size)

    layers = _attn_params(kg, cfg, nl, dtype)
    if not cfg.parallel_block:
        layers["mlp_norm"] = jnp.ones((nl, d), dtype)
    if cfg.grouped_moe:
        layers.update(M.init_grouped_moe_params(kg, cfg, nl, dtype))
    elif cfg.num_experts:
        layers.update(M.init_moe_params(kg, cfg, nl, dtype))
    else:
        layers.update(_mlp_params(kg, cfg, nl, cfg.d_ff, dtype))

    params = {
        "embed": L.dense_init(kg(), (vp, d), scale=0.02, dtype=dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(kg(), (d, vp), dtype=dtype)
    if cfg.family == "vlm":
        params["connector"] = {
            "wi": L.dense_init(kg(), (d, d), dtype=dtype),
            "wo": L.dense_init(kg(), (d, d), dtype=dtype),
        }
    if nd:
        lead = _attn_params(kg, cfg, nd, dtype)
        if not cfg.parallel_block:
            lead["mlp_norm"] = jnp.ones((nd, d), dtype)
        lead.update(_mlp_params(kg, cfg, nd, cfg.d_ff, dtype))
        params.update({DENSE + k: v for k, v in lead.items()})
    return params


DENSE = "dense_"  # prefix of the leading dense layers' top-level leaves


def dense_layers(params) -> dict:
    """The leading dense layers' leaves, stacked over those layers, under
    their names in a layer (empty when the model has none)."""
    return {k[len(DENSE):]: v for k, v in params.items()
            if k.startswith(DENSE)}


# ---------------------------------------------------------------------------
# attention (shared with the audio/hybrid families)
# ---------------------------------------------------------------------------


def attention(p, cfg, x, cos, sin, *, causal=True, window=0, q_offset=0,
              kv_input=None, kv_cos_sin=None, return_kv=False):
    """x: (B, S, d) -> (B, S, d). kv_input enables cross-attention."""
    B, S, d = x.shape
    hd = cfg.resolved_head_dim()
    H, K = cfg.num_heads, cfg.num_kv_heads
    xkv = x if kv_input is None else kv_input
    Skv = xkv.shape[1]

    q = jnp.einsum("bsd,dh->bsh", x, p["wq"], preferred_element_type=jnp.float32)
    k = jnp.einsum("bsd,dh->bsh", xkv, p["wk"], preferred_element_type=jnp.float32)
    v = jnp.einsum("bsd,dh->bsh", xkv, p["wv"], preferred_element_type=jnp.float32)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.astype(x.dtype).reshape(B, S, H, hd)
    k = k.astype(x.dtype).reshape(B, Skv, K, hd)
    v = v.astype(x.dtype).reshape(B, Skv, K, hd)
    if "q_norm" in p:
        q = L.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cos is not None and kv_input is None:  # no rope in cross-attention
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    elif cos is not None:
        q = L.apply_rope(q, cos, sin)
        if kv_cos_sin is not None:
            k = L.apply_rope(k, *kv_cos_sin)
    q = constrain(q, "attn_q")
    k = constrain(k, "attn_kv")
    v = constrain(v, "attn_kv")

    o = ops.flash_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        window=window,
        q_offset=q_offset,
    )
    o = o.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    out = jnp.einsum(
        "bsh,hd->bsd", o, p["wo"], preferred_element_type=jnp.float32
    ).astype(x.dtype)
    if return_kv:
        return out, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    return out


def attention_decode(p, cfg, x, cos, sin, k_cache, v_cache, position, *,
                     window=0, update_cache=True):
    """x: (B, d); caches (B, K, Smax, hd); position (B,) absolute index."""
    B, d = x.shape
    hd = cfg.resolved_head_dim()
    H, K = cfg.num_heads, cfg.num_kv_heads

    q = (x @ p["wq"]).astype(x.dtype)
    if update_cache:
        k = (x @ p["wk"]).astype(x.dtype)
        v = (x @ p["wv"]).astype(x.dtype)
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        k = k.reshape(B, K, hd)
        v = v.reshape(B, K, hd)
    elif "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, H, hd)
    if "q_norm" in p:
        q = L.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        if update_cache:
            k = L.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cos is not None:
        # cos/sin: (B, hd/2) from per-row positions
        q = L.apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
        if update_cache:
            k = L.apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]

    if update_cache:
        def upd(cache, new, pos):
            return jax.lax.dynamic_update_slice_in_dim(
                cache, new[:, None, :], pos, axis=1
            )

        k_cache = jax.vmap(upd)(k_cache, k, position)
        v_cache = jax.vmap(upd)(v_cache, v, position)

    o = ops.decode_attention(q, k_cache, v_cache, position, window=window)
    o = o.reshape(B, H * hd)
    o = jnp.einsum(
        "bh,hd->bd", o, p["wo"], preferred_element_type=jnp.float32
    ).astype(x.dtype)
    return o, k_cache, v_cache


# ---------------------------------------------------------------------------
# latent attention (MLA)
# ---------------------------------------------------------------------------


def _mla_project(p, cfg, x):
    """Per token: the query (..., H, qk) and the latent row ``[c, k_pe]``
    (..., latent_dim) before the rotary embedding; ``c`` is RMS-normed."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    q = jnp.einsum("...d,dh->...h", x, p["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    kva = jnp.einsum("...d,dl->...l", x, p["wkv_a"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    c = L.rms_norm(kva[..., :r], p["kv_norm"], cfg.norm_eps)
    return q.reshape(*x.shape[:-1], H, cfg.qk_head_dim), c, kva[..., r:]


def mla_attention(p, cfg, x, cos, sin, *, q_offset=0):
    """Expanded MLA over (B, S, d): keys and values per head from the
    latent, causal attention through ``flash_attention`` (v narrower than
    q and k). Returns (out (B, S, d), latent rows (B, S, latent_dim))."""
    B, S, _ = x.shape
    H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q, c, k_pe = _mla_project(p, cfg, x)
    kv = jnp.einsum("bsr,rh->bsh", c, p["wkv_b"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    kv = kv.reshape(B, S, H, dn + dv)
    q_pe = L.apply_rope(q[..., dn:], cos, sin)
    k_pe = L.apply_rope(k_pe[:, :, None, :], cos, sin)  # (B, S, 1, dr)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, H, k_pe.shape[-1]))],
        axis=-1)
    o = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        kv[..., dn:].transpose(0, 2, 1, 3), causal=True, q_offset=q_offset,
        scale=1.0 / math.sqrt(cfg.qk_head_dim),
    )
    o = o.transpose(0, 2, 1, 3).reshape(B, S, H * dv)
    out = jnp.einsum(
        "bsh,hd->bsd", o, p["wo"], preferred_element_type=jnp.float32
    ).astype(x.dtype)
    return out, jnp.concatenate([c, k_pe[:, :, 0]], axis=-1)


def mla_decode_paged(p, cfg, x, cos, sin, pool, block_table, position, *,
                     attn_fn=None):
    """One layer's absorbed MLA decode against a latent page pool.

    ``pool``: (P, 1, bs, width) pages of ``[c, k_pe]`` rows, zero-padded to
    the pool's ``width`` (``serving.paged_cache.LANES``). The new token's
    row is written at page ``block_table[b, pos // bs]``, row ``pos % bs``;
    each head's query moves into latent space (``q_nope W_uk``) beside its
    rotary part, padded alike, attends over the latent rows
    (``attn_fn``, by default the registered ``latent_decode_attention``),
    and the latent output goes back through ``W_uv`` and ``wo``."""
    with jax.named_scope("mla_decode"):
        B, _ = x.shape
        H, r = cfg.num_heads, cfg.kv_lora_rank
        dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
        bs = pool.shape[2]
        q, c, k_pe = _mla_project(p, cfg, x)
        q_pe = L.apply_rope(q[:, None, :, dn:], cos[:, None],
                            sin[:, None])[:, 0]
        k_pe = L.apply_rope(k_pe[:, None, None], cos[:, None],
                            sin[:, None])[:, 0, 0]
        phys = jnp.take_along_axis(block_table, (position // bs)[:, None],
                                   axis=1)[:, 0]
        pad = ((0, 0), (0, pool.shape[-1] - cfg.latent_dim))
        row = jnp.pad(jnp.concatenate([c, k_pe], axis=-1), pad)
        row = row.astype(pool.dtype)
        pool = pool.at[phys, 0, position % bs].set(row)
        wkv_b = p["wkv_b"].reshape(r, H, dn + dv)
        q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :dn], wkv_b[..., :dn],
                           preferred_element_type=jnp.float32)
        q = jnp.pad(jnp.concatenate([q_lat.astype(x.dtype), q_pe], axis=-1),
                    ((0, 0),) + pad)
        attn = attn_fn or ops.latent_decode_attention
        o = attn(q, pool, position, block_table, v_width=r,
                 scale=1.0 / math.sqrt(cfg.qk_head_dim))
        o = jnp.einsum("bhr,rhv->bhv", o, wkv_b[..., dn:],
                       preferred_element_type=jnp.float32).astype(x.dtype)
        out = jnp.einsum("bh,hd->bd", o.reshape(B, H * dv), p["wo"],
                         preferred_element_type=jnp.float32).astype(x.dtype)
    return out, pool


def _rope_dim(cfg) -> int:
    return cfg.qk_rope_head_dim if cfg.mla else cfg.resolved_head_dim()


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _mlp_p(p):
    q = {"wi": p["wi"], "wo": p["wo_mlp"]}
    if "wg" in p:
        q["wg"] = p["wg"]
    return q


def _ffn(p, cfg, x, rows=None):
    """The layer's MLP over (B, S, d): (out, aux loss, rows per held
    expert). The last is None except under a grouped MoE, whose ``rows``
    (B, S) mask leaves tokens out of the routed experts."""
    if "moe_wi" in p and cfg.grouped_moe:
        B, S, d = x.shape
        out, sizes = M.moe_grouped(
            p, x.reshape(B * S, d), cfg,
            None if rows is None else rows.reshape(B * S))
        return out.reshape(B, S, d), 0.0, sizes
    if "moe_wi" in p:
        return (*M.moe_mlp(p, x, cfg), None)
    return L.mlp(_mlp_p(p), x, cfg.activation), 0.0, None


def _attn(p, cfg, x, cos, sin, *, window=0, q_offset=0, return_kv=False):
    """Self-attention of either kind; with ``return_kv``, also what the
    cache keeps: (k, v) per head, or MLA's latent rows."""
    if cfg.mla:
        out, latent = mla_attention(p, cfg, x, cos, sin, q_offset=q_offset)
        return (out, latent) if return_kv else out
    return attention(p, cfg, x, cos, sin, window=window, q_offset=q_offset,
                     return_kv=return_kv)


def block(p, cfg, h, cos, sin, *, window=0, q_offset=0):
    if cfg.parallel_block:
        n = L.rms_norm(h, p["attn_norm"], cfg.norm_eps)
        a = _attn(p, cfg, n, cos, sin, window=window, q_offset=q_offset)
        m, aux, _ = _ffn(p, cfg, n)
        h = h + a + m
    else:
        n = L.rms_norm(h, p["attn_norm"], cfg.norm_eps)
        h = h + _attn(p, cfg, n, cos, sin, window=window, q_offset=q_offset)
        n = L.rms_norm(h, p["mlp_norm"], cfg.norm_eps)
        m, aux, _ = _ffn(p, cfg, n)
        h = h + m
    return constrain(h, "residual"), aux


def remat_wrap(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.gather_save_policy:
        # save cross-device-gathered tensors so the backward pass does not
        # re-issue the TP/FSDP all-gathers (collective bytes vs memory trade)
        policy = jax.checkpoint_policies.save_only_these_names("gathered")
    elif cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = None
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def embed_inputs(params, cfg, batch):
    """Token (+ multimodal prefix) embedding. Returns (h, label_offset)."""
    tokens = batch["tokens"]
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family == "vlm":
        patches = batch["patches"].astype(h.dtype)  # (B, P, d) from the stub
        c = params["connector"]
        pe = jnp.einsum("bpd,de->bpe", jax.nn.gelu(patches @ c["wi"]), c["wo"])
        h = jnp.concatenate([pe.astype(h.dtype), h], axis=1)
    return constrain(h, "residual")


def forward(params, cfg, batch, *, q_offset=0):
    """-> (logits (B, S_total, V_pad), aux_loss)."""
    h = embed_inputs(params, cfg, batch)
    S = h.shape[1]
    positions = jnp.arange(S) + q_offset
    cos, sin = (
        L.rope_cos_sin(positions, _rope_dim(cfg), cfg.rope_theta)
        if cfg.rope_theta
        else (None, None)
    )

    blk = remat_wrap(
        cfg,
        functools.partial(
            block, cfg=cfg, window=cfg.sliding_window, q_offset=q_offset
        ),
    )

    def body(carry, lp):
        h, aux = carry
        h, a = blk(lp, h=h, cos=cos, sin=sin)
        return (h, aux + a), None

    carry = (h, jnp.float32(0.0))
    if cfg.first_dense_layers:
        carry, _ = jax.lax.scan(body, carry, dense_layers(params))
    (h, aux), _ = jax.lax.scan(body, carry, params["layers"],
                               unroll=cfg.scan_unroll)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum(
        "bsd,dv->bsv", h, head, preferred_element_type=jnp.float32
    ).astype(h.dtype)
    return constrain(logits, "logits"), aux


def loss_fn(params, cfg, batch, *, q_offset=0):
    logits, aux = forward(params, cfg, batch, q_offset=q_offset)
    return L.cross_entropy_loss(logits, batch["labels"], cfg.vocab_size) + aux


def prefill_step(params, cfg, batch, max_len: int, *, stats=False):
    """Process a full prompt, returning (logits, cache) for decode to extend.

    The cache is ``{"k", "v"}`` (nl, B, K, max_len, hd) per head, or under
    MLA ``{"latent"}`` (nl, B, max_len, latent_dim) for the scanned layers;
    ``lead`` holds the same for the leading dense layers. ``batch`` may
    carry ``length``, the prompt's real tokens (the rest is padding): under
    a grouped MoE the padding then reaches no routed expert, and with
    ``stats`` a third value ``{"held_rows"}`` counts the (layer, token,
    choice) rows that the held experts computed."""
    h = embed_inputs(params, cfg, batch)
    B, S = h.shape[:2]
    positions = jnp.arange(S)
    cos, sin = (
        L.rope_cos_sin(positions, _rope_dim(cfg), cfg.rope_theta)
        if cfg.rope_theta
        else (None, None)
    )
    rows = None
    if "length" in batch:
        rows = jnp.broadcast_to(positions[None, :] < batch["length"], (B, S))

    def blk(lp, h):
        if cfg.parallel_block:
            n = L.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            a, kv = _attn(lp, cfg, n, cos, sin,
                          window=cfg.sliding_window, return_kv=True)
            m, _, sizes = _ffn(lp, cfg, n, rows)
            h = h + a + m
        else:
            n = L.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            a, kv = _attn(lp, cfg, n, cos, sin,
                          window=cfg.sliding_window, return_kv=True)
            h = h + a
            n = L.rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
            m, _, sizes = _ffn(lp, cfg, n, rows)
            h = h + m
        held = None if sizes is None else sizes.sum()
        return constrain(h, "residual"), (kv, held)

    def body(h, lp):
        h, kv = blk(lp, h)
        return h, kv

    cache = {}
    if cfg.first_dense_layers:
        h, (cache["lead"], _) = jax.lax.scan(body, h, dense_layers(params))
    h, (kv, held) = jax.lax.scan(body, h, params["layers"],
                                 unroll=cfg.scan_unroll)
    pad = max_len - S
    if cfg.mla:
        cache["latent"] = kv
        if pad > 0:
            cache = {k: jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
                     for k, v in cache.items()}
    else:
        ks, vs = kv
        if pad > 0:
            ks = jnp.pad(ks, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
            vs = jnp.pad(vs, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        cache.update(k=ks, v=vs)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum(
        "bsd,dv->bsv", h, head, preferred_element_type=jnp.float32
    ).astype(h.dtype)
    if stats:
        return logits, cache, {"held_rows": jnp.int32(0) if held is None
                               else held.sum()}
    return logits, cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def cache_spec(cfg, batch: int, max_len: int):
    if cfg.mla or cfg.first_dense_layers:
        raise NotImplementedError(
            f"{cfg.name}: latent attention and leading dense layers are "
            "decoded from the paged latent pool only (decode_step_paged)")
    hd = cfg.resolved_head_dim()
    K, nl = cfg.num_kv_heads, cfg.num_layers
    dt = jnp.dtype(cfg.dtype)
    kv = jax.ShapeDtypeStruct((nl, batch, K, max_len, hd), dt)
    return {"k": kv, "v": kv}


def init_cache(cfg, batch: int, max_len: int):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_spec(cfg, batch, max_len)
    )


def decode_step(params, cfg, cache, batch):
    """batch: {"token": (B,), "position": (B,)} -> (logits (B, V_pad), cache)."""
    tokens, position = batch["token"], batch["position"]
    h = jnp.take(params["embed"], tokens, axis=0)  # (B, d)
    hd = cfg.resolved_head_dim()
    cos, sin = (
        L.rope_cos_sin(position, hd, cfg.rope_theta)
        if cfg.rope_theta
        else (None, None)
    )

    def body(h, xs):
        lp, kc, vc = xs
        n = L.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        a, kc, vc = attention_decode(
            lp, cfg, n, cos, sin, kc, vc, position, window=cfg.sliding_window
        )
        if cfg.parallel_block:
            m, _ = _ffn_decode(lp, cfg, n)
            h = h + a + m
        else:
            h = h + a
            n = L.rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
            m, _ = _ffn_decode(lp, cfg, n)
            h = h + m
        return h, (kc, vc)

    h, (ks, vs) = jax.lax.scan(
        body, h, (params["layers"], cache["k"], cache["v"]),
        unroll=cfg.scan_unroll,
    )
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum(
        "bd,dv->bv", h, head, preferred_element_type=jnp.float32
    )
    return logits, {"k": ks, "v": vs}


def _ffn_decode(p, cfg, x):
    if "moe_wi" in p and cfg.grouped_moe:
        return M.moe_grouped(p, x, cfg)[0], 0.0
    if "moe_wi" in p:
        return M.moe_mlp_decode(p, x, cfg)
    out = L.mlp(_mlp_p(p), x[:, None, :], cfg.activation)[:, 0]
    return out, 0.0


# ---------------------------------------------------------------------------
# paged decode (serving engine: block-table KV cache)
# ---------------------------------------------------------------------------


def attention_decode_paged(p, cfg, x, cos, sin, k_pool, v_pool, k_scale,
                           v_scale, block_table, position, *, window=0,
                           policy=None, attn_fn=None):
    """One layer's decode against a paged KV pool.

    ``k_pool``/``v_pool``: (P, K, bs, hd) physical pages (+ per-row fp32
    scales when ``policy`` holds the cache narrow); ``block_table``:
    (B, NB) int32 pool slots per sequence; ``position``: (B,). The new
    token's K/V is written into page ``block_table[b, pos // bs]`` at row
    ``pos % bs`` (quantized per row under ``policy`` — the same
    quantization ``precision.quantize_kv_cache`` applies), then attention
    runs through the registered paged ``decode_attention`` — or through
    ``attn_fn(q, k_pool, v_pool, k_scale, v_scale, block_table, position,
    window)`` when the serving layer injects a distribution (ring decode).
    Every row writes every step: inactive slots point at the shared
    scratch page, which live prefixes never reference."""
    B, d = x.shape
    hd = cfg.resolved_head_dim()
    H, K = cfg.num_heads, cfg.num_kv_heads
    bs = k_pool.shape[2]

    q = (x @ p["wq"]).astype(x.dtype)
    k = (x @ p["wk"]).astype(x.dtype)
    v = (x @ p["wv"]).astype(x.dtype)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, H, hd)
    k = k.reshape(B, K, hd)
    v = v.reshape(B, K, hd)
    if "q_norm" in p:
        q = L.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cos is not None:
        q = L.apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
        k = L.apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]

    phys = jnp.take_along_axis(block_table, (position // bs)[:, None],
                               axis=1)[:, 0]
    offset = position % bs
    heads = jnp.arange(K)[None, :]
    def at(pool):
        return pool.at[phys[:, None], heads, offset[:, None]]

    if policy is not None:
        from repro.core import precision as prec

        kq, ks, vq, vs = prec.quantize_kv_cache(k, v, policy)
        k_pool = at(k_pool).set(kq.astype(k_pool.dtype))
        v_pool = at(v_pool).set(vq.astype(v_pool.dtype))
        k_scale = at(k_scale).set(ks)
        v_scale = at(v_scale).set(vs)
    else:
        k_pool = at(k_pool).set(k.astype(k_pool.dtype))
        v_pool = at(v_pool).set(v.astype(v_pool.dtype))

    if attn_fn is None:
        o = ops.decode_attention(
            q, k_pool, v_pool, position, paged=True, block_table=block_table,
            k_scale=k_scale, v_scale=v_scale, window=window,
        )
    else:
        o = attn_fn(q, k_pool, v_pool, k_scale, v_scale, block_table,
                    position, window)
    o = o.reshape(B, H * hd)
    o = jnp.einsum(
        "bh,hd->bd", o, p["wo"], preferred_element_type=jnp.float32
    ).astype(x.dtype)
    return o, k_pool, v_pool, k_scale, v_scale


def decode_step_paged(params, cfg, cache, batch, *, attn_fn=None,
                      stats=False):
    """Paged twin of ``decode_step``: batch additionally carries the
    (B, NB) int32 ``block_table``; ``cache`` is a
    ``serving.paged_cache.PagedKVCache`` (duck-typed — only its pools,
    scales, and static policy are touched, so this module stays below the
    serving layer). Returns (logits (B, V_pad), updated cache); under MLA
    see ``latent_decode_step``, which ``stats`` asks for its counter."""
    import dataclasses as _dc

    if cfg.mla:
        return latent_decode_step(params, cfg, cache, batch, attn_fn=attn_fn,
                                  stats=stats)

    tokens, position = batch["token"], batch["position"]
    block_table = batch["block_table"]
    h = jnp.take(params["embed"], tokens, axis=0)
    hd = cfg.resolved_head_dim()
    cos, sin = (
        L.rope_cos_sin(position, hd, cfg.rope_theta)
        if cfg.rope_theta
        else (None, None)
    )

    def body(h, xs):
        lp, kp, vp, ks, vs = xs
        n = L.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        a, kp, vp, ks, vs = attention_decode_paged(
            lp, cfg, n, cos, sin, kp, vp, ks, vs, block_table, position,
            window=cfg.sliding_window, policy=cache.policy, attn_fn=attn_fn,
        )
        if cfg.parallel_block:
            m, _ = _ffn_decode(lp, cfg, n)
            h = h + a + m
        else:
            h = h + a
            n = L.rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
            m, _ = _ffn_decode(lp, cfg, n)
            h = h + m
        return h, (kp, vp, ks, vs)

    h, (kp, vp, ks, vs) = jax.lax.scan(
        body, h,
        (params["layers"], cache.k_pool, cache.v_pool,
         cache.k_scale, cache.v_scale),
        unroll=cfg.scan_unroll,
    )
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum(
        "bd,dv->bv", h, head, preferred_element_type=jnp.float32
    )
    cache = _dc.replace(cache, k_pool=kp, v_pool=vp, k_scale=ks, v_scale=vs)
    return logits, cache


def latent_decode_step(params, cfg, cache, batch, *, attn_fn=None,
                       stats=False):
    """``decode_step_paged`` under MLA: ``cache.k_pool`` holds the latent
    rows of the scanned layers and ``cache.lead_pool`` those of the leading
    dense layers, each (n, P, 1, bs, width), and there is no V pool.
    A row whose first table column is the scratch page (``NULL_BLOCK``,
    page 0) is an empty slot and reaches no routed expert. With ``stats``
    a third value ``{"experts_hit"}`` counts the (layer, held expert) pairs
    that computed at least one row this step."""
    import dataclasses as _dc

    tokens, position = batch["token"], batch["position"]
    block_table = batch["block_table"]
    live = block_table[:, 0] != 0
    h = jnp.take(params["embed"], tokens, axis=0)
    cos, sin = L.rope_cos_sin(position, _rope_dim(cfg), cfg.rope_theta)

    def body(h, xs):
        lp, pool = xs
        n = L.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        a, pool = mla_decode_paged(lp, cfg, n, cos, sin, pool, block_table,
                                   position, attn_fn=attn_fn)
        h = h + a
        n = L.rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
        m, _, sizes = _ffn(lp, cfg, n[:, None], live[:, None])
        hit = None if sizes is None else jnp.count_nonzero(sizes)
        return h + m[:, 0], (pool, hit)

    lead = cache.lead_pool
    if cfg.first_dense_layers:
        h, (lead, _) = jax.lax.scan(body, h, (dense_layers(params), lead))
    h, (pool, hit) = jax.lax.scan(body, h, (params["layers"], cache.k_pool),
                                  unroll=cfg.scan_unroll)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum(
        "bd,dv->bv", h, head, preferred_element_type=jnp.float32
    )
    cache = _dc.replace(cache, k_pool=pool, lead_pool=lead)
    if stats:
        return logits, cache, {"experts_hit": jnp.int32(0) if hit is None
                               else hit.sum().astype(jnp.int32)}
    return logits, cache
