"""RWKV6 ("Finch"): attention-free LM with data-dependent per-channel decay.

Time-mix uses the chunked linear-attention kernel (kernels/rwkv6.py); the
recurrence S_t = diag(w_t) S_{t-1} + k_t v_t^T with w_t produced by a low-rank
data-dependent projection is the Finch contribution. Token-shift mixing uses
static interpolation factors (the full ddlerp LoRA is simplified; noted in
DESIGN.md). Channel-mix is the squared-ReLU RWKV FFN.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import layers as L
from repro.parallel.sharding import constrain

LORA_RANK = 64


def _num_heads(cfg) -> int:
    return cfg.d_model // cfg.resolved_head_dim()


def init_params(cfg, rng):
    kg = L.KeyGen(rng)
    dtype = jnp.dtype(cfg.dtype)
    d, f, nl = cfg.d_model, cfg.d_ff, cfg.num_layers
    N = cfg.resolved_head_dim()
    H = _num_heads(cfg)
    vp = L.padded_vocab(cfg.vocab_size)

    decay_bias = jnp.tile(
        jnp.linspace(-5.0, -0.5, d, dtype=jnp.float32)[None, :], (nl, 1)
    )
    layers = {
        "tm_norm": jnp.ones((nl, d), dtype),
        "cm_norm": jnp.ones((nl, d), dtype),
        "mu_r": jnp.full((nl, d), 0.5, dtype),
        "mu_k": jnp.full((nl, d), 0.5, dtype),
        "mu_v": jnp.full((nl, d), 0.5, dtype),
        "mu_g": jnp.full((nl, d), 0.5, dtype),
        "mu_w": jnp.full((nl, d), 0.5, dtype),
        "mu_ck": jnp.full((nl, d), 0.5, dtype),
        "mu_cr": jnp.full((nl, d), 0.5, dtype),
        "wr_t": L.dense_init(kg(), (nl, d, d), dtype=dtype),
        "wk_t": L.dense_init(kg(), (nl, d, d), dtype=dtype),
        "wv_t": L.dense_init(kg(), (nl, d, d), dtype=dtype),
        "wg_t": L.dense_init(kg(), (nl, d, d), dtype=dtype),
        "wo_t": L.dense_init(kg(), (nl, d, d), dtype=dtype),
        "w0": decay_bias,  # fp32: decay dynamics are sensitive
        "w_lora_a": L.dense_init(kg(), (nl, d, LORA_RANK), scale=0.01, dtype=dtype),
        "w_lora_b": L.dense_init(
            kg(), (nl, LORA_RANK, d), scale=0.01, dtype=dtype
        ),
        "u": L.dense_init(kg(), (nl, H, N), scale=0.5, dtype=jnp.float32),
        "ln_x": jnp.ones((nl, d), dtype),
        "wk_c": L.dense_init(kg(), (nl, d, f), dtype=dtype),
        "wv_c": L.dense_init(kg(), (nl, f, d), scale=1.0 / math.sqrt(f), dtype=dtype),
        "wr_c": L.dense_init(kg(), (nl, d, d), dtype=dtype),
    }
    params = {
        "embed": L.dense_init(kg(), (vp, d), scale=0.02, dtype=dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": L.dense_init(kg(), (d, vp), dtype=dtype),
    }
    return params


def _shift(x, cfg=None):  # (B, S, d): x_prev[t] = x[t-1]; zero at seq start
    """Token shift. With halo_shift and a seq-sharded residual, exchange ONLY
    the boundary column over `model` (ppermute; absent sources yield the
    zero column) instead of letting GSPMD permute full tensors — the fix for
    the 241 GB/step collective-permutes measured on hymba/rwkv (§Perf)."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import current_mesh, dp_axes

    mesh = current_mesh() if cfg is not None and cfg.halo_shift else None
    if (
        mesh is not None
        and cfg.seq_shard_activations
        and x.shape[1] % mesh.shape["model"] == 0
    ):
        n = mesh.shape["model"]
        dp = dp_axes(mesh)

        def local(xl):  # (B, S/n, d) on each model rank
            last = xl[:, -1:, :]
            prev = jax.lax.ppermute(
                last, "model", [(i, i + 1) for i in range(n - 1)]
            )  # rank 0 receives zeros == sequence start
            return jnp.concatenate([prev, xl[:, :-1, :]], axis=1)

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=P(dp, "model", None), out_specs=P(dp, "model", None),
            check_vma=False,
        )(x)
    return jnp.pad(x[:, :-1], ((0, 0), (1, 0), (0, 0)))


def _heads(x, H, N):  # (B, S, H*N) -> (B, H, S, N)
    B, S, _ = x.shape
    return x.reshape(B, S, H, N).transpose(0, 2, 1, 3)


def _unheads(x):  # (B, H, S, N) -> (B, S, H*N)
    B, H, S, N = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * N)


def _decay_log(p, mixed_w):
    """w_log = -exp(w0 + tanh(x A) B), the Finch data-dependent decay."""
    lora = jnp.einsum(
        "bsr,rd->bsd",
        jnp.tanh(
            jnp.einsum("bsd,dr->bsr", mixed_w, p["w_lora_a"],
                       preferred_element_type=jnp.float32)
        ),
        p["w_lora_b"],
        preferred_element_type=jnp.float32,
    )
    return -jnp.exp(p["w0"] + lora)


def time_mix(p, cfg, x, x_prev, state=None):
    """x: (B,S,d). state: (B,H,N,N) incoming wkv state (None => zeros).
    Returns (out, final_state)."""
    N = cfg.resolved_head_dim()
    H = _num_heads(cfg)

    def mix(mu):
        return x * mu + x_prev * (1.0 - mu)

    r = mix(p["mu_r"]) @ p["wr_t"]
    k = mix(p["mu_k"]) @ p["wk_t"]
    v = mix(p["mu_v"]) @ p["wv_t"]
    g = mix(p["mu_g"]) @ p["wg_t"]
    w_log = _decay_log(p, mix(p["mu_w"]))

    o, S = ops.linear_attention(
        _heads(r, H, N), _heads(k, H, N), _heads(v, H, N),
        _heads(w_log, H, N), p["u"], s0=state,
    )
    o = _unheads(o)
    # per-head group norm + learned scale
    B_, S_, _ = o.shape
    o = L.rms_norm(o.reshape(B_, S_, H, N), jnp.ones((N,), o.dtype), cfg.norm_eps)
    o = (o.reshape(B_, S_, H * N) * p["ln_x"]).astype(x.dtype)
    o = o * jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
    return o @ p["wo_t"], S


def channel_mix(p, cfg, x, x_prev):
    def mix(mu):
        return x * mu + x_prev * (1.0 - mu)

    kk = jnp.square(
        jax.nn.relu(
            jnp.einsum("bsd,df->bsf", mix(p["mu_ck"]), p["wk_c"],
                       preferred_element_type=jnp.float32)
        )
    ).astype(x.dtype)
    out = jnp.einsum("bsf,fd->bsd", kk, p["wv_c"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    rr = jax.nn.sigmoid(
        jnp.einsum("bsd,de->bse", mix(p["mu_cr"]), p["wr_c"],
                   preferred_element_type=jnp.float32)
    ).astype(x.dtype)
    return rr * out


def block(p, cfg, h):
    x = L.rms_norm(h, p["tm_norm"], cfg.norm_eps)
    o, _ = time_mix(p, cfg, x, _shift(x, cfg))
    h = h + o
    x = L.rms_norm(h, p["cm_norm"], cfg.norm_eps)
    h = h + channel_mix(p, cfg, x, _shift(x, cfg))
    return constrain(h, "residual")


def forward(params, cfg, batch, *, q_offset=0):
    from repro.models import transformer as T

    h = jnp.take(params["embed"], batch["tokens"], axis=0)
    h = constrain(h, "residual")
    blk = T.remat_wrap(cfg, functools.partial(block, cfg=cfg))

    def body(h, lp):
        return blk(lp, h=h), None

    h, _ = jax.lax.scan(body, h, params["layers"], unroll=cfg.scan_unroll)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,dv->bsv", h, params["lm_head"], preferred_element_type=jnp.float32
    ).astype(h.dtype)
    return constrain(logits, "logits"), jnp.float32(0.0)


def loss_fn(params, cfg, batch, *, q_offset=0):
    logits, aux = forward(params, cfg, batch, q_offset=q_offset)
    return L.cross_entropy_loss(logits, batch["labels"], cfg.vocab_size) + aux


# ---------------------------------------------------------------------------
# decode: constant-size state (B,H,N,N) + two token-shift states
# ---------------------------------------------------------------------------


def cache_spec(cfg, batch: int, max_len: int):
    del max_len  # constant-size state: the point of the ssm family
    d = cfg.d_model
    N = cfg.resolved_head_dim()
    H = _num_heads(cfg)
    return {
        "ssm_state": jax.ShapeDtypeStruct((cfg.num_layers, batch, H, N, N),
                                          jnp.float32),
        "ts_time": jax.ShapeDtypeStruct((cfg.num_layers, batch, d),
                                        jnp.dtype(cfg.dtype)),
        "ts_chan": jax.ShapeDtypeStruct((cfg.num_layers, batch, d),
                                        jnp.dtype(cfg.dtype)),
    }


def init_cache(cfg, batch: int, max_len: int):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_spec(cfg, batch, max_len)
    )


def decode_step(params, cfg, cache, batch):
    tokens = batch["token"]
    N = cfg.resolved_head_dim()
    H = _num_heads(cfg)
    h = jnp.take(params["embed"], tokens, axis=0)  # (B, d)

    def body(h, xs):
        lp, S, ts1, ts2 = xs
        x = L.rms_norm(h, lp["tm_norm"], cfg.norm_eps)
        def mix(mu, xp):
            return x * mu + xp * (1.0 - mu)

        r = mix(lp["mu_r"], ts1) @ lp["wr_t"]
        k = mix(lp["mu_k"], ts1) @ lp["wk_t"]
        v = mix(lp["mu_v"], ts1) @ lp["wv_t"]
        g = mix(lp["mu_g"], ts1) @ lp["wg_t"]
        wl = -jnp.exp(
            lp["w0"]
            + jnp.tanh(mix(lp["mu_w"], ts1) @ lp["w_lora_a"]) @ lp["w_lora_b"]
        )
        def hv(t):
            return t.reshape(-1, H, N)

        o, S = ops.linear_attention_step(
            hv(r), hv(k), hv(v), hv(wl), lp["u"], S
        )
        o = L.rms_norm(o, jnp.ones((N,), o.dtype), cfg.norm_eps)
        o = (o.reshape(-1, H * N) * lp["ln_x"]).astype(h.dtype)
        o = o * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
        h = h + o @ lp["wo_t"]
        ts1_new = x
        x2 = L.rms_norm(h, lp["cm_norm"], cfg.norm_eps)
        def mix2(mu):
            return x2 * mu + ts2 * (1.0 - mu)

        kk = jnp.square(jax.nn.relu(mix2(lp["mu_ck"]) @ lp["wk_c"])).astype(h.dtype)
        out = kk @ lp["wv_c"]
        rr = jax.nn.sigmoid(mix2(lp["mu_cr"]) @ lp["wr_c"]).astype(h.dtype)
        h = h + rr * out
        return h, (S, ts1_new, x2)

    h, (S, ts1, ts2) = jax.lax.scan(
        body, h, (params["layers"], cache["ssm_state"], cache["ts_time"],
                  cache["ts_chan"]),
        unroll=cfg.scan_unroll,
    )
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bd,dv->bv", h, params["lm_head"], preferred_element_type=jnp.float32
    )
    return logits, {"ssm_state": S, "ts_time": ts1, "ts_chan": ts2}
