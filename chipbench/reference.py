"""Plain float32 reference of the served model, and its fp8 control.

Imports nothing of the program. The weights are drawn again from the seed
by ``weights`` (the benchmark's own generator), one layer at a time, so the
reference never holds the whole model and never reads what the engine
made. Every matmul runs at ``Precision.HIGHEST``.

The equations are the repository's decoder block as the configuration's
``departures`` list them: RMSNorm, rotary embedding over the whole head
with half-split pairing, causal softmax attention with grouped KV heads,
a plain or gated MLP, and a parallel or sequential residual.

``logits_at`` runs teacher-forced over whole sequences (prompt and served
tokens) and returns the logits at the positions asked for. With
``fp8=True`` every matmul operand is rounded to float8_e4m3fn (per row for
activations, per output column for weights) first: the control, the
reference computed one precision step below the served bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
Q_BLOCK = 512  # query rows per attention block, so long rows fit


def _fp8(x, axis):
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8):
    """x (..., k) @ w (k, n); under ``fp8`` the weight is already rounded."""
    if fp8:
        x = _fp8(x, -1)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (L, heads, hd) at positions 0..L-1; half-split pairing over the
    whole head."""
    L, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs  # (L, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, fp8):
    """Causal attention of one sequence, q (L, H, hd), k/v (L, K, hd), in
    blocks of query rows."""
    L, H, hd = q.shape
    K = k.shape[1]
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    q = q.reshape(L, K, H // K, hd) / np.sqrt(hd)
    cols = jnp.arange(L)
    outs = []
    for r0 in range(0, L, Q_BLOCK):
        qb = q[r0:r0 + Q_BLOCK]
        rows = r0 + jnp.arange(qb.shape[0])
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _fp8(p, -1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI))
    return jnp.concatenate(outs, 0).reshape(L, H * hd)


def _act(name, x):
    if name in ("gelu", "geglu"):
        return jax.nn.gelu(x)
    if name == "swiglu":
        return jax.nn.silu(x)
    raise ValueError(f"activation {name!r}")


@functools.partial(jax.jit, static_argnames=("mkey", "fp8"))
def _layer(h, lo, hi, layer, *, mkey, fp8):
    """One decoder layer over (n, L, d), one sequence at a time."""
    m = dict(mkey)
    p = W.layer_weights(m, W.root_of(lo, hi), layer)
    if fp8:
        p = {k: (w if w.ndim == 1 else _fp8(w, 0)) for k, w in p.items()}
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]

    def mlp(x):
        u = _mm(x, p["wi"], fp8)
        if m["gated"]:
            u = _act(m["activation"], _mm(x, p["wg"], fp8)) * u
        else:
            u = _act(m["activation"], u)
        return _mm(u, p["wo_mlp"], fp8)

    def one(h):  # (L, d)
        L = h.shape[0]
        x = _rms(h, p["attn_norm"], m["norm_eps"])
        q = _rope(_mm(x, p["wq"], fp8).reshape(L, H, hd), m["rope_theta"])
        k = _rope(_mm(x, p["wk"], fp8).reshape(L, K, hd), m["rope_theta"])
        v = _mm(x, p["wv"], fp8).reshape(L, K, hd)
        a = _mm(_attention(q, k, v, fp8), p["wo"], fp8)
        if m["parallel_block"]:
            return h + a + mlp(x)
        h = h + a
        return h + mlp(_rms(h, p["mlp_norm"], m["norm_eps"]))

    return jax.lax.map(one, h)


@functools.partial(jax.jit, static_argnames=("mkey",))
def _embed(tokens, lo, hi, *, mkey):
    m = dict(mkey)
    return W.top_weight(m, W.root_of(lo, hi), "embed")[tokens]


@functools.partial(jax.jit, static_argnames=("mkey", "fp8"))
def _head(rows, lo, hi, *, mkey, fp8):
    m = dict(mkey)
    root = W.root_of(lo, hi)
    x = _rms(rows, W.top_weight(m, root, "final_norm"), m["norm_eps"])
    if m["tie_embeddings"]:
        head = W.top_weight(m, root, "embed").T
    else:
        head = W.top_weight(m, root, "lm_head")
    if fp8:
        head = _fp8(head, 0)
    return _mm(x, head, fp8)[:, : m["vocab"]]


def model_key(m: dict) -> tuple:
    """The sizes as a hashable static argument."""
    return tuple(sorted(m.items()))


def logits_at(m: dict, seed, tokens: np.ndarray, where, *, fp8=False):
    """Reference logits (len(where), vocab) float32.

    ``tokens``: (n, L) int32, each row a whole sequence padded at its end
    (causal attention keeps the padding out of every real position);
    ``where``: list of (row, position) pairs to read."""
    mk = model_key(m)
    lo, hi = W.seed_key(seed)
    h = _embed(jnp.asarray(tokens), lo, hi, mkey=mk)
    for layer in range(m["num_layers"]):
        h = _layer(h, lo, hi, jnp.int32(layer), mkey=mk, fp8=fp8)
    rows = np.asarray([r for r, _ in where], np.int32)
    cols = np.asarray([c for _, c in where], np.int32)
    picked = h[rows, cols]
    del h
    return np.asarray(_head(picked, lo, hi, mkey=mk, fp8=fp8))


def pack(seqs, length: int):
    """Whole sequences (prompt + served tokens) into an (n, length) array.
    Rows past the number of sequences stay zero; they are never read."""
    out = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        out[i, : len(s)] = s
    return out
