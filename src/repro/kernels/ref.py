"""Pure-jnp oracles for every kernel in this package.

These are the *naive, obviously-correct* implementations used as ground truth
by the test suite. Lowering-representative blocked implementations (same
algorithm the Pallas kernels use, expressed in jnp so they lower on any
backend) live in ops.py; the TPU kernels live in the sibling modules.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Dense GEMM (paper Fig. 9a / Fig. 10): multi-precision, expanding accumulation
# ---------------------------------------------------------------------------


def gemm_ref(a: jax.Array, b: jax.Array, out_dtype=None, accum_dtype=jnp.float32):
    """C = A @ B with widening accumulation (paper's EXP sum-dot-product)."""
    out_dtype = out_dtype or a.dtype
    acc = jnp.matmul(a, b, preferred_element_type=accum_dtype)
    return acc.astype(out_dtype)


def gemm_scaled_ref(a, b, precision, *, out_dtype=None,
                    accum_dtype=jnp.float32, bk=None):
    """Scaled-GEMM oracle: quantize both operands per K-block exactly as
    the production kernels do, dequantize to fp32, and matmul — the ground
    truth the blocked scaled impls (which never materialize the fp32
    dequantized operands) must match bit-for-bit up to reassociation."""
    from repro.core import precision as prec
    from repro.kernels import registry

    p = prec.resolve(precision)
    K = a.shape[1]
    bk = min(registry.resolve_blocks("gemm", bk=bk)["bk"], K)
    af = prec.dequantize_blockwise(
        *prec.quantize_blockwise(a, p, axis=1, block=bk), axis=1, block=bk
    )
    bf = prec.dequantize_blockwise(
        *prec.quantize_blockwise(b, p, axis=0, block=bk), axis=0, block=bk
    )
    return gemm_ref(af, bf, out_dtype or jnp.float32, accum_dtype)


def mha_scaled_ref(q, k, v, precision, **kwargs):
    """Scaled-attention oracle: per-row quantize/dequantize of q/k/v over
    the head dimension, then the exact softmax oracle ``mha_ref``."""
    from repro.core import precision as prec

    p = prec.resolve(precision)
    deq = []
    for x in (q, k, v):
        vals, scales = prec.quantize_blockwise(
            x, p, axis=-1, block=x.shape[-1]
        )
        deq.append(prec.dequantize_blockwise(vals, scales, axis=-1))
    return mha_ref(*deq, **kwargs)


# ---------------------------------------------------------------------------
# Attention (paper Sec. V-C: FlashAttention-2 inside GPT-J)
# ---------------------------------------------------------------------------


def mha_ref(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, K, Sk, D)  -- GQA: H = K * G
    v: jax.Array,  # (B, K, Sk, D)
    *,
    causal: bool = True,
    window: int = 0,  # 0 = unbounded; else LOOKBACK window (implies k <= q)
    q_offset: int = 0,  # absolute position of q[0] (for prefill continuation)
    scale: float | None = None,
    return_lse: bool = False,
):
    """Attention oracle. ``window > 0`` is a *lookback* window: each query
    attends to keys in ``(q_pos - window, q_pos]``, so the window itself
    imposes the ``k_pos <= q_pos`` upper bound even with ``causal=False``
    (the semantics every impl shares — see the cross-impl window test).
    ``return_lse=True`` additionally returns the per-row log-sum-exp of the
    masked scores, (B, H, Sq) fp32 — the ring-attention merge statistic.
    """
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qf = q.reshape(B, K, G, Sq, D).astype(jnp.float32)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qf, k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(Sq)[:, None] + q_offset
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal or window:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    o = o.reshape(B, H, Sq, v.shape[-1]).astype(q.dtype)
    if not return_lse:
        return o
    lse = jax.scipy.special.logsumexp(s, axis=-1)  # -inf on fully-masked rows
    lse = jnp.maximum(lse, -1e30).reshape(B, H, Sq)  # keep merges finite
    return o, lse


def decode_attention_ref(
    q: jax.Array,  # (B, H, D) one new token per sequence
    k: jax.Array,  # (B, K, S, D) cache
    v: jax.Array,  # (B, K, S, D)
    position: jax.Array,  # (B,) int32 absolute position of the new token
    *,
    window: int = 0,
    scale: float | None = None,
    pos_offset=0,
    return_lse: bool = False,
) -> jax.Array:
    """Decode oracle. ``pos_offset`` is the absolute position of cache row
    0 (a cache *shard*'s base in ring decode); ``return_lse=True`` adds the
    (B, H) fp32 log-sum-exp the per-shard online-softmax merge consumes
    (floored at -1e30 so fully-masked shards merge as exact no-ops)."""
    B, H, D = q.shape
    K, S = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qf = q.reshape(B, K, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qf, k.astype(jnp.float32)) * scale
    idx = jnp.arange(S)[None, :] + pos_offset
    mask = idx <= position[:, None]
    if window:
        mask &= idx > (position[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows (empty shards)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v.astype(jnp.float32))
    o = o.reshape(B, H, v.shape[-1]).astype(q.dtype)
    if not return_lse:
        return o
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    lse = jnp.maximum(lse, -1e30).reshape(B, H)
    return o, lse


def decode_attention_scaled_ref(q, k, v, position, *, precision, **kwargs):
    """Quantized-KV-cache decode oracle: quantize the cache per row exactly
    as the serving path does, dequantize, and run the exact oracle."""
    from repro.core import precision as prec

    kq, ks, vq, vs = prec.quantize_kv_cache(k, v, precision)
    kf = prec.dequantize_blockwise(kq, ks, axis=-1)
    vf = prec.dequantize_blockwise(vq, vs, axis=-1)
    return decode_attention_ref(q, kf, vf, position, **kwargs)


def decode_attention_paged_ref(
    q,  # (B, H, D)
    k,  # (P, K, bs, D) physical block pool
    v,  # (P, K, bs, D)
    block_table,  # (B, NB) int32 pool slots per logical cache block
    position,  # (B,)
    *,
    window: int = 0,
    scale: float | None = None,
    precision=None,
    k_scale=None,  # (P, K, bs, 1) fp32 pool scales (pre-quantized cache)
    v_scale=None,
    pos_offset=0,
    return_lse: bool = False,
):
    """Paged-cache oracle: gather each sequence's pages back into the
    contiguous (B, K, NB*bs, D) layout and run the exact contiguous oracle
    — the ground truth the blocked gather path must match bitwise."""
    from repro.core import precision as prec

    if precision is not None and k_scale is None:
        k, k_scale, v, v_scale = prec.quantize_kv_cache(k, v, precision)
    if k_scale is not None:
        k = prec.dequantize_blockwise(k, k_scale, axis=-1)
        v = prec.dequantize_blockwise(v, v_scale, axis=-1)
    B, nb = block_table.shape
    K, bs = k.shape[1], k.shape[2]
    def gather(pool):
        return jnp.moveaxis(pool[block_table], 1, 2).reshape(
            B, K, nb * bs, pool.shape[-1])
    return decode_attention_ref(
        q, gather(k), gather(v), position, window=window, scale=scale,
        pos_offset=pos_offset, return_lse=return_lse,
    )


# ---------------------------------------------------------------------------
# Chunked linear attention with data-dependent decay (RWKV6 "Finch" + SSD)
# ---------------------------------------------------------------------------


def linear_attention_scan_ref(
    r: jax.Array,  # (B, H, T, N) receptance / C
    k: jax.Array,  # (B, H, T, N) key / B
    v: jax.Array,  # (B, H, T, M) value / x
    w_log: jax.Array,  # (B, H, T, N) log-decay, <= 0
    u: jax.Array | None,  # (H, N) rwkv bonus; None => SSD mode
    s0: jax.Array | None = None,  # (B, H, N, M) incoming state
) -> tuple[jax.Array, jax.Array]:
    """Exact per-token recurrence (the oracle).

    rwkv mode (u given):  o_t = r_t . S_{t-1} + (r_t * u * k_t) v_t;
                          S_t = diag(exp(w_t)) S_{t-1} + k_t v_t^T
    ssd  mode (u None):   S_t as above; o_t = r_t . S_t
    """
    B, H, T, N = r.shape
    M = v.shape[-1]
    ssd = u is None
    S = s0 if s0 is not None else jnp.zeros((B, H, N, M), jnp.float32)

    def step(S, xs):
        rt, kt, vt, wt = xs  # (B,H,N), (B,H,N), (B,H,M), (B,H,N)
        kv = jnp.einsum("bhn,bhm->bhnm", kt, vt)
        S_new = jnp.exp(wt)[..., None] * S + kv
        if ssd:
            o = jnp.einsum("bhn,bhnm->bhm", rt, S_new)
        else:
            o = jnp.einsum("bhn,bhnm->bhm", rt, S) + jnp.einsum(
                "bhn,bhn,bhm->bhm", rt, u[None] * kt, vt
            )
        return S_new, o

    xs = tuple(
        jnp.moveaxis(x.astype(jnp.float32), 2, 0) for x in (r, k, v, w_log)
    )
    S, o = jax.lax.scan(step, S, xs)
    return jnp.moveaxis(o, 0, 2).astype(v.dtype), S


# ---------------------------------------------------------------------------
# Sparse-dense matmul (paper Fig. 9c) on the blocked-ELL value/index format
# ---------------------------------------------------------------------------


def spmm_ref(values: jax.Array, cols: jax.Array, dense: jax.Array) -> jax.Array:
    """values/cols: (R, L) ELL rows (padding: value 0, col 0); dense: (C, F)."""
    gathered = dense[cols]  # (R, L, F)
    return jnp.einsum(
        "rl,rlf->rf", values.astype(jnp.float32), gathered.astype(jnp.float32)
    ).astype(dense.dtype)


# ---------------------------------------------------------------------------
# Sparse-sparse matmul (paper Fig. 9d): index intersection
# ---------------------------------------------------------------------------


def spmspm_ref(
    a_values: jax.Array,  # (R, La) ELL rows of A
    a_cols: jax.Array,  # (R, La) sorted indices into the contraction dim
    b_values: jax.Array,  # (C, Lb) ELL *columns* of B (CSC-like)
    b_rows: jax.Array,  # (C, Lb) sorted indices into the contraction dim
    contraction_dim: int,
) -> jax.Array:
    """out[r, c] = sum over the index intersection of A.row(r) and B.col(c).

    Oracle: densify both operands and matmul. Padding entries carry value 0.
    """
    R, La = a_values.shape
    C, Lb = b_values.shape
    a_dense = jnp.zeros((R, contraction_dim), jnp.float32)
    a_dense = a_dense.at[jnp.arange(R)[:, None], a_cols].add(
        a_values.astype(jnp.float32)
    )
    b_dense = jnp.zeros((C, contraction_dim), jnp.float32)
    b_dense = b_dense.at[jnp.arange(C)[:, None], b_rows].add(
        b_values.astype(jnp.float32)
    )
    return a_dense @ b_dense.T


def spmspm_comparisons(a_cols: jax.Array, b_rows: jax.Array) -> int:
    """Paper figure of merit: index comparisons performed (GCOMP)."""
    R, La = a_cols.shape
    C, Lb = b_rows.shape
    return int(R) * int(C) * int(La) * int(Lb)


# ---------------------------------------------------------------------------
# Stencil (paper Fig. 9b): offset streams over a 3D grid, periodic boundary
# ---------------------------------------------------------------------------


def stencil_ref(
    grid: jax.Array,  # (X, Y, Z)
    offsets: np.ndarray,  # (P, 3) int offsets
    weights: jax.Array,  # (P,)
) -> jax.Array:
    out = jnp.zeros_like(grid, dtype=jnp.float32)
    for p in range(offsets.shape[0]):
        dx, dy, dz = (int(o) for o in offsets[p])
        out = out + weights[p].astype(jnp.float32) * jnp.roll(
            grid, (-dx, -dy, -dz), axis=(0, 1, 2)
        ).astype(jnp.float32)
    return out.astype(grid.dtype)
