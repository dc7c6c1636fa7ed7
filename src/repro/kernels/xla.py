"""Blocked jnp implementations of the stream kernels ("xla" impls).

Each function implements the *same algorithm* as its Pallas StreamProgram
sibling — same FLOPs, same memory behaviour — expressed in jnp so it lowers
on any backend. The multi-pod dry-run compiles these where Pallas cannot
lower on CPU; ``registry.unroll_inner()`` swaps their inner lax.scan for a
python loop so XLA's HloCostAnalysis (which counts while-loop bodies once)
sees true FLOP/byte/collective counts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import registry


# ---------------------------------------------------------------------------
# Per-block scaled GEMM (the narrow-precision path)
# ---------------------------------------------------------------------------


def gemm_scaled_xla(a, b, precision, *, out_dtype=None,
                    accum_dtype=jnp.float32, bm=None, bk=None, bn=None):
    """Blocked per-block scaled GEMM in jnp: the same (values, scales)
    dataflow as ``gemm.gemm_scaled_pallas`` — quantize per K-block of size
    ``bk``, run the narrow dot per block, rescale inside the fp32
    accumulator — expressed as a scan over K blocks so it lowers anywhere.
    """
    from repro.core import precision as prec

    p = prec.resolve(precision)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    out_dtype = out_dtype or jnp.float32
    bk = min(registry.resolve_blocks("gemm", bm=bm, bk=bk, bn=bn)["bk"], K)
    pad = (-K) % bk
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
    Kp = K + pad
    nk = Kp // bk

    aq, a_scale = prec.quantize_blockwise(a, p, axis=1, block=bk)
    bq, b_scale = prec.quantize_blockwise(b, p, axis=0, block=bk)
    ab = jnp.moveaxis(aq.reshape(M, nk, bk), 1, 0)  # (nk, M, bk)
    bb = bq.reshape(nk, bk, N)

    def body(acc, xs):
        ablk, bblk, asc, bsc = xs
        part = jnp.dot(ablk, bblk, preferred_element_type=accum_dtype)
        return acc + part * (asc[:, None] * bsc[None, :]), None

    acc0 = jnp.zeros((M, N), accum_dtype)
    xs = (ab, bb, jnp.moveaxis(a_scale, 1, 0), b_scale)
    if registry.unroll_inner_enabled():
        acc = acc0
        for i in range(nk):
            acc, _ = body(acc, jax.tree.map(lambda x: x[i], xs))
    else:
        acc, _ = jax.lax.scan(body, acc0, xs)
    return acc.astype(out_dtype)


# ---------------------------------------------------------------------------
# FlashAttention-2 (forward)
# ---------------------------------------------------------------------------


def flash_attention_xla(q, k, v, *, causal=True, window=0, q_offset=0,
                        scale=None, bq=None, bk=None, return_lse=False):
    """Online-softmax over KV blocks (FlashAttention-2 dataflow in jnp).

    Memory is O(Sq * bk) per head instead of O(Sq * Sk): this is the
    C4 double-buffered-tile structure the paper uses, expressed as a scan.
    ``bq``/``bk`` resolve through the registry (explicit > override >
    default), the same block geometry the Pallas kernel reads. A lookback
    ``window`` bounds attention to ``(q_pos - window, q_pos]`` regardless
    of ``causal`` (the shared window semantics — see ``ref.mha_ref``).
    ``return_lse=True`` also returns the (B, H, Sq) fp32 log-sum-exp the
    ring-attention merge consumes. ``v`` may be narrower than ``q``/``k``
    (latent attention's value heads): the output takes ``v``'s width.
    """
    B, H, Sq, D = q.shape
    Dv = v.shape[-1]
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    if registry.unroll_inner_enabled():
        # q-blocked form with STATIC skipping of fully-masked (q, kv) block
        # pairs — cost-representative of the Pallas kernel's pl.when skips
        # (causal halves attention FLOPs; sliding windows keep only a band)
        return _flash_attention_xla_unrolled(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            scale=scale, bq=bq, bk=bk, return_lse=return_lse,
        )
    block_k = min(registry.resolve_blocks("flash_attention", bk=bk)["bk"], Sk)
    pad = (-Sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nb = (Sk + pad) // block_k

    qf = (q.astype(jnp.float32) * scale).reshape(B, K, G, Sq, D)
    kb = jnp.moveaxis(k.reshape(B, K, nb, block_k, D), 2, 0)
    vb = jnp.moveaxis(v.reshape(B, K, nb, block_k, Dv), 2, 0)
    q_pos = jnp.arange(Sq) + q_offset  # absolute positions

    NEG = jnp.float32(-1e30)

    def body(carry, xs):
        m, denom, acc = carry
        kblk, vblk, bidx = xs
        s = jnp.einsum("bkgqd,bksd->bkgqs", qf, kblk.astype(jnp.float32))
        k_pos = bidx * block_k + jnp.arange(block_k)
        mask = k_pos[None, :] < Sk
        if causal or window:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # fully-masked rows: exp(NEG - NEG) == 1, so zero by mask explicitly
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        denom = denom * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bksd->bkgqd", p, vblk.astype(jnp.float32)
        )
        return (m_new, denom, acc), None

    m0 = jnp.full((B, K, G, Sq), NEG)
    l0 = jnp.zeros((B, K, G, Sq))
    acc0 = jnp.zeros((B, K, G, Sq, Dv))
    (m, denom, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kb, vb, jnp.arange(nb))
    )
    o = acc / jnp.maximum(denom, 1e-30)[..., None]
    o = o.reshape(B, H, Sq, Dv).astype(q.dtype)
    if not return_lse:
        return o
    lse = (m + jnp.log(jnp.maximum(denom, 1e-30))).reshape(B, H, Sq)
    return o, lse


def _flash_attention_xla_unrolled(q, k, v, *, causal, window, q_offset, scale,
                                  bq=None, bk=None, return_lse=False):
    B, H, Sq, D = q.shape
    Dv = v.shape[-1]
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    NEG = jnp.float32(-1e30)
    # the same single block-geometry path every impl uses (explicit >
    # set_block_override > default) — no private env-var escape hatch
    blocks = registry.resolve_blocks("flash_attention", bq=bq, bk=bk)
    bq, bk = min(blocks["bq"], Sq), min(blocks["bk"], Sk)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq, nk = (Sq + pq) // bq, (Sk + pk) // bk
    qf = (q.astype(jnp.float32) * scale).reshape(B, K, G, nq, bq, D)

    outs, lses = [], []
    for i in range(nq):
        qi = qf[:, :, :, i]  # (B,K,G,bq,D)
        q_lo, q_hi = q_offset + i * bq, q_offset + (i + 1) * bq - 1
        m = jnp.full((B, K, G, bq), NEG)
        denom = jnp.zeros((B, K, G, bq))
        acc = jnp.zeros((B, K, G, bq, Dv))
        for j in range(nk):
            k_lo, k_hi = j * bk, (j + 1) * bk - 1
            if (causal or window) and k_lo > q_hi:
                continue  # static skip: above the diagonal (window implies it)
            if window and k_hi <= q_lo - window:
                continue  # static skip: older than every row's window
            kj = k[:, :, j * bk : (j + 1) * bk].astype(jnp.float32)
            vj = v[:, :, j * bk : (j + 1) * bk].astype(jnp.float32)
            s = jnp.einsum("bkgqd,bksd->bkgqs", qi, kj)
            q_pos = q_lo + jnp.arange(bq)[:, None]
            k_pos = k_lo + jnp.arange(bk)[None, :]
            mask = k_pos < Sk
            if causal or window:
                mask &= k_pos <= q_pos
            if window:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask, s, NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            denom = denom * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bkgqs,bksd->bkgqd", p, vj)
            m = m_new
        outs.append(acc / jnp.maximum(denom, 1e-30)[..., None])
        lses.append(m + jnp.log(jnp.maximum(denom, 1e-30)))
    o = jnp.concatenate(outs, axis=3).reshape(B, H, Sq + pq, Dv)[:, :, :Sq]
    o = o.astype(q.dtype)
    if not return_lse:
        return o
    lse = jnp.concatenate(lses, axis=3).reshape(B, H, Sq + pq)[:, :, :Sq]
    return o, lse


def flash_attention_scaled_xla(q, k, v, precision, *, causal=True, window=0,
                               q_offset=0, scale=None, bq=None, bk=None,
                               return_lse=False):
    """Low-precision FA-2 in jnp: per-row quantize/dequantize of q/k/v (one
    fp32 scale per (b, h, s) row over D), then the unchanged blocked
    online-softmax scan. The quantization error is in operand storage only
    — the algorithm and its fp32 accumulation are identical to
    ``flash_attention_xla``, matching the Pallas kernel's dequantize-at-use
    dataflow."""
    from repro.core import precision as prec

    p = prec.resolve(precision)
    deq = []
    for x in (q, k, v):
        vals, scales = prec.quantize_blockwise(x, p, axis=-1,
                                               block=x.shape[-1])
        deq.append(prec.dequantize_blockwise(vals, scales, axis=-1))
    return flash_attention_xla(
        deq[0], deq[1], deq[2], causal=causal, window=window,
        q_offset=q_offset, scale=scale, bq=bq, bk=bk, return_lse=return_lse,
    )


def decode_attention_xla(q, k, v, position, *, window=0, scale=None, bs=None,
                         precision=None, block_table=None, k_scale=None,
                         v_scale=None, pos_offset=0, return_lse=False):
    """Blocked single-token attention against a cache (online softmax over
    cache blocks, the memory-bound decode form GPT-J hits every step).

    The cache streams through in ``bs``-sized blocks — O(B*H*bs) live state
    instead of the ref form's O(B*H*S) score matrix — mirroring the C4
    double-buffered cache-tile traffic. ``bs`` resolves through the registry
    (explicit > override > default) like every other block parameter.

    ``precision`` enables the quantized-cache serving path: the KV cache is
    held as narrow values plus one fp32 scale per cached (b, k, s) row
    (``precision.quantize_kv_cache``), each streamed block is dequantized
    at use inside the fp32 online softmax — the cache's HBM footprint and
    stream traffic shrink by the compute dtype's width ratio.

    ``block_table`` switches the cache operands to the *paged* layout: k/v
    are physical block pools ``(P, K, bs, D)`` and ``block_table`` is a
    ``(B, NB)`` int32 map from each sequence's logical cache block to its
    pool slot. The pool's own block extent pins ``bs`` (the page size is
    the stream tile), the gathered blocks stream through the *same* online
    softmax body as the contiguous path — so the two layouts are bitwise
    equal whenever the contiguous length is ``NB * bs``. Table entries past
    a sequence's ``position`` may point anywhere valid (the mask makes
    those blocks exact no-ops). ``k_scale``/``v_scale`` pass pre-quantized
    pool scales (``(P, K, bs, 1)``) so a cache held narrow by the serving
    engine skips the quantize-at-use step. ``pos_offset`` shifts the
    absolute position of logical block 0 (the cache-shard offset ring
    decode folds over); ``return_lse`` additionally returns the (B, H)
    fp32 log-sum-exp the per-shard online-softmax merge consumes.
    """
    B, H, D = q.shape
    Dv = v.shape[-1]
    K = k.shape[1]
    G = H // K
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    paged = block_table is not None
    if precision is not None and k_scale is None:
        from repro.core import precision as prec

        k, k_scale, v, v_scale = prec.quantize_kv_cache(k, v, precision)
    if paged:
        bs = k.shape[2]  # the pool's page size IS the stream tile
        nb = block_table.shape[1]
        S = nb * bs
        # gather pool pages into the (nb, B, K, bs, d) stream the scan eats
        def blk(x):
            return jnp.moveaxis(x[block_table], 1, 0)

        kb, vb = blk(k), blk(v)
        ksb = blk(k_scale) if k_scale is not None else jnp.zeros((nb,))
        vsb = blk(v_scale) if v_scale is not None else jnp.zeros((nb,))
    else:
        S = k.shape[2]
        bs = min(registry.resolve_blocks("decode_attention", bs=bs)["bs"], S)
        pad = (-S) % bs
        if pad:
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            if k_scale is not None:
                k_scale = jnp.pad(k_scale, ((0, 0), (0, 0), (0, pad), (0, 0)))
                v_scale = jnp.pad(v_scale, ((0, 0), (0, 0), (0, pad), (0, 0)))
        nb = (S + pad) // bs
        def blk(x, d):
            return jnp.moveaxis(x.reshape(B, K, nb, bs, d), 2, 0)

        kb, vb = blk(k, D), blk(v, Dv)
        ksb = blk(k_scale, 1) if k_scale is not None else jnp.zeros((nb,))
        vsb = blk(v_scale, 1) if v_scale is not None else jnp.zeros((nb,))
    qf = (q.astype(jnp.float32) * scale).reshape(B, K, G, D)
    NEG = jnp.float32(-1e30)

    def body(carry, xs):
        m, denom, acc = carry
        kblk, vblk, ksblk, vsblk, bidx = xs
        kf = kblk.astype(jnp.float32)
        vf = vblk.astype(jnp.float32)
        if k_scale is not None:  # dequantize the cache block at use
            kf = kf * ksblk
            vf = vf * vsblk
        s = jnp.einsum("bkgd,bksd->bkgs", qf, kf)
        # absolute positions of this block's rows (paged pools shift by the
        # shard offset; the gathered page's rows stay block-contiguous)
        idx = pos_offset + bidx * bs + jnp.arange(bs)[None, :]
        mask = (idx < pos_offset + S) & (idx <= position[:, None])
        if window:
            mask &= idx > position[:, None] - window
        mask = mask[:, None, None, :]  # (B, 1, 1, bs)
        s = jnp.where(mask, s, NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        denom = denom * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgs,bksd->bkgd", p, vf)
        return (m_new, denom, acc), None

    m0 = jnp.full((B, K, G), NEG)
    l0 = jnp.zeros((B, K, G))
    acc0 = jnp.zeros((B, K, G, Dv))
    if registry.unroll_inner_enabled() and not paged:
        carry = (m0, l0, acc0)
        for i in range(nb):
            carry, _ = body(
                carry, (kb[i], vb[i], ksb[i], vsb[i], jnp.int32(i))
            )
        m, denom, acc = carry
    else:
        (m, denom, acc), _ = jax.lax.scan(
            body, (m0, l0, acc0), (kb, vb, ksb, vsb, jnp.arange(nb))
        )
    o = acc / jnp.maximum(denom, 1e-30)[..., None]
    o = o.reshape(B, H, Dv).astype(q.dtype)
    if not return_lse:
        return o
    lse = (m + jnp.log(jnp.maximum(denom, 1e-30))).reshape(B, H)
    return o, lse


# ---------------------------------------------------------------------------
# Chunked linear attention with data-dependent decay (RWKV6 / SSD)
# ---------------------------------------------------------------------------


def linear_attention_xla(r, k, v, w_log, u=None, s0=None, *, chunk=None):
    chunk = registry.resolve_blocks("linear_attention", chunk=chunk)["chunk"]
    B, H, T, N = r.shape
    M = v.shape[-1]
    pad = (-T) % chunk
    if pad:
        def zr(x):
            return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))

        r, k, v, w_log = zr(r), zr(k), zr(v), zr(w_log)
    Tp = T + pad
    nc = Tp // chunk
    ssd = u is None

    # (nc, B, H, C, ...) for scan over chunks
    def cs(x):
        return jnp.moveaxis(
            x.astype(jnp.float32).reshape(B, H, nc, chunk, -1), 2, 0
        )
    rc, kc, vc, wc = cs(r), cs(k), cs(v), cs(w_log)

    def body(S, xs):
        rch, kch, vch, wch = xs  # (B,H,C,N|M)
        inc = jnp.cumsum(wch, axis=2)  # inclusive log-decay (B,H,C,N)
        exc = inc - wch  # exclusive
        e = inc if ssd else exc
        total = inc[:, :, -1:, :]  # (B,H,1,N)
        # inter-chunk: o_t += (r_t * exp(e_t)) @ S_in
        r_dec = rch * jnp.exp(e)
        o = jnp.einsum("bhcn,bhnm->bhcm", r_dec, S)
        # intra-chunk: coeff[t,s] = exp(e_t)*exp(-inc_s) for s<t (ssd: s<=t;
        # coeff<=1 overall; factors bounded: chunk*|W_LOG_FLOOR| < log(f32max))
        k_dec = kch * jnp.exp(-inc)
        scores = jnp.einsum("bhtn,bhsn->bhts", r_dec, k_dec)
        t_idx = jnp.arange(chunk)
        mask = (
            t_idx[:, None] >= t_idx[None, :]
            if ssd
            else t_idx[:, None] > t_idx[None, :]
        )
        scores = jnp.where(mask, scores, 0.0)
        o = o + jnp.einsum("bhts,bhsm->bhtm", scores, vch)
        if not ssd:  # rwkv diagonal bonus
            o = o + jnp.einsum("bhcn,bhcn,bhcm->bhcm", rch, u[None, :, None] * kch, vch)
        # state update: S_out = exp(total) * S_in + sum_s exp(total-inc_s) k_s v_s
        k_tail = kch * jnp.exp(total - inc)
        S = jnp.exp(total)[..., 0, :, None] * S + jnp.einsum(
            "bhsn,bhsm->bhnm", k_tail, vch
        )
        return S, o

    S0 = (
        s0.astype(jnp.float32)
        if s0 is not None
        else jnp.zeros((B, H, N, M), jnp.float32)
    )
    if registry.unroll_inner_enabled():
        S, os_ = S0, []
        for i in range(nc):
            S, oi = body(S, (rc[i], kc[i], vc[i], wc[i]))
            os_.append(oi)
        o = jnp.stack(os_, 0)
    else:
        S, o = jax.lax.scan(body, S0, (rc, kc, vc, wc))
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, Tp, M)[:, :, :T]
    return o.astype(v.dtype), S


# ---------------------------------------------------------------------------
# BSR SpMM / SpMSpM blocked forms
# ---------------------------------------------------------------------------


def bsr_spmm_xla(tile_values, tile_rows, tile_cols, dense, num_rows):
    """Scatter-accumulate the per-tile matmuls (same tile economy as the
    StreamProgram: compute scales with nnz blocks only)."""
    T, bm, bk = tile_values.shape
    gathered = jax.vmap(
        lambda c: jax.lax.dynamic_slice_in_dim(dense, c * bk, bk, axis=0)
    )(tile_cols)
    prods = jnp.einsum(
        "tmk,tkf->tmf",
        tile_values.astype(jnp.float32),
        gathered.astype(jnp.float32),
    )
    out = jnp.zeros((num_rows // bm, bm, dense.shape[1]), jnp.float32)
    out = out.at[tile_rows].add(prods)
    return out.reshape(num_rows, dense.shape[1])


def spmspm_xla(a_values, a_cols, b_values, b_rows, contraction_dim):
    """One-side-densified intersection (blocked gather; representative of
    the kernel's VMEM bitmap intersect)."""
    R = a_values.shape[0]
    a_dense = jnp.zeros((R, contraction_dim), jnp.float32)
    a_dense = a_dense.at[jnp.arange(R)[:, None], a_cols].add(
        a_values.astype(jnp.float32)
    )
    gathered = a_dense[:, b_rows]  # (R, C, Lb)
    return jnp.einsum("cj,rcj->rc", b_values.astype(jnp.float32), gathered)
