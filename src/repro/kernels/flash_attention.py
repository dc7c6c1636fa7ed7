"""FlashAttention-2 forward kernel (paper Sec. V-C uses FA-2 inside GPT-J).

Online-softmax over KV blocks with the running (m, l, acc) statistics held in
VMEM scratch across the innermost grid dimension. The KV block stream is the
paper's C4 double-buffered DMA tile stream; causal/window masking is applied
with iota position comparisons, and fully-masked blocks skip their compute
(pl.when) — the control-flow analogue of the SUs skipping dead iterations.
Supports GQA (H = K * G) via the k/v stream index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.streams import AffineStream, StreamProgram, stream_compute
from repro.kernels.registry import resolve_blocks

NEG = -1e30


def zigzag_indices(S: int, d: int) -> np.ndarray:
    """The zigzag (head+tail) sequence permutation of a ``d``-rank causal
    KV ring: split ``S`` rows into ``2d`` half-chunks and give rank ``r``
    half-chunks ``r`` and ``2d-1-r`` — one from the causal head, one from
    the tail — so every rank does the same 2·(S/2d)² score work per hop
    instead of rank 0 idling on every wrapped hop.

    Returns the length-``S`` gather index array ``idx``: natural-order row
    ``idx[i]`` lands at zigzag position ``i``; sharding positions over the
    ``data`` axis then hands rank ``r`` exactly its two half-chunks, head
    half first. Within each half the natural order is preserved and every
    head position precedes every tail position, so the concatenated local
    block is order-isomorphic to its global rows — a plain causal mask on
    the local block IS the global causal mask restricted to them (the
    property the ring's hop-0 kernel call relies on). Requires
    ``S % (2 * d) == 0``.
    """
    c2 = S // (2 * d)
    parts = []
    for r in range(d):
        parts.append(np.arange(r * c2, (r + 1) * c2))
        parts.append(np.arange((2 * d - 1 - r) * c2, (2 * d - r) * c2))
    return np.concatenate(parts)


def zigzag_inverse(S: int, d: int) -> np.ndarray:
    """Inverse of ``zigzag_indices``: gathering with it restores natural
    sequence order (``zz[zigzag_inverse(S, d)] == natural``)."""
    return np.argsort(zigzag_indices(S, d), kind="stable")


def _fa_kernel(
    q_ref, k_ref, v_ref, *refs,
    scale, causal, window, q_offset, sk, bq, bk, nk, return_lse,
    scaled=False,
):
    # scaled programs bind three per-row fp32 scale streams after v; then
    # the o out-ref; refs ends with the (m, l, acc) scratch — preceded by
    # the lse out-ref when the program was built with return_lse (out refs
    # bind before scratch)
    if scaled:
        qs_ref, ks_ref, vs_ref, *refs = refs
    else:
        qs_ref = ks_ref = vs_ref = None
    o_ref, *refs = refs
    lse_ref, (m_ref, l_ref, acc_ref) = (
        (refs[0], refs[1:]) if return_lse else (None, refs)
    )
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level early-out: skip fully-masked KV blocks. A lookback window
    # bounds positions like causal does (k_pos <= q_pos), so the
    # above-the-diagonal skip applies to windowed non-causal blocks too.
    run = None
    if causal or window:  # block strictly above the (implied) diagonal
        run = ik * bk <= q_offset + (iq + 1) * bq - 1
    if window:  # block entirely older than every q row's window
        in_window = (ik + 1) * bk - 1 > q_offset + iq * bq - window
        run = jnp.logical_and(run, in_window)

    def _compute():
        # dequantize at use: narrow values ride the streams, the rescale
        # happens inside the fp32 block compute (widening accumulation)
        q = q_ref[0, 0].astype(jnp.float32)
        if qs_ref is not None:
            q = q * qs_ref[0, 0]
        q = q * scale
        k = k_ref[0, 0].astype(jnp.float32)
        if ks_ref is not None:
            k = k * ks_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        mask = k_pos < sk
        if causal or window:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # fully-masked rows: exp(NEG - NEG) == 1, zero them via the mask
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        vblk = v_ref[0, 0].astype(jnp.float32)
        if vs_ref is not None:
            vblk = vblk * vs_ref[0, 0]
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p, vblk, preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    if run is None:
        _compute()
    else:
        pl.when(run)(_compute)

    @pl.when(ik == nk - 1)
    def _flush():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = (
                m_ref[..., 0] + jnp.log(jnp.maximum(l_ref[..., 0], 1e-30))
            )


def flash_attention_program(
    B, H, G, Sqp, D, nq, nk, bq, bk, dtype, k_dtype, v_dtype,
    *, scale, causal, window, q_offset, sk, return_lse=False, scaled=False,
    Dv=None,
) -> StreamProgram:
    """FA-2 as a stream program: q/o stream over (b, h, iq); the k/v streams
    revisit the shared KV head h//G — the GQA index map. ``return_lse``
    adds a second (B, H, Sqp) fp32 output stream carrying the per-row
    log-sum-exp (the ring-attention merge statistic). ``scaled`` adds
    three per-row fp32 scale streams (q, k, v) riding the same index maps
    as their value streams — the quantized-operand path. ``Dv`` (default
    ``D``) is the width of v and o, narrower than q and k under latent
    attention."""
    Dv = Dv or D
    body = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, sk=sk, bq=bq, bk=bk, nk=nk, return_lse=return_lse,
        scaled=scaled,
    )
    def kv_stream(dt, width):
        return AffineStream(
            (1, 1, bk, width), lambda b, h, i, j: (b, h // G, j, 0), dtype=dt
        )
    in_streams = [
        AffineStream(
            (1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0), dtype=dtype
        ),
        kv_stream(k_dtype, D),
        kv_stream(v_dtype, Dv),
    ]
    if scaled:
        in_streams.append(AffineStream(
            (1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0),
            dtype=jnp.float32,
        ))
        in_streams.extend(
            AffineStream(
                (1, 1, bk, 1), lambda b, h, i, j: (b, h // G, j, 0),
                dtype=jnp.float32,
            )
            for _ in range(2)
        )
    out_streams = [
        AffineStream(
            (1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0),
            dtype=jnp.float32 if scaled else dtype
        ),
    ]
    out_shapes = [jax.ShapeDtypeStruct(
        (B, H, Sqp, Dv), jnp.float32 if scaled else dtype
    )]
    if return_lse:
        out_streams.append(AffineStream(
            (1, 1, bq), lambda b, h, i, j: (b, h, i), dtype=jnp.float32
        ))
        out_shapes.append(jax.ShapeDtypeStruct((B, H, Sqp), jnp.float32))
    return StreamProgram(
        name="flash_attention_scaled" if scaled else "flash_attention",
        body=body,
        grid=(B, H, nq, nk),
        in_streams=tuple(in_streams),
        out_streams=tuple(out_streams),
        out_shapes=tuple(out_shapes),
        scratch=(
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ),
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )


def flash_attention_pallas(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, K, Sk, D)
    v: jax.Array,  # (B, K, Sk, Dv), Dv <= D
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: float | None = None,
    bq: int | None = None,
    bk: int | None = None,
    return_lse: bool = False,
    interpret: bool = False,
):
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    blocks = resolve_blocks("flash_attention", bq=bq, bk=bk)
    bq = min(blocks["bq"], Sq)
    bk = min(blocks["bk"], Sk)
    pq, pk_ = (-Sq) % bq, (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk_:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk_), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk_), (0, 0)))
    nq, nk = (Sq + pq) // bq, (Sk + pk_) // bk

    program = flash_attention_program(
        B, H, G, Sq + pq, D, nq, nk, bq, bk, q.dtype, k.dtype, v.dtype,
        scale=scale, causal=causal, window=window, q_offset=q_offset, sk=Sk,
        return_lse=return_lse, Dv=v.shape[-1],
    )
    out = stream_compute(program, q, k, v, interpret=interpret)
    if return_lse:
        o, lse = out
        return o[:, :, :Sq], lse[:, :, :Sq]
    return out[:, :, :Sq]


def flash_attention_scaled_pallas(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, K, Sk, D)
    v: jax.Array,
    precision,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: float | None = None,
    bq: int | None = None,
    bk: int | None = None,
    return_lse: bool = False,
    interpret: bool = False,
):
    """Low-precision FA-2: operands quantized per row over D (one fp32
    scale per (b, h, s) position — the KV-cache layout), values streamed
    narrow, dequantized inside the fp32 block compute."""
    from repro.core import precision as prec

    p = prec.resolve(precision)
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    blocks = resolve_blocks("flash_attention", bq=bq, bk=bk)
    bq = min(blocks["bq"], Sq)
    bk = min(blocks["bk"], Sk)
    pq, pk_ = (-Sq) % bq, (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk_:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk_), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk_), (0, 0)))
    nq, nk = (Sq + pq) // bq, (Sk + pk_) // bk

    qq, q_scale = prec.quantize_blockwise(q, p, axis=-1, block=D)
    kq, k_scale = prec.quantize_blockwise(k, p, axis=-1, block=D)
    vq, v_scale = prec.quantize_blockwise(v, p, axis=-1, block=D)

    program = flash_attention_program(
        B, H, G, Sq + pq, D, nq, nk, bq, bk,
        p.compute_dtype, p.compute_dtype, p.compute_dtype,
        scale=scale, causal=causal, window=window, q_offset=q_offset, sk=Sk,
        return_lse=return_lse, scaled=True,
    )
    out = stream_compute(
        program, qq, kq, vq, q_scale, k_scale, v_scale, interpret=interpret
    )
    if return_lse:
        o, lse = out
        return o[:, :, :Sq], lse[:, :, :Sq]
    return out[:, :, :Sq]
