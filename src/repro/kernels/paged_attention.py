"""Paged decode attention: one new token per sequence against the serving
engine's page pool, read through the block table.

The pool is page-major, ``(P, K, bs, D)``: one page of all KV heads is one
contiguous ``(K, bs, D)`` tile. The block table ``(B, NB)`` maps each
sequence's logical page to its pool slot. This is the paper's indirect
stream (C2, Fig. 4b): the table is scalar-prefetched and the K/V streams'
index maps read it, so the pipeliner's DMAs fetch the live pages straight
from the pool and no gathered copy of the table's pages is ever made.

Grid ``(B, NB / pages)``: one step covers ``pages`` consecutive table
columns of one row, each bound to its own K and V stream (``pages`` comes
from the registry, ``resolve_blocks("decode_attention")``). A column
outside the row's live columns (past its position, or before
its lookback window) maps to the nearest live page, which the stream
already holds, so the pipeliner elides the fetch; the body skips the
step with ``pl.when`` and masks the dead columns of a live one. A step's
pages are set side by side on the sequence axis, so that each KV head's
two dots span ``pages * bs`` rows: one page at a time leaves the MXU
mostly idle (16 rows a dot), and the kernel then runs several times
slower than its DMA. The online softmax keeps ``(m, l, acc)`` in f32
scratch across the row's steps and writes the output at its last step.

Latent attention (MLA, absorbed) is the same program over a pool of one
"KV head" whose rows are the latent and the shared rotary key
(``latent_decode_attention_pallas``): every query head reads the one page
tile, the keys are its whole width and the values its first ``v_width``
columns, so one DMA per page serves both. That instance is named
``mla_decode_attention`` in the compiled program and the profiler's trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.streams import (
    AffineStream,
    IndirectStream,
    StreamProgram,
    stream_compute,
)
from repro.kernels.registry import resolve_blocks

NEG = -1e30


def _live_bounds(position, *, bs: int, nb: int, window: int, pos_offset):
    """Per row, the first and last table position (relative to column 0)
    that the row attends to, and the first and last table column that holds
    one: ``(first, last, lo, hi)``. A row with none (a ring shard wholly
    before or past it) has ``last < first`` and ``lo == hi``."""
    rel = (position - pos_offset).astype(jnp.int32)
    last = jnp.minimum(rel, nb * bs - 1)
    first = jnp.zeros_like(rel)
    if window:
        first = jnp.maximum(rel - window + 1, 0)
    lo = first // bs
    return first, last, lo, jnp.maximum(last // bs, lo)


def _paged_kernel(tbl_ref, first_ref, last_ref, lo_ref, hi_ref, q_ref, *refs,
                  pages, bs, nsteps, scale, scaled, return_lse, shared_v=0):
    # refs: `pages` K page refs, `pages` V page refs (none where the values
    # are K's first `shared_v` columns), then (scaled) as many K and V
    # scale refs; then o (and lse); then the (m, l, acc) scratch
    k_refs = refs[:pages]
    if shared_v:
        v_refs, refs = None, refs[pages:]
    else:
        v_refs, refs = refs[pages:2 * pages], refs[2 * pages:]
    if scaled:
        ks_refs, vs_refs = refs[:pages], refs[pages:2 * pages]
        refs = refs[2 * pages:]
    o_ref, *refs = refs
    lse_ref, (m_ref, l_ref, acc_ref) = (
        (refs[0], refs[1:]) if return_lse else (None, refs)
    )
    b, j = pl.program_id(0), pl.program_id(1)
    first, last = first_ref[b], last_ref[b]
    c0 = j * pages  # the step's first table column

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def pages_of(refs, scale_refs):
        # the step's pages side by side on the sequence axis, (K, pages*bs,
        # D), so that each head's dot spans them all; a narrow pool is
        # dequantized at use
        if scale_refs is None:
            return jnp.concatenate([r[0] for r in refs], axis=1)
        return jnp.concatenate(
            [r[0].astype(jnp.float32) * s[0] for r, s in zip(refs, scale_refs)],
            axis=1)

    @pl.when(jnp.logical_and(c0 <= hi_ref[b], c0 + pages > lo_ref[b]))
    def _step():
        k = pages_of(k_refs, ks_refs if scaled else None)
        q = (q_ref[0].astype(jnp.float32) * scale).astype(k.dtype)
        # (K, G, D) x (K, N, D) -> (K, G, N), batched over the KV heads
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        idx = c0 * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = jnp.logical_and(idx >= first, idx <= last)
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        if shared_v:
            v = k[:, :, :shared_v]
        else:
            v = pages_of(v_refs, vs_refs if scaled else None)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(j == nsteps - 1)
    def _flush():
        den = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / den).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = m_ref[...] + jnp.log(den)


def paged_decode_program(B, K, G, D, bs, nbp, pages, dtype, pool_dtype,
                         index_args, *, scale, scaled=False,
                         return_lse=False, shared_v=0) -> StreamProgram:
    """The paged decode as a stream program over grid ``(B, nbp / pages)``.

    ``index_args`` are the scalar-prefetched ``(table, first, last, lo,
    hi)``: the row-major ``(B * nbp,)`` table and each row's bounds
    (``_live_bounds``). Page stream ``i`` of step ``j`` reads table column
    ``j * pages + i``, clamped into the row's live columns ``[lo, hi]``.
    ``scaled`` binds a per-row fp32 scale stream beside every K and V page
    stream. ``shared_v`` > 0 binds no V streams: the values are the first
    ``shared_v`` columns of the K pages (latent attention), and the output
    is that wide."""
    nsteps = nbp // pages
    Dv = shared_v or D

    def page_stream(i, block, dt):
        def index_map(b, j, tbl, first, last, lo, hi):
            col = jnp.minimum(jnp.maximum(j * pages + i, lo[b]), hi[b])
            return (tbl[b * nbp + col], 0, 0, 0)

        return IndirectStream(block, index_map, dtype=dt)

    def streams(block, dt):
        return [page_stream(i, block, dt) for i in range(pages)]

    in_streams = [AffineStream((1, K, G, D), lambda b, j: (b, 0, 0, 0),
                               dtype=dtype)]
    in_streams += streams((1, K, bs, D), pool_dtype)  # K pages
    if not shared_v:
        in_streams += streams((1, K, bs, D), pool_dtype)  # V pages
    if scaled:
        in_streams += streams((1, K, bs, 1), jnp.float32)
        in_streams += streams((1, K, bs, 1), jnp.float32)
    out_streams = [AffineStream((1, K, G, Dv), lambda b, j: (b, 0, 0, 0),
                                dtype=dtype)]
    out_shapes = [jax.ShapeDtypeStruct((B, K, G, Dv), dtype)]
    if return_lse:
        out_streams.append(AffineStream(
            (1, K, G, 1), lambda b, j: (b, 0, 0, 0), dtype=jnp.float32))
        out_shapes.append(jax.ShapeDtypeStruct((B, K, G, 1), jnp.float32))
    body = functools.partial(
        _paged_kernel, pages=pages, bs=bs, nsteps=nsteps, scale=scale,
        scaled=scaled, return_lse=return_lse, shared_v=shared_v,
    )
    name = "mla_decode_attention" if shared_v else "paged_decode_attention"
    return StreamProgram(
        name=name,
        trace_name=name if shared_v else None,
        body=body,
        grid=(B, nsteps),
        in_streams=tuple(in_streams),
        out_streams=tuple(out_streams),
        out_shapes=tuple(out_shapes),
        index_args=tuple(index_args),
        scratch=(
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, Dv), jnp.float32),
        ),
        dimension_semantics=("parallel", "arbitrary"),
    )


def paged_decode_attention_pallas(
    q: jax.Array,  # (B, H, D)
    k: jax.Array,  # (P, K, bs, D) page pool
    v: jax.Array,
    position: jax.Array,  # (B,)
    block_table: jax.Array,  # (B, NB) int32 pool slots
    *,
    window: int = 0,
    scale: float | None = None,
    precision=None,
    k_scale=None,  # (P, K, bs, 1) fp32 pool scales (a pool held narrow)
    v_scale=None,
    pos_offset=0,
    return_lse: bool = False,
    interpret: bool = False,
):
    """``decode_attention_xla``'s paged semantics, reading only live pages.

    A pool held narrow dequantizes each page at use (``k_scale``/
    ``v_scale``); ``precision`` without scales quantizes the pool first,
    as the xla form does. ``pos_offset`` (a ring shard's base, possibly
    traced) shifts the table's positions; ``return_lse`` adds the (B, H)
    fp32 log-sum-exp."""
    B, H, D = q.shape
    K, bs = k.shape[1], k.shape[2]
    G = H // K
    nb = block_table.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    if precision is not None and k_scale is None:
        from repro.core import precision as prec

        k, k_scale, v, v_scale = prec.quantize_kv_cache(k, v, precision)
    pages = min(resolve_blocks("decode_attention")["pages"], nb)
    nbp = nb + (-nb) % pages
    table = jnp.pad(block_table.astype(jnp.int32), ((0, 0), (0, nbp - nb)))
    bounds = _live_bounds(position, bs=bs, nb=nb, window=window,
                          pos_offset=pos_offset)
    scaled = k_scale is not None
    program = paged_decode_program(
        B, K, G, D, bs, nbp, pages, q.dtype, k.dtype,
        (table.reshape(-1), *bounds), scale=scale, scaled=scaled,
        return_lse=return_lse,
    )
    operands = [q.reshape(B, K, G, D)] + [k] * pages + [v] * pages
    if scaled:
        operands += [k_scale] * pages + [v_scale] * pages
    out = stream_compute(program, *operands, interpret=interpret)
    if not return_lse:
        return out.reshape(B, H, D)
    o, lse = out
    return o.reshape(B, H, D), lse.reshape(B, H)


def latent_decode_attention_pallas(
    q: jax.Array,  # (B, H, D): the absorbed query, latent then rotary part
    pool: jax.Array,  # (P, 1, bs, D) page pool of latent rows
    position: jax.Array,  # (B,)
    block_table: jax.Array,  # (B, NB) int32 pool slots
    *,
    v_width: int,
    scale: float,
    interpret: bool = False,
):
    """Absorbed latent attention of one new token per sequence, reading
    only live pages: scores over each row's whole width, values its first
    ``v_width`` columns. Returns (B, H, v_width)."""
    B, H, D = q.shape
    bs, nb = pool.shape[2], block_table.shape[1]
    pages = min(resolve_blocks("decode_attention")["pages"], nb)
    nbp = nb + (-nb) % pages
    table = jnp.pad(block_table.astype(jnp.int32), ((0, 0), (0, nbp - nb)))
    bounds = _live_bounds(position, bs=bs, nb=nb, window=0, pos_offset=0)
    program = paged_decode_program(
        B, 1, H, D, bs, nbp, pages, q.dtype, pool.dtype,
        (table.reshape(-1), *bounds), scale=scale, shared_v=v_width,
    )
    out = stream_compute(program, q.reshape(B, 1, H, D), *[pool] * pages,
                         interpret=interpret)
    return out.reshape(B, H, v_width)
