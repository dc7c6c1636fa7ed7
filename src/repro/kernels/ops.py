"""Jit-ready kernel entry points, dispatched through the kernel registry.

Public signatures are stable; every op resolves its implementation through
``repro.kernels.registry`` (explicit ``impl=`` arg > ``set_default_impl()`` >
``REPRO_KERNEL_IMPL`` env var > auto). The implementations themselves live in:

  - StreamProgram kernels (``pallas``/``interpret``): sibling kernel modules,
    executed through ``core.streams.stream_compute``
  - blocked jnp forms (``xla``): kernels/xla.py
  - naive oracles (``ref``): kernels/ref.py

Sparse ops additionally accept the pytree formats from ``core.sparse``
(EllMatrix / BsrMatrix) in place of their unpacked value/index arrays, so
sparse operands pass whole through ``jax.jit`` boundaries.

Block geometry resolves the same way for every op, in exactly one place:
``registry.resolve_blocks(op, **explicit)`` (explicit kwarg > autotuner/user
``set_block_override`` > static default). The dispatcher resolves once and
passes identical resolved sizes to whichever impl runs, so an explicit
``bk=`` and a ``set_block_override`` behave the same under pallas,
interpret, and xla alike — no impl carries its own block literal.

Partitioning is the third dispatch axis (kernels/partition.py): every op
accepts ``mesh=`` (or picks the mesh up from ``sharding.use_mesh``) and the
dispatcher resolves the op's PartitionRule once per call, wrapping whichever
registered impl runs in ``shard_map`` — same public signature, sharded
execution. On a multi-pod mesh plans resolve TWO-LEVEL, jointly over
``("pod", "model")`` with per-level collective epilogues (intra-pod psum
before the cross-pod D2D hop); indivisible shapes walk the replication
fallback ladder (drop the pod level, then replicate) instead of failing.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.sparse import BsrMatrix, EllMatrix
from repro.kernels import ref as _ref
from repro.kernels import registry
from repro.kernels import xla as _xla
from repro.kernels.registry import (  # re-exported: the public dispatch API
    kernel_call,
    resolve_blocks,
    resolve_impl,
)
from repro.kernels.registry import set_default_impl  # noqa: F401  (re-export)


def _dispatch(op, *args, mesh=None, impl=None, **kwargs):
    """The one mesh-aware dispatch seam: explicit ``mesh=`` kwarg, else the
    ``sharding.use_mesh`` context, else plain single-device kernel_call.

    Plan-only schedule kwargs (``partition.PLAN_KWARGS``: overlap/zigzag/
    remote_copy) ride through to the partition layer and are stripped
    before any direct kernel_call — a single device has no ring to
    schedule."""
    from repro.kernels import partition

    if mesh is None:
        from repro.parallel import sharding as _sh

        mesh = _sh.kernel_mesh()
    if mesh is not None:
        return partition.sharded_call(op, mesh, *args, impl=impl, **kwargs)
    return kernel_call(op, *args, impl=impl,
                       **partition.strip_plan_kwargs(kwargs))

# roofline dry-run context (see registry.unroll_inner): kept under its
# historical name for callers that patched the old ops-level flag
unrolled_inner = registry.unroll_inner


# ---------------------------------------------------------------------------
# Dense GEMM (paper Fig. 9a / Fig. 10)
# ---------------------------------------------------------------------------


def gemm(a, b, *, out_dtype=None, accum_dtype=jnp.float32, precision=None,
         impl=None, mesh=None, bm=None, bk=None, bn=None):
    """C = A @ B with widening accumulation.

    ``precision`` selects a low-precision policy (``core.precision``): the
    operands are quantized per K-block to the policy's compute dtype, the
    narrow dot runs at the scaled MXU rate, and the per-block fp32 scales
    rescale inside the fp32 accumulator. ``None`` is the exact legacy
    full-precision path — byte-identical dispatch, no quantization."""
    precision = _resolve_precision(precision)
    blocks = resolve_blocks("gemm", bm=bm, bk=bk, bn=bn)
    return _dispatch(
        "gemm", a, b, out_dtype=out_dtype, accum_dtype=accum_dtype,
        mesh=mesh, impl=impl, **_precision_kwargs(precision), **blocks,
    )


def _resolve_precision(precision):
    if precision is None:
        return None
    from repro.core import precision as _prec

    return _prec.resolve(precision)


def _precision_kwargs(precision):
    # precision rides dispatch only when set, so impls and rules without a
    # scaled path never see the kwarg (the PLAN_KWARGS signature-filter
    # discipline) and the None path stays byte-identical to the legacy one
    return {} if precision is None else {"precision": precision}


@registry.register_stream_kernel("gemm")
def _gemm_stream(a, b, *, out_dtype=None, accum_dtype=jnp.float32,
                 precision=None, bm=None, bk=None, bn=None, interpret=False):
    from repro.kernels import gemm as _gemm

    if precision is not None:
        return _gemm.gemm_scaled_pallas(
            a, b, precision, out_dtype=out_dtype, accum_dtype=accum_dtype,
            bm=bm, bk=bk, bn=bn, interpret=interpret,
        )
    return _gemm.gemm_pallas(
        a, b, out_dtype=out_dtype, accum_dtype=accum_dtype,
        bm=bm, bk=bk, bn=bn, interpret=interpret,
    )


@registry.register_kernel("gemm", impl="xla")
def _gemm_xla(a, b, *, out_dtype=None, accum_dtype=jnp.float32,
              precision=None, bm=None, bk=None, bn=None):
    if precision is not None:
        return _xla.gemm_scaled_xla(
            a, b, precision, out_dtype=out_dtype, accum_dtype=accum_dtype,
            bm=bm, bk=bk, bn=bn,
        )
    return _ref.gemm_ref(a, b, out_dtype=out_dtype, accum_dtype=accum_dtype)


@registry.register_kernel("gemm", impl="ref")
def _gemm_ref(a, b, *, out_dtype=None, accum_dtype=jnp.float32,
              precision=None, bm=None, bk=None, bn=None):
    if precision is not None:
        return _ref.gemm_scaled_ref(
            a, b, precision, out_dtype=out_dtype, accum_dtype=accum_dtype,
            bk=bk,
        )
    return _ref.gemm_ref(a, b, out_dtype=out_dtype, accum_dtype=accum_dtype)


# ---------------------------------------------------------------------------
# FlashAttention-2 (forward) — paper Sec. V-C
# ---------------------------------------------------------------------------


def flash_attention(
    q, k, v, *, causal=True, window=0, q_offset=0, scale=None,
    precision=None, impl=None, mesh=None, bq=None, bk=None, block_k=None,
    return_lse=False, overlap=True, zigzag=True, remote_copy=False,
):
    """q: (B,H,Sq,D); k,v: (B,K,Sk,D). Returns (B,H,Sq,D).

    ``window > 0`` is a *lookback* window: each query attends to keys in
    ``(q_pos - window, q_pos]``, so a window bounds future positions even
    with ``causal=False`` (identical semantics across every impl).
    ``return_lse=True`` additionally returns the per-row log-sum-exp,
    (B,H,Sq) fp32 — the statistic the sequence-parallel ring merge
    (``parallel.collectives.online_softmax_merge``) consumes.

    ``overlap``/``zigzag``/``remote_copy`` are mesh-schedule knobs for the
    sequence-parallel KV ring (no-ops on a single device): ``overlap``
    double-buffers the hop transfers behind the hop kernels,
    ``zigzag`` load-balances causal Q ownership across head/tail chunks,
    ``remote_copy`` opts the hop into the pallas async-remote-copy path on
    TPU backends. ``overlap=False`` + ``zigzag=False`` is the synchronous
    contiguous-chunk oracle. Numerics are unchanged either way.

    ``precision`` quantizes q/k/v per row over D (fp8/bf16 values + fp32
    per-row scales); the scaled kernels dequantize inside the fp32 block
    compute, so only the operand streams narrow. Scaled attention always
    returns fp32.

    ``block_k`` is the historical spelling of ``bk``; both resolve through
    the registry, so an explicit argument and ``set_block_override`` reach
    the pallas and xla impls identically.
    """
    if block_k is not None:
        if bk is not None and bk != block_k:
            raise TypeError(
                f"flash_attention: bk={bk} and its alias block_k={block_k} disagree"
            )
        bk = block_k
    precision = _resolve_precision(precision)
    blocks = resolve_blocks("flash_attention", bq=bq, bk=bk)
    return _dispatch(
        "flash_attention", q, k, v, causal=causal, window=window,
        q_offset=q_offset, scale=scale, return_lse=return_lse, mesh=mesh,
        impl=impl, overlap=overlap, zigzag=zigzag, remote_copy=remote_copy,
        **_precision_kwargs(precision), **blocks,
    )


@registry.register_stream_kernel("flash_attention")
def _fa_stream(q, k, v, *, causal, window, q_offset, scale, precision=None,
               bq=None, bk=None, return_lse=False, interpret=False):
    from repro.kernels import flash_attention as _fa

    if precision is not None:
        return _fa.flash_attention_scaled_pallas(
            q, k, v, precision, causal=causal, window=window,
            q_offset=q_offset, scale=scale, bq=bq, bk=bk,
            return_lse=return_lse, interpret=interpret,
        )
    return _fa.flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        scale=scale, bq=bq, bk=bk, return_lse=return_lse, interpret=interpret,
    )


@registry.register_kernel("flash_attention", impl="xla")
def _fa_xla(q, k, v, *, causal, window, q_offset, scale, precision=None,
            bq=None, bk=None, return_lse=False):
    if precision is not None:
        return _xla.flash_attention_scaled_xla(
            q, k, v, precision, causal=causal, window=window,
            q_offset=q_offset, scale=scale, bq=bq, bk=bk,
            return_lse=return_lse,
        )
    return _xla.flash_attention_xla(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        scale=scale, bq=bq, bk=bk, return_lse=return_lse,
    )


@registry.register_kernel("flash_attention", impl="ref")
def _fa_ref(q, k, v, *, causal, window, q_offset, scale, precision=None,
            bq=None, bk=None, return_lse=False):
    if precision is not None:
        return _ref.mha_scaled_ref(
            q, k, v, precision, causal=causal, window=window,
            q_offset=q_offset, scale=scale, return_lse=return_lse,
        )
    return _ref.mha_ref(
        q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale,
        return_lse=return_lse,
    )


def decode_attention(q, k, v, position, *, window=0, scale=None,
                     precision=None, impl=None, mesh=None, bs=None,
                     paged=False, block_table=None, k_scale=None,
                     v_scale=None, pos_offset=0, return_lse=False):
    """Single-token attention against a cache. Linear in cache length.

    ``precision`` holds the KV cache quantized — narrow values plus one
    fp32 scale per cached row (``core.precision.quantize_kv_cache``) —
    and dequantizes each streamed block at use: the serving path where the
    cache dominates HBM footprint and decode is purely memory-bound.

    ``paged=True`` switches k/v to the serving engine's block-pool layout:
    ``(P, K, bs, D)`` physical pages plus a ``(B, NB)`` int32
    ``block_table`` mapping each sequence's logical cache block to its
    pool slot (``serving.paged_cache``). The gathered pages stream through
    the same online-softmax body as the contiguous cache, so the two
    layouts are bitwise-equal at matching geometry; ``k_scale``/``v_scale``
    pass a pre-quantized pool's per-row fp32 scales. ``pos_offset`` is the
    absolute position of logical block 0 (nonzero for ring-decode cache
    shards) and ``return_lse=True`` adds the (B, H) fp32 log-sum-exp the
    per-shard ``online_softmax_merge`` fold consumes. All paged kwargs
    ride dispatch only when set, so the legacy contiguous path stays
    byte-identical."""
    precision = _resolve_precision(precision)
    if paged and block_table is None:
        raise TypeError("decode_attention: paged=True requires block_table")
    if block_table is not None and not paged:
        raise TypeError("decode_attention: block_table requires paged=True")
    if paged:
        if k.ndim != 4 or k.shape[:3] != v.shape[:3]:
            raise ValueError(
                f"decode_attention(paged): pools must be (P, K, bs, D), got "
                f"k={k.shape} v={v.shape}"
            )
        paged_kwargs = {"block_table": block_table}
        if k_scale is not None:
            paged_kwargs.update(k_scale=k_scale, v_scale=v_scale)
        blocks = {}  # the pool's page extent pins bs; no registry tile
    else:
        if k_scale is not None or v_scale is not None:
            raise TypeError(
                "decode_attention: k_scale/v_scale are pool scales for the "
                "paged path; the contiguous path quantizes via precision="
            )
        paged_kwargs = {}
        # the contiguous scan's tile; ``pages`` is the paged kernel's own
        blocks = {"bs": resolve_blocks("decode_attention", bs=bs)["bs"]}
    extra = {}
    # pos_offset may be a traced per-shard scalar; ride only when set so the
    # legacy kwarg surface (and its dispatch bytes) stay unchanged
    if not (isinstance(pos_offset, int) and pos_offset == 0):
        extra["pos_offset"] = pos_offset
    if return_lse:
        extra["return_lse"] = True
    return _dispatch(
        "decode_attention", q, k, v, position, window=window, scale=scale,
        mesh=mesh, impl=impl, **_precision_kwargs(precision),
        **paged_kwargs, **extra, **blocks,
    )


@registry.register_kernel("decode_attention", impl="xla")
def _decode_xla(q, k, v, position, *, window, scale, precision=None, bs=None,
                block_table=None, k_scale=None, v_scale=None, pos_offset=0,
                return_lse=False):
    return _xla.decode_attention_xla(
        q, k, v, position, window=window, scale=scale, bs=bs,
        precision=precision, block_table=block_table, k_scale=k_scale,
        v_scale=v_scale, pos_offset=pos_offset, return_lse=return_lse,
    )


@registry.register_stream_kernel("decode_attention")
def _decode_stream(q, k, v, position, *, window, scale, precision=None,
                   bs=None, block_table=None, k_scale=None, v_scale=None,
                   pos_offset=0, return_lse=False, interpret=False):
    """Paged calls stream the live pages through the block table
    (``kernels/paged_attention.py``). A contiguous cache has no table to
    stream through: those calls run the blocked xla form unchanged."""
    if block_table is None:
        return _decode_xla(
            q, k, v, position, window=window, scale=scale,
            precision=precision, bs=bs, pos_offset=pos_offset,
            return_lse=return_lse,
        )
    from repro.kernels import paged_attention as _pa

    return _pa.paged_decode_attention_pallas(
        q, k, v, position, block_table, window=window, scale=scale,
        precision=precision, k_scale=k_scale, v_scale=v_scale,
        pos_offset=pos_offset, return_lse=return_lse, interpret=interpret,
    )


@registry.register_kernel("decode_attention", impl="ref")
def _decode_ref(q, k, v, position, *, window, scale, precision=None, bs=None,
                block_table=None, k_scale=None, v_scale=None, pos_offset=0,
                return_lse=False):
    if block_table is not None:
        return _ref.decode_attention_paged_ref(
            q, k, v, block_table, position, window=window, scale=scale,
            precision=precision, k_scale=k_scale, v_scale=v_scale,
            pos_offset=pos_offset, return_lse=return_lse,
        )
    if precision is not None:
        return _ref.decode_attention_scaled_ref(
            q, k, v, position, precision=precision, window=window,
            scale=scale, pos_offset=pos_offset, return_lse=return_lse,
        )
    return _ref.decode_attention_ref(q, k, v, position, window=window,
                                     scale=scale, pos_offset=pos_offset,
                                     return_lse=return_lse)


def latent_decode_attention(q, pool, position, block_table, *, v_width,
                            scale, impl=None):
    """Absorbed latent attention (MLA) of one new token per sequence
    against the serving engine's latent page pool.

    ``q``: (B, H, D), each head's query in latent space followed by its
    rotary part; ``pool``: (P, 1, bs, D) pages of latent rows (the latent,
    then the shared rotary key); ``block_table``: (B, NB) int32 pool slots.
    Every head attends over the rows' whole width, and the values are their
    first ``v_width`` columns, so the pool is read once for both. Returns
    (B, H, v_width). Its Pallas form is an instance of ``decode_attention``'s
    paged kernel and takes that op's ``pages`` geometry; it has no block
    table or partition rule of its own (one device only: the engine refuses
    a mesh for a latent pool)."""
    if pool.ndim != 4 or pool.shape[1] != 1 or pool.shape[3] != q.shape[-1]:
        raise ValueError(
            f"latent_decode_attention: pool must be (P, 1, bs, {q.shape[-1]})"
            f", got {pool.shape}")
    return kernel_call("latent_decode_attention", q, pool, position,
                       block_table, v_width=v_width, scale=scale, impl=impl)


@registry.register_stream_kernel("latent_decode_attention")
def _latent_stream(q, pool, position, block_table, *, v_width, scale,
                   interpret=False):
    from repro.kernels import paged_attention as _pa

    return _pa.latent_decode_attention_pallas(
        q, pool, position, block_table, v_width=v_width, scale=scale,
        interpret=interpret,
    )


@registry.register_kernel("latent_decode_attention", impl="xla")
def _latent_xla(q, pool, position, block_table, *, v_width, scale):
    return _xla.decode_attention_xla(
        q, pool, pool[..., :v_width], position, scale=scale,
        block_table=block_table,
    )


@registry.register_kernel("latent_decode_attention", impl="ref")
def _latent_ref(q, pool, position, block_table, *, v_width, scale):
    return _ref.decode_attention_paged_ref(
        q, pool, pool[..., :v_width], block_table, position, scale=scale,
    )


# ---------------------------------------------------------------------------
# Chunked linear attention with data-dependent decay (RWKV6 / SSD)
# ---------------------------------------------------------------------------

# per-token decay floor; the chunked kernels exponentiate at most
# chunk * |W_LOG_FLOOR| in one fp32 exp, so chunk is bounded by _MAX_CHUNK_EXP
# (log(f32max) ~= 88.7, kept with margin). The chunk default lives in
# registry.block_defaults("linear_attention").
W_LOG_FLOOR = -2.5
_MAX_CHUNK_EXP = 85.0


def linear_attention(r, k, v, w_log, u=None, s0=None, *, impl=None, mesh=None,
                     chunk=None):
    """Chunked scan: S_t = diag(exp(w_t)) S_{t-1} + k_t v_t^T.

    u given  => RWKV6 read-out (o_t from S_{t-1} plus u-bonus for token t)
    u None   => SSD/Mamba read-out (o_t from S_t)
    Returns (o (B,H,T,M), S_final (B,H,N,M)).
    """
    chunk = resolve_blocks("linear_attention", chunk=chunk)["chunk"]
    # ref runs the exact per-token scan and never exponentiates a chunk span
    exact = resolve_impl(impl, "linear_attention") == "ref"
    if not exact and chunk * -W_LOG_FLOOR > _MAX_CHUNK_EXP:
        raise ValueError(
            f"chunk={chunk} overflows fp32: chunk * |W_LOG_FLOOR| = "
            f"{chunk * -W_LOG_FLOOR} must stay <= {_MAX_CHUNK_EXP} "
            f"(max chunk {int(_MAX_CHUNK_EXP / -W_LOG_FLOOR)})"
        )
    w_log = jnp.maximum(w_log, W_LOG_FLOOR)
    return _dispatch(
        "linear_attention", r, k, v, w_log, u, s0, chunk=chunk, mesh=mesh,
        impl=impl,
    )


@registry.register_stream_kernel("linear_attention")
def _la_stream(r, k, v, w_log, u, s0, *, chunk, interpret=False):
    from repro.kernels import rwkv6 as _rwkv

    return _rwkv.linear_attention_pallas(
        r, k, v, w_log, u, s0, chunk=chunk, interpret=interpret
    )


@registry.register_kernel("linear_attention", impl="xla")
def _la_xla(r, k, v, w_log, u, s0, *, chunk):
    return _xla.linear_attention_xla(r, k, v, w_log, u, s0, chunk=chunk)


@registry.register_kernel("linear_attention", impl="ref")
def _la_ref(r, k, v, w_log, u, s0, *, chunk=None):
    return _ref.linear_attention_scan_ref(r, k, v, w_log, u, s0)


def linear_attention_step(r, k, v, w_log, u, S):
    """Single-token decode step. r,k: (B,H,N); v: (B,H,M); S: (B,H,N,M)."""
    w_log = jnp.maximum(w_log, W_LOG_FLOOR)
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w_log))
    kv = jnp.einsum("bhn,bhm->bhnm", kf, vf)
    S_new = jnp.exp(wf)[..., None] * S + kv
    if u is None:
        o = jnp.einsum("bhn,bhnm->bhm", rf, S_new)
    else:
        o = jnp.einsum("bhn,bhnm->bhm", rf, S) + jnp.einsum(
            "bhn,bhn,bhm->bhm", rf, u[None] * kf, vf
        )
    return o.astype(v.dtype), S_new


# ---------------------------------------------------------------------------
# SpMM (sparse-dense, ELL value/index rows)
# ---------------------------------------------------------------------------


def spmm(values, cols=None, dense=None, *, impl=None, mesh=None, bm=None):
    """ELL sparse-dense matmul. Either ``spmm(A, dense)`` with A an
    EllMatrix, or the unpacked ``spmm(values, cols, dense)``."""
    if isinstance(values, EllMatrix):
        if cols is not None and dense is not None:
            raise TypeError(
                "spmm(A, dense): extra operand alongside the EllMatrix form"
            )
        if dense is None:  # positional form: spmm(A, dense)
            dense = cols
        values, cols = values.values, values.cols
    if cols is None or dense is None:
        raise TypeError("spmm: cols and dense operands are required")
    blocks = resolve_blocks("spmm", bm=bm)
    return _dispatch("spmm", values, cols, dense, mesh=mesh, impl=impl,
                     **blocks)


@registry.register_stream_kernel("spmm")
def _spmm_stream(values, cols, dense, *, bm=None, interpret=False):
    from repro.kernels import spmm as _spmm

    return _spmm.spmm_pallas(values, cols, dense, bm=bm, interpret=interpret)


@registry.register_kernel("spmm", impl="xla")
@registry.register_kernel("spmm", impl="ref")
def _spmm_ref(values, cols, dense, *, bm=None):
    return _ref.spmm_ref(values, cols, dense)


def bsr_spmm(tile_values, tile_rows=None, tile_cols=None, dense=None,
             num_rows=None, *, impl=None, mesh=None, bf=None):
    """Block-sparse rows x dense (the MXU-native sparse-dense form).

    Either ``bsr_spmm(A, dense)`` with A a BsrMatrix, or the unpacked
    ``bsr_spmm(tile_values, tile_rows, tile_cols, dense, num_rows)``.
    """
    if isinstance(tile_values, BsrMatrix):
        A = tile_values
        if (tile_cols is not None or num_rows is not None
                or (tile_rows is not None and dense is not None)):
            raise TypeError(
                "bsr_spmm(A, dense): extra operands alongside the BsrMatrix form"
            )
        if dense is None:  # positional form: bsr_spmm(A, dense)
            dense = tile_rows
        tile_values, tile_rows, tile_cols = A.tile_values, A.tile_rows, A.tile_cols
        num_rows = A.shape[0]
    if tile_rows is None or tile_cols is None or dense is None or num_rows is None:
        raise TypeError(
            "bsr_spmm: tile coordinates, dense operand and num_rows are required"
        )
    blocks = resolve_blocks("bsr_spmm", bf=bf)
    return _dispatch(
        "bsr_spmm", tile_values, tile_rows, tile_cols, dense,
        num_rows=num_rows, mesh=mesh, impl=impl, **blocks,
    )


@registry.register_stream_kernel("bsr_spmm")
def _bsr_stream(tile_values, tile_rows, tile_cols, dense, num_rows,
                *, bf=None, interpret=False):
    from repro.kernels import spmm as _spmm

    return _spmm.bsr_spmm_pallas(
        tile_values, tile_rows, tile_cols, dense, num_rows, bf=bf,
        interpret=interpret,
    )


@registry.register_kernel("bsr_spmm", impl="xla")
@registry.register_kernel("bsr_spmm", impl="ref")
def _bsr_xla(tile_values, tile_rows, tile_cols, dense, num_rows, *, bf=None):
    return _xla.bsr_spmm_xla(tile_values, tile_rows, tile_cols, dense, num_rows)


# ---------------------------------------------------------------------------
# SpMSpM (sparse-sparse, index intersection)
# ---------------------------------------------------------------------------


def spmspm(a_values, a_cols, b_values=None, b_rows=None, contraction_dim=None,
           *, impl=None, mesh=None, bm=None, bn=None):
    """Sparse x sparse by index intersection. Either ``spmspm(A, B, k)`` with
    ELL operands (B holding the right matrix's columns), or unpacked arrays.
    """
    if isinstance(a_values, EllMatrix):
        A, B = a_values, a_cols
        if not isinstance(B, EllMatrix):
            raise TypeError("spmspm(A, B, k): B must also be an EllMatrix")
        if b_rows is not None or (b_values is not None
                                  and contraction_dim is not None):
            raise TypeError(
                "spmspm(A, B, k): extra operands alongside the EllMatrix form"
            )
        if b_values is not None:  # positional form: spmspm(A, B, k)
            contraction_dim = b_values
        a_values, a_cols = A.values, A.cols
        b_values, b_rows = B.values, B.cols
    if b_values is None or b_rows is None or contraction_dim is None:
        raise TypeError(
            "spmspm: b_values, b_rows and contraction_dim are required"
        )
    blocks = resolve_blocks("spmspm", bm=bm, bn=bn)
    return _dispatch(
        "spmspm", a_values, a_cols, b_values, b_rows,
        contraction_dim=contraction_dim, mesh=mesh, impl=impl, **blocks,
    )


@registry.register_stream_kernel("spmspm")
def _spmspm_stream(a_values, a_cols, b_values, b_rows, contraction_dim,
                   *, bm=None, bn=None, interpret=False):
    from repro.kernels import spmspm as _spmspm

    return _spmspm.spmspm_pallas(
        a_values, a_cols, b_values, b_rows, contraction_dim,
        bm=bm, bn=bn, interpret=interpret,
    )


@registry.register_kernel("spmspm", impl="xla")
def _spmspm_xla(a_values, a_cols, b_values, b_rows, contraction_dim,
                *, bm=None, bn=None):
    return _xla.spmspm_xla(a_values, a_cols, b_values, b_rows, contraction_dim)


@registry.register_kernel("spmspm", impl="ref")
def _spmspm_ref(a_values, a_cols, b_values, b_rows, contraction_dim,
                *, bm=None, bn=None):
    return _ref.spmspm_ref(a_values, a_cols, b_values, b_rows, contraction_dim)


# ---------------------------------------------------------------------------
# Stencil (indirect offset streams, periodic boundary)
# ---------------------------------------------------------------------------


def stencil(grid, offsets: np.ndarray, weights, *, impl=None, mesh=None,
            bx=None, overlap=True):
    """``overlap`` double-buffers the sharded halo exchange (interior rows
    compute while the boundary planes fly); ``overlap=False`` is the
    synchronous pad-then-kernel oracle. No-op on a single device."""
    blocks = resolve_blocks("stencil", bx=bx)
    return _dispatch("stencil", grid, offsets=offsets, weights=weights,
                     mesh=mesh, impl=impl, overlap=overlap, **blocks)


@registry.register_stream_kernel("stencil")
def _stencil_stream(grid, offsets, weights, *, bx=None, interpret=False):
    from repro.kernels import stencil as _stencil

    return _stencil.stencil_pallas(grid, offsets, weights, bx=bx,
                                   interpret=interpret)


@registry.register_kernel("stencil", impl="xla")
@registry.register_kernel("stencil", impl="ref")
def _stencil_ref(grid, offsets, weights, *, bx=None):
    return _ref.stencil_ref(grid, offsets, weights)
