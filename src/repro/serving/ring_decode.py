"""Cache-sharded ring decode over the ``data`` axis.

The serving case: a paged KV cache bigger than one device's HBM. The page
pools shard across ``data`` — rank r owns the pages holding every
sequence's logical cache blocks ``[r*NB_l, (r+1)*NB_l)`` — and each decode
step folds per-shard attention partials into the exact softmax:

  1. every rank runs the registered paged ``decode_attention`` over its
     local table slab with ``pos_offset = r * NB_l * bs`` and
     ``return_lse=True`` → a partial ``(o_r, lse_r)``;
  2. the partials rotate through ``collectives.ring_scan`` (the same
     double-buffered ppermute ring flash attention hops KV through —
     ``overlap=True`` flies hop t+1 behind hop t's fold);
  3. each rank stashes every arriving partial at its *global* shard index
     and folds the full set in rank order 0..n-1 through
     ``collectives.online_softmax_merge``.

Folding in global order — not arrival order, which differs per rank — is
what makes the result *replicated bitwise*: every rank performs the
identical merge chain, so the output legally carries a replicated
out_spec and is bit-equal to ``ring_decode_reference`` (the same chain on
one device). Fully-masked shards (a sequence shorter than a shard's base
offset) carry ``lse ≈ NEG_LSE`` and merge as exact no-ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops, registry
from repro.parallel import collectives

__all__ = ["ring_decode", "ring_decode_reference"]


def _shard_partial(q, k_pool, v_pool, block_table, position, *, layer,
                   base, window, scale, k_scale, v_scale, impl):
    """One shard's paged decode partial over layer ``layer`` of its stacked
    pools: (o, lse), lse fp32 (B, H).

    Calls the registered impl directly (the partition-rule idiom) — this
    runs inside ``shard_map``, below the mesh-aware dispatch seam."""
    return registry.kernel_call(
        "decode_attention", q, k_pool, v_pool, position, impl=impl,
        window=window, scale=scale, block_table=block_table,
        k_scale=k_scale, v_scale=v_scale, pos_offset=base, return_lse=True,
        layer=layer,
    )


def ring_decode(q, k_pool, v_pool, block_table, position, mesh, *,
                axis: str = "data", window: int = 0, scale=None,
                k_scale=None, v_scale=None, impl=None, overlap: bool = True,
                layer=None):
    """Decode against a cache sharded over ``mesh[axis]``.

    Args: ``q`` (B, H, D) and ``position`` (B,) — replicated; ``k_pool``/
    ``v_pool`` (P, K, bs, D) — sharded on P (rank r holds pages
    ``[r*P/n, (r+1)*P/n)``), or with ``layer`` the engine's stacked
    (nl, P, K, bs, D) pools, sharded on P alike and read at that layer in
    place; ``block_table`` (B, NB) — sharded on columns,
    with the convention that each entry indexes the *owning rank's local*
    pool (the engine's per-shard allocators hand out local page ids);
    ``k_scale``/``v_scale`` — optional pool scales, (P, K, bs, 1) or
    stacked, sharded like the pools. ``overlap=False`` is the
    synchronous-ring oracle — bit-identical fold values, only transfer
    issue order differs.

    Returns (B, H, D) in ``q.dtype``, replicated across ``axis`` and
    bitwise-equal to ``ring_decode_reference`` on the unsharded operands.
    """
    layer, (k_pool, v_pool, k_scale, v_scale) = ops.stacked_pools(
        layer, k_pool, v_pool, k_scale, v_scale)
    n = mesh.shape[axis]
    B, NB = block_table.shape
    bs = k_pool.shape[3]
    if NB % n or k_pool.shape[1] % n:
        raise ValueError(
            f"ring_decode: table columns ({NB}) and pool pages "
            f"({k_pool.shape[1]}) must divide the {axis} axis ({n})"
        )
    nb_l = NB // n

    def local(q_l, k_l, v_l, tbl_l, pos_l, ks_l, vs_l, layer_l):
        me = jax.lax.axis_index(axis)
        o_l, lse_l = _shard_partial(
            q_l, k_l, v_l, tbl_l, pos_l, layer=layer_l, base=me * nb_l * bs,
            window=window, scale=scale, k_scale=ks_l, v_scale=vs_l,
            impl=impl,
        )
        # rotate the partials; stash each at its GLOBAL shard index so the
        # final merge chain is identical (and the output replicated) on
        # every rank
        buf_o = jnp.zeros((n,) + o_l.shape, jnp.float32)
        buf_lse = jnp.full((n,) + lse_l.shape, collectives.NEG_LSE,
                           jnp.float32)

        def stash(carry, blk, t):
            bo, bl = carry
            o_t, lse_t = blk
            src = (me - t) % n
            return bo.at[src].set(o_t), bl.at[src].set(lse_t)

        bo, bl = collectives.ring_scan(
            stash, (buf_o, buf_lse), (o_l.astype(jnp.float32), lse_l),
            axis, n, overlap=overlap,
        )
        o_acc = jnp.zeros(o_l.shape, jnp.float32)
        lse_acc = jnp.full(lse_l.shape, collectives.NEG_LSE, jnp.float32)
        for r in range(n):
            o_acc, lse_acc = collectives.online_softmax_merge(
                o_acc, lse_acc, bo[r], bl[r]
            )
        return o_acc.astype(q_l.dtype)

    pool_spec = P(None, axis, None, None, None)
    scale_spec = pool_spec if k_scale is not None else P()
    args = (q, k_pool, v_pool, block_table, position,
            k_scale if k_scale is not None else jnp.zeros(()),
            v_scale if v_scale is not None else jnp.zeros(()),
            jnp.asarray(layer, jnp.int32))

    def wrapped(q_l, k_l, v_l, tbl_l, pos_l, ks_l, vs_l, layer_l):
        if k_scale is None:
            ks_l = vs_l = None
        return local(q_l, k_l, v_l, tbl_l, pos_l, ks_l, vs_l, layer_l)

    return jax.shard_map(
        wrapped,
        mesh=mesh,
        in_specs=(P(None, None, None), pool_spec, pool_spec, P(None, axis),
                  P(None), scale_spec, scale_spec, P()),
        out_specs=P(None, None, None),
        check_vma=False,
    )(*args)


def ring_decode_reference(q, k_pool, v_pool, block_table, position, n, *,
                          window: int = 0, scale=None, k_scale=None,
                          v_scale=None, impl=None, layer=None):
    """Single-device simulation of the n-shard merge chain: the same
    per-shard paged partials, folded in the same global order — the
    bitwise oracle for ``ring_decode`` (and itself allclose to plain
    contiguous ``decode_attention``, which sums the cache in one scan
    rather than via the merge chain)."""
    layer, (k_pool, v_pool, k_scale, v_scale) = ops.stacked_pools(
        layer, k_pool, v_pool, k_scale, v_scale)
    B, NB = block_table.shape
    bs = k_pool.shape[3]
    nb_l = NB // n
    p_l = k_pool.shape[1] // n
    o_acc = jnp.zeros(q.shape, jnp.float32)
    lse_acc = jnp.full(q.shape[:2], collectives.NEG_LSE, jnp.float32)
    for r in range(n):
        sl = (slice(None), slice(r * p_l, (r + 1) * p_l))
        o_r, lse_r = _shard_partial(
            q, k_pool[sl], v_pool[sl],
            block_table[:, r * nb_l:(r + 1) * nb_l], position, layer=layer,
            base=r * nb_l * bs, window=window, scale=scale,
            k_scale=None if k_scale is None else k_scale[sl],
            v_scale=None if v_scale is None else v_scale[sl],
            impl=impl,
        )
        o_acc, lse_acc = collectives.online_softmax_merge(
            o_acc, lse_acc, o_r.astype(jnp.float32), lse_r
        )
    return o_acc.astype(q.dtype)
