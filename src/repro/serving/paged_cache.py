"""PagedKVCache: block-table KV storage for the serving engine.

Physical layout (``core/sparse.py``-style registered pytree): per layer a
pool of fixed-size KV *pages* — ``k_pool``/``v_pool`` shaped
``(nl, P, K, bs, hd)`` — addressed through per-sequence block tables the
scheduler maintains (``serving/scheduler.py`` owns which physical page
belongs to whom; this module owns the tensors). Page ``NULL_BLOCK`` (0) is
the shared scratch page: inactive decode slots and unwritten table tails
point at it, and the decode mask makes every read of it an exact no-op.

With a ``policy`` (``core.precision``) the pools hold the cache *narrow*:
values in the policy's compute dtype plus per-row fp32 scales
``(nl, P, K, bs, 1)`` from the same per-row quantization
``precision.quantize_kv_cache`` applies — each page is dequantized at use
inside ``decode_attention``'s fp32 online softmax, so the resident cache
(the HBM footprint that dominates serving) shrinks by the width ratio.

Under latent attention (``cfg.mla``) the cache holds no per-head K/V:
``k_pool`` is ``(nl, P, 1, bs, latent_dim)``, one row of ``[c, k_pe]``
(512 + 64 values for DeepSeek-V3's block) per token and layer, read as
both keys and values, and ``v_pool`` is None. The leading dense layers,
which run before the layer scan, keep theirs in ``lead_pool``
(``(first_dense_layers, P, 1, bs, latent_dim)``), so that neither scan
slices or re-stacks the other's pool. Each row is padded with zeros to a
whole number of 128-lane tiles (``LANES``): 576 values take 640 (11 %
more memory). At 576 the TPU compiler lays the stacked pool out with its
page axis minor, and the decode program then relays each layer's pool out
for the kernel and back, two copies of a layer's pool per layer and step;
at 640 it keeps the tiled row-major layout the kernel reads.

Everything here is pure: writes return a new ``PagedKVCache`` (jit/donate
friendly); allocation lives in the scheduler's ``BlockAllocator``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.serving.scheduler import NULL_BLOCK  # re-export: table sentinel

__all__ = ["PagedKVCache", "NULL_BLOCK", "init_paged_cache"]

LANES = 128  # a latent row is padded to a multiple of this many values


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """KV page pools (+ optional quantization scales) for every layer.

    ``k_pool``/``v_pool``: (nl, P, K, bs, hd); ``k_scale``/``v_scale``:
    (nl, P, K, bs, 1) fp32 when ``policy`` is set, else None. ``block_size``
    and ``policy`` are static aux data (they select traced code paths).
    A latent cache has ``v_pool`` None and, with leading dense layers, a
    ``lead_pool`` (module docstring).
    """

    k_pool: jax.Array
    v_pool: jax.Array | None
    k_scale: jax.Array | None
    v_scale: jax.Array | None
    block_size: int
    policy: str | None = None
    lead_pool: jax.Array | None = None

    def tree_flatten(self):
        return (
            (self.k_pool, self.v_pool, self.k_scale, self.v_scale,
             self.lead_pool),
            (self.block_size, self.policy),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        *pools, lead = children
        return cls(*pools, *aux, lead_pool=lead)

    @property
    def num_blocks(self) -> int:
        return self.k_pool.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def latent(self) -> bool:
        return self.v_pool is None

    # -- pure writes --------------------------------------------------------

    def write_prompt(self, block_ids, k_rows, v_rows=None,
                     lead_rows=None) -> "PagedKVCache":
        """Scatter a prefilled prompt's KV into this cache's pages.

        ``block_ids``: (nbp,) int32 physical pages (the allocator's grant,
        in logical order); ``k_rows``/``v_rows``: (nl, nbp, K, bs, hd) — the
        prompt cache reshaped to page granularity (tail page zero-padded;
        the padding is never unmasked). Quantizes per row first when this
        cache holds a narrow policy. A latent cache takes its rows as
        ``k_rows`` and the leading layers' as ``lead_rows``, each (n, nbp,
        1, bs, latent_dim), padded here to the pool's width."""
        if self.latent:
            def put(pool, rows):
                pad = pool.shape[-1] - rows.shape[-1]
                rows = jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, pad),))
                return pool.at[:, block_ids].set(rows.astype(pool.dtype))

            return dataclasses.replace(
                self, k_pool=put(self.k_pool, k_rows),
                lead_pool=(None if lead_rows is None
                           else put(self.lead_pool, lead_rows)))
        k_rows, ks, v_rows, vs = _maybe_quantize(k_rows, v_rows, self.policy)
        new = dataclasses.replace(
            self,
            k_pool=self.k_pool.at[:, block_ids].set(
                k_rows.astype(self.k_pool.dtype)
            ),
            v_pool=self.v_pool.at[:, block_ids].set(
                v_rows.astype(self.v_pool.dtype)
            ),
        )
        if ks is not None:
            new = dataclasses.replace(
                new,
                k_scale=self.k_scale.at[:, block_ids].set(ks),
                v_scale=self.v_scale.at[:, block_ids].set(vs),
            )
        return new

    def gather_blocks(self, block_ids):
        """Host-transferable copy of the listed pages (the preemption
        payload): dict of (nl, n, K, bs, hd) values (+ scales when
        quantized). Bitwise round-trips through ``restore_blocks``."""
        out = {"k": self.k_pool[:, block_ids]}
        if not self.latent:
            out["v"] = self.v_pool[:, block_ids]
        if self.lead_pool is not None:
            out["lead"] = self.lead_pool[:, block_ids]
        if self.quantized:
            out["k_scale"] = self.k_scale[:, block_ids]
            out["v_scale"] = self.v_scale[:, block_ids]
        return out

    def restore_blocks(self, block_ids, payload) -> "PagedKVCache":
        """Write a ``gather_blocks`` payload into (possibly different)
        physical pages — the resume half of the preemption round-trip."""
        new = dataclasses.replace(
            self, k_pool=self.k_pool.at[:, block_ids].set(payload["k"]))
        if not self.latent:
            new = dataclasses.replace(
                new, v_pool=self.v_pool.at[:, block_ids].set(payload["v"]))
        if self.lead_pool is not None:
            new = dataclasses.replace(
                new,
                lead_pool=self.lead_pool.at[:, block_ids].set(
                    payload["lead"]))
        if self.quantized:
            new = dataclasses.replace(
                new,
                k_scale=self.k_scale.at[:, block_ids].set(payload["k_scale"]),
                v_scale=self.v_scale.at[:, block_ids].set(payload["v_scale"]),
            )
        return new


def _maybe_quantize(k_rows, v_rows, policy):
    if policy is None:
        return k_rows, None, v_rows, None
    from repro.core import precision as prec

    kq, ks, vq, vs = prec.quantize_kv_cache(k_rows, v_rows, policy)
    return kq, ks, vq, vs


def init_paged_cache(cfg, *, num_blocks: int, block_size: int,
                     policy: str | None = None) -> PagedKVCache:
    """Zero-initialized pools sized from the model config. With a policy,
    values live in the policy's compute dtype with unit fp32 scales. Under
    latent attention: the latent pools, and no policy. ``cfg`` is duck-typed:
    a config without ``mla`` is a per-head KV cache."""
    if getattr(cfg, "mla", False):
        if policy is not None:
            raise NotImplementedError(
                f"{cfg.name}: the latent cache is held in the model's dtype; "
                f"got the policy {policy!r}")
        dt, nd = jnp.dtype(cfg.dtype), cfg.first_dense_layers

        width = -(-cfg.latent_dim // LANES) * LANES

        def pool(n):
            return jnp.zeros((n, num_blocks, 1, block_size, width), dt)

        return PagedKVCache(
            k_pool=pool(cfg.num_layers - nd), v_pool=None, k_scale=None,
            v_scale=None, block_size=block_size,
            lead_pool=pool(nd) if nd else None)
    hd = cfg.resolved_head_dim()
    K, nl = cfg.num_kv_heads, cfg.num_layers
    if policy is None:
        dt = jnp.dtype(cfg.dtype)
        k_scale = v_scale = None
    else:
        from repro.core import precision as prec

        dt = prec.resolve(policy).compute_dtype
        k_scale = jnp.ones((nl, num_blocks, K, block_size, 1), jnp.float32)
        v_scale = jnp.ones((nl, num_blocks, K, block_size, 1), jnp.float32)
    shape = (nl, num_blocks, K, block_size, hd)
    return PagedKVCache(
        k_pool=jnp.zeros(shape, dt),
        v_pool=jnp.zeros(shape, dt),
        k_scale=k_scale,
        v_scale=v_scale,
        block_size=block_size,
        policy=policy,
    )
