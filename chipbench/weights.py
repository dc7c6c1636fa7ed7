"""Seeded random weights, made by the benchmark and handed to the engine.

The weights are drawn on the device by one jitted program from ``--seed``,
in the dtype they are served in. Every leaf, and every layer of a stacked
leaf, has a key of its own (``fold_in`` of the leaf's name, then of the
layer index), so the reference can draw one layer at a time and get the
same values as the stacked leaf the engine holds.

Only the shapes and the names of the leaves follow the engine's parameter
tree; the values come from here, never from the program's own init.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

VOCAB_PAD_MULTIPLE = 128  # the engine pads its embedding rows to this


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD_MULTIPLE) * VOCAB_PAD_MULTIPLE


def seed_key(seed):
    """A PRNG key from a seed of up to 64 bits, as two 32-bit words, so that
    one compiled program serves every seed."""
    seed = int(seed) % (1 << 64)
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32))


def root_of(lo, hi):
    """The key every leaf is folded from, for a seed split by ``seed_key``."""
    k = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(k, lo), hi)


def leaf_specs(m: dict) -> dict:
    """name -> (shape of one layer or of the leaf, stacked, kind, scale).

    ``m`` holds a configuration file's ``model`` sizes. Kinds: ``norm`` (1 + 0.1 N),
    ``dense`` (N / sqrt(fan-in) unless a scale is given)."""
    d, f, hd = m["d_model"], m["d_ff"], m["head_dim"]
    H, K = m["num_heads"], m["num_kv_heads"]
    vp = padded_vocab(m["vocab"])
    specs = {
        "layers/attn_norm": ((d,), True, "norm", None),
        "layers/wq": ((d, H * hd), True, "dense", None),
        "layers/wk": ((d, K * hd), True, "dense", None),
        "layers/wv": ((d, K * hd), True, "dense", None),
        "layers/wo": ((H * hd, d), True, "dense", None),
        "layers/wi": ((d, f), True, "dense", None),
        "layers/wo_mlp": ((f, d), True, "dense", None),
        "embed": ((vp, d), False, "dense", m["embed_scale"]),
        "final_norm": ((d,), False, "norm", None),
    }
    if m["gated"]:
        specs["layers/wg"] = ((d, f), True, "dense", None)
    if not m["parallel_block"]:
        specs["layers/mlp_norm"] = ((d,), True, "norm", None)
    if not m["tie_embeddings"]:
        specs["lm_head"] = ((d, vp), False, "dense", None)
    return specs


def draw(root, name: str, layer, shape, kind: str, scale, dtype):
    """One leaf (or one layer of a stacked leaf), rounded to ``dtype``."""
    k = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "norm":
        return (1.0 + 0.1 * z).astype(dtype)
    s = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
    return (z * s).astype(dtype)


def make_params(m: dict, seed, dtype):
    """The whole tree on the device, drawn by one jitted program."""
    specs = leaf_specs(m)
    nl = m["num_layers"]

    def build(lo, hi):
        root = root_of(lo, hi)
        out: dict = {"layers": {}}
        for name, (shape, stacked, kind, scale) in specs.items():
            if stacked:
                leaf = jax.vmap(lambda i, n=name, s=shape, k=kind, c=scale:
                                draw(root, n, i, s, k, c, dtype))(
                    jnp.arange(nl))
                out["layers"][name.split("/", 1)[1]] = leaf
            else:
                out[name] = draw(root, name, None, shape, kind, scale, dtype)
        return out

    lo, hi = seed_key(seed)
    return jax.block_until_ready(jax.jit(build)(lo, hi))


def layer_weights(m: dict, root, layer, dtype=jnp.float32) -> dict:
    """Layer ``layer``'s weights alone, as the stacked leaves hold them,
    widened to ``dtype`` after rounding to the served dtype."""
    served = jnp.dtype(m["dtype"])
    out = {}
    for name, (shape, stacked, kind, scale) in leaf_specs(m).items():
        if stacked:
            w = draw(root, name, layer, shape, kind, scale, served)
            out[name.split("/", 1)[1]] = w.astype(dtype)
    return out


def top_weight(m: dict, root, name: str, dtype=jnp.float32):
    """An unstacked leaf (``embed``, ``final_norm``, ``lm_head``)."""
    shape, _, kind, scale = leaf_specs(m)[name]
    return draw(root, name, None, shape, kind, scale,
                jnp.dtype(m["dtype"])).astype(dtype)
