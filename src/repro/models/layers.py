"""Shared neural-net building blocks (functional, pytree params)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops  # noqa: F401  (imports register kernel impls)

VOCAB_PAD_MULTIPLE = 128  # embeddings padded so the vocab dim shards cleanly


def padded_vocab(vocab_size: int, multiple: int = VOCAB_PAD_MULTIPLE) -> int:
    return int(-(-vocab_size // multiple) * multiple)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(
        x.dtype
    )


def head_rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """qk-norm: normalize each head's vector (last dim) independently."""
    return rms_norm(x, weight, eps)


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float):
    """positions: (...,) int -> cos/sin (..., head_dim//2) in float32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (S, D/2) or (B, S, D/2). Half-split pairing."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    if cos.ndim == 2:  # (S, half) -> broadcast over batch and heads
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:  # (B, S, half)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def activation_fn(name: str):
    if name == "swiglu":
        return jax.nn.silu
    if name == "geglu":
        return jax.nn.gelu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu_sq":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def is_gated(name: str) -> bool:
    return name in ("swiglu", "geglu")


def mlp(p: dict, x: jax.Array, activation: str) -> jax.Array:
    """Gated or plain MLP. Gate/up projections are SEPARATE leaves ("wg"/"wi"):
    a fused (d, 2f) weight would make the activation split halve a TP-sharded
    axis, which GSPMD lowers to per-layer collective-permutes."""
    act = activation_fn(activation)
    h = jnp.einsum("bsd,df->bsf", x, p["wi"], preferred_element_type=jnp.float32)
    if is_gated(activation):
        g = jnp.einsum(
            "bsd,df->bsf", x, p["wg"], preferred_element_type=jnp.float32
        )
        h = act(g) * h
    else:
        h = act(h)
    h = h.astype(x.dtype)
    return jnp.einsum(
        "bsf,fd->bsd", h, p["wo"], preferred_element_type=jnp.float32
    ).astype(x.dtype)


def dense_init(rng, shape, scale=None, dtype=jnp.bfloat16):
    """Normal init scaled by 1/sqrt(fan-in). The fan-in is ``shape[-2]``:
    weights are (..., in, out), with stacked layers or experts in front."""
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[-2])
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


class KeyGen:
    """Deterministic per-leaf rng stream."""

    def __init__(self, rng):
        self._rng = rng
        self._i = 0

    def __call__(self):
        self._i += 1
        return jax.random.fold_in(self._rng, self._i)


def cross_entropy_loss(
    logits: jax.Array,  # (B, S, V_pad) — padded vocab tail masked here
    labels: jax.Array,  # (B, S) int32; negative = ignore
    vocab_size: int,
) -> jax.Array:
    lf = logits.astype(jnp.float32)
    v_pad = lf.shape[-1]
    vocab_iota = jnp.arange(v_pad)
    if v_pad > vocab_size:
        lf = jnp.where(vocab_iota >= vocab_size, -1e30, lf)
    logz = jax.nn.logsumexp(lf, axis=-1)
    # one-hot product form: stays local when the vocab dim is TP-sharded
    # (take_along_axis would force an all-gather of the logits under GSPMD)
    onehot = (vocab_iota[None, None, :] == labels[..., None]).astype(jnp.float32)
    gold = jnp.sum(lf * onehot, axis=-1)
    valid = labels >= 0
    nll = jnp.where(valid, logz - gold, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)
