"""Family dispatch: one uniform API over all model families.

  init_params(cfg, rng)            -> params pytree
  forward(params, cfg, batch)      -> (logits, aux)   [train / prefill]
  loss_fn(params, cfg, batch)      -> scalar
  cache_spec / init_cache          -> decode-state pytree (ShapeDtypeStructs / zeros)
  decode_step(params, cfg, cache, batch) -> (logits, cache)
  input_specs(cfg, shape)          -> dict of ShapeDtypeStruct (dry-run stand-ins)
  make_batch(cfg, shape, rng, batch_override) -> concrete synthetic batch
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import hybrid, multimodal, ssm, transformer


def _family_mod(cfg: ModelConfig):
    return {
        "dense": transformer,
        "moe": transformer,
        "vlm": transformer,
        "ssm": ssm,
        "hybrid": hybrid,
        "audio": multimodal,
    }[cfg.family]


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg, rng):
    """One compiled program: each weight is drawn, scaled and cast in one
    fused pass, so no float32 temporary of a whole weight is ever held."""
    return _family_mod(cfg).init_params(cfg, rng)


def param_shapes(cfg):
    """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))
    )


def forward(params, cfg, batch, **kw):
    return _family_mod(cfg).forward(params, cfg, batch, **kw)


def loss_fn(params, cfg, batch, **kw):
    return _family_mod(cfg).loss_fn(params, cfg, batch, **kw)


def cache_spec(cfg, batch: int, max_len: int):
    return _family_mod(cfg).cache_spec(cfg, batch, max_len)


def init_cache(cfg, batch: int, max_len: int):
    return _family_mod(cfg).init_cache(cfg, batch, max_len)


def decode_step(params, cfg, cache, batch):
    return _family_mod(cfg).decode_step(params, cfg, cache, batch)


# ---------------------------------------------------------------------------
# input specs per (arch x shape) cell
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    emb = jnp.dtype(cfg.dtype)
    d = cfg.d_model

    if shape.kind in ("train", "prefill"):
        specs = {}
        s_text = S
        if cfg.family == "vlm":
            s_text = S - cfg.num_patches
            specs["patches"] = jax.ShapeDtypeStruct((B, cfg.num_patches, d), emb)
        if cfg.family == "audio":
            specs["frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_seq, d), emb)
        specs["tokens"] = jax.ShapeDtypeStruct((B, s_text), i32)
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
        return specs

    assert shape.kind == "decode"
    return {
        "token": jax.ShapeDtypeStruct((B,), i32),
        "position": jax.ShapeDtypeStruct((B,), i32),
    }


def make_batch(cfg: ModelConfig, shape: ShapeSpec, rng=None,
               batch_override: int | None = None, seq_override: int | None = None):
    """Concrete synthetic batch matching input_specs (for smoke tests)."""
    rng = rng if rng is not None else np.random.default_rng(0)
    B = batch_override or shape.global_batch
    S = seq_override or shape.seq_len
    d = cfg.d_model
    out = {}
    if shape.kind in ("train", "prefill"):
        s_text = S
        if cfg.family == "vlm":
            s_text = S - cfg.num_patches
            out["patches"] = jnp.asarray(
                rng.standard_normal((B, cfg.num_patches, d)), jnp.dtype(cfg.dtype)
            )
        if cfg.family == "audio":
            out["frames"] = jnp.asarray(
                rng.standard_normal((B, cfg.encoder_seq, d)), jnp.dtype(cfg.dtype)
            )
        out["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, s_text)), jnp.int32
        )
        if shape.kind == "train":
            labels = rng.integers(0, cfg.vocab_size, (B, S))
            if cfg.family == "vlm":
                labels[:, : cfg.num_patches] = -1  # no loss on image positions
            out["labels"] = jnp.asarray(labels, jnp.int32)
    else:
        out["token"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)), jnp.int32)
        out["position"] = jnp.asarray(
            rng.integers(S // 2, S - 1, (B,)), jnp.int32
        )
    return out
