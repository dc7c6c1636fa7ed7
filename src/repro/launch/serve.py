"""Batched serving launcher: prefill a batch of prompts, then decode.

CPU-container demo: PYTHONPATH=src python -m repro.launch.serve \
    --arch occamy-gptj --reduced --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry, transformer, multimodal


def scan_prefill(params, cfg, cache, tokens):
    """Prompt prefill for recurrent-cache families (ssm/hybrid/audio):
    scan ``registry.decode_step`` over the prompt inside one jit. Returns
    (last-token logits (B, V_pad), cache after the full prompt)."""
    B, S0 = tokens.shape

    def run(params, cache, tokens):
        def body(c, xs):
            tok, t = xs
            lg, c = registry.decode_step(
                params, cfg, c,
                {"token": tok, "position": jnp.full((B,), t, jnp.int32)},
            )
            return c, lg

        xs = (tokens.T, jnp.arange(S0, dtype=jnp.int32))
        cache, logits = jax.lax.scan(body, cache, xs)
        return logits[-1], cache

    return jax.jit(run, donate_argnums=(1,))(params, cache, tokens)


def generate(cfg, params, tokens, gen_len: int, max_len: int,
             extra_batch: dict | None = None, greedy: bool = True):
    """tokens: (B, S0) prompt; returns (B, S0+gen_len)."""
    B, S0 = tokens.shape
    if cfg.family in ("dense", "moe", "vlm"):
        batch = {"tokens": tokens}
        if cfg.family == "vlm" and extra_batch:
            batch["patches"] = extra_batch["patches"]
        logits, cache = transformer.prefill_step(params, cfg, batch, max_len)
        pos0 = S0 + (cfg.num_patches if cfg.family == "vlm" else 0)
    else:
        # ssm / hybrid / audio: feed the prompt through decode_step — as ONE
        # jitted lax.scan over the prompt axis, not a per-token Python loop
        # (the old loop retraced/dispatched decode_step S0 times un-jitted;
        # the scan traces the body once, so prefill cost is one compile +
        # one device launch regardless of prompt length)
        cache = registry.init_cache(cfg, B, max_len)
        if cfg.family == "audio" and extra_batch:
            ck, cv = multimodal.build_cross_cache(
                params, cfg, extra_batch["frames"]
            )
            cache["cross_k"], cache["cross_v"] = ck, cv
        logits, cache = scan_prefill(params, cfg, cache, tokens)
        logits = logits[:, None, :]
        pos0 = S0

    step = jax.jit(lambda p, c, b: registry.decode_step(p, cfg, c, b),
                   donate_argnums=(1,))
    last = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1).astype(jnp.int32)
    out = [last]
    for i in range(gen_len - 1):
        logits_i, cache = step(
            params, cache,
            {"token": last, "position": jnp.full((B,), pos0 + i, jnp.int32)},
        )
        last = jnp.argmax(logits_i[:, : cfg.vocab_size], axis=-1).astype(jnp.int32)
        out.append(last)
    return jnp.concatenate([tokens, jnp.stack(out, 1)], axis=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="occamy-gptj")
    ap.add_argument("--reduced", action="store_true",
                    help="the tiny same-family config instead of the published one")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    rng = np.random.default_rng(0)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32,
    )
    extra = None
    if cfg.family == "vlm":
        extra = {"patches": jnp.asarray(
            rng.standard_normal((args.batch, cfg.num_patches, cfg.d_model)),
            jnp.dtype(cfg.dtype))}
    if cfg.family == "audio":
        extra = {"frames": jnp.asarray(
            rng.standard_normal((args.batch, cfg.encoder_seq, cfg.d_model)),
            jnp.dtype(cfg.dtype))}

    max_len = args.prompt_len + args.gen + (cfg.num_patches or 0) + 1
    t0 = time.time()
    out = generate(cfg, params, tokens, args.gen, max_len, extra)
    dt = time.time() - t0
    toks = args.batch * args.gen
    print(f"generated {out.shape} in {dt:.2f}s = {toks/dt:.1f} tok/s")
    print("sample:", np.asarray(out[0, -args.gen:]))


if __name__ == "__main__":
    main()
