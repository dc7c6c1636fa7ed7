"""Per-arch smoke tests (required): every assigned architecture instantiates
a REDUCED same-family config and runs one forward + one train step on CPU,
asserting output shapes and no NaNs. Plus decode-vs-forward consistency."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SHAPES, all_arch_ids, get_config
from repro.models import layers as L
from repro.models import multimodal, registry, transformer
from repro.runtime import train_loop

ARCHS = all_arch_ids()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_no_nan(arch):
    cfg = get_config(arch, reduced=True)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = registry.make_batch(cfg, SHAPES["train_4k"], batch_override=B,
                                seq_override=S)
    logits, aux = registry.forward(params, cfg, batch)
    assert logits.shape == (B, S, L.padded_vocab(cfg.vocab_size))
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_config(arch, reduced=True)
    state = train_loop.init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(train_loop.make_train_step(cfg))
    batch = registry.make_batch(cfg, SHAPES["train_4k"], batch_override=2,
                                seq_override=16)
    state, metrics = step(state, batch)
    assert float(metrics["loss"]) > 0 and not jnp.isnan(metrics["loss"])
    assert float(metrics["grad_norm"]) > 0
    assert int(state["opt"]["step"]) == 1
    # params actually moved
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(
                            train_loop.init_train_state(
                                cfg, jax.random.PRNGKey(0))["params"]))
    )
    assert moved


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_config(arch, reduced=True)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    cache = registry.init_cache(cfg, 2, 16)
    batch = registry.make_batch(cfg, SHAPES["decode_32k"], batch_override=2,
                                seq_override=16)
    logits, cache2 = registry.decode_step(params, cfg, cache, batch)
    assert logits.shape == (2, L.padded_vocab(cfg.vocab_size))
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert jax.tree.structure(cache2) == jax.tree.structure(cache)


@pytest.mark.parametrize(
    "arch",
    ["qwen3-14b", "grok-1-314b", "hymba-1.5b", "rwkv6-3b", "whisper-large-v3"],
)
def test_decode_matches_forward(arch):
    """Incremental decode must reproduce teacher-forced logits exactly."""
    cfg = get_config(arch, reduced=True)
    if cfg.num_experts:
        cfg = cfg.replace(capacity_factor=8.0)  # no drops => exact match
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    S = 10
    b = registry.make_batch(cfg, SHAPES["prefill_32k"], batch_override=2,
                            seq_override=S)
    full, _ = registry.forward(params, cfg, b)
    cache = registry.init_cache(cfg, 2, S)
    if cfg.family == "audio":
        ck, cv = multimodal.build_cross_cache(params, cfg, b["frames"])
        cache["cross_k"], cache["cross_v"] = ck, cv
    step = jax.jit(lambda p, c, db: registry.decode_step(p, cfg, c, db))
    outs = []
    for t in range(S):
        db = {"token": b["tokens"][:, t],
              "position": jnp.full((2,), t, jnp.int32)}
        lg, cache = step(params, cache, db)
        outs.append(lg)
    dec = jnp.stack(outs, 1)
    err = float(jnp.max(jnp.abs(dec - full.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(full)))
    assert err / scale < 2e-2, err / scale


def test_vlm_prefill_then_decode():
    cfg = get_config("pixtral-12b", reduced=True)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    S, P = 12, cfg.num_patches
    b = registry.make_batch(cfg, SHAPES["prefill_32k"], batch_override=2,
                            seq_override=S)
    full, _ = registry.forward(params, cfg, b)
    plog, cache = transformer.prefill_step(
        params, cfg, {"tokens": b["tokens"][:, :4], "patches": b["patches"]},
        max_len=S,
    )
    errs = [float(jnp.max(jnp.abs(
        plog.astype(jnp.float32) - full[:, : P + 4].astype(jnp.float32))))]
    step = jax.jit(lambda p, c, db: registry.decode_step(p, cfg, c, db))
    for t in range(4, S - P):
        db = {"token": b["tokens"][:, t],
              "position": jnp.full((2,), P + t, jnp.int32)}
        lg, cache = step(params, cache, db)
        errs.append(float(jnp.max(jnp.abs(lg - full[:, P + t].astype(jnp.float32)))))
    assert max(errs) / float(jnp.max(jnp.abs(full))) < 2e-2


def test_sliding_window_matches_full_for_short_seq():
    """window >= seq must equal full attention exactly."""
    cfg = get_config("qwen3-14b", reduced=True)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    b = registry.make_batch(cfg, SHAPES["prefill_32k"], batch_override=2,
                            seq_override=8)
    full, _ = registry.forward(params, cfg, b)
    win, _ = registry.forward(params, cfg.replace(sliding_window=64), b)
    assert float(jnp.max(jnp.abs(full.astype(jnp.float32) -
                                 win.astype(jnp.float32)))) < 1e-4


def test_vocab_padding_never_predicted():
    cfg = get_config("whisper-large-v3", reduced=True).replace(vocab_size=500)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    b = registry.make_batch(cfg, SHAPES["train_4k"], batch_override=1,
                            seq_override=8)
    loss = registry.loss_fn(params, cfg, b)
    assert jnp.isfinite(loss)  # padded tail masked to -1e30, not NaN


def test_dense_init_scales_by_the_input_axis():
    # stacked (layers, in, out): the fan-in is `in`, not the layer count
    w = L.dense_init(jax.random.PRNGKey(0), (28, 256, 64), dtype=jnp.float32)
    assert abs(float(jnp.std(w)) * 16 - 1) < 0.05
    cfg = get_config("occamy-gptj", reduced=True)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    wi = params["layers"]["wi"].astype(jnp.float32)
    assert abs(float(jnp.std(wi)) * cfg.d_model ** 0.5 - 1) < 0.05
