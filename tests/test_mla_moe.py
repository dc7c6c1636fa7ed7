"""DeepSeek-V3's block (Moonlight-16B-A3B) served through the engine at a
reduced size: latent attention over a latent page pool, a leading dense
layer, and an expert layer that holds a share of the routed experts.

The plain reference here is written from the block's equations alone
(float32, per token, every expert computed and weighted where chosen); it
shares no code with the model but the parameter tree it reads."""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import ops
from repro.models import layers, moe, registry
from repro.serving.engine import PagedModel, Request, ServingEngine
from repro.serving.scheduler import NULL_BLOCK

CFG = get_config("moonlight-16b-a3b", reduced=True)  # float32, 16 experts
SHARE = CFG.replace(experts_held=4, experts_first=4)  # rank 1 of four


def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (L, heads, d) at positions 0..L-1, half-split pairing."""
    L, _, d = x.shape
    half = d // 2
    ang = np.arange(L)[:, None] * (1.0 / theta ** (np.arange(half) / half))
    c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _swiglu(x, wi, wg, wo):
    return (_silu(x @ wg) * (x @ wi)) @ wo


def _routed(p, x, cfg):
    """The held experts' part of the routed output, every expert computed
    for every token and weighted where chosen."""
    s = 1.0 / (1.0 + np.exp(-(x @ p["router"])))
    top = np.argsort(-(s + p["router_bias"]), axis=-1, kind="stable")[
        :, : cfg.experts_per_token]
    w = np.take_along_axis(s, top, -1)
    w = w / w.sum(-1, keepdims=True) * cfg.routed_scale
    first, n = cfg.held_experts
    out = np.zeros_like(x)
    for j in range(n):
        y = _swiglu(x, p["moe_wi"][j], p["moe_wg"][j], p["moe_wo"][j])
        out += (w * (top == first + j)).sum(-1, keepdims=True) * y
    return out


def plain_logits(params, cfg, tokens):
    """(L, vocab) float32 logits of one whole sequence."""
    P = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    L = len(tokens)
    h = P["embed"][np.asarray(tokens)]
    lead = [{k[6:]: v[i] for k, v in P.items() if k.startswith("dense_")}
            for i in range(cfg.first_dense_layers)]
    rest = [{k: v[i] for k, v in P["layers"].items()}
            for i in range(cfg.num_layers - cfg.first_dense_layers)]
    causal = np.tril(np.ones((L, L), bool))
    for p in lead + rest:
        x = _rms(h, p["attn_norm"], cfg.norm_eps)
        q = (x @ p["wq"]).reshape(L, H, dn + dr)
        kva = x @ p["wkv_a"]
        c = _rms(kva[:, :r], p["kv_norm"], cfg.norm_eps)
        kv = (c @ p["wkv_b"]).reshape(L, H, dn + dv)
        q_pe = _rope(q[..., dn:], cfg.rope_theta)
        k_pe = _rope(kva[:, None, r:], cfg.rope_theta)
        s = (np.einsum("qhn,khn->hqk", q[..., :dn], kv[..., :dn])
             + np.einsum("qhr,kr->hqk", q_pe, k_pe[:, 0]))
        s = np.where(causal, s / math.sqrt(dn + dr), -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        a /= a.sum(-1, keepdims=True)
        o = np.einsum("hqk,khv->qhv", a, kv[..., dn:]).reshape(L, H * dv)
        h = h + o @ p["wo"]
        x = _rms(h, p["mlp_norm"], cfg.norm_eps)
        if "moe_wi" in p:
            m = _routed(p, x, cfg) + _swiglu(x, p["shared_wi"],
                                             p["shared_wg"], p["shared_wo"])
        else:
            m = _swiglu(x, p["wi"], p["wg"], p["wo_mlp"])
        h = h + m
    x = _rms(h, P["final_norm"], cfg.norm_eps)
    return (x @ P["lm_head"])[:, : cfg.vocab_size]


@pytest.fixture(scope="module")
def params():
    return registry.init_params(SHARE, jax.random.PRNGKey(7))


def test_paged_prefill_and_decode_match_the_plain_reference(params):
    """Prefill, then paged decode steps of two sequences of different
    lengths sharing the batch, against the plain forward pass over each
    whole sequence. Tolerance: both sides run float32 on the CPU; the
    engine's absorbed decode and online softmax reassociate the same sums,
    which moves logits of order 1 by about 1e-6, so 1e-4 leaves room and
    still fails any wrong expert, weight or rotary pairing (those move
    logits by 1e-2 or more)."""
    engine = ServingEngine.with_model(
        SHARE, params, num_blocks=24, block_size=4, max_slots=3,
        max_blocks_per_seq=16, impl="interpret")
    model = engine.model
    steps = []
    decode = model.decode

    def record(tokens, positions, tables, active):
        out = decode(tokens, positions, tables, active)
        steps.append((np.array(positions), np.array(active),
                      np.asarray(model.last_logits)))
        return out

    model.decode = record
    rng = np.random.default_rng(3)
    prompts = {0: tuple(int(t) for t in rng.integers(0, 512, 9)),
               1: tuple(int(t) for t in rng.integers(0, 512, 22))}
    for rid, pr in prompts.items():
        engine.submit(Request(rid=rid, prompt=pr, max_new_tokens=5,
                              arrival=0))
    done = engine.run()
    slot_of = {0: 0, 1: 1}  # admitted in order into free slots
    for rid, pr in prompts.items():
        seq = pr + done[rid]
        ref = plain_logits(params, SHARE, seq)
        assert int(np.argmax(ref[len(pr) - 1])) == done[rid][0]
        n = 0
        for pos, active, logits in steps:
            s = slot_of[rid]
            if not active[s] or pos[s] >= len(seq):
                continue
            got = logits[s, : SHARE.vocab_size]
            np.testing.assert_allclose(got, ref[pos[s]], atol=1e-4,
                                       rtol=1e-4)
            assert int(np.argmax(got)) == seq[pos[s] + 1]
            n += 1
        assert n == len(done[rid]) - 1


def test_expert_shares_sum_to_the_uncut_layer():
    """Four devices' shares of the routed experts, plus the shared experts
    counted once, give the layer with all 16 experts, at a batch where the
    capacity rule would drop tokens; the shares' rows add up to every
    token's top-k choice."""
    full = CFG.replace(experts_held=0)
    kg = jax.random.PRNGKey(11)
    p = registry.init_params(full, kg)["layers"]
    p = jax.tree.map(lambda a: a[0], p)  # one MoE layer
    N, d = 96, full.d_model
    x = jax.random.normal(jax.random.PRNGKey(12), (N, d), jnp.float32)
    whole, rows = moe.moe_grouped(p, x, full)
    assert int(rows.sum()) == N * full.experts_per_token
    # the capacity rule keeps ceil(k S / E * 1.25) rows per expert: the
    # busiest expert here gets more than that
    assert int(rows.max()) > moe.capacity(full, N)
    shared = layers.mlp({"wi": p["shared_wi"], "wg": p["shared_wg"],
                         "wo": p["shared_wo"]}, x[None], "swiglu")[0]
    parts, got_rows = [], 0
    for rank in range(4):
        cfg = full.replace(experts_held=4, experts_first=4 * rank)
        sl = slice(4 * rank, 4 * rank + 4)
        share = dict(p, moe_wi=p["moe_wi"][sl], moe_wg=p["moe_wg"][sl],
                     moe_wo=p["moe_wo"][sl])
        out, r = moe.moe_grouped(share, x, cfg)
        parts.append(out - shared)
        got_rows += int(r.sum())
    assert got_rows == N * full.experts_per_token
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5,
                               rtol=2e-5)
    ref = _routed(jax.tree.map(np.asarray, p), np.asarray(x), full)
    np.testing.assert_allclose(whole - shared, ref, atol=2e-5, rtol=2e-5)


def test_masked_rows_reach_no_expert():
    cfg = SHARE
    p = jax.tree.map(lambda a: a[0],
                     registry.init_params(cfg, jax.random.PRNGKey(2))["layers"])
    x = jax.random.normal(jax.random.PRNGKey(3), (6, cfg.d_model))
    keep = jnp.asarray([True, False, True, False, False, True])
    out, rows = moe.moe_grouped(p, x, cfg, keep)
    _, rows_kept = moe.moe_grouped(p, x[keep], cfg)
    np.testing.assert_array_equal(rows, rows_kept)
    full, _ = moe.moe_grouped(p, x, cfg)
    np.testing.assert_allclose(out[keep], full[keep], atol=1e-6)


@pytest.mark.parametrize("pools", ["one", "stacked"])
def test_latent_kernel_matches_its_reference_forms(pools):
    """Interpret mode of the Pallas kernel against the xla and ref forms:
    pages in no order, a partial last page, and an inactive row on the
    scratch page; and on the engine's stacked pool of 3 layers, each layer
    read in place at its index, the same numbers as on that layer alone."""
    rng = np.random.default_rng(0)
    B, H, D, dv, bs, P, NB = 3, 4, 40, 32, 4, 12, 5
    stack = jnp.asarray(rng.standard_normal((3, P, 1, bs, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    table = np.full((B, NB), NULL_BLOCK, np.int32)
    table[0, :3] = [7, 2, 9]  # position 9: the third page holds one row
    table[1, :5] = [3, 11, 1, 5, 8]  # position 19: all five, full
    pos = jnp.asarray([9, 19, 0], jnp.int32)  # row 2: an empty slot

    def attend(pool, impl, **layer):
        return np.asarray(ops.latent_decode_attention(
            q, pool, pos, jnp.asarray(table), v_width=dv, scale=0.3,
            impl=impl, **layer))

    impls = ("interpret", "xla", "ref")
    for layer in (range(3) if pools == "stacked" else [1]):
        pool = stack[layer]
        outs = {impl: attend(pool, impl) for impl in impls}
        assert outs["ref"].shape == (B, H, dv)
        for impl in ("interpret", "xla"):
            np.testing.assert_allclose(outs[impl], outs["ref"], atol=1e-5,
                                       rtol=1e-5)
        if pools == "stacked":
            for impl in impls:
                np.testing.assert_array_equal(
                    attend(stack, impl, layer=layer), outs[impl])
    # the values are the first dv columns of the very rows the keys are
    rows = np.asarray(pool)[table[0, :3], 0].reshape(-1, D)[:10]
    s = np.asarray(q)[0] @ rows.T * 0.3
    a = np.exp(s - s.max(-1, keepdims=True))
    a /= a.sum(-1, keepdims=True)
    np.testing.assert_allclose(outs["interpret"][0], a @ rows[:, :dv],
                               atol=1e-5)


@pytest.mark.parametrize("arch", ["occamy-gptj", "moonlight-16b-a3b"])
def test_prefill_buckets_leave_other_pages_untouched(arch):
    """A prompt padded to 128 tokens writes its granted pages and the
    scratch page only: a live sequence's pages keep every bit."""
    cfg = get_config(arch, reduced=True)
    if cfg.mla:
        cfg = SHARE
    params = registry.init_params(cfg, jax.random.PRNGKey(5))
    engine = ServingEngine.with_model(
        cfg, params, num_blocks=40, block_size=4, max_slots=2,
        max_blocks_per_seq=40, impl="interpret")
    model = engine.model
    assert [model._bucket(n) for n in (1, 128, 129, 300)] == [128, 128, 256,
                                                             384]
    engine.submit(Request(rid=0, prompt=tuple(range(1, 30)),
                          max_new_tokens=3, arrival=0))
    engine.step()
    pages_a = list(engine.scheduler.running[0].blocks)

    def pools():
        c = model.cache
        return [np.asarray(x) for x in (c.k_pool, c.v_pool, c.lead_pool)
                if x is not None]

    before = pools()
    engine.submit(Request(rid=1, prompt=tuple(range(3, 12)),
                          max_new_tokens=3, arrival=engine.step_count))
    seq = engine.scheduler.admit(engine.step_count)[0]
    model.prefill(seq, seq.blocks)
    after = pools()
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b[:, pages_a], a[:, pages_a])
        untouched = sorted(set(range(b.shape[1])) - set(seq.blocks)
                           - {NULL_BLOCK})
        np.testing.assert_array_equal(b[:, untouched], a[:, untouched])
    assert len(seq.blocks) < model._bucket(9) // 4


def test_latent_pool_holds_only_the_latent():
    """One row of the latent and the rotary key a token and layer, padded
    with zeros to whole 128-lane tiles, and no V pool."""
    cfg = SHARE
    engine = ServingEngine.with_model(
        cfg, registry.init_params(cfg, jax.random.PRNGKey(1)),
        num_blocks=10, block_size=4, max_slots=2, max_blocks_per_seq=8)
    c = engine.model.cache
    assert c.v_pool is None and c.k_scale is None
    assert c.k_pool.shape == (cfg.num_layers - 1, 10, 1, 4, 128)
    assert c.lead_pool.shape == (1,) + c.k_pool.shape[1:]
    engine.submit(Request(rid=0, prompt=tuple(range(1, 10)),
                          max_new_tokens=4, arrival=0))
    engine.run()
    for pool in (engine.model.cache.k_pool, engine.model.cache.lead_pool):
        pool = np.asarray(pool)
        assert np.abs(pool[..., : cfg.latent_dim]).sum() > 0
        assert not pool[..., cfg.latent_dim:].any()


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_latent_decode_step_writes_only_the_new_rows(params, impl):
    """One decode step over the stacked latent pools, each carried through
    its layer scan and updated in place: in every layer of both pools
    exactly the batch's new rows change (the scratch page's among them),
    and every other value keeps its bits."""
    from repro.models import transformer
    from repro.serving import paged_cache

    rng = np.random.default_rng(4)
    B, P, bs, NB = 3, 12, 4, 4
    cache = paged_cache.init_paged_cache(SHARE, num_blocks=P, block_size=bs)
    width = cache.k_pool.shape[-1]

    def drawn(pool):
        rows = rng.standard_normal(pool.shape[:-1] + (SHARE.latent_dim,))
        pad = ((0, 0),) * (pool.ndim - 1) + ((0, width - SHARE.latent_dim),)
        return jnp.asarray(np.pad(rows, pad), pool.dtype)

    cache = dataclasses.replace(cache, k_pool=drawn(cache.k_pool),
                                lead_pool=drawn(cache.lead_pool))
    table = np.full((B, NB), NULL_BLOCK, np.int32)
    table[0, :2] = [5, 2]
    table[1, :4] = [7, 1, 11, 3]  # row 2: an empty slot on the scratch page
    pos = np.asarray([6, 13, 0], np.int32)
    batch = {"token": jnp.asarray([3, 9, 0], jnp.int32),
             "position": jnp.asarray(pos),
             "block_table": jnp.asarray(table)}
    attn = functools.partial(ops.latent_decode_attention, impl=impl)
    _, new = transformer.decode_step_paged(params, SHARE, cache, batch,
                                           attn_fn=attn)
    phys = table[np.arange(B), pos // bs]
    for before, after in ((cache.k_pool, new.k_pool),
                          (cache.lead_pool, new.lead_pool)):
        before, after = np.asarray(before), np.asarray(after)
        rows = np.zeros(before.shape[1:4], bool)  # (P, 1, bs)
        rows[phys, 0, pos % bs] = True
        np.testing.assert_array_equal(after[:, ~rows], before[:, ~rows])
        written = after[:, phys, 0, pos % bs]  # (n, B, width)
        assert (written[..., :SHARE.latent_dim]
                != before[:, phys, 0, pos % bs][..., :SHARE.latent_dim]).all()
        assert not written[..., SHARE.latent_dim:].any()


def test_the_latent_pool_refuses_a_mesh_and_fp8():
    params = registry.init_params(SHARE, jax.random.PRNGKey(1))
    geo = dict(num_blocks=10, block_size=4, max_slots=2, max_blocks_per_seq=8)
    with pytest.raises(NotImplementedError, match="latent"):
        PagedModel(SHARE, params, precision="fp8", **geo)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    with pytest.raises(NotImplementedError, match="latent"):
        PagedModel(SHARE, params, mesh=mesh, **geo)


@pytest.mark.parametrize("field", moe.GROUPED_ONLY)
def test_the_softmax_router_refuses_the_grouped_fields(field):
    # the capacity layer would run such a config as a plain softmax router
    cfg = get_config("phi3.5-moe-42b-a6.6b", reduced=True)
    assert not cfg.grouped_moe
    cfg = cfg.replace(**{field: getattr(SHARE, field) or 1})
    with pytest.raises(ValueError, match=field):
        registry.init_params(cfg, jax.random.PRNGKey(0))


def test_the_counters_ride_the_read_back_into_spans(params, tmp_path):
    """``experts_hit`` comes back with each step's tokens onto a
    ``serve.decode.experts`` span, and each prompt's ``held_rows`` onto a
    ``serve.prefill.experts`` span; an empty slot reaches no expert."""
    import glob

    from jax.profiler import ProfileData

    from repro.models import transformer

    engine = ServingEngine.with_model(
        SHARE, params, num_blocks=24, block_size=4, max_slots=3,
        max_blocks_per_seq=8)
    for rid, n in enumerate((6, 11)):
        engine.submit(Request(rid=rid, prompt=tuple(range(1, n + 1)),
                              max_new_tokens=4, arrival=0))
    with jax.profiler.trace(str(tmp_path)):
        engine.run()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = sorted((e.start_ns, e.end_ns, e.name, dict(e.stats))
                   for plane in ProfileData.from_file(path).planes
                   for line in plane.lines for e in line.events
                   if e.name.startswith("serve."))
    moe_layers = SHARE.num_layers - SHARE.first_dense_layers
    held = SHARE.held_experts[1]
    for outer, inner, arg, most in (
            ("serve.decode", "serve.decode.experts", "experts_hit",
             moe_layers * held),
            ("serve.prefill", "serve.prefill.experts", "held_rows",
             moe_layers * 11 * SHARE.experts_per_token)):
        outs = [s for s in spans if s[2] == outer]
        ins = [s for s in spans if s[2] == inner]
        assert len(ins) == len(outs) > 0
        for o, i in zip(outs, ins):
            assert o[0] <= i[0] and i[1] <= o[1]
            assert 0 < int(i[3][arg]) <= most
    # the decode program of a batch of empty slots computes no expert
    cache = engine.model.cache
    batch = {"token": jnp.zeros((3,), jnp.int32),
             "position": jnp.zeros((3,), jnp.int32),
             "block_table": jnp.zeros((3, 8), jnp.int32)}
    _, _, stats = transformer.decode_step_paged(params, SHARE, cache, batch,
                                                stats=True)
    assert int(stats["experts_hit"]) == 0
