"""Paged decode attention: the paged path must be *bitwise* equal to the
contiguous oracle (same online-softmax scan over the same values, only
addressed through a block table), across impl x GQA x window x precision,
and the cache-sharded ring decode must be bitwise-replicated across ranks.

The equivalence construction: a contiguous cache (B, K, S, D) with
S = NB * bs is cut into NB pages per sequence and scattered into a pool at
arbitrary physical indices; the block table maps logical page j back to
its physical slot. Pool page extent pins bs, so both paths stream
identical (bs x D) tiles through the identical scan body.

The Pallas paged kernel (run interpreted here) sums in another order, so it
is checked against the paged oracle with allclose.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision as prec
from repro.kernels import ops
from repro.serving.paged_cache import NULL_BLOCK, init_paged_cache
from repro.serving.ring_decode import ring_decode_reference


def _paged_setup(rng, *, B=3, H=8, K=4, S=64, D=16, bs=16, policy=None):
    """Contiguous cache + the equivalent paged pool/table. Returns
    (q, k, v, position, k_pool, v_pool, k_scale, v_scale, table)."""
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, K, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, K, S, D)), jnp.float32)
    position = jnp.asarray(rng.integers(1, S, B), jnp.int32)

    nb = S // bs
    P_pool = B * nb + 1  # + the null page
    perm = rng.permutation(B * nb) + 1  # physical slots, never NULL_BLOCK
    table = np.zeros((B, nb), np.int32)
    k_pool = np.zeros((P_pool, K, bs, D), np.float32)
    v_pool = np.zeros((P_pool, K, bs, D), np.float32)
    for b in range(B):
        for j in range(nb):
            phys = int(perm[b * nb + j])
            table[b, j] = phys
            k_pool[phys] = np.asarray(k[b, :, j * bs:(j + 1) * bs])
            v_pool[phys] = np.asarray(v[b, :, j * bs:(j + 1) * bs])
    k_scale = v_scale = None
    kp, vp = jnp.asarray(k_pool), jnp.asarray(v_pool)
    if policy == "prequant":
        kq, ks, vq, vs = prec.quantize_kv_cache(kp, vp, "fp8")
        kp, vp, k_scale, v_scale = kq, vq, ks, vs
    return q, k, v, position, kp, vp, k_scale, v_scale, jnp.asarray(table)


@pytest.mark.parametrize("impl", ["xla", "ref", "auto"])
@pytest.mark.parametrize("gqa_k", [1, 4])
@pytest.mark.parametrize("window", [0, 13])
def test_paged_bitwise_vs_contiguous(rng, impl, gqa_k, window):
    q, k, v, pos, kp, vp, _, _, tbl = _paged_setup(rng, K=gqa_k)
    want = ops.decode_attention(q, k, v, pos, window=window, impl=impl,
                                bs=16)
    got = ops.decode_attention(q, kp, vp, pos, window=window, impl=impl,
                               paged=True, block_table=tbl)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["xla", "ref"])
@pytest.mark.parametrize("policy", ["fp8", "bf16"])
def test_paged_bitwise_quantize_at_use(rng, impl, policy):
    # per-row quantization (axis=-1, block=D) is layout-independent, so
    # quantize-at-use over pool pages == quantize-at-use over the
    # contiguous cache, bitwise
    q, k, v, pos, kp, vp, _, _, tbl = _paged_setup(rng)
    want = ops.decode_attention(q, k, v, pos, precision=policy, impl=impl,
                                bs=16)
    got = ops.decode_attention(q, kp, vp, pos, precision=policy, impl=impl,
                               paged=True, block_table=tbl)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["xla", "ref"])
def test_paged_prequantized_pool_bitwise(rng, impl):
    # pools stored narrow (values + per-row scales) skip quantize and
    # dequantize identically to quantize-at-use on the same pages
    q, k, v, pos, kp, vp, ks, vs, tbl = _paged_setup(rng, policy="prequant")
    want = ops.decode_attention(q, k, v, pos, precision="fp8", impl=impl,
                                bs=16)
    got = ops.decode_attention(q, kp, vp, pos, impl=impl,
                               paged=True, block_table=tbl,
                               k_scale=ks, v_scale=vs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["xla", "ref"])
def test_paged_return_lse_bitwise(rng, impl):
    q, k, v, pos, kp, vp, _, _, tbl = _paged_setup(rng)
    wo, wl = ops.decode_attention(q, k, v, pos, impl=impl, return_lse=True,
                                  bs=16)
    go, gl = ops.decode_attention(q, kp, vp, pos, impl=impl, paged=True,
                                  block_table=tbl, return_lse=True)
    np.testing.assert_array_equal(np.asarray(go), np.asarray(wo))
    np.testing.assert_array_equal(np.asarray(gl), np.asarray(wl))
    assert gl.dtype == jnp.float32 and gl.shape == q.shape[:2]


def test_paged_validation_errors(rng):
    q, k, v, pos, kp, vp, ks, vs, tbl = _paged_setup(rng, policy="prequant")
    with pytest.raises(TypeError, match="block_table"):
        ops.decode_attention(q, kp, vp, pos, paged=True)
    with pytest.raises(TypeError, match="paged"):
        ops.decode_attention(q, k, v, pos, block_table=tbl)
    with pytest.raises(TypeError, match="k_scale"):
        ops.decode_attention(q, k, v, pos, k_scale=ks, v_scale=vs)
    with pytest.raises(ValueError, match="pools"):
        ops.decode_attention(q, kp, vp[:-1], pos, paged=True,
                             block_table=tbl)


def test_null_block_rows_are_exact_noops(rng):
    # duplicate the null page into a live slot's UNREACHED table tail:
    # positions mask those reads, so output is unchanged bitwise
    q, k, v, pos, kp, vp, _, _, tbl = _paged_setup(rng, S=64, bs=16)
    pos_short = jnp.minimum(pos, 15)  # only logical page 0 is ever live
    want = ops.decode_attention(q, kp, vp, pos_short, paged=True,
                                block_table=tbl)
    tbl_null = np.asarray(tbl).copy()
    tbl_null[:, 1:] = NULL_BLOCK
    got = ops.decode_attention(q, kp, vp, pos_short, paged=True,
                               block_table=jnp.asarray(tbl_null))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# The Pallas paged kernel (interpret mode) against the paged oracle. Its
# summation order differs from the scan's, so the check is allclose.
# ---------------------------------------------------------------------------


def _kernel_vs_ref(q, kp, vp, pos, tbl, **kw):
    got = ops.decode_attention(q, kp, vp, pos, paged=True, block_table=tbl,
                               impl="interpret", **kw)
    want = ops.decode_attention(q, kp, vp, pos, paged=True, block_table=tbl,
                                impl="ref", **kw)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


# each row's position sits on the last row of a page, or one past it (the
# first row of the next page, the only live token there)
_BOUNDARY = {"on": [15, 31, 47], "past": [16, 32, 48]}


@pytest.mark.parametrize("kv_heads", [8, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("window", [0, 13])
@pytest.mark.parametrize("where", sorted(_BOUNDARY))
@pytest.mark.parametrize("pools", ["one", "stacked"])
def test_pallas_paged_matches_ref(rng, kv_heads, window, where, pools):
    q, _, _, _, kp, vp, _, _, tbl = _paged_setup(rng, K=kv_heads)
    pos = jnp.asarray(_BOUNDARY[where], jnp.int32)
    if pools == "one":
        _kernel_vs_ref(q, kp, vp, pos, tbl, window=window)
        return
    # the engine's stacked pools of 3 layers, each read in place at its
    # index: the same numbers as the kernel and the ref on that layer alone
    ks = jnp.asarray(rng.standard_normal((3,) + kp.shape), kp.dtype)
    vs = jnp.asarray(rng.standard_normal((3,) + vp.shape), vp.dtype)
    for layer in range(3):
        got = ops.decode_attention(q, ks, vs, pos, paged=True,
                                   block_table=tbl, window=window,
                                   layer=layer, impl="interpret")
        alone = ops.decode_attention(q, ks[layer], vs[layer], pos,
                                     paged=True, block_table=tbl,
                                     window=window, impl="interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
        _kernel_vs_ref(q, ks[layer], vs[layer], pos, tbl, window=window)


@pytest.mark.parametrize("pages", [1, 3, 8])
def test_pallas_paged_pages_per_step(rng, pages):
    # 3 does not divide the table's 4 columns (the table is padded) and 8
    # exceeds them (one step covers the row)
    from repro.kernels import registry as kreg

    q, _, _, pos, kp, vp, _, _, tbl = _paged_setup(rng)
    with kreg.block_override("decode_attention", pages=pages):
        _kernel_vs_ref(q, kp, vp, pos, tbl)


def test_pallas_paged_inactive_row_on_scratch_page(rng):
    # an inactive slot: position 0, its whole table row on scratch page 0
    q, _, _, pos, kp, vp, _, _, tbl = _paged_setup(rng)
    pos = pos.at[1].set(0)
    tbl = tbl.at[1].set(NULL_BLOCK)
    _kernel_vs_ref(q, kp, vp, pos, tbl)


@pytest.mark.parametrize("traced", [False, True])
def test_pallas_paged_pos_offset_return_lse(rng, traced):
    # a ring shard's table starts at pos_offset 32: row 0 (position 25) has
    # no live column there and must come back as o = 0, lse = -1e30
    q, _, _, _, kp, vp, _, _, tbl = _paged_setup(rng)
    pos = jnp.asarray([25, 40, 63], jnp.int32)
    if traced:
        got = jax.jit(lambda off: ops.decode_attention(
            q, kp, vp, pos, paged=True, block_table=tbl, impl="interpret",
            pos_offset=off, return_lse=True))(jnp.int32(32))
        want = ops.decode_attention(q, kp, vp, pos, paged=True,
                                    block_table=tbl, impl="ref",
                                    pos_offset=32, return_lse=True)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5)
    else:
        _kernel_vs_ref(q, kp, vp, pos, tbl, pos_offset=32, return_lse=True)
    o, lse = ops.decode_attention(q, kp, vp, pos, paged=True, block_table=tbl,
                                  impl="interpret", pos_offset=32,
                                  return_lse=True)
    assert not np.asarray(o[0]).any() and np.all(np.asarray(lse[0]) <= -1e29)


@pytest.mark.parametrize("policy", ["fp8", "prequant"])
def test_pallas_paged_narrow_pool(rng, policy):
    # quantize-at-use, and a pool held narrow: pages dequantize in-kernel
    if policy == "prequant":
        q, _, _, pos, kp, vp, ks, vs, tbl = _paged_setup(rng,
                                                        policy="prequant")
        _kernel_vs_ref(q, kp, vp, pos, tbl, k_scale=ks, v_scale=vs)
    else:
        q, _, _, pos, kp, vp, _, _, tbl = _paged_setup(rng)
        _kernel_vs_ref(q, kp, vp, pos, tbl, precision=policy)


def test_engine_serves_same_tokens_interpret_vs_xla():
    # the engine's decode through the Pallas kernel (interpret) gives the
    # greedy tokens of the xla form
    from repro.configs.base import get_config
    from repro.models import registry as mreg
    from repro.serving.engine import Request, ServingEngine

    cfg = get_config("occamy-gptj", reduced=True)
    params = mreg.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [tuple(range(1 + i, 1 + i + n)) for i, n in enumerate((5, 18, 9))]

    def serve(impl):
        eng = ServingEngine.with_model(cfg, params, num_blocks=16,
                                       block_size=4, max_slots=2,
                                       max_blocks_per_seq=8, impl=impl)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=p, max_new_tokens=5))
        return eng.run()

    assert serve("interpret") == serve("xla")


# ---------------------------------------------------------------------------
# PagedKVCache pytree + round-trips
# ---------------------------------------------------------------------------


class _Cfg:
    num_layers, num_kv_heads, vocab_size = 2, 4, 128
    dtype = "float32"

    def resolved_head_dim(self):
        return 16


@pytest.mark.parametrize("policy", [None, "fp8"])
def test_paged_cache_roundtrips(rng, policy):
    cache = init_paged_cache(_Cfg(), num_blocks=8, block_size=4,
                             policy=policy)
    assert cache.num_blocks == 8 and cache.quantized == (policy is not None)

    # pytree: flatten/unflatten preserves aux + children identity
    leaves, tree = jax.tree.flatten(cache)
    back = jax.tree.unflatten(tree, leaves)
    assert back.block_size == 4 and back.policy == policy

    nl, K, bs, hd = 2, 4, 4, 16
    k_rows = jnp.asarray(rng.standard_normal((nl, 3, K, bs, hd)), jnp.float32)
    v_rows = jnp.asarray(rng.standard_normal((nl, 3, K, bs, hd)), jnp.float32)
    ids = jnp.asarray([2, 5, 7], jnp.int32)
    cache = cache.write_prompt(ids, k_rows, v_rows)

    # gather -> restore into different physical pages is bitwise
    payload = jax.device_get(cache.gather_blocks(ids))
    ids2 = jnp.asarray([1, 3, 6], jnp.int32)
    cache2 = cache.restore_blocks(ids2, payload)
    np.testing.assert_array_equal(
        np.asarray(cache2.k_pool[:, ids2]), np.asarray(cache.k_pool[:, ids]))
    np.testing.assert_array_equal(
        np.asarray(cache2.v_pool[:, ids2]), np.asarray(cache.v_pool[:, ids]))
    if policy:
        np.testing.assert_array_equal(
            np.asarray(cache2.k_scale[:, ids2]),
            np.asarray(cache.k_scale[:, ids]))


def test_paged_cache_quantized_write_matches_oracle(rng):
    # write_prompt under a policy stores exactly quantize_kv_cache's output
    cache = init_paged_cache(_Cfg(), num_blocks=8, block_size=4, policy="fp8")
    nl, K, bs, hd = 2, 4, 4, 16
    k_rows = jnp.asarray(rng.standard_normal((nl, 2, K, bs, hd)), jnp.float32)
    v_rows = jnp.asarray(rng.standard_normal((nl, 2, K, bs, hd)), jnp.float32)
    ids = jnp.asarray([4, 6], jnp.int32)
    cache = cache.write_prompt(ids, k_rows, v_rows)
    kq, ks, vq, vs = prec.quantize_kv_cache(k_rows, v_rows, "fp8")
    np.testing.assert_array_equal(
        np.asarray(cache.k_pool[:, ids]), np.asarray(kq))
    np.testing.assert_array_equal(
        np.asarray(cache.k_scale[:, ids]), np.asarray(ks))
    np.testing.assert_array_equal(
        np.asarray(cache.v_scale[:, ids]), np.asarray(vs))


# ---------------------------------------------------------------------------
# Model layer: decode_step_paged vs contiguous decode_step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["gemma-2b", "occamy-gptj"])
def test_decode_step_paged_bitwise_vs_contiguous(arch):
    # multi-query (gemma) and multi-head with a parallel block (GPT-J)
    from repro.configs.base import get_config
    from repro.models import registry as mreg, transformer

    cfg = get_config(arch, reduced=True)
    params = mreg.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    B, S0, bs, nb = 2, 8, 4, 4
    max_len = nb * bs
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, S0)), jnp.int32)

    _, cache = transformer.prefill_step(params, cfg, {"tokens": tokens},
                                        max_len)
    nl = cfg.num_layers
    K = cfg.num_kv_heads
    hd = cfg.resolved_head_dim()

    # paged mirror: pool pages <- contiguous cache pages, shuffled physical
    paged = init_paged_cache(cfg, num_blocks=B * nb + 1, block_size=bs)
    perm = rng.permutation(B * nb) + 1
    table = np.zeros((B, nb), np.int32)
    kp = np.zeros((nl, B * nb + 1, K, bs, hd), np.float32)
    vp = np.zeros_like(kp)
    for b in range(B):
        for j in range(nb):
            phys = int(perm[b * nb + j])
            table[b, j] = phys
            kp[:, phys] = np.asarray(cache["k"][:, b, :, j * bs:(j + 1) * bs])
            vp[:, phys] = np.asarray(cache["v"][:, b, :, j * bs:(j + 1) * bs])
    import dataclasses
    paged = dataclasses.replace(paged, k_pool=jnp.asarray(kp),
                                v_pool=jnp.asarray(vp))

    tok = jnp.asarray(rng.integers(1, cfg.vocab_size, B), jnp.int32)
    posn = jnp.full((B,), S0, jnp.int32)
    # pin the contiguous scan to the pool's page extent so both paths
    # stream identical (bs x D) tiles (bitwise needs matching partitions)
    from repro.kernels import registry as kreg
    with kreg.block_override("decode_attention", bs=bs):
        want, cache2 = transformer.decode_step(
            params, cfg, cache, {"token": tok, "position": posn})
    got, paged2 = transformer.decode_step_paged(
        params, cfg, paged,
        {"token": tok, "position": posn, "block_table": jnp.asarray(table)})
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # in every layer's pool the step wrote exactly this position's B rows,
    # the contiguous step's new K/V, and left every other row as it was
    rows = np.zeros(kp.shape[1:4], bool)  # (P, K, bs)
    phys = table[np.arange(B), S0 // bs]
    rows[phys, :, S0 % bs] = True
    for name, before, new in (("k", kp, paged2.k_pool),
                              ("v", vp, paged2.v_pool)):
        new = np.asarray(new)
        np.testing.assert_array_equal(new[:, ~rows], before[:, ~rows])
        written = new[:, phys][:, :, :, S0 % bs]  # (nl, B, K, hd)
        np.testing.assert_array_equal(written,
                                      np.asarray(cache2[name][..., S0, :]))
        assert not np.array_equal(written, before[:, phys][:, :, :, S0 % bs])


# ---------------------------------------------------------------------------
# Ring decode: single-device merge-chain oracle + 8-device subprocess
# ---------------------------------------------------------------------------


def test_ring_reference_allclose_contiguous(rng):
    q, k, v, pos, kp, vp, _, _, tbl = _paged_setup(rng, S=64, bs=16)
    # ring table convention: entries index the owning rank's LOCAL pool.
    # Rebuild per-rank local pools by slicing logical pages per rank.
    n, nb = 2, 4
    nb_l = nb // n
    B = int(tbl.shape[0])
    kp_l, vp_l, tbl_l = _localize(np.asarray(kp), np.asarray(vp),
                                  np.asarray(tbl), n)
    want = ops.decode_attention(q, k, v, pos)
    got = ring_decode_reference(q, jnp.asarray(kp_l), jnp.asarray(vp_l),
                                jnp.asarray(tbl_l), pos, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)
    assert nb_l * n == nb and B == 3


def _localize(kp, vp, tbl, n):
    """Re-home a global paged layout to the ring convention: rank r's local
    pool holds the pages behind table columns [r*nb_l, (r+1)*nb_l), and
    those columns index the local pool. Returns (k_pools, v_pools, table)
    with pools concatenated in rank order (what shard_map splits)."""
    B, nb = tbl.shape
    nb_l = nb // n
    K, bs, D = kp.shape[1:]
    p_l = B * nb_l + 1
    k_out = np.zeros((n * p_l, K, bs, D), kp.dtype)
    v_out = np.zeros_like(k_out)
    t_out = np.zeros((B, nb), np.int32)
    for r in range(n):
        nxt = 1  # local slot 0 is each rank's null page
        for b in range(B):
            for j in range(r * nb_l, (r + 1) * nb_l):
                k_out[r * p_l + nxt] = kp[tbl[b, j]]
                v_out[r * p_l + nxt] = vp[tbl[b, j]]
                t_out[b, j] = nxt
                nxt += 1
    return k_out, v_out, t_out


_RING_SUBPROCESS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.serving import ring_decode as rd
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(0)
    B, H, K, D, bs, nb, n = 3, 8, 4, 16, 8, 8, 4
    S = nb * bs
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, K, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, K, S, D)), jnp.float32)
    pos = jnp.asarray(rng.integers(1, S, B), jnp.int32)

    nb_l = nb // n
    p_l = B * nb_l + 1
    kp = np.zeros((n * p_l, K, bs, D), np.float32)
    vp = np.zeros_like(kp)
    tbl = np.zeros((B, nb), np.int32)
    for r in range(n):
        nxt = 1
        for b in range(B):
            for j in range(r * nb_l, (r + 1) * nb_l):
                kp[r * p_l + nxt] = np.asarray(k[b, :, j * bs:(j + 1) * bs])
                vp[r * p_l + nxt] = np.asarray(v[b, :, j * bs:(j + 1) * bs])
                tbl[b, j] = nxt
                nxt += 1
    kp, vp, tbl = jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl)

    mesh = make_mesh((4, 2), ("data", "model"))
    got = rd.ring_decode(q, kp, vp, tbl, pos, mesh, axis="data")
    sync = rd.ring_decode(q, kp, vp, tbl, pos, mesh, axis="data",
                          overlap=False)
    want = rd.ring_decode_reference(q, kp, vp, tbl, pos, n)
    contig = ops.decode_attention(q, k, v, pos)
    out = {
        "ring_vs_ref_bitwise": bool(
            np.array_equal(np.asarray(got), np.asarray(want))),
        "overlap_invariant": bool(
            np.array_equal(np.asarray(got), np.asarray(sync))),
        "ring_vs_contig_err": float(
            np.max(np.abs(np.asarray(got) - np.asarray(contig)))),
    }
    print("RESULT:" + json.dumps(out))
    """
)


@pytest.mark.slow
def test_ring_decode_8dev_bitwise_vs_reference():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _RING_SUBPROCESS],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    assert out["ring_vs_ref_bitwise"], out
    assert out["overlap_invariant"], out
    assert out["ring_vs_contig_err"] < 1e-5, out


# ---------------------------------------------------------------------------
# Engine: ring decode over data=4 serves the same tokens as one device
# ---------------------------------------------------------------------------

_ENGINE_RING_SUBPROCESS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import numpy as np

    from repro.configs.base import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import registry as mreg
    from repro.serving.engine import Request, ServingEngine

    cfg = get_config("occamy-gptj", reduced=True)
    params = mreg.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # prompts long enough that their blocks span several shards
    prompts = [tuple(rng.integers(0, cfg.vocab_size, n)) for n in
               (5, 19, 33, 47)]
    geo = dict(num_blocks=32, block_size=8, max_slots=4, max_blocks_per_seq=8)
    RING_IMPL = None  # the decode attention impl of the ring's shards

    def serve(mesh):
        eng = ServingEngine.with_model(
            cfg, params, mesh=mesh, impl=None if mesh is None else RING_IMPL,
            **geo)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
        return eng, eng.run()

    _, want = serve(None)
    eng, got = serve(make_mesh((4,), ("data",)))
    pool = eng.model.cache.k_pool
    out = {
        "same_tokens": want == got,
        "leaked": eng.leaked_blocks(),
        "pool_spec": str(pool.sharding.spec),
        "pool_pages": int(pool.shape[1]),
    }
    print("RESULT:" + json.dumps(out))
    """
)


def _run_engine_ring(ring_impl=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _ENGINE_RING_SUBPROCESS.replace(
        "RING_IMPL = None", f"RING_IMPL = {ring_impl!r}")
    return subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=900,
    )


@pytest.mark.slow
def test_engine_ring_decode_through_the_kernel_4dev():
    # every shard runs the Pallas paged kernel (interpreted), with its
    # traced pos_offset and the log-sum-exp the ring folds
    proc = _run_engine_ring("interpret")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    assert out["same_tokens"], out
    assert out["leaked"] == 0, out


@pytest.mark.slow
def test_engine_ring_decode_serves_same_tokens_4dev():
    proc = _run_engine_ring()
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    assert out["same_tokens"], out
    assert out["leaked"] == 0, out
    # pools sharded on their page axis: 4 shards of (1 scratch + 4 slots x 2)
    assert out["pool_pages"] == 4 * (1 + 4 * 2), out
    assert out["pool_spec"] == "PartitionSpec(None, 'data')", out
