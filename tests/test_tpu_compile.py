"""The main-path kernels and the GPT-J decode step, compiled for a TPU v5e.

No chip is attached: the TPU compiler installed with JAX compiles for a
described ``v5e:2x2`` topology, which refuses what the chip would refuse
(tiling, fast-memory limits, unsupported vector casts) and nothing runs.
The topology is described inside a fixture, never while a module imports:
only one process at a time may load the TPU library, and every test worker
imports every test file. A kernel that the compiler refuses today is a
strict xfail naming the error, so its repair has to remove the mark.
"""
from __future__ import annotations

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for one described chip at ``shapes`` ((shape, dtype)
    pairs); returns the compiled program's text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_pallas_gptj_prefill(one_chip):
    qkv = ((1, 16, 256, 256), BF16)
    text = _compile(functools.partial(ops.flash_attention, impl="pallas"),
                    one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_gemm_pallas_bf16(one_chip):
    text = _compile(functools.partial(ops.gemm, impl="pallas"), one_chip,
                    ((2048, 4096), BF16), ((4096, 4096), BF16))
    assert "tpu_custom_call" in text


def test_bsr_spmm_pallas(one_chip):
    def f(vals, rows, cols, dense):
        return ops.bsr_spmm(vals, rows, cols, dense, 1024, impl="pallas")

    text = _compile(f, one_chip, ((64, 128, 128), BF16), ((64,), I32),
                    ((64,), I32), ((2048, 512), BF16))
    assert "tpu_custom_call" in text


def test_linear_attention_pallas(one_chip):
    rkv = ((1, 40, 512, 64), F32)
    text = _compile(functools.partial(ops.linear_attention, impl="pallas"),
                    one_chip, rkv, rkv, rkv, rkv, ((40, 64), F32))
    assert "tpu_custom_call" in text


def test_paged_decode_attention_xla_engine_shapes(one_chip):
    # the engine's GPT-J geometry: 8 slots, 128 pages of 16, 16 per slot
    def f(q, kp, vp, pos, tbl):
        return ops.decode_attention(q, kp, vp, pos, paged=True,
                                    block_table=tbl, impl="xla")

    pool = ((128, 16, 16, 256), BF16)
    _compile(f, one_chip, ((8, 16, 256), BF16), pool, pool, ((8,), I32),
             ((8, 16), I32))


@pytest.mark.parametrize("ring", [False, True], ids=["engine", "ring"])
def test_paged_decode_attention_pallas_cell_geometry(one_chip, ring):
    # the gptj-chat cell: 3 slots of 128 pages of 16, a pool of 304 pages;
    # ring decode adds a traced pos_offset and the log-sum-exp
    def f(q, kp, vp, pos, tbl, off):
        extra = dict(pos_offset=off, return_lse=True) if ring else {}
        return ops.decode_attention(q, kp, vp, pos, paged=True,
                                    block_table=tbl, impl="pallas", **extra)

    pool = ((304, 16, 16, 256), BF16)
    text = _compile(f, one_chip, ((3, 16, 256), BF16), pool, pool,
                    ((3,), I32), ((3, 128), I32), ((), I32))
    assert "tpu_custom_call" in text


_MOVES = re.compile(r"= \w+\[([\d,]*)\]\S* (copy|dynamic-slice|"
                    r"dynamic-update-slice)\(")


def _assert_pool_stays_in_place(compiled, cache):
    """The decode program updates the stacked pools in place: its
    temporaries stay under a tenth of the pools, and no copy, slice or
    slice update has a result of a stacked pool's or one layer's pool's
    shape (compared with unit dims dropped, as the compiler's bitcasts
    drop them)."""
    pools = [x for x in jax.tree.leaves(cache) if x.ndim == 5]
    pool_bytes = sum(x.size * x.dtype.itemsize for x in pools)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 10, (temp, pool_bytes)

    def squeezed(shape):
        return tuple(d for d in shape if d != 1)

    shapes = {squeezed(s) for x in pools for s in (x.shape, x.shape[1:])}
    moves = [m.group(0) for m in _MOVES.finditer(compiled.as_text())
             if squeezed(int(d) for d in m.group(1).split(",") if d)
             in shapes]
    assert not moves, moves


def test_gptj_decode_step_paged_fits_one_chip(one_chip):
    from repro.configs.base import get_config
    from repro.kernels import registry as kernels
    from repro.models import registry, transformer
    from repro.serving import paged_cache

    cfg = get_config("occamy-gptj")

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(registry.param_shapes(cfg))
    cache = on_chip(jax.eval_shape(lambda: paged_cache.init_paged_cache(
        cfg, num_blocks=128, block_size=16)))
    batch = on_chip({"token": jax.ShapeDtypeStruct((8,), I32),
                     "position": jax.ShapeDtypeStruct((8,), I32),
                     "block_table": jax.ShapeDtypeStruct((8, 16), I32)})
    with kernels.default_impl("pallas"):
        step = jax.jit(lambda p, c, b: transformer.decode_step_paged(
            p, cfg, c, b), donate_argnums=(1,))
        compiled = step.lower(params, cache, batch).compile()
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 16e9, held  # one v5e holds 16 GB
    assert "tpu_custom_call" in compiled.as_text()
    _assert_pool_stays_in_place(compiled, cache)


def test_flash_attention_pallas_mla_prefill(one_chip):
    # Moonlight's expanded MLA prefill: q and k 192 wide, v 128
    qk = ((1, 16, 512, 192), BF16)
    text = _compile(functools.partial(ops.flash_attention, impl="pallas"),
                    one_chip, qk, qk, ((1, 16, 512, 128), BF16))
    assert "tpu_custom_call" in text


def test_latent_decode_attention_pallas_cell_geometry(one_chip):
    # the moonlight-chat cell: 32 slots of 128 pages of 16, a pool of 4096
    # pages of latent rows (576 values padded to 640); the kernel is named
    # in the program
    def f(q, pool, pos, tbl):
        return ops.latent_decode_attention(q, pool, pos, tbl, v_width=512,
                                           scale=192 ** -0.5, impl="pallas")

    text = _compile(f, one_chip, ((32, 16, 640), BF16),
                    ((4096, 1, 16, 640), BF16), ((32,), I32),
                    ((32, 128), I32))
    assert "%mla_decode_attention" in text


def test_moonlight_decode_step_paged_fits_one_chip(one_chip):
    # rank 0 of EP4: 16 of 64 experts held, every width as published
    from repro.configs.base import get_config
    from repro.kernels import registry as kernels
    from repro.models import registry, transformer
    from repro.serving import paged_cache

    cfg = get_config("moonlight-16b-a3b").replace(experts_held=16)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(registry.param_shapes(cfg))
    cache = on_chip(jax.eval_shape(lambda: paged_cache.init_paged_cache(
        cfg, num_blocks=4096, block_size=16)))
    batch = on_chip({"token": jax.ShapeDtypeStruct((32,), I32),
                     "position": jax.ShapeDtypeStruct((32,), I32),
                     "block_table": jax.ShapeDtypeStruct((32, 128), I32)})
    attn = functools.partial(ops.latent_decode_attention, impl="pallas")
    with kernels.default_impl("pallas"):
        step = jax.jit(lambda p, c, b: transformer.decode_step_paged(
            p, cfg, c, b, attn_fn=attn, stats=True), donate_argnums=(1,))
        compiled = step.lower(params, cache, batch).compile()
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 16e9, held
    text = compiled.as_text()
    assert "%mla_decode_attention" in text and "ragged-dot" in text
    _assert_pool_stays_in_place(compiled, cache)


# Refused by the TPU compiler today; each needs its own kernel rework. A
# test counts as the expected failure only when the compiler's own message
# matches; any other error fails it.


class Refused(Exception):
    """The TPU compiler refused the kernel with the recorded message."""


@contextlib.contextmanager
def refused(message: str):
    try:
        yield
    except Exception as e:
        if re.search(message, str(e)):
            raise Refused(message) from e
        raise


def refused_by_compiler(message: str, where: str):
    return pytest.mark.xfail(raises=Refused, strict=True,
                             reason=f"{message!r} ({where})")


@refused_by_compiler("Invalid type", "jnp.roll in kernels/stencil.py")
def test_stencil_pallas(one_chip):
    offsets = np.array([(0, 0, 0), (1, 0, 0), (-1, 0, 0), (0, 1, 0),
                        (0, -1, 0), (0, 0, 1), (0, 0, -1)], np.int32)
    weights = np.full(7, 1 / 7, np.float32)
    with refused("Invalid type"):
        _compile(functools.partial(ops.stencil, offsets=offsets,
                                   weights=weights, impl="pallas"),
                 one_chip, ((64, 128, 128), F32))


@refused_by_compiler("Cannot do int indexing on TPU",
                     "in-kernel gather in kernels/spmm.py")
def test_ell_spmm_pallas(one_chip):
    with refused("Cannot do int indexing on TPU"):
        _compile(functools.partial(ops.spmm, impl="pallas"), one_chip,
                 ((1024, 16), BF16), ((1024, 16), I32), ((2048, 256), BF16))


@refused_by_compiler("unsupported shape cast",
                     "vector<8x16xi32> -> vector<8x16x1x1xi32> in "
                     "kernels/spmspm.py")
def test_spmspm_pallas(one_chip):
    def f(av, ac, bv, br):
        return ops.spmspm(av, ac, bv, br, 2048, impl="pallas")

    ell = ((1024, 16), F32)
    idx = ((1024, 16), I32)
    with refused("unsupported shape cast"):
        _compile(f, one_chip, ell, idx, ell, idx)


@refused_by_compiler("divisible by 8 and 128",
                     "fp8 gemm scale block (256, 1) over (2048, 16), "
                     "kernels/gemm.py")
def test_gemm_pallas_fp8(one_chip):
    with refused("divisible by 8 and 128"):
        _compile(functools.partial(ops.gemm, impl="pallas", precision="fp8"),
                 one_chip, ((2048, 4096), BF16), ((4096, 4096), BF16))
