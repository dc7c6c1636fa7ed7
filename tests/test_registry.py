"""Dispatch-layer and formats-layer tests: kernel registry resolution,
pytree sparse formats, and the StreamProgram substrate metadata."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sparse as sp
from repro.core import streams
from repro.kernels import ops, registry


@pytest.fixture(autouse=True)
def _clean_registry_state():
    yield
    registry.set_default_impl(None)
    registry.clear_block_overrides()


# ---------------------------------------------------------------------------
# Registry: errors and resolution precedence
# ---------------------------------------------------------------------------


def test_unknown_op_raises():
    with pytest.raises(KeyError, match="unknown kernel op"):
        registry.kernel_call("not_an_op", 1, 2)


def test_unknown_impl_raises():
    with pytest.raises(ValueError, match="unknown impl"):
        registry.kernel_call("gemm", None, None, impl="cuda")
    with pytest.raises(ValueError, match="unknown impl"):
        registry.set_default_impl("cuda")


def test_register_rejects_auto():
    with pytest.raises(ValueError):
        registry.register_kernel("gemm", impl="auto")


def test_all_ops_registered_with_all_impls():
    for op in ("gemm", "flash_attention", "linear_attention", "spmm",
               "bsr_spmm", "spmspm", "stencil", "decode_attention"):
        assert registry.implementations(op) == [
            "interpret", "pallas", "ref", "xla"
        ], op
    # an op without a kernel has no pallas entry, and asking for one raises
    registry.register_kernel("_test_xla_only", impl="xla")(lambda: "xla")
    try:
        assert registry.implementations("_test_xla_only") == ["xla"]
        with pytest.raises(NotImplementedError, match="no 'pallas'"):
            registry.kernel_call("_test_xla_only", impl="pallas")
    finally:
        registry._REGISTRY.pop("_test_xla_only")


def test_auto_resolves_per_op_on_tpu(monkeypatch):
    # steer the backend the resolver sees; no TPU is needed to check it
    monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
    monkeypatch.setattr(registry.jax, "default_backend", lambda: "tpu")
    assert registry.resolve_impl(None, "flash_attention") == "pallas"
    assert registry.resolve_impl(None, "gemm") == "pallas"
    assert registry.resolve_impl(None, "decode_attention") == "pallas"
    registry.register_kernel("_test_xla_only", impl="xla")(lambda: "xla")
    try:  # an op without a kernel runs its xla form
        assert registry.resolve_impl(None, "_test_xla_only") == "xla"
    finally:
        registry._REGISTRY.pop("_test_xla_only")
    assert registry.resolve_impl("ref", "flash_attention") == "ref"
    monkeypatch.setattr(registry.jax, "default_backend", lambda: "cpu")
    assert registry.resolve_impl(None, "flash_attention") == "xla"
    assert registry.resolve_impl(None, "decode_attention") == "xla"


def test_impl_precedence_env_default_arg(monkeypatch):
    probe = "_test_precedence_probe"
    for impl in ("pallas", "interpret", "xla", "ref"):
        registry.register_kernel(probe, impl=impl)(lambda _i=impl: _i)
    try:
        # no signal at all: auto => xla on CPU
        monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
        assert registry.kernel_call(probe) == "xla"
        # env var beats auto
        monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
        assert registry.kernel_call(probe) == "ref"
        # set_default_impl beats the env var
        registry.set_default_impl("interpret")
        assert registry.kernel_call(probe) == "interpret"
        # explicit argument beats everything
        assert registry.kernel_call(probe, impl="pallas") == "pallas"
    finally:
        registry._REGISTRY.pop(probe, None)  # don't leak the probe op


def test_block_override_table():
    assert registry.block_defaults("gemm")["bm"] == 256
    registry.set_block_override("gemm", bm=128)
    assert registry.block_defaults("gemm")["bm"] == 128
    assert registry.block_defaults("gemm")["bn"] == 256  # untouched
    registry.clear_block_overrides("gemm")
    assert registry.block_defaults("gemm")["bm"] == 256
    with pytest.raises(ValueError, match="no block parameters"):
        registry.set_block_override("gemm", bogus=1)
    with pytest.raises(KeyError, match="no block-size table"):
        registry.set_block_override("gem", bm=512)  # typo'd op: loud, not a no-op


def test_linear_attention_chunk_overflow_guard(rng):
    r = jnp.asarray(rng.standard_normal((1, 1, 64, 4)), jnp.float32)
    wl = jnp.zeros((1, 1, 64, 4), jnp.float32)
    with pytest.raises(ValueError, match="overflows fp32"):
        ops.linear_attention(r, r, r, wl, impl="xla", chunk=64)
    registry.set_block_override("linear_attention", chunk=64)
    with pytest.raises(ValueError, match="overflows fp32"):
        ops.linear_attention(r, r, r, wl, impl="xla")
    # ref runs the exact scan: chunk is irrelevant, so no guard
    o, _ = ops.linear_attention(r, r, r, wl, impl="ref", chunk=64)
    assert bool(jnp.all(jnp.isfinite(o)))


def test_block_override_feeds_kernels(rng):
    a = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    want = np.asarray(a @ b)
    registry.set_block_override("gemm", bm=32, bk=32, bn=32)
    got = ops.gemm(a, b, impl="interpret", out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_resolve_blocks_precedence():
    # default layer
    assert registry.resolve_blocks("gemm")["bm"] == 256
    # override layer beats default
    registry.set_block_override("gemm", bm=128)
    assert registry.resolve_blocks("gemm")["bm"] == 128
    # explicit kwarg beats override; None falls through
    resolved = registry.resolve_blocks("gemm", bm=64, bk=None)
    assert resolved == {"bm": 64, "bk": 256, "bn": 256}
    with pytest.raises(ValueError, match="no block parameters"):
        registry.resolve_blocks("gemm", bogus=1)
    with pytest.raises(KeyError, match="no block-size table"):
        registry.resolve_blocks("gem")


def test_block_override_scoped_context():
    registry.set_block_override("gemm", bm=128)
    with registry.block_override("gemm", bm=32, bk=32):
        assert registry.block_defaults("gemm")["bm"] == 32
        assert registry.block_defaults("gemm")["bk"] == 32
    # prior override restored exactly, including the untouched key
    assert registry.block_defaults("gemm") == {"bm": 128, "bk": 256, "bn": 256}
    registry.clear_block_overrides("gemm")
    with registry.block_override("gemm", bm=32):
        pass
    assert registry.block_defaults("gemm")["bm"] == 256  # no leak


def _observed_grid(monkeypatch, module_name, call):
    """Run ``call`` with the kernel module's stream_compute spied on and
    return the StreamProgram grid that actually executed."""
    import importlib

    from repro.core import streams

    mod = importlib.import_module(module_name)
    captured = {}
    orig = streams.stream_compute

    def spy(program, *operands, **kw):
        captured["grid"] = program.grid
        return orig(program, *operands, **kw)

    monkeypatch.setattr(mod, "stream_compute", spy)
    call()
    return captured["grid"]


def _geometry_cases(rng):
    """(op, kernel module, override, expected grid, call) for every op in the
    block table: the override must change the actually-executed geometry."""
    f32 = jnp.float32
    a = jnp.asarray(rng.standard_normal((64, 64)), f32)
    qkv = [jnp.asarray(rng.standard_normal((1, 2, 64, 8)), f32)
           for _ in range(3)]
    rkvw = [jnp.asarray(rng.standard_normal((1, 1, 64, 8)), f32)
            for _ in range(3)] + [
        jnp.asarray(-rng.uniform(0.01, 1.0, (1, 1, 64, 8)), f32)]
    ellA = sp.random_ell(rng, 64, 32, 0.1)
    spd = jnp.asarray(rng.standard_normal((32, 8)), f32)
    bsr_dense = np.zeros((16, 256), np.float32)
    bsr_dense[::3, ::17] = 1.0
    bsrA = sp.dense_to_bsr(bsr_dense, bm=8, bk=128)
    bsr_rhs = jnp.asarray(rng.standard_normal((256, 64)), f32)
    iA, iB = sp.random_ell(rng, 32, 64, 0.1), sp.random_ell(rng, 64, 64, 0.1)
    grid3 = jnp.asarray(rng.standard_normal((16, 8, 8)), f32)
    offs = np.array([(0, 0, 0), (1, 0, 0)], np.int32)
    w = np.array([0.5, 0.5], np.float32)
    T = len(bsrA.tile_rows)
    dq = jnp.asarray(rng.standard_normal((2, 4, 8)), f32)
    pool = jnp.asarray(rng.standard_normal((9, 4, 4, 8)), f32)
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    dpos = jnp.asarray([9, 14], jnp.int32)
    return [
        ("decode_attention", "repro.kernels.paged_attention", dict(pages=2),
         (2, 2),
         lambda: ops.decode_attention(dq, pool, pool, dpos, paged=True,
                                      block_table=table, impl="interpret")),
        ("gemm", "repro.kernels.gemm", dict(bm=16, bk=32, bn=16), (4, 4, 2),
         lambda: ops.gemm(a, a, impl="interpret")),
        ("flash_attention", "repro.kernels.flash_attention",
         dict(bq=16, bk=32), (1, 2, 4, 2),
         lambda: ops.flash_attention(*qkv, impl="interpret")),
        ("linear_attention", "repro.kernels.rwkv6", dict(chunk=16), (1, 4),
         lambda: ops.linear_attention(*rkvw, impl="interpret")),
        ("spmm", "repro.kernels.spmm", dict(bm=16), (4,),
         lambda: ops.spmm(ellA, spd, impl="interpret")),
        ("bsr_spmm", "repro.kernels.spmm", dict(bf=32), (2, T),
         lambda: ops.bsr_spmm(bsrA, bsr_rhs, impl="interpret")),
        ("spmspm", "repro.kernels.spmspm", dict(bm=16, bn=32), (2, 2),
         lambda: ops.spmspm(iA, iB, 64, impl="interpret")),
        ("stencil", "repro.kernels.stencil", dict(bx=4), (4,),
         lambda: ops.stencil(grid3, offs, w, impl="interpret")),
    ]


def test_block_override_changes_geometry_for_every_op(rng, monkeypatch):
    cases = _geometry_cases(rng)
    # decode_attention's case is its paged kernel (``pages``); the
    # contiguous cache's ``bs`` reaches the xla form, covered by
    # test_partition.test_decode_attention_override_reaches_xla_impl
    assert {c[0] for c in cases} == set(registry._BLOCK_DEFAULTS)
    for op, module, override, want_grid, call in cases:
        registry.clear_block_overrides()
        registry.set_block_override(op, **override)
        got = _observed_grid(monkeypatch, module, call)
        assert got == want_grid, (op, got, want_grid)


def test_flash_attention_override_reaches_xla_impl(rng, monkeypatch):
    """Split-brain regression: set_block_override and an explicit arg must
    reach the xla impl identically (the old ops.py block_k=512 literal only
    reached xla, and pallas silently ignored block_k=)."""
    import repro.kernels.xla as xla_mod

    q = jnp.asarray(rng.standard_normal((1, 2, 64, 8)), jnp.float32)
    captured = {}
    orig = xla_mod.flash_attention_xla

    def spy(*a, **kw):
        captured["bk"] = kw.get("bk")
        return orig(*a, **kw)

    monkeypatch.setattr(xla_mod, "flash_attention_xla", spy)
    registry.set_block_override("flash_attention", bk=16)
    ops.flash_attention(q, q, q, impl="xla")
    assert captured["bk"] == 16
    ops.flash_attention(q, q, q, impl="xla", bk=32)  # explicit beats override
    assert captured["bk"] == 32
    ops.flash_attention(q, q, q, impl="xla", block_k=8)  # historical alias
    assert captured["bk"] == 8
    with pytest.raises(TypeError, match="disagree"):
        ops.flash_attention(q, q, q, impl="xla", bk=8, block_k=16)


def test_flash_attention_explicit_bk_same_result_across_impls(rng):
    q = jnp.asarray(rng.standard_normal((1, 2, 64, 8)), jnp.float32)
    want = ops.flash_attention(q, q, q, impl="ref")
    got_xla = ops.flash_attention(q, q, q, impl="xla", bk=16)
    got_int = ops.flash_attention(q, q, q, impl="interpret", bk=16)
    np.testing.assert_allclose(np.asarray(got_xla), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_int), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_block_resolution_single_path():
    """Single-path invariant, now owned by the static checker: ops.py
    carries no block-size literals, every block-tabled op resolves through
    registry.resolve_blocks, and no kernel impl module keeps private
    block_defaults plumbing or an environment escape hatch (the
    REPRO_UNROLL_GRID regression). Positive coverage — proof the rules
    actually fire — lives in tests/test_analysis.py."""
    from repro.analysis import run_rules

    findings = run_rules(
        ["block-geometry-registry-only", "no-environ-in-kernels"]
    )
    assert findings == [], "\n".join(f.format() for f in findings)


def test_unrolled_flash_blocks_route_through_registry(rng):
    """The unrolled (roofline) flash path honours set_block_override and
    explicit bq/bk exactly like the scan path — no private geometry."""
    import repro.kernels.xla as xla_mod

    q = jnp.asarray(rng.standard_normal((1, 2, 64, 8)), jnp.float32)
    want = ops.flash_attention(q, q, q, impl="ref")
    with registry.unroll_inner():
        registry.set_block_override("flash_attention", bq=16, bk=32)
        got = ops.flash_attention(q, q, q, impl="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        # explicit kwarg beats the override, same as every other impl
        got = ops.flash_attention(q, q, q, impl="xla", bq=8, bk=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        # geometry actually reached the unrolled loop: a bq that doesn't
        # divide Sq exercises its padding path
        registry.set_block_override("flash_attention", bq=48, bk=48)
        got = ops.flash_attention(q, q, q, impl="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_default_impl_context_manager():
    assert registry.resolve_impl(None) in ("pallas", "xla")  # auto
    with registry.default_impl("ref"):
        assert registry.resolve_impl(None) == "ref"
        with registry.default_impl("interpret"):
            assert registry.resolve_impl(None) == "interpret"
        assert registry.resolve_impl(None) == "ref"
    assert registry.resolve_impl(None) in ("pallas", "xla")  # restored
    with pytest.raises(ValueError, match="unknown impl"):
        with registry.default_impl("cuda"):
            pass
    # a raise inside the scope still restores
    with pytest.raises(RuntimeError):
        with registry.default_impl("ref"):
            raise RuntimeError
    assert registry.resolve_impl(None) in ("pallas", "xla")


# ---------------------------------------------------------------------------
# Formats: pytree round trips (including all-zero rows)
# ---------------------------------------------------------------------------


def _random_dense(rng, r, c, density, zero_rows=()):
    dense = np.zeros((r, c), np.float32)
    mask = rng.random((r, c)) < density
    dense[mask] = rng.standard_normal(mask.sum())
    for zr in zero_rows:
        dense[zr] = 0.0
    return dense


@pytest.mark.parametrize("zero_rows", [(), (0, 3, 7)])
def test_dense_roundtrip_all_formats(rng, zero_rows):
    dense = _random_dense(rng, 16, 256, 0.05, zero_rows)
    for convert in (sp.dense_to_ell, sp.dense_to_csr,
                    lambda d: sp.dense_to_bsr(d, bm=8, bk=128)):
        A = convert(dense)
        np.testing.assert_allclose(np.asarray(A.todense()), dense, err_msg=str(convert))


def test_conversion_path_csr_ell_bsr(rng):
    # rows 0-7 all zero: the whole first 8-row block is empty, exercising the
    # empty-tile insertion in csr_to_bsr
    dense = _random_dense(rng, 16, 256, 0.04, zero_rows=tuple(range(8)) + (9,))
    ell = sp.dense_to_ell(dense)
    csr = sp.ell_to_csr(ell)
    np.testing.assert_allclose(np.asarray(csr.todense()), dense)
    ell2 = sp.csr_to_ell(csr)
    np.testing.assert_allclose(np.asarray(ell2.todense()), dense)
    bsr = sp.csr_to_bsr(csr, bm=8, bk=128)
    np.testing.assert_allclose(np.asarray(bsr.todense()), dense)
    np.testing.assert_allclose(np.asarray(sp.bsr_to_csr(bsr).todense()), dense)
    np.testing.assert_allclose(
        np.asarray(sp.bsr_to_ell(sp.ell_to_bsr(ell)).todense()), dense
    )


def test_ell_padding_never_contributes(rng):
    # padded slots alias column 0 with value 0: col 0's true value must
    # survive the aliased scatter-adds exactly
    dense = _random_dense(rng, 8, 64, 0.1)
    dense[:, 0] = 7.0  # every row has a real entry at the aliased column
    A = sp.dense_to_ell(dense, max_nnz=32)  # force padding slots
    got = np.asarray(A.todense())
    assert np.all(got[:, 0] == 7.0)
    np.testing.assert_allclose(got, dense)
    # the micro-assert itself: zeroing all padded slots changes nothing
    mask = np.asarray(A.values) != 0
    stripped = sp.EllMatrix(
        jnp.where(jnp.asarray(mask), A.values, 0.0), A.cols, A.shape
    )
    np.testing.assert_allclose(np.asarray(stripped.todense()), got)


def test_dense_to_ell_honors_wide_max_nnz(rng):
    dense = _random_dense(rng, 4, 8, 0.5)
    A = sp.dense_to_ell(dense, max_nnz=12)  # wider than the matrix itself
    assert A.values.shape == (4, 12) and A.cols.shape == (4, 12)
    np.testing.assert_allclose(np.asarray(A.todense()), dense)


def test_dense_to_ell_rejects_narrow_max_nnz():
    # row 1 has 5 nonzeros; a narrower max_nnz must be loud, never a silent
    # drop of the overflow entries
    dense = np.zeros((3, 8), np.float32)
    dense[1, :5] = 1.0
    dense[2, :2] = 1.0
    with pytest.raises(ValueError, match=r"row 1 has 5 nonzeros > max_nnz=3"):
        sp.dense_to_ell(dense, max_nnz=3)
    # exactly-fitting width still works
    np.testing.assert_allclose(
        np.asarray(sp.dense_to_ell(dense, max_nnz=5).todense()), dense
    )


def test_csr_to_ell_rejects_narrow_max_nnz():
    dense = np.zeros((4, 8), np.float32)
    dense[2, :6] = 2.0
    csr = sp.dense_to_csr(dense)
    with pytest.raises(ValueError, match=r"row 2 has 6 nonzeros > max_nnz=4"):
        sp.csr_to_ell(csr, max_nnz=4)
    np.testing.assert_allclose(
        np.asarray(sp.csr_to_ell(csr, max_nnz=6).todense()), dense
    )


def test_launchers_append_xla_flags(monkeypatch):
    """Regression: hillclimb (PR 2) and dryrun (this PR) used to clobber any
    caller-set XLA_FLAGS with a bare ``os.environ[...] = ...`` assignment.
    Both now route through launch.xla_flags.ensure_host_device_count."""
    import importlib

    import repro.launch.dryrun as dr
    import repro.launch.hillclimb as hc

    for mod in (hc, dr):
        monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/tmp/x")
        importlib.reload(mod)
        flags = os.environ["XLA_FLAGS"].split()
        assert "--xla_dump_to=/tmp/x" in flags, mod.__name__
        assert "--xla_force_host_platform_device_count=512" in flags
        importlib.reload(mod)  # idempotent: appending twice adds nothing
        assert os.environ["XLA_FLAGS"].split().count(
            "--xla_force_host_platform_device_count=512"
        ) == 1
        # a caller-chosen device count survives (no conflicting append)
        monkeypatch.setenv(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
        )
        importlib.reload(mod)
        assert os.environ["XLA_FLAGS"] == \
            "--xla_force_host_platform_device_count=8", mod.__name__


def test_launchers_never_assign_xla_flags_directly():
    """XLA_FLAGS is only ever APPENDED via the shared bootstrap, never
    assigned a fresh literal (the clobber pattern that silently discarded
    user flags) — enforced tree-wide by the static checker's
    xla-flags-append-only rule; this wrapper keeps the invariant in the
    tier-1 suite."""
    import pathlib

    from repro.analysis import run_rules

    findings = run_rules(["xla-flags-append-only"])
    assert findings == [], "\n".join(f.format() for f in findings)
    # the one place that may write the variable is the append-only helper
    import repro.launch.xla_flags as xf

    helper = pathlib.Path(xf.__file__).read_text()
    assert "existing" in helper and "_DEVICE_FLAG" in helper


def test_formats_are_pytrees(rng):
    dense = _random_dense(rng, 16, 256, 0.05)
    ell = sp.dense_to_ell(dense)
    bsr = sp.dense_to_bsr(dense)
    csr = sp.dense_to_csr(dense)
    assert len(jax.tree_util.tree_leaves(ell)) == 2
    assert len(jax.tree_util.tree_leaves(bsr)) == 3
    assert len(jax.tree_util.tree_leaves(csr)) == 3
    # shape is static aux data: it survives flatten/unflatten
    flat, treedef = jax.tree_util.tree_flatten(ell)
    assert jax.tree_util.tree_unflatten(treedef, flat).shape == (16, 256)


def test_ell_jit_traces_without_densifying(rng):
    R, C, F = 24, 512, 8
    dense = _random_dense(rng, R, C, 0.02)
    A = sp.dense_to_ell(dense)
    D = jnp.asarray(rng.standard_normal((C, F)), jnp.float32)

    @jax.jit
    def agg(A, D):
        return ops.spmm(A, D, impl="ref")

    got = agg(A, D)
    np.testing.assert_allclose(
        np.asarray(got), dense @ np.asarray(D), rtol=1e-4, atol=1e-4
    )
    # keyword form of the overload behaves identically
    got_kw = ops.spmm(A, dense=D, impl="ref")
    np.testing.assert_allclose(np.asarray(got_kw), np.asarray(got),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(TypeError, match="required"):
        ops.spmm(A)
    # half-migrated old-style call: extra operands must be loud, not ignored
    with pytest.raises(TypeError, match="extra operand"):
        ops.spmm(A, A.cols, D)
    # no (R, C) dense adjacency anywhere in the traced program
    jaxpr = str(jax.make_jaxpr(lambda A, D: ops.spmm(A, D, impl="ref"))(A, D))
    assert f"{R},{C}" not in jaxpr


def test_bsr_jit_roundtrip(rng):
    dense = _random_dense(rng, 64, 256, 0.03)
    bsr = sp.dense_to_bsr(dense, bm=8, bk=128)
    D = jnp.asarray(rng.standard_normal((256, 16)), jnp.float32)
    got = jax.jit(lambda a, d: ops.bsr_spmm(a, d))(bsr, D)
    np.testing.assert_allclose(
        np.asarray(got), dense @ np.asarray(D), rtol=1e-4, atol=1e-4
    )
    # keyword form of the overload behaves identically
    got_kw = ops.bsr_spmm(bsr, dense=D, impl="xla")
    np.testing.assert_allclose(np.asarray(got_kw), np.asarray(got),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(TypeError, match="required"):
        ops.bsr_spmm(bsr)
    with pytest.raises(TypeError, match="extra operands"):
        ops.bsr_spmm(bsr, bsr.tile_rows, bsr.tile_cols, D, 64)


def test_spmspm_accepts_ell_operands(rng):
    A = sp.random_ell(rng, 32, 128, 0.1)
    B = sp.random_ell(rng, 48, 128, 0.1)
    from repro.kernels import ref

    want = ref.spmspm_ref(A.values, A.cols, B.values, B.cols, 128)
    got = jax.jit(lambda a, b: ops.spmspm(a, b, 128))(A, B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # keyword form of the overload must behave identically
    got_kw = ops.spmspm(A, B, contraction_dim=128, impl="xla")
    np.testing.assert_allclose(np.asarray(got_kw), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(TypeError, match="contraction_dim"):
        ops.spmspm(A, B)
    with pytest.raises(TypeError, match="extra operands"):
        ops.spmspm(A, B, 128, contraction_dim=128)
    with pytest.raises(TypeError, match="must also be an EllMatrix"):
        ops.spmspm(A, B.values, 128)


# ---------------------------------------------------------------------------
# Streams: program metadata
# ---------------------------------------------------------------------------


def test_stream_program_metadata():
    from repro.kernels.gemm import gemm_program

    prog = gemm_program(
        256, 256, 256, 128, 128, 128,
        a_dtype=jnp.bfloat16, b_dtype=jnp.float32,
        out_dtype=jnp.float32, accum_dtype=jnp.float32,
    )
    assert prog.steps == 2 * 2 * 2
    # per step: one bf16 A tile, one f32 B tile, one f32 output tile
    per_step = 128 * 128 * 2 + 2 * (128 * 128 * 4)
    assert prog.traffic_bytes() == per_step * prog.steps
    assert prog.in_streams[0].bytes_per_step == 128 * 128 * 2
    assert prog.in_streams[1].bytes_per_step == 128 * 128 * 4


def test_stream_compute_multi_output(rng):
    # the linear-attention program: two output streams through one launch
    r = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
    wl = jnp.asarray(-rng.uniform(0.01, 2.0, (1, 2, 32, 8)), jnp.float32)
    o, S = ops.linear_attention(r, k, v, wl, impl="interpret", chunk=16)
    from repro.kernels import ref

    o_ref, s_ref = ref.linear_attention_scan_ref(
        r, k, v, jnp.maximum(wl, ops.W_LOG_FLOOR), None, None
    )
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


def test_no_pallas_call_outside_streams():
    """The substrate invariant: core/streams.py is the only pallas_call
    site — enforced by the static checker's single-pallas-site rule."""
    from repro.analysis import run_rules

    findings = run_rules(["single-pallas-site"])
    assert findings == [], "\n".join(f.format() for f in findings)
