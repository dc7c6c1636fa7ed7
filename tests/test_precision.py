"""Precision ladder tests: blockwise quantization round-trips, the scaled
kernel paths against the fp32 oracles across every CPU impl, the
policy-aware cost model (dtype aliases, peak-flops override, dry-run
sweep cells), gradient-compression unbiasedness, and the sharded fp8
paths on forced host devices (subprocess, like test_partition)."""
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
from repro.kernels import ops, ref


def _rel(got, want):
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    return float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))


# ---------------------------------------------------------------------------
# Policy resolution + blockwise quantization
# ---------------------------------------------------------------------------


def test_resolve_policy_seam():
    assert prec.resolve(None) is None
    p = prec.resolve("fp8")
    assert p.compute_dtype == jnp.float8_e4m3fn and p.scale_block == 128
    assert prec.resolve(p) is p
    with pytest.raises(KeyError, match="known:"):
        prec.resolve("fp4")
    assert prec.supported_policies("gemm") == (
        "fp32", "bf16", "fp8", "fp8_e5m2"
    )
    assert prec.supported_policies("spmm") == ("fp32",)


@pytest.mark.parametrize("pol,tol", [("fp8", 0.05), ("fp8_e5m2", 0.12)])
def test_quantize_blockwise_roundtrip(rng, pol, tol):
    x = jnp.asarray(rng.standard_normal((5, 300)), jnp.float32)
    vals, scales = prec.quantize_blockwise(x, pol, axis=-1, block=128)
    assert vals.dtype == prec.resolve(pol).compute_dtype
    assert vals.shape == x.shape
    assert scales.shape == (5, 3) and scales.dtype == jnp.float32  # ceil(300/128)
    deq = prec.dequantize_blockwise(vals, scales, axis=-1, block=128)
    assert deq.dtype == jnp.float32
    assert _rel(deq, x) < tol
    # per-block scaling: every scaled value fits the narrow format's range
    fmax = float(jnp.finfo(prec.resolve(pol).compute_dtype).max)
    assert float(jnp.max(jnp.abs(jnp.asarray(vals, jnp.float32)))) <= fmax


def test_quantize_wide_policies_are_plain_casts(rng):
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    vals, scales = prec.quantize_blockwise(x, "bf16", axis=-1)
    assert vals.dtype == jnp.bfloat16
    assert scales.shape == (4, 1)  # scale_block=0: one whole-axis unit scale
    np.testing.assert_array_equal(np.asarray(scales), 1.0)
    deq = prec.dequantize_blockwise(vals, scales, axis=-1)
    np.testing.assert_array_equal(
        np.asarray(deq), np.asarray(x.astype(jnp.bfloat16), np.float32)
    )


def test_quantize_zero_blocks_roundtrip_exactly():
    x = jnp.zeros((2, 256), jnp.float32)
    vals, scales = prec.quantize_blockwise(x, "fp8", axis=-1, block=128)
    np.testing.assert_array_equal(np.asarray(scales), 1.0)  # not 0/0
    deq = prec.dequantize_blockwise(vals, scales, axis=-1, block=128)
    np.testing.assert_array_equal(np.asarray(deq), 0.0)


def test_dequantize_ragged_axis_needs_explicit_block(rng):
    # K=160 quantized at block=64 -> nb=3 with a ragged final block; the
    # inferred block ceil(160/3)=54 would misalign every scale boundary
    # (the bug the explicit ``block=`` parameter exists for)
    x = jnp.asarray(rng.standard_normal((8, 160)), jnp.float32)
    vals, scales = prec.quantize_blockwise(x, "fp8", axis=1, block=64)
    assert scales.shape == (8, 3)
    good = prec.dequantize_blockwise(vals, scales, axis=1, block=64)
    assert _rel(good, x) < 0.05
    assert _rel(
        prec.dequantize_blockwise(vals, scales, axis=1), x
    ) > _rel(good, x)


def test_quantize_kv_cache_layout(rng):
    k = jnp.asarray(rng.standard_normal((2, 4, 32, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 4, 32, 16)), jnp.float32)
    kq, ks, vq, vs = prec.quantize_kv_cache(k, v, "fp8")
    assert kq.dtype == jnp.float8_e4m3fn and kq.shape == k.shape
    assert ks.shape == (2, 4, 32, 1)  # one fp32 scale per cached token row
    assert _rel(prec.dequantize_blockwise(kq, ks, axis=-1), k) < 0.05
    assert _rel(prec.dequantize_blockwise(vq, vs, axis=-1), v) < 0.05


# ---------------------------------------------------------------------------
# Scaled kernels vs the fp32 oracle, across every CPU impl
# ---------------------------------------------------------------------------

_GEMM_TOL = {"fp32": 1e-5, "bf16": 0.02, "fp8": 0.1, "fp8_e5m2": 0.2}


@pytest.mark.parametrize("pol", ["fp32", "bf16", "fp8", "fp8_e5m2"])
def test_scaled_gemm_cross_impl(rng, pol):
    a = jnp.asarray(rng.standard_normal((96, 160)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((160, 80)), jnp.float32)
    oracle = ref.gemm_ref(a, b, jnp.float32)
    outs = {
        impl: ops.gemm(a, b, precision=pol, impl=impl, bk=64)
        for impl in ("xla", "interpret", "ref")
    }
    for impl, got in outs.items():
        assert got.dtype == jnp.float32
        assert _rel(got, oracle) < _GEMM_TOL[pol], (impl, _rel(got, oracle))
    # the impls implement ONE quantization scheme: they agree far tighter
    # with each other than any of them does with the unquantized oracle
    for impl in ("xla", "interpret"):
        assert _rel(outs[impl], outs["ref"]) < 1e-4, impl


@pytest.mark.parametrize("pol,tol", [("bf16", 0.02), ("fp8", 0.1)])
def test_scaled_flash_attention_cross_impl(rng, pol, tol):
    q = jnp.asarray(rng.standard_normal((1, 4, 64, 32)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((1, 2, 64, 32)), jnp.float32)
    oracle = ref.mha_ref(q, kv, kv, causal=True)
    outs = {
        impl: ops.flash_attention(q, kv, kv, causal=True, precision=pol,
                                  impl=impl)
        for impl in ("xla", "interpret", "ref")
    }
    for impl, got in outs.items():
        assert got.dtype == jnp.float32  # scaled path always widens out
        assert _rel(got, oracle) < tol, (impl, _rel(got, oracle))
    for impl in ("xla", "interpret"):
        assert _rel(outs[impl], outs["ref"]) < 1e-4, impl


@pytest.mark.parametrize("pol,tol", [("bf16", 0.02), ("fp8", 0.1)])
def test_scaled_decode_attention_cross_impl(rng, pol, tol):
    q = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((2, 4, 32, 16)), jnp.float32)
    pos = jnp.asarray([5, 30], jnp.int32)
    oracle = ref.decode_attention_ref(q, kv, kv, pos)
    outs = {
        impl: ops.decode_attention(q, kv, kv, pos, precision=pol, impl=impl)
        for impl in ("xla", "auto", "ref")
    }
    for impl, got in outs.items():
        assert _rel(got, oracle) < tol, (impl, _rel(got, oracle))
    assert _rel(outs["xla"], outs["ref"]) < 1e-4


def test_precision_none_is_the_exact_legacy_path(rng):
    a = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    legacy = ops.gemm(a, b, impl="xla")
    np.testing.assert_array_equal(
        np.asarray(ops.gemm(a, b, impl="xla", precision=None)),
        np.asarray(legacy),
    )
    # fp32 *policy* runs the scaled machinery with unit scales: numerically
    # equivalent, reassociated over K blocks
    assert _rel(ops.gemm(a, b, impl="xla", precision="fp32"), legacy) < 1e-5


# ---------------------------------------------------------------------------
# Cost model: dtype aliases, peak-flops override, sweep cells
# ---------------------------------------------------------------------------


def test_collective_bytes_fp8_and_bf16_alias_spellings():
    from repro.launch import roofline

    # every fp8 spelling XLA emits is one byte — a missing entry silently
    # fell back to 4 B/elem and quadrupled low-precision collective bytes
    for alias in ("f8e4m3", "f8e3m4", "f8e4m3fn", "f8e4m3fnuz",
                  "f8e4m3b11fnuz", "f8e5m2", "f8e5m2fnuz", "s4", "u4"):
        assert roofline._DTYPE_BYTES[alias] == 1, alias
    hlo = textwrap.dedent("""
        %big = f8e5m2fnuz[256] parameter(0)
        %ag = f8e4m3[128,64] all-gather(%x), replica_groups={}
        %ar = bf16[256] all-reduce(%y), to_apply=%sum
        %rs = f8e5m2fnuz[64] reduce-scatter(%big), dimensions={0}
    """)
    got = roofline.collective_bytes(hlo)
    assert got["by_kind"]["all-gather"] == 128 * 64 * 1
    assert got["by_kind"]["all-reduce"] == 2.0 * 256 * 2
    assert got["by_kind"]["reduce-scatter"] == 256 * 1  # operand side
    assert got["total"] == 8192 + 1024 + 256


def test_roofline_terms_peak_flops_override():
    from repro.launch import roofline

    base = roofline.roofline_terms(1e12, 0.0, 0.0)
    fp8 = roofline.roofline_terms(
        1e12, 0.0, 0.0, peak_flops=prec.peak_flops("fp8")
    )
    assert fp8["compute_s"] == pytest.approx(
        base["compute_s"] * roofline.PEAK_FLOPS / prec.peak_flops("fp8")
    )
    ov = roofline.overlapped_terms(
        1e12, 0.0, 0.0, d2d_s=0.0, hops=4,
        peak_flops=prec.peak_flops("fp8"),
    )
    assert ov["compute_s"] == fp8["compute_s"]


def test_op_roofline_cells_precision_sweep():
    from repro.launch.dryrun import op_roofline_cells

    f32 = {c["op"]: c for c in op_roofline_cells(precision="fp32")}
    fp8 = {c["op"]: c for c in op_roofline_cells(precision="fp8")}
    g32, g8 = f32["gemm"], fp8["gemm"]
    assert g8["precision"] == "fp8" and g32["precision"] == "fp32"
    # 4x flop ceiling: same flops, a quarter of the compute time
    assert g32["roofline"]["compute_s"] >= 2 * g8["roofline"]["compute_s"]
    # narrow storage (+ one fp32 scale per 128 elems) and bf16 psum reduce
    assert g8["bytes_per_device"] <= 0.5 * g32["bytes_per_device"]
    assert g8["d2d_bytes"] <= 0.5 * g32["d2d_bytes"]
    assert "bfloat16 reduce" in g8["partition"]
    # the ring's per-hop KV permutes shrink with the storage width too
    fa32, fa8 = f32["flash_attention"], fp8["flash_attention"]
    assert fa8["d2d_bytes"] <= 0.5 * fa32["d2d_bytes"]
    # ops without a scaled path keep their full-precision cell
    assert fp8["stencil"]["precision"] == "fp32"
    # no-precision cells carry no precision key at all (legacy output)
    assert "precision" not in op_roofline_cells()[0]


def test_docgen_dispatch_table_lists_precisions():
    from repro.launch import docgen

    text = docgen.generate()
    assert "| precisions |" in text
    assert "| `gemm` | " in text and "fp32, bf16, fp8, fp8_e5m2" in text
    # fp32-only ops say so (no scaled path advertised)
    line = next(ln for ln in text.splitlines() if ln.startswith("| `stencil`"))
    assert line.rstrip().endswith("| fp32 |")


# ---------------------------------------------------------------------------
# Gradient compression: error feedback stays unbiased per policy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pol", ["bf16", "fp8"])
def test_compression_error_feedback_telescopes(rng, pol):
    from repro.optim import compression

    grads = {
        "w": jnp.asarray(rng.standard_normal((4, 300)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal((7,)), jnp.float32),
        "s": jnp.asarray(rng.standard_normal(()), jnp.float32),
    }
    err = compression.init_error_state(grads)
    total = jax.tree.map(jnp.zeros_like, grads)
    steps = 6
    for _ in range(steps):
        sent, err = compression.compress_decompress(grads, err, policy=pol)
        assert jax.tree.structure(sent) == jax.tree.structure(grads)
        total = jax.tree.map(lambda t, s: t + s, total, sent)
    # unbiasedness: what was sent plus the final residual is EXACTLY the
    # sum of the true gradients (the round-trip error telescopes)
    for leaf, g in (("w", grads["w"]), ("b", grads["b"]), ("s", grads["s"])):
        np.testing.assert_allclose(
            np.asarray(total[leaf] + err[leaf]),
            steps * np.asarray(g),
            rtol=2e-5, atol=2e-5,
        )


def test_compression_default_policy_is_legacy_bf16(rng):
    from repro.optim import compression

    g = {"w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)}
    e = compression.init_error_state(g)
    sent, _ = compression.compress_decompress(g, e)  # positional callers
    np.testing.assert_array_equal(
        np.asarray(sent["w"]),
        np.asarray(g["w"].astype(jnp.bfloat16), np.float32),
    )


# ---------------------------------------------------------------------------
# Sharded fp8: the scaled paths under real shard_map plans (subprocess so
# the forced-device-count flag never leaks into this process)
# ---------------------------------------------------------------------------

_SHARDED = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.kernels import ops, partition
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(0)
    f32 = jnp.float32
    out = {"ok": []}

    def check(name, got, want, tol):
        g = np.asarray(got, np.float32)
        w = np.asarray(want, np.float32)
        rel = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        assert rel < tol, (name, rel)
        out["ok"].append(name)

    # fp8 gemm over a genuine 2-way K-shard (model=2): per-shard
    # quantization + fp32 accumulate + bf16-reduce psum epilogue
    a = jnp.asarray(rng.standard_normal((64, 256)), f32)
    b = jnp.asarray(rng.standard_normal((256, 48)), f32)
    mesh = make_mesh((1, 2), ("data", "model"))
    plan = partition.plan_for("gemm", mesh, a, b, precision="fp8")
    assert "k-sharded" in plan.note and "bfloat16 reduce" in plan.note, plan.note
    want = ops.gemm(a, b, impl="ref", out_dtype=f32)
    for impl in ("xla", "interpret"):
        got = ops.gemm(a, b, mesh=mesh, impl=impl, precision="fp8", bk=64)
        single = ops.gemm(a, b, impl=impl, precision="fp8", bk=64)
        check(f"gemm_fp8[{impl}]", got, want, 0.1)
        check(f"gemm_fp8_vs_single[{impl}]", got, single, 0.02)

    # fp8 flash over batch x kv-head sharding (data=2, model=4)
    q = jnp.asarray(rng.standard_normal((2, 8, 32, 16)), f32)
    kv = jnp.asarray(rng.standard_normal((2, 4, 32, 16)), f32)
    mesh8 = make_mesh((2, 4), ("data", "model"))
    want = ops.flash_attention(q, kv, kv, impl="ref")
    for impl in ("xla", "interpret"):
        got = ops.flash_attention(q, kv, kv, mesh=mesh8, impl=impl,
                                  precision="fp8")
        check(f"flash_fp8[{impl}]", got, want, 0.1)

    # fp8 flash on the B=1 sequence-parallel KV ring (data=4): per-hop
    # quantization inside the ring fold
    q1 = jnp.asarray(rng.standard_normal((1, 4, 64, 16)), f32)
    kv1 = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), f32)
    mesh42 = make_mesh((4, 2), ("data", "model"))
    plan = partition.plan_for("flash_attention", mesh42, q1, kv1, kv1,
                              precision="fp8")
    assert "ring seq-parallel" in plan.note, plan.note
    want = ops.flash_attention(q1, kv1, kv1, impl="ref")
    got = ops.flash_attention(q1, kv1, kv1, mesh=mesh42, impl="xla",
                              precision="fp8")
    check("flash_fp8_ring", got, want, 0.1)
    print("RESULT:" + json.dumps(out))
    """
)


def test_sharded_fp8_equivalence_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    for impl in ("xla", "interpret"):
        assert f"gemm_fp8[{impl}]" in out["ok"]
        assert f"gemm_fp8_vs_single[{impl}]" in out["ok"]
        assert f"flash_fp8[{impl}]" in out["ok"]
    assert "flash_fp8_ring" in out["ok"]
