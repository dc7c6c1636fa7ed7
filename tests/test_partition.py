"""Partitioning-layer tests: PartitionRule resolution (device-free, via
partition.MeshSpec), sharded-vs-single-device numerical equivalence for every
partitioned op (subprocess with 8 forced host devices, like
test_distribution.py), halo-exchange correctness at block boundaries,
replication fallback on indivisible shapes, and the host_device_mesh
graceful-degradation contract."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, partition, registry
from repro.launch import roofline


@pytest.fixture(autouse=True)
def _clean_registry_state():
    yield
    registry.set_default_impl(None)
    registry.clear_block_overrides()


S = jax.ShapeDtypeStruct
MESH8 = partition.MeshSpec({"data": 2, "model": 4})
MESH_2POD = partition.MeshSpec({"pod": 2, "data": 2, "model": 4})


# ---------------------------------------------------------------------------
# Rule resolution (no devices needed: plans resolve from shapes alone)
# ---------------------------------------------------------------------------


def test_every_block_table_op_has_a_partition_rule():
    assert set(partition.partitioned_ops()) == set(registry._BLOCK_DEFAULTS)


def test_partition_axis_prefers_model():
    assert partition.partition_axis(MESH8) == "model"
    assert partition.partition_axis(partition.MeshSpec({"pod": 2, "x": 4})) == "x"


def test_partition_levels_resolution():
    assert partition.partition_levels(MESH8) == (("model", 4),)
    assert partition.partition_levels(MESH_2POD) == (("pod", 2), ("model", 4))
    # size-1 axes drop out of the level stack
    assert partition.partition_levels(
        partition.MeshSpec({"pod": 1, "data": 2, "model": 4})
    ) == (("model", 4),)
    assert partition.partition_levels(
        partition.MeshSpec({"pod": 2, "data": 2, "model": 1})
    ) == (("pod", 2),)
    assert partition.partition_levels(
        partition.MeshSpec({"data": 1, "model": 1})
    ) == ()


def test_gemm_two_level_plan_and_per_level_costs():
    f32 = jnp.float32
    plan = partition.plan_for(
        "gemm", MESH_2POD, S((32, 64), f32), S((64, 16), f32))
    assert plan.levels == (("pod", 2), ("model", 4)) and plan.n == 8
    assert plan.axis == ("pod", "model")
    assert "k-sharded" in plan.note and "pod=2+model=4" in plan.note
    # hierarchical all-reduce: intra-pod psum fires first, then the D2D hop,
    # each costed at its own level's ring size
    assert [(c.kind, c.axis, c.n) for c in plan.collectives] == [
        ("all_reduce", "model", 4), ("all_reduce", "pod", 2)]
    assert all(c.nbytes == 32 * 16 * 4 for c in plan.collectives)
    by_level = roofline.plan_collective_seconds_by_level(plan)
    assert set(by_level) == {"model", "pod"}
    # same payload, but the pod hop rides the narrow D2D link: the 2-ring at
    # half bandwidth must out-cost nothing implicitly — check against the
    # topology model directly
    from repro.core import topology

    nb = 32 * 16 * 4
    assert by_level["model"] == pytest.approx(
        topology.collective_seconds("all_reduce", nb, "model", 4))
    assert by_level["pod"] == pytest.approx(
        topology.collective_seconds("all_reduce", nb, "pod", 2))
    assert roofline.plan_collective_seconds(plan) == pytest.approx(
        by_level["model"] + by_level["pod"])


def test_fallback_ladder_drops_pod_level_before_replicating():
    f32 = jnp.float32
    # 4 kv heads divide model=4 but not pod*model=8: the ladder drops the
    # pod level and head-shards intra-pod (composed with B over data)
    q, kv = S((2, 8, 32, 16), f32), S((2, 4, 32, 16), f32)
    plan = partition.plan_for("flash_attention", MESH_2POD, q, kv, kv)
    assert plan is not None
    assert plan.levels == (("data", 2), ("model", 4))
    # 8 kv heads divide pod*model=8: full head placement + batch over data
    kv8 = S((2, 8, 32, 16), f32)
    plan = partition.plan_for("flash_attention", MESH_2POD, q, kv8, kv8)
    assert plan.levels == (("pod", 2), ("data", 2), ("model", 4))
    # TP-hostile heads: the head split drops but B over data survives
    kv5 = S((2, 5, 32, 16), f32)
    q20 = S((2, 20, 32, 16), f32)
    plan = partition.plan_for("flash_attention", MESH_2POD, q20, kv5, kv5)
    assert plan.levels == (("data", 2),)
    assert "batch-sharded" in plan.note and "head" not in plan.note
    # nothing divides at all (B=1, odd seq, hostile heads): replicate
    q1 = S((1, 5, 33, 16), f32)
    kv1 = S((1, 5, 33, 16), f32)
    assert partition.plan_for("flash_attention", MESH_2POD, q1, kv1, kv1) is None


def test_stencil_two_level_distinguishes_pod_boundary_hop():
    f32 = jnp.float32
    offs = np.array([(-1, 0, 0), (0, 0, 0), (1, 0, 0)], np.int32)
    w = np.ones((3,), np.float32)
    plan = partition.plan_for(
        "stencil", MESH_2POD, S((32, 8, 8), f32), offsets=offs, weights=w)
    assert plan.levels == (("pod", 2), ("model", 4))
    assert "pod boundary hop" in plan.note
    # two intra-pod ring hops (model axis) + two cross-pod boundary hops
    kinds = [(c.kind, c.axis, c.n) for c in plan.collectives]
    assert kinds == [("permute", "model", 4), ("permute", "model", 4),
                     ("permute", "pod", 2), ("permute", "pod", 2)]
    by_level = roofline.plan_collective_seconds_by_level(plan)
    assert by_level["pod"] > 0 and by_level["model"] > 0
    # single-level meshes keep the flat note (no phantom pod hop)
    plan1 = partition.plan_for(
        "stencil", MESH8, S((32, 8, 8), f32), offsets=offs, weights=w)
    assert "pod boundary hop" not in plan1.note
    assert {c.axis for c in plan1.collectives} == {"model"}


def test_two_level_sparse_rules_divide_over_pod_times_model():
    f32, i32 = jnp.float32, jnp.int32
    plan = partition.plan_for(
        "spmm", MESH_2POD, S((64, 8), f32), S((64, 8), i32), S((32, 4), f32))
    assert plan.levels == (("pod", 2), ("model", 4))
    plan = partition.plan_for(
        "bsr_spmm", MESH_2POD, S((8, 8, 128), f32), S((8,), i32),
        S((8,), i32), S((256, 16), f32), num_rows=64)
    assert [(c.axis, c.n) for c in plan.collectives] == [("model", 4),
                                                         ("pod", 2)]
    # rows divide model but not pod*model: ladder lands on the model level
    plan = partition.plan_for(
        "spmm", MESH_2POD, S((36, 8), f32), S((36, 8), i32), S((32, 4), f32))
    assert plan is not None and plan.levels == (("model", 4),)


def test_local_operand_structs_shard_geometry():
    f32 = jnp.float32
    a, b = S((256, 256), f32), S((256, 256), f32)
    plan = partition.plan_for("gemm", MESH8, a, b)
    la, lb = partition.local_operand_structs(plan, MESH8, (a, b))
    assert la.shape == (256, 64) and lb.shape == (64, 256)  # K/4 each side
    plan2 = partition.plan_for("gemm", MESH_2POD, a, b)
    la2, lb2 = partition.local_operand_structs(plan2, MESH_2POD, (a, b))
    assert la2.shape == (256, 32) and lb2.shape == (32, 256)  # K/(2*4)
    # replication passes shapes through whole; None holes are skipped
    structs = partition.local_operand_structs(None, MESH8, (a, None, b))
    assert [s.shape for s in structs] == [(256, 256), (256, 256)]


def test_gemm_rule_k_shard_then_m_shard_then_replicate():
    f32 = jnp.float32
    plan = partition.plan_for("gemm", MESH8, S((32, 64), f32), S((64, 16), f32))
    assert plan.axis == "model" and plan.n == 4
    assert "k-sharded" in plan.note
    assert plan.collectives[0].kind == "all_reduce"
    assert plan.collectives[0].nbytes == 32 * 16 * 4  # fp32 accum partials
    # K=61 resists, M=32 divides: degrade to row sharding, no collective
    plan = partition.plan_for("gemm", MESH8, S((32, 61), f32), S((61, 16), f32))
    assert "m-row-sharded" in plan.note and plan.collectives == ()
    # nothing divides: replicate
    assert partition.plan_for(
        "gemm", MESH8, S((30, 61), f32), S((61, 16), f32)) is None


def test_attention_rules_are_gqa_aware():
    f32 = jnp.float32
    q, kv = S((2, 8, 32, 16), f32), S((2, 4, 32, 16), f32)
    plan = partition.plan_for("flash_attention", MESH8, q, kv, kv)
    assert plan is not None and "head-sharded" in plan.note
    # 20 q heads but 5 kv heads on a 4-way axis: never split a GQA group
    # across devices (the paper's TP-hostile head counts) — the head split
    # drops, but B over the data axis still composes
    q5, kv5 = S((2, 20, 32, 16), f32), S((2, 5, 32, 16), f32)
    plan = partition.plan_for("flash_attention", MESH8, q5, kv5, kv5)
    assert plan.levels == (("data", 2),) and "head" not in plan.note
    pos = S((2,), jnp.int32)
    assert partition.plan_for(
        "decode_attention", MESH8, S((2, 8, 16), f32), kv, kv, pos
    ) is not None
    plan = partition.plan_for(
        "decode_attention", MESH8, S((2, 20, 16), f32), kv5, kv5, pos
    )
    assert plan.levels == (("data", 2),) and "head" not in plan.note
    # a truly hostile decode (odd batch too) replicates
    assert partition.plan_for(
        "decode_attention", MESH8, S((3, 20, 16), f32),
        S((3, 5, 32, 16), f32), S((3, 5, 32, 16), f32), S((3,), jnp.int32)
    ) is None


def test_linear_attention_rule_head_divisibility():
    f32 = jnp.float32
    ok = tuple(S((1, 8, 64, 8), f32) for _ in range(4))
    assert partition.plan_for("linear_attention", MESH8, *ok) is not None
    bad = tuple(S((1, 6, 64, 8), f32) for _ in range(4))
    assert partition.plan_for("linear_attention", MESH8, *bad) is None


def test_sparse_rules_row_and_tile_divisibility():
    f32, i32 = jnp.float32, jnp.int32
    assert partition.plan_for(
        "spmm", MESH8, S((64, 8), f32), S((64, 8), i32), S((32, 4), f32)
    ) is not None
    assert partition.plan_for(
        "spmm", MESH8, S((62, 8), f32), S((62, 8), i32), S((32, 4), f32)
    ) is None
    plan = partition.plan_for(
        "bsr_spmm", MESH8, S((8, 8, 128), f32), S((8,), i32), S((8,), i32),
        S((256, 16), f32), num_rows=64,
    )
    assert plan is not None and plan.collectives[0].kind == "all_reduce"
    assert partition.plan_for(
        "bsr_spmm", MESH8, S((6, 8, 128), f32), S((6,), i32), S((6,), i32),
        S((256, 16), f32), num_rows=64,
    ) is None


def test_stencil_rule_halo_metadata():
    f32 = jnp.float32
    offs = np.array([(-2, 0, 0), (0, 0, 0), (1, 0, 0)], np.int32)
    w = np.ones((3,), np.float32)
    plan = partition.plan_for(
        "stencil", MESH8, S((16, 8, 8), f32), offsets=offs, weights=w
    )
    assert "halo h=2" in plan.note
    # two boundary-plane permutes of h*Y*Z fp32 each
    assert [c.kind for c in plan.collectives] == ["permute", "permute"]
    assert all(c.nbytes == 2 * 8 * 8 * 4 for c in plan.collectives)
    # halo wider than a slab (|dx|=5 > 16/4): replicate, never multi-hop
    wide = np.array([(-5, 0, 0), (0, 0, 0)], np.int32)
    assert partition.plan_for(
        "stencil", MESH8, S((16, 8, 8), f32),
        offsets=wide, weights=np.ones((2,), np.float32),
    ) is None
    # X itself indivisible
    assert partition.plan_for(
        "stencil", MESH8, S((18, 8, 8), f32), offsets=offs, weights=w
    ) is None


def test_plan_costing_feeds_roofline_d2d_term():
    f32 = jnp.float32
    plan = partition.plan_for(
        "gemm", MESH8, S((1024, 4096), f32), S((4096, 1024), f32))
    d2d = roofline.plan_collective_seconds(plan)
    assert d2d > 0.0
    assert roofline.op_collective_seconds(
        "gemm", MESH8, S((1024, 4096), f32), S((4096, 1024), f32)) == d2d
    # replicated ops move no D2D bytes
    assert roofline.op_collective_seconds(
        "gemm", MESH8, S((30, 61), f32), S((61, 16), f32)) == 0.0
    terms = roofline.roofline_terms(1e6, 1e6, 0.0, d2d_s=d2d)
    assert terms["d2d_s"] == d2d and "dominant" in terms
    # the d2d term participates in dominance
    big = roofline.roofline_terms(1.0, 1.0, 0.0, d2d_s=1e9)
    assert big["dominant"] == "d2d_s"


def test_meshspec_plans_but_does_not_execute(rng):
    a = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    with pytest.raises(TypeError, match="needs a device mesh"):
        partition.sharded_call("gemm", MESH8, a, b)


def test_single_axis_mesh_replicates(rng):
    a = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    trivial = partition.MeshSpec({"data": 1, "model": 1})
    assert partition.plan_for("gemm", trivial, a, a) is None
    # and ops.* still runs (plain kernel_call fallback) via the mesh kwarg
    got = ops.gemm(a, a, mesh=trivial, impl="ref", out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ a),
                               rtol=1e-5, atol=1e-5)


def test_dryrun_op_roofline_cells():
    from repro.launch import dryrun

    cells = dryrun.op_roofline_cells(multi_pod=False)
    assert {c["op"] for c in cells} == set(partition.partitioned_ops())
    for c in cells:
        assert c["partition"] != "replicated", c["op"]
        assert c["roofline"]["dominant"] in (
            "compute_s", "memory_s", "collective_s", "d2d_s")
    by_op = {c["op"]: c for c in cells}
    # the split-K gemm and the tile-sharded bsr carry psum D2D bytes
    assert by_op["gemm"]["d2d_bytes"] > 0
    assert by_op["bsr_spmm"]["d2d_bytes"] > 0
    assert by_op["stencil"]["d2d_bytes"] > 0  # halo planes
    # the B=1 long-context flash cell rides the KV ring: its (n-1) per-hop
    # ppermutes (x2: k and v) are priced into the data level
    fa = by_op["flash_attention"]
    assert "ring seq-parallel" in fa["partition"]
    assert fa["d2d_bytes"] > 0
    assert fa["collective_s_per_level"].get("data", 0) > 0


def test_dryrun_op_roofline_multi_pod_emits_per_level_seconds():
    from repro.launch import dryrun

    cells = dryrun.op_roofline_cells(multi_pod=True)
    assert {c["op"] for c in cells} == set(partition.partitioned_ops())
    by_op = {c["op"]: c for c in cells}
    # every cell carries the per-level breakdown (empty only if no collective)
    for c in cells:
        assert "collective_s_per_level" in c and "partition_levels" in c
    # hierarchical psums price intra-pod (model/ICI) vs cross-pod (pod/D2D)
    for op in ("gemm", "bsr_spmm", "stencil"):
        per = by_op[op]["collective_s_per_level"]
        assert per.get("model", 0) > 0 and per.get("pod", 0) > 0, op
        assert by_op[op]["partition_levels"] == ["pod=2", "model=16"]
        total = sum(per.values())
        assert by_op[op]["roofline"]["d2d_s"] == pytest.approx(total)
    # 16 kv heads resist pod*model=32: the ladder drops the pod level. The
    # B=1 long-context flash cell then rides the sequence-parallel KV ring
    # over the data axis (heads intra-pod), pricing its per-hop ppermutes
    assert by_op["flash_attention"]["partition_levels"] == [
        "data=16", "model=16"]
    assert "ring seq-parallel" in by_op["flash_attention"]["partition"]
    assert by_op["flash_attention"]["collective_s_per_level"]["data"] > 0
    # decode (B=8) and linear attention (B=1) have no ring: head-only plans
    for op in ("decode_attention", "linear_attention"):
        assert by_op[op]["partition_levels"] == ["model=16"], op
        assert "pod" not in by_op[op]["collective_s_per_level"]


# ---------------------------------------------------------------------------
# decode_attention: the blocked xla impl (single device)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [0, 7])
def test_decode_attention_blocked_xla_matches_ref(rng, window):
    q = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 4, 50, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 4, 50, 16)), jnp.float32)
    pos = jnp.asarray([5, 49], jnp.int32)
    want = ops.decode_attention(q, k, v, pos, impl="ref", window=window)
    for bs in (8, 16, 64):  # 64 > S exercises the clamp
        got = ops.decode_attention(q, k, v, pos, impl="xla", window=window,
                                   bs=bs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_decode_attention_unrolled_matches_scan(rng):
    q = jnp.asarray(rng.standard_normal((1, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 33, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 33, 8)), jnp.float32)
    pos = jnp.asarray([30], jnp.int32)
    want = ops.decode_attention(q, k, v, pos, impl="xla", bs=8)
    with registry.unroll_inner():
        got = ops.decode_attention(q, k, v, pos, impl="xla", bs=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_decode_attention_override_reaches_xla_impl(rng, monkeypatch):
    import repro.kernels.xla as xla_mod

    q = jnp.asarray(rng.standard_normal((1, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
    pos = jnp.asarray([31], jnp.int32)
    captured = {}
    orig = xla_mod.decode_attention_xla

    def spy(*a, **kw):
        captured["bs"] = kw.get("bs")
        return orig(*a, **kw)

    monkeypatch.setattr(xla_mod, "decode_attention_xla", spy)
    registry.set_block_override("decode_attention", bs=16)
    ops.decode_attention(q, k, k, pos, impl="xla")
    assert captured["bs"] == 16
    ops.decode_attention(q, k, k, pos, impl="xla", bs=8)  # explicit wins
    assert captured["bs"] == 8


# ---------------------------------------------------------------------------
# host_device_mesh graceful degradation (single device is enough)
# ---------------------------------------------------------------------------


def test_host_device_mesh_degrades_with_warning():
    from repro.launch.mesh import host_device_mesh

    n = len(jax.devices())
    with pytest.warns(UserWarning, match="degrading to tp="):
        mesh = host_device_mesh(tp=n + 3)  # cannot divide; 1 always fits
    assert mesh.shape["model"] <= n
    assert mesh.shape["data"] * mesh.shape["model"] == n


def test_use_mesh_does_not_leak_into_model_mesh():
    """use_mesh keys kernels only: current_mesh() — which the model-level
    shard_map paths (moe dispatch, ssm halo shift) read — must stay None, or
    a kernel-only mesh context would silently re-route model internals."""
    from repro.parallel import sharding as sh

    fake = object()  # plans never dereference devices, a sentinel suffices
    with sh.use_mesh(fake):
        assert sh.kernel_mesh() is fake
        assert sh.current_mesh() is None
    assert sh.kernel_mesh() is None


def test_autotune_suite_covers_every_block_table_op():
    """PR 2's invariant, kept: every op the registry advertises as tunable
    has an autotune case (decode_attention included)."""
    from repro.launch import autotune as at

    assert set(at.DEFAULT_SUITE) == set(registry._BLOCK_DEFAULTS)
    # the decode feasibility probe scales with bs and respects clamping
    case = at.DEFAULT_SUITE["decode_attention"](np.random.default_rng(0))
    small = case.program({"bs": 128}).vmem_bytes()
    big = case.program({"bs": 1024}).vmem_bytes()
    assert small < big
    assert case.program({"bs": 4096}).vmem_bytes() == big  # clamped to S


def test_host_device_mesh_rejects_invalid_tp():
    from repro.launch.mesh import host_device_mesh

    with pytest.raises(ValueError, match="not a valid mesh factorisation"):
        host_device_mesh(tp=0)
    with pytest.raises(ValueError, match="not a valid mesh factorisation"):
        host_device_mesh(tp=1, pods=0)
    mesh = host_device_mesh(tp=1)  # exact fit: no warning path
    assert mesh.shape["model"] == 1
    assert tuple(mesh.axis_names) == ("data", "model")  # pods=1: legacy shape


def test_host_device_mesh_three_axis_construction():
    from repro.launch.mesh import host_device_mesh

    n = len(jax.devices())
    # an exactly-dividing pod request yields the (pod, data, model) hierarchy
    # with no warning; with 1 device the pod axis degrades to 1 but the axis
    # names stay stable for pod-aware callers
    if n % 2 == 0:
        import warnings as _w

        with _w.catch_warnings():
            _w.simplefilter("error")
            mesh = host_device_mesh(tp=1, pods=2)
        assert mesh.shape["pod"] == 2
    else:
        with pytest.warns(UserWarning, match="degrading to tp="):
            mesh = host_device_mesh(tp=1, pods=2)
        assert mesh.shape["pod"] == 1
    assert tuple(mesh.axis_names) == ("pod", "data", "model")
    assert mesh.shape["pod"] * mesh.shape["data"] * mesh.shape["model"] == n
    # the mesh feeds the partition layer: pod level present iff pod > 1
    levels = partition.partition_levels(mesh)
    if mesh.shape["pod"] > 1:
        assert levels[0] == ("pod", mesh.shape["pod"])
    else:
        assert all(a != "pod" for a, _ in levels)


def test_host_device_mesh_degrades_when_pod_times_tp_indivisible():
    from repro.launch.mesh import host_device_mesh

    n = len(jax.devices())
    # pods*tp cannot divide n (both exceed it): degrade both with a warning
    with pytest.warns(UserWarning, match="degrading to tp="):
        mesh = host_device_mesh(tp=n + 1, pods=n + 1)
    assert tuple(mesh.axis_names) == ("pod", "data", "model")
    assert mesh.shape["pod"] * mesh.shape["data"] * mesh.shape["model"] == n
    assert mesh.shape["pod"] <= n and mesh.shape["model"] <= n


# ---------------------------------------------------------------------------
# Sharded execution: numerical equivalence on 8 forced host devices
# (subprocess so the device-count flag never leaks into this process)
# ---------------------------------------------------------------------------

_EQUIV = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import sparse as sp
    from repro.kernels import ops, partition
    from repro.launch.mesh import make_mesh
    from repro.models import gcn
    from repro.parallel import sharding as sh

    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    f32 = jnp.float32
    out = {"ok": [], "fallbacks": []}

    def check(name, got, want, tol=1e-4):
        pairs = zip(got, want) if isinstance(got, tuple) else [(got, want)]
        err = max(float(jnp.max(jnp.abs(jnp.asarray(g) - jnp.asarray(w))))
                  for g, w in pairs)
        assert err < tol, (name, err)
        out["ok"].append(name)

    a = jnp.asarray(rng.standard_normal((32, 64)), f32)
    b = jnp.asarray(rng.standard_normal((64, 32)), f32)
    q = jnp.asarray(rng.standard_normal((2, 8, 32, 16)), f32)
    kv = jnp.asarray(rng.standard_normal((2, 4, 32, 16)), f32)
    qd = jnp.asarray(rng.standard_normal((2, 8, 16)), f32)
    pos = jnp.asarray([5, 30], jnp.int32)
    r = jnp.asarray(rng.standard_normal((1, 4, 64, 8)), f32)
    wl = jnp.asarray(-rng.uniform(0.01, 1.0, (1, 4, 64, 8)), f32)
    u = jnp.asarray(rng.standard_normal((4, 8)), f32)
    ell = sp.random_ell(rng, 64, 32, 0.1)
    dn = jnp.asarray(rng.standard_normal((32, 8)), f32)
    bsr_dense = np.zeros((16, 256), np.float32)
    bsr_dense[::3, ::17] = 1.0
    bsrA = sp.dense_to_bsr(bsr_dense, bm=8, bk=128)
    brhs = jnp.asarray(rng.standard_normal((256, 16)), f32)
    sA, sB = sp.random_ell(rng, 32, 64, 0.1), sp.random_ell(rng, 64, 64, 0.1)
    grid = jnp.asarray(rng.standard_normal((16, 8, 8)), f32)
    # offsets reach ACROSS slab boundaries (|dx|=2 on 4-plane slabs): the
    # halo-exchange correctness case, incl. the periodic wrap at the ends
    offs = np.array([(-2, 0, 0), (0, 0, 0), (1, 1, 0), (2, 0, 1)], np.int32)
    w = np.array([0.2, 0.3, 0.4, 0.1], np.float32)

    # stream ops cover interpret/xla/ref (the pallas entry is the same
    # StreamProgram, compiled); decode_attention's stream kernel is paged
    # only (a contiguous cache runs the xla form), so it covers auto/xla/ref
    for impl in ("interpret", "xla", "ref"):
        check(f"gemm[{impl}]",
              ops.gemm(a, b, mesh=mesh, impl=impl, out_dtype=f32),
              ops.gemm(a, b, impl="ref", out_dtype=f32))
        check(f"flash[{impl}]",
              ops.flash_attention(q, kv, kv, mesh=mesh, impl=impl),
              ops.flash_attention(q, kv, kv, impl="ref"))
        check(f"linattn_rwkv[{impl}]",
              ops.linear_attention(r, r, r, wl, u, mesh=mesh, impl=impl),
              ops.linear_attention(r, r, r, wl, u, impl="ref"))
        check(f"linattn_ssd[{impl}]",
              ops.linear_attention(r, r, r, wl, mesh=mesh, impl=impl),
              ops.linear_attention(r, r, r, wl, impl="ref"))
        check(f"spmm[{impl}]", ops.spmm(ell, dn, mesh=mesh, impl=impl),
              ops.spmm(ell, dn, impl="ref"))
        check(f"bsr_spmm[{impl}]",
              ops.bsr_spmm(bsrA, brhs, mesh=mesh, impl=impl),
              ops.bsr_spmm(bsrA, brhs, impl="xla"))
        check(f"spmspm[{impl}]",
              ops.spmspm(sA, sB, 64, mesh=mesh, impl=impl),
              ops.spmspm(sA, sB, 64, impl="ref"))
        check(f"stencil[{impl}]",
              ops.stencil(grid, offs, w, mesh=mesh, impl=impl),
              ops.stencil(grid, offs, w, impl="ref"))
    for impl in ("auto", "xla", "ref"):
        check(f"decode[{impl}]",
              ops.decode_attention(qd, kv, kv, pos, mesh=mesh, impl=impl),
              ops.decode_attention(qd, kv, kv, pos, impl="ref"))

    # gemm k-shard must preserve an explicit narrower out_dtype
    got16 = ops.gemm(a, b, mesh=mesh, impl="xla", out_dtype=jnp.bfloat16)
    assert got16.dtype == jnp.bfloat16
    out["ok"].append("gemm[out_dtype]")

    # replication fallback on indivisible shapes: same signature, same
    # answer. B=1 + TP-hostile heads + odd seq defeats head, batch AND the
    # seq-parallel ring
    q5 = jnp.asarray(rng.standard_normal((1, 5, 15, 8)), f32)
    check("fallback_flash",
          ops.flash_attention(q5, q5, q5, mesh=mesh, impl="xla"),
          ops.flash_attention(q5, q5, q5, impl="ref"))
    ell62 = sp.random_ell(rng, 62, 32, 0.1)
    check("fallback_spmm", ops.spmm(ell62, dn, mesh=mesh, impl="xla"),
          ops.spmm(ell62, dn, impl="ref"))
    for name, args in (("flash", (q5, q5, q5)), ("spmm",
                       (ell62.values, ell62.cols, dn))):
        op = "flash_attention" if name == "flash" else "spmm"
        assert partition.plan_for(op, mesh, *args) is None
        out["fallbacks"].append(name)

    # halo exchange at every slab width that divides X=16
    for tp in (2, 4, 8):
        m2 = make_mesh((8 // tp, tp), ("data", "model"))
        check(f"stencil_halo_tp{tp}",
              ops.stencil(grid, offs, w, mesh=m2, impl="interpret"),
              ops.stencil(grid, offs, w, impl="ref"))

    # row-sharded GCN end to end (explicit mesh kwarg AND use_mesh context)
    feats = jnp.asarray(rng.standard_normal((64, 16)), f32)
    params = gcn.init_params(jax.random.PRNGKey(0), [16, 32, 8])
    adj = sp.random_ell(rng, 64, 64, 0.05)
    want = gcn.forward(params, adj, feats)
    check("gcn_mesh_kwarg",
          jax.jit(lambda p, a_, f_: gcn.forward(p, a_, f_, mesh=mesh))(
              params, adj, feats), want)
    with sh.use_mesh(mesh):
        check("gcn_use_mesh", gcn.forward(params, adj, feats), want)
    assert sh.kernel_mesh() is None  # context restored
    print("RESULT:" + json.dumps(out))
    """
)


def test_sharded_equivalence_all_ops():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _EQUIV],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    # every partitioned op x impl combination ran and matched
    for op_tag in ("gemm", "flash", "linattn_rwkv", "linattn_ssd", "spmm",
                   "bsr_spmm", "spmspm", "stencil"):
        for impl in ("interpret", "xla", "ref"):
            assert f"{op_tag}[{impl}]" in out["ok"], (op_tag, impl)
    for impl in ("auto", "xla", "ref"):
        assert f"decode[{impl}]" in out["ok"]
    assert set(out["fallbacks"]) == {"flash", "spmm"}
    assert {"stencil_halo_tp2", "stencil_halo_tp4", "stencil_halo_tp8",
            "gcn_mesh_kwarg", "gcn_use_mesh"} <= set(out["ok"])


# Three-axis variant: the same every-op x every-impl equivalence on a
# (pod, data, model) = 2x2x2 mesh, where plans resolve TWO-LEVEL (joint
# pod x model sharding, hierarchical psums, cross-pod halo hop) and the
# level ladder drops to model-only for pod-indivisible shapes.
_EQUIV_3AX = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import sparse as sp
    from repro.kernels import ops, partition
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    rng = np.random.default_rng(0)
    f32 = jnp.float32
    out = {"ok": [], "two_level": [], "ladder": []}

    def check(name, got, want, tol=1e-4):
        pairs = zip(got, want) if isinstance(got, tuple) else [(got, want)]
        err = max(float(jnp.max(jnp.abs(jnp.asarray(g) - jnp.asarray(w))))
                  for g, w in pairs)
        assert err < tol, (name, err)
        out["ok"].append(name)

    a = jnp.asarray(rng.standard_normal((32, 64)), f32)
    b = jnp.asarray(rng.standard_normal((64, 32)), f32)
    q = jnp.asarray(rng.standard_normal((2, 8, 32, 16)), f32)
    kv = jnp.asarray(rng.standard_normal((2, 4, 32, 16)), f32)
    qd = jnp.asarray(rng.standard_normal((2, 8, 16)), f32)
    pos = jnp.asarray([5, 30], jnp.int32)
    r = jnp.asarray(rng.standard_normal((1, 4, 64, 8)), f32)
    wl = jnp.asarray(-rng.uniform(0.01, 1.0, (1, 4, 64, 8)), f32)
    u = jnp.asarray(rng.standard_normal((4, 8)), f32)
    ell = sp.random_ell(rng, 64, 32, 0.1)
    dn = jnp.asarray(rng.standard_normal((32, 8)), f32)
    bsr_dense = np.zeros((16, 256), np.float32)
    bsr_dense[::3, ::17] = 1.0
    bsrA = sp.dense_to_bsr(bsr_dense, bm=8, bk=128)
    brhs = jnp.asarray(rng.standard_normal((256, 16)), f32)
    sA, sB = sp.random_ell(rng, 32, 64, 0.1), sp.random_ell(rng, 64, 64, 0.1)
    grid = jnp.asarray(rng.standard_normal((16, 8, 8)), f32)
    # |dx|=2 on 4-plane slabs: halo planes cross slab AND pod boundaries
    offs = np.array([(-2, 0, 0), (0, 0, 0), (1, 1, 0), (2, 0, 1)], np.int32)
    w = np.array([0.2, 0.3, 0.4, 0.1], np.float32)

    # every op resolves two-level here: pod*model = 4 divides K=64, kv=4
    # heads, H=4, 64 rows, 4 tiles, 32 rows, X=16. Attention rules also
    # compose B=2 over the data axis (three levels); linattn has B=1
    two_level_cases = [
        ("gemm", (a, b), {}, (("pod", 2), ("model", 2))),
        ("flash", (q, kv, kv), {}, (("pod", 2), ("data", 2), ("model", 2))),
        ("decode", (qd, kv, kv, pos), {},
         (("pod", 2), ("data", 2), ("model", 2))),
        ("linattn", (r, r, r, wl), {}, (("pod", 2), ("model", 2))),
        ("spmm", (ell.values, ell.cols, dn), {}, (("pod", 2), ("model", 2))),
        ("bsr_spmm", (bsrA.tile_values, bsrA.tile_rows, bsrA.tile_cols,
                      brhs), {"num_rows": 16}, (("pod", 2), ("model", 2))),
        ("spmspm", (sA.values, sA.cols, sB.values, sB.cols),
         {"contraction_dim": 64}, (("pod", 2), ("model", 2))),
        ("stencil", (grid,), {"offsets": offs, "weights": w},
         (("pod", 2), ("model", 2))),
    ]
    op_names = {"linattn": "linear_attention", "flash": "flash_attention",
                "decode": "decode_attention"}
    for tag, args, kw, want_levels in two_level_cases:
        plan = partition.plan_for(op_names.get(tag, tag), mesh, *args, **kw)
        assert plan.levels == want_levels, (tag, plan.levels)
        out["two_level"].append(tag)

    # the ladder on a live mesh: kv=2 heads / 38 rows resist pod*model=4
    # but divide model=2 -> dropped-pod plans that still execute correctly
    kv2 = jnp.asarray(rng.standard_normal((2, 2, 32, 16)), f32)
    plan = partition.plan_for("flash_attention", mesh, q, kv2, kv2)
    assert plan.levels == (("data", 2), ("model", 2)), plan.levels
    check("ladder_flash",
          ops.flash_attention(q, kv2, kv2, mesh=mesh, impl="xla"),
          ops.flash_attention(q, kv2, kv2, impl="ref"))
    out["ladder"].append("flash")
    ell38 = sp.random_ell(rng, 38, 32, 0.1)
    plan = partition.plan_for("spmm", mesh, ell38.values, ell38.cols, dn)
    assert plan.levels == (("model", 2),), plan.levels
    check("ladder_spmm", ops.spmm(ell38, dn, mesh=mesh, impl="xla"),
          ops.spmm(ell38, dn, impl="ref"))
    out["ladder"].append("spmm")

    for impl in ("interpret", "xla", "ref"):
        check(f"gemm[{impl}]",
              ops.gemm(a, b, mesh=mesh, impl=impl, out_dtype=f32),
              ops.gemm(a, b, impl="ref", out_dtype=f32))
        check(f"flash[{impl}]",
              ops.flash_attention(q, kv, kv, mesh=mesh, impl=impl),
              ops.flash_attention(q, kv, kv, impl="ref"))
        check(f"linattn_rwkv[{impl}]",
              ops.linear_attention(r, r, r, wl, u, mesh=mesh, impl=impl),
              ops.linear_attention(r, r, r, wl, u, impl="ref"))
        check(f"linattn_ssd[{impl}]",
              ops.linear_attention(r, r, r, wl, mesh=mesh, impl=impl),
              ops.linear_attention(r, r, r, wl, impl="ref"))
        check(f"spmm[{impl}]", ops.spmm(ell, dn, mesh=mesh, impl=impl),
              ops.spmm(ell, dn, impl="ref"))
        check(f"bsr_spmm[{impl}]",
              ops.bsr_spmm(bsrA, brhs, mesh=mesh, impl=impl),
              ops.bsr_spmm(bsrA, brhs, impl="xla"))
        check(f"spmspm[{impl}]",
              ops.spmspm(sA, sB, 64, mesh=mesh, impl=impl),
              ops.spmspm(sA, sB, 64, impl="ref"))
        check(f"stencil[{impl}]",
              ops.stencil(grid, offs, w, mesh=mesh, impl=impl),
              ops.stencil(grid, offs, w, impl="ref"))
    for impl in ("auto", "xla", "ref"):
        check(f"decode[{impl}]",
              ops.decode_attention(qd, kv, kv, pos, mesh=mesh, impl=impl),
              ops.decode_attention(qd, kv, kv, pos, impl="ref"))
    print("RESULT:" + json.dumps(out))
    """
)


def test_sharded_equivalence_all_ops_three_axis():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _EQUIV_3AX],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    for op_tag in ("gemm", "flash", "linattn_rwkv", "linattn_ssd", "spmm",
                   "bsr_spmm", "spmspm", "stencil"):
        for impl in ("interpret", "xla", "ref"):
            assert f"{op_tag}[{impl}]" in out["ok"], (op_tag, impl)
    for impl in ("auto", "xla", "ref"):
        assert f"decode[{impl}]" in out["ok"]
    # the joint pod x model plans actually engaged (not a silent fallback)
    assert set(out["two_level"]) == {"gemm", "flash", "decode", "linattn",
                                     "spmm", "bsr_spmm", "spmspm", "stencil"}
    assert set(out["ladder"]) == {"flash", "spmm"}
    assert {"ladder_flash", "ladder_spmm"} <= set(out["ok"])


# ---------------------------------------------------------------------------
# Sequence-parallel ring flash attention: device-free plan units, the merge
# and per-shard q_offset math on one device, and 8-device equivalence
# ---------------------------------------------------------------------------


def test_attention_levels_vocabulary():
    # the data axis slots between pod and model for the attention family;
    # the default vocabulary (partition_levels) is untouched
    assert partition.attention_levels(MESH8) == (("data", 2), ("model", 4))
    assert partition.attention_levels(MESH_2POD) == (
        ("pod", 2), ("data", 2), ("model", 4))
    assert partition.partition_levels(MESH8) == (("model", 4),)
    # size-1 or missing data axes drop out
    assert partition.attention_levels(
        partition.MeshSpec({"data": 1, "model": 4})) == (("model", 4),)
    assert partition.attention_levels(
        partition.MeshSpec({"model": 4})) == (("model", 4),)


def test_flash_ring_rule_resolution():
    f32 = jnp.float32
    qL = S((1, 8, 256, 16), f32)
    kL = S((1, 4, 256, 16), f32)
    # B=1 blocks the batch split: the data axis carries the sequence
    plan = partition.plan_for("flash_attention", MESH8, qL, kL, kL)
    assert "ring seq-parallel" in plan.note and "head-sharded" in plan.note
    assert plan.levels == (("data", 2), ("model", 4))
    # (n-1) hops x (k and v): per-hop permutes priced on the data level at
    # the local shard payload
    assert len(plan.collectives) == 2 * (2 - 1)
    kv_local = 1 * (4 // 4) * (256 // 2) * 16 * 4
    assert all(
        c == partition.CollectiveCost("permute", "data", kv_local, 2)
        for c in plan.collectives
    )
    assert roofline.plan_collective_seconds_by_level(plan)["data"] > 0
    # a lookback window prunes whole tail hops statically: of 8 ring steps
    # only ceil((33+31)/32) = 2 kernel steps (1 rotation) survive
    wide = partition.MeshSpec({"data": 8, "model": 1})
    plan = partition.plan_for("flash_attention", wide, qL, kL, kL, window=33)
    assert "1 kv hops" in plan.note
    assert len(plan.collectives) == 2 * 1
    # batch sharding is preferred over the ring when B divides
    qB = S((2, 8, 256, 16), f32)
    kB = S((2, 4, 256, 16), f32)
    plan = partition.plan_for("flash_attention", MESH8, qB, kB, kB)
    assert "batch-sharded" in plan.note and "ring" not in plan.note
    # the ring declines bounded masks at nonzero q_offset (the wrap would
    # alias past positions) and cross-attention (Sq != Sk)
    plan = partition.plan_for(
        "flash_attention", MESH8, qL, kL, kL, causal=True, q_offset=7)
    assert plan is not None and "ring" not in plan.note  # head-only
    qX = S((1, 8, 128, 16), f32)
    plan = partition.plan_for(
        "flash_attention", MESH8, qX, kL, kL, causal=False)
    assert plan is None or "ring" not in plan.note
    # ...but an unbounded (causal=False, window=0) ring tolerates q_offset
    plan = partition.plan_for(
        "flash_attention", MESH8, qL, kL, kL, causal=False, q_offset=7)
    assert "ring seq-parallel" in plan.note


def test_online_softmax_merge_reconstructs_full_softmax(rng):
    from repro.parallel.collectives import NEG_LSE, online_softmax_merge

    q = jnp.asarray(rng.standard_normal((1, 4, 32, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
    for kw in (dict(causal=True), dict(causal=True, window=5),
               dict(causal=False)):
        want = ops.flash_attention(q, k, v, impl="ref", **kw)
        # split KV in half; the second half's mask needs q shifted LEFT by
        # the split point (the same q_offset hook the ring uses per hop)
        half = 16
        o = jnp.zeros(q.shape, jnp.float32)
        lse = jnp.full(q.shape[:-1], NEG_LSE, jnp.float32)
        for j, off in ((0, 0), (1, -half)):
            o_t, lse_t = ops.flash_attention(
                q, k[:, :, j * half:(j + 1) * half],
                v[:, :, j * half:(j + 1) * half],
                impl="ref", return_lse=True,
                **{**kw, "q_offset": kw.get("q_offset", 0) + off},
            )
            o, lse = online_softmax_merge(o, lse, o_t, lse_t)
        np.testing.assert_allclose(
            np.asarray(o.astype(q.dtype)), np.asarray(want),
            rtol=1e-5, atol=1e-5,
        )


@pytest.mark.parametrize("kw", [
    dict(causal=True), dict(causal=True, window=9),
    dict(causal=False), dict(causal=False, window=9),
])
def test_ring_per_shard_q_offset_single_device_simulation(rng, kw):
    """The ring's per-(rank, hop) masking, simulated without devices: rank
    ``me``'s hop ``t`` runs the kernel at static ``q_offset = t*c`` on the
    KV chunk of rank ``(me - t) % d``; under causal/window masking the
    wrapped hops (me < t) merge as no-ops. Folding every rank's hops must
    reproduce the full single-device attention row-for-row."""
    from repro.parallel.collectives import NEG_LSE, online_softmax_merge

    d, c = 4, 16
    S_ = d * c
    q = jnp.asarray(rng.standard_normal((1, 4, S_, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, S_, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, S_, 8)), jnp.float32)
    want = ops.flash_attention(q, k, v, impl="ref", **kw)
    bounded = kw.get("causal") or kw.get("window", 0)
    outs = []
    for me in range(d):
        q_l = q[:, :, me * c:(me + 1) * c]
        o = jnp.zeros(q_l.shape, jnp.float32)
        lse = jnp.full(q_l.shape[:-1], NEG_LSE, jnp.float32)
        for t in range(d):
            src = (me - t) % d
            o_t, lse_t = ops.flash_attention(
                q_l, k[:, :, src * c:(src + 1) * c],
                v[:, :, src * c:(src + 1) * c],
                impl="ref", return_lse=True, q_offset=t * c, **kw,
            )
            if bounded and t and me < t:  # wrapped: KV chunk is in the future
                continue
            o, lse = online_softmax_merge(o, lse, o_t, lse_t)
        outs.append(o)
    got = jnp.concatenate(outs, axis=2).astype(q.dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# 8-device subprocess suite for the data-axis attention rules: ring flash
# vs single device across causal x window x GQA (including a TP-hostile
# head count that forces the head rule onto the ladder), batch-composed
# plans, and the ring_scan_carry threading unit.
_EQUIV_RING = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.kernels import ops, partition
    from repro.launch.mesh import make_mesh
    from repro.parallel.collectives import ring_scan_carry
    from jax import shard_map

    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    f32 = jnp.float32
    out = {"ok": [], "ring": [], "batch": []}

    def check(name, got, want, tol=1e-4):
        err = float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))
        assert err < tol, (name, err)
        out["ok"].append(name)

    # B=1 forces the ring; 8 q heads / 2 kv heads = GQA groups of 4
    q = jnp.asarray(rng.standard_normal((1, 8, 64, 16)), f32)
    kv = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), f32)
    # TP-hostile: 5 kv heads resist model=2, so the head rule drops off the
    # ladder and the data level carries the ring alone
    qh = jnp.asarray(rng.standard_normal((1, 10, 64, 16)), f32)
    kvh = jnp.asarray(rng.standard_normal((1, 5, 64, 16)), f32)

    cases = [("gqa", q, kv, kv), ("hostile", qh, kvh, kvh)]
    kws = [dict(causal=True), dict(causal=True, window=9),
           dict(causal=False), dict(causal=False, window=9)]
    for tag, qq, kk, vv in cases:
        for kw in kws:
            plan = partition.plan_for("flash_attention", mesh, qq, kk, vv, **kw)
            assert "ring seq-parallel" in plan.note, (tag, kw, plan.note)
            if tag == "hostile":
                assert plan.levels == (("data", 4),), plan.levels
            else:
                assert plan.levels == (("data", 4), ("model", 2))
            for impl in ("interpret", "xla", "ref"):
                name = f"ring_{tag}[{impl}]" + (
                    f"w{kw.get('window', 0)}c{int(kw['causal'])}")
                check(name,
                      ops.flash_attention(qq, kk, vv, mesh=mesh, impl=impl, **kw),
                      ops.flash_attention(qq, kk, vv, impl="ref", **kw))
            out["ring"].append(f"{tag}_w{kw.get('window', 0)}c{int(kw['causal'])}")

    # ring + return_lse through the sharded path
    o, lse = ops.flash_attention(q, kv, kv, mesh=mesh, impl="xla",
                                 return_lse=True)
    ow, lw = ops.flash_attention(q, kv, kv, impl="ref", return_lse=True)
    check("ring_lse_o", o, ow)
    check("ring_lse", lse, lw, tol=1e-3)

    # batch-composed plans: B over data x heads over model
    qb = jnp.asarray(rng.standard_normal((4, 8, 32, 16)), f32)
    kvb = jnp.asarray(rng.standard_normal((4, 2, 32, 16)), f32)
    plan = partition.plan_for("flash_attention", mesh, qb, kvb, kvb)
    assert "batch-sharded" in plan.note and "head-sharded" in plan.note
    check("batch_flash", ops.flash_attention(qb, kvb, kvb, mesh=mesh, impl="xla"),
          ops.flash_attention(qb, kvb, kvb, impl="ref"))
    out["batch"].append("flash")
    qd = jnp.asarray(rng.standard_normal((4, 8, 16)), f32)
    pos = jnp.asarray([5, 30, 12, 31], jnp.int32)
    plan = partition.plan_for("decode_attention", mesh, qd, kvb, kvb, pos)
    assert "batch-sharded" in plan.note
    check("batch_decode",
          ops.decode_attention(qd, kvb, kvb, pos, mesh=mesh, impl="xla"),
          ops.decode_attention(qd, kvb, kvb, pos, impl="ref"))
    out["batch"].append("decode")
    r = jnp.asarray(rng.standard_normal((4, 4, 64, 8)), f32)
    wl = jnp.asarray(-rng.uniform(0.01, 1.0, (4, 4, 64, 8)), f32)
    plan = partition.plan_for("linear_attention", mesh, r, r, r, wl)
    assert "batch-sharded" in plan.note
    got = ops.linear_attention(r, r, r, wl, mesh=mesh, impl="xla")
    want = ops.linear_attention(r, r, r, wl, impl="ref")
    check("batch_linattn_o", got[0], want[0])
    check("batch_linattn_s", got[1], want[1])
    out["batch"].append("linattn")

    # ring_scan_carry threads the TRUE carry rank to rank (the fixed
    # primitive: the old single-ppermute version delivered each rank only
    # its neighbour's locally-seeded state)
    xs = jnp.asarray(rng.standard_normal((8, 4)), f32)

    def chunk(s, x):  # running prefix-sum recurrence over the local chunk
        ys = s + jnp.cumsum(x[0])
        return ys[-1], ys[None]

    def local(x_l):
        ys, s = ring_scan_carry(chunk, x_l, jnp.float32(0.0), "data", 4)
        return ys, s[None]

    ys, s_fin = shard_map(
        local, mesh=mesh, in_specs=(P("data", None),),
        out_specs=(P("data", None), P("data")), check_vma=False,
    )(xs[:4])
    want = jnp.cumsum(xs[:4].reshape(-1)).reshape(4, 4)
    check("ring_scan_carry_ys", ys, want, tol=1e-5)
    check("ring_scan_carry_final", s_fin[-1], want[-1, -1], tol=1e-5)
    print("RESULT:" + json.dumps(out))
    """
)


def test_ring_and_batch_attention_equivalence_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _EQUIV_RING],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    # every mask x GQA x impl ring combination ran and matched
    for tag in ("gqa", "hostile"):
        for impl in ("interpret", "xla", "ref"):
            for c, w in ((1, 0), (1, 9), (0, 0), (0, 9)):
                assert f"ring_{tag}[{impl}]w{w}c{c}" in out["ok"], (tag, impl)
    assert set(out["batch"]) == {"flash", "decode", "linattn"}
    assert {"ring_lse_o", "ring_lse", "ring_scan_carry_ys",
            "ring_scan_carry_final"} <= set(out["ok"])
