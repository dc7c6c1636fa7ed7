"""Latency-tolerant ring overlap: equivalence + warm-start autotune tests.

The overlapped schedules (double-buffered ring in parallel/collectives.py,
zigzag causal KV ring and split halo stencil in kernels/partition.py) must
be DROP-IN: every ``overlap=True`` path has its synchronous oracle behind
``overlap=False``, and the two must agree exactly — overlap only moves
*when* the hop transfer is issued, never what is computed. The 8-device
checks run in a subprocess with forced host devices (like
tests/test_partition.py) so the device-count flag never leaks.

The autotune half pins the warm-start contract: feasible candidates are
measured in roofline-prior order (the analytic top pick first) and a
``trial_budget`` cuts the modeled-slow tail while the default geometry
always stays measured.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_OVERLAP_EQUIV = textwrap.dedent(
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
    out = {"ok": [], "exact": [], "notes": {}}

    def check(name, got, want, tol=1e-4):
        err = float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))
        assert err < tol, (name, err)
        out["ok"].append(name)

    def check_exact(name, got, want):
        # overlap vs sync: same math in the same order, only the hop
        # transfer is issued earlier -- must agree bitwise
        err = float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))
        assert err == 0.0, (name, err)
        out["exact"].append(name)

    # B=1 forces the ring; Sq=64 over data=4 gives 8 zigzag half-chunks
    q = jnp.asarray(rng.standard_normal((1, 8, 64, 16)), f32)
    kv = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), f32)

    # mask matrix: zigzag engages only on plain-causal; windowed and
    # non-causal fall back to the legacy hop schedule (still overlapped)
    kws = [dict(causal=True), dict(causal=True, window=9),
           dict(causal=False), dict(causal=False, window=9)]
    for kw in kws:
        tag = f"w{kw.get('window', 0)}c{int(kw['causal'])}"
        plan = partition.plan_for("flash_attention", mesh, q, kv, kv, **kw)
        zig = kw["causal"] and not kw.get("window", 0)
        assert ("zigzag" in plan.note) == zig, (kw, plan.note)
        # a lookback window prunes wrapped hops (w=9 < the 16-row chunk
        # leaves 2); every variant keeps at least one hop to overlap
        assert plan.overlappable and plan.hops >= 2, (kw, plan.note)
        if zig:
            assert plan.hops == 4, plan.note
        out["notes"][tag] = plan.note
        for impl in ("interpret", "xla", "ref"):
            want = ops.flash_attention(q, kv, kv, impl="ref", **kw)
            o_ovl, lse_ovl = ops.flash_attention(
                q, kv, kv, mesh=mesh, impl=impl, overlap=True,
                return_lse=True, **kw)
            o_sync, lse_sync = ops.flash_attention(
                q, kv, kv, mesh=mesh, impl=impl, overlap=False,
                return_lse=True, **kw)
            check(f"ring[{impl}]{tag}", o_ovl, want)
            check_exact(f"ring_o[{impl}]{tag}", o_ovl, o_sync)
            check_exact(f"ring_lse[{impl}]{tag}", lse_ovl, lse_sync)

    # zigzag explicitly disabled: the legacy causal ring, still overlapped
    plan = partition.plan_for(
        "flash_attention", mesh, q, kv, kv, zigzag=False)
    assert "zigzag" not in plan.note and plan.overlappable
    check("ring_nozig",
          ops.flash_attention(q, kv, kv, mesh=mesh, impl="xla", zigzag=False),
          ops.flash_attention(q, kv, kv, impl="ref"))
    check_exact(
        "ring_nozig_sync",
        ops.flash_attention(q, kv, kv, mesh=mesh, impl="xla", zigzag=False,
                            overlap=True),
        ops.flash_attention(q, kv, kv, mesh=mesh, impl="xla", zigzag=False,
                            overlap=False))

    # zigzag-ineligible sequence length (Sq=68 splits over d=4 but not
    # into 2*d=8 half-chunks): must silently fall back and still match
    q68 = jnp.asarray(rng.standard_normal((1, 8, 68, 16)), f32)
    kv68 = jnp.asarray(rng.standard_normal((1, 2, 68, 16)), f32)
    plan = partition.plan_for("flash_attention", mesh, q68, kv68, kv68)
    assert "zigzag" not in plan.note, plan.note
    check("ring_s68",
          ops.flash_attention(q68, kv68, kv68, mesh=mesh, impl="xla"),
          ops.flash_attention(q68, kv68, kv68, impl="ref"))

    # stencil: split-halo overlapped schedule vs the fused sync oracle
    grid = jnp.asarray(rng.standard_normal((32, 8, 8)), f32)
    offs = np.array([(0, 0, 0), (2, 0, 0), (-2, 0, 0), (0, 1, 0)], np.int32)
    w = np.full((4,), 0.25, np.float32)
    plan = partition.plan_for("stencil", mesh, grid, offsets=offs, weights=w)
    assert "(overlapped)" in plan.note and plan.hops == 2, plan.note
    out["notes"]["stencil"] = plan.note
    for impl in ("interpret", "xla", "ref"):
        s_ovl = ops.stencil(grid, offs, w, mesh=mesh, impl=impl, overlap=True)
        s_sync = ops.stencil(grid, offs, w, mesh=mesh, impl=impl,
                             overlap=False)
        check(f"stencil[{impl}]", s_ovl,
              ops.stencil(grid, offs, w, impl="ref"))
        check_exact(f"stencil_sync[{impl}]", s_ovl, s_sync)
    plan = partition.plan_for(
        "stencil", mesh, grid, offsets=offs, weights=w, overlap=False)
    assert "(overlapped)" not in plan.note and not plan.overlappable

    # ring_scan_carry: the double-buffered carry thread vs the sync loop
    xs = jnp.asarray(rng.standard_normal((8, 4)), f32)

    def chunk(s, x):
        ys = s + jnp.cumsum(x[0])
        return ys[-1], ys[None]

    def local(ov):
        def f(x_l):
            ys, s = ring_scan_carry(chunk, x_l, jnp.float32(0.0), "data", 4,
                                    overlap=ov)
            return ys, s[None]
        return f

    run = lambda ov: shard_map(
        local(ov), mesh=mesh, in_specs=(P("data", None),),
        out_specs=(P("data", None), P("data")), check_vma=False,
    )(xs[:4])
    ys_o, s_o = run(True)
    ys_s, s_s = run(False)
    check_exact("carry_ys", ys_o, ys_s)
    check_exact("carry_final", s_o, s_s)
    check("carry_semantics", ys_o,
          jnp.cumsum(xs[:4].reshape(-1)).reshape(4, 4), tol=1e-5)
    print("RESULT:" + json.dumps(out))
    """
)


def test_overlap_equivalence_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _OVERLAP_EQUIV],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    # every mask x impl combination matched the single-device reference AND
    # agreed bitwise (o and lse) with its synchronous oracle
    for impl in ("interpret", "xla", "ref"):
        for c, w in ((1, 0), (1, 9), (0, 0), (0, 9)):
            assert f"ring[{impl}]w{w}c{c}" in out["ok"]
            assert f"ring_o[{impl}]w{w}c{c}" in out["exact"]
            assert f"ring_lse[{impl}]w{w}c{c}" in out["exact"]
        assert f"stencil[{impl}]" in out["ok"]
        assert f"stencil_sync[{impl}]" in out["exact"]
    assert "zigzag" in out["notes"]["w0c1"]
    assert "(overlapped)" in out["notes"]["stencil"]
    assert {"ring_nozig", "ring_s68", "carry_semantics"} <= set(out["ok"])
    assert {"ring_nozig_sync", "carry_ys", "carry_final"} <= set(out["exact"])


def test_ring_scan_replays_the_published_schedule():
    """ring_scan executes exactly the HopEvent sequence ring_schedule
    returns — the artifact the repro.analysis overlap-schedule rule
    checks IS the executed schedule, by construction."""
    from repro.parallel import collectives

    calls = []

    def fake_send(x):
        calls.append(("send", x))
        return x + 100

    orig = collectives._hop_send
    collectives._hop_send = lambda axis, n, remote: fake_send
    try:
        folds = []
        collectives.ring_scan(
            lambda carry, block, t: folds.append((t, int(block))) or carry,
            carry=0, block=0, axis="data", n=4, overlap=True,
        )
    finally:
        collectives._hop_send = orig
    # folds consumed hops 0..3 in order, each reading the t-hops-rotated
    # block (one +100 per hop), exactly as the schedule prescribes
    assert folds == [(0, 0), (1, 100), (2, 200), (3, 300)]
    assert len(calls) == 3  # n-1 transfers, issued one hop ahead


def test_remote_copy_fallback_warns_once():
    """remote_copy=True off-TPU degrades to ppermute with one (and only
    one) ReproDegradeWarning — never a silent transport swap."""
    import warnings

    from repro.diagnostics import ReproDegradeWarning, reset_degrade_warnings
    from repro.parallel import collectives

    reset_degrade_warnings()
    try:
        with pytest.warns(ReproDegradeWarning, match="falling back to ppermute"):
            collectives._hop_send("data", 4, True)
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # one-shot: second call is silent
            collectives._hop_send("data", 4, True)
    finally:
        reset_degrade_warnings()


# ---------------------------------------------------------------------------
# Autotune warm start: roofline-prior ordering + trial budget
# ---------------------------------------------------------------------------


def _case():
    from repro.launch import autotune as at

    return at._gemm_case(np.random.default_rng(0))


def test_autotune_measures_prior_top_pick_first():
    from repro.launch import autotune as at

    case = _case()
    entry = at.autotune_case(case, time_candidate=lambda c, b: 1.0)
    priors = [t["prior_s"] for t in entry["timed"]]
    assert priors == sorted(priors)
    # the analytic top pick is the first candidate measured
    all_priors = priors + [s["prior_s"] for s in entry["skipped_by_budget"]]
    assert entry["timed"][0]["prior_s"] == min(all_priors)
    assert entry["timed"][0]["prior_s"] == pytest.approx(
        at.candidate_prior_seconds(case, entry["timed"][0]["blocks"])
    )


def test_autotune_trial_budget_caps_measurements():
    from repro.launch import autotune as at

    case = _case()
    full = at.autotune_case(case, time_candidate=lambda c, b: 1.0)
    n_feasible = len(full["timed"])
    assert n_feasible >= 3  # the gemm case has a real candidate table

    entry = at.autotune_case(
        case, trial_budget=1, time_candidate=lambda c, b: 1.0
    )
    timed_blocks = [t["blocks"] for t in entry["timed"]]
    # prior top pick measured, defaults force-included, everything else
    # skipped with its prior recorded for the audit trail
    assert entry["timed"][0]["blocks"] == full["timed"][0]["blocks"]
    assert entry["default_blocks"] in timed_blocks
    assert len(timed_blocks) <= 2
    assert len(entry["skipped_by_budget"]) == n_feasible - len(timed_blocks)
    assert all(s["blocks"] not in timed_blocks
               for s in entry["skipped_by_budget"])
    assert entry["trial_budget"] == 1


def test_autotune_budget_keeps_default_selection_invariant():
    from repro.launch import autotune as at

    case = _case()
    # adversarial timer: the prior's top pick measures SLOWER than default;
    # under a budget of 1 the default must still be present so the
    # strictly-faster rule can keep it
    defaults = __import__("repro.kernels.registry", fromlist=["registry"]) \
        .block_defaults(case.op, overrides=False)
    entry = at.autotune_case(
        case, trial_budget=1,
        time_candidate=lambda c, b: 1.0 if b == defaults else 2.0,
    )
    assert entry["blocks"] == defaults
    assert entry["default_us"] is not None
