"""Measured block-size autotuner over the registry override table.

Occamy's headline utilizations come from matching stream/tile geometry to the
memory hierarchy (the C4 double-buffering discipline); mistuned tiles show up
directly as lost FPU cycles. This module closes that loop for the TPU
translation: per **(op, operand shapes, dtypes, backend, impl)** it

1. generates candidate block geometries around the registry defaults,
2. prunes infeasible candidates *analytically* — each candidate's
   ``StreamProgram.vmem_bytes()`` (block footprint x double-buffering +
   scratch) is checked against the VMEM budget before anything compiles,
3. times the survivors through the **normal registry dispatch** (each
   candidate is staged with ``registry.block_override`` so the measured path
   is exactly the production path),
4. writes the winner through ``registry.set_block_override`` — the seam the
   registry reserved for this — and
5. persists a JSON tuning record that later runs load deterministically
   (``load_record`` + ``apply_record`` re-apply the selections without
   re-searching).

A candidate is only selected if it measured strictly faster than the
default geometry, so a recorded selection is never worse than the default
it replaced.

**Tuning under a mesh.** When a ``mesh`` is passed (``benchmarks/run.py
--autotune --mesh DxM`` or ``PxDxM``), every case is timed through the
sharded dispatch (``ops.* (mesh=...)``) and — the part that matters for
record validity — the entry is keyed by the **local shard geometry**
(``partition.local_operand_structs``), not the global operand shapes: the
kernel the block override feeds only ever sees the per-device shard, so a
record tuned at global shape 256x256 over a 4-way K-shard is really
evidence about 256x64 tiles. Records carry the mesh they were tuned under
and ``record_matches_environment`` refuses to silently apply one across
mesh boundaries.

CLI::

    PYTHONPATH=src python -m repro.launch.autotune --out autotune_record.json

or through the benchmark harness: ``python -m benchmarks.run --autotune``
(also triggered by ``REPRO_AUTOTUNE=1``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.streams import StreamProgram
from repro.kernels import ops, registry

# ~16 MB/core of VMEM; the budget caps what one pipelined StreamProgram may
# hold resident (double-buffered stream blocks + scratch)
VMEM_BUDGET_BYTES = int(os.environ.get("REPRO_VMEM_BUDGET", 16 * 2**20))
RECORD_VERSION = 1


@dataclasses.dataclass
class TuneCase:
    """One tunable call.

    Fields: ``op`` — the registry op name; ``args`` — the jax array
    operands, passed positionally to ``fn``; ``fn`` — the dispatch-level
    callable ``fn(*args, mesh=None)`` routed through ``ops.*`` (the measured
    path is exactly the production path, sharded when a mesh is given);
    ``candidates`` — partial block dicts, merged onto the registry defaults;
    ``program`` — the StreamProgram builder the VMEM feasibility probe
    uses; ``plan_kwargs`` — extra keyword operands the op's PartitionRule
    needs to resolve a plan (e.g. ``num_rows`` for bsr_spmm, ``offsets`` /
    ``weights`` for stencil); ``mesh`` — the mesh the case is tuned under
    (None for single-device tuning; set by ``autotune``, not by factories);
    ``precision`` — the ``core.precision`` policy NAME the case dispatches
    under (None = the legacy full-precision path). Timings of the scaled
    kernel are not evidence about the unscaled one (different stream
    count, operand widths, and rescale epilogue), so the policy joins the
    record key and gates ``apply_record``. ``consumer`` — the call-site
    shape class the timings are evidence about (``"prefill"`` = batched
    B x S operands, ``"decode"`` = single-position B x 1 operands). An
    attention geometry tuned at prefill shape says nothing about the
    decode step's one-row grid (and vice versa), so the consumer tag joins
    the record key and gates ``apply_record`` exactly like the policy.
    """

    op: str
    args: tuple
    fn: Callable
    candidates: list[dict[str, int]]
    program: Callable[[dict[str, int]], StreamProgram]
    plan_kwargs: dict = dataclasses.field(default_factory=dict)
    mesh: Any = None
    precision: str | None = None
    consumer: str | None = None


def mesh_tag(mesh) -> str | None:
    """Canonical record tag for the mesh a search ran under: ``"2x4"`` /
    ``"2x2x2"`` style (axis sizes in axis order), or None for no mesh.
    Works for a Mesh or a device-free partition.MeshSpec."""
    if mesh is None:
        return None
    return "x".join(str(int(mesh.shape[a])) for a in mesh.axis_names)


def local_case_shapes(case: TuneCase, impl: str) -> tuple:
    """The operand geometry that keys ``case``'s record entry.

    Args: ``case`` — the TuneCase (its ``mesh`` decides); ``impl`` — the
    resolved registry impl the plan would dispatch to.

    Without a mesh this is just ``case.args``. Under a mesh it is the
    per-device shard geometry from ``partition.local_operand_structs`` —
    the shapes the kernel actually runs on, which is the only geometry a
    tuned block size is evidence about. A case whose plan resolves to
    replication keys identically to the unmeshed case (same local kernel,
    same record entry — deliberately shared).
    """
    if case.mesh is None:
        return case.args
    from repro.kernels import partition

    plan = partition.plan_for(
        case.op, case.mesh, *case.args, impl=impl, **case.plan_kwargs
    )
    return partition.local_operand_structs(plan, case.mesh, case.args)


def case_key(op: str, arrays, backend: str, impl: str,
             precision: str | None = None,
             consumer: str | None = None) -> str:
    """Record key for one tuning entry: ``op|shapes:dtypes|backend|impl``
    (``|precision`` appended for policy-scoped entries, ``#consumer`` for
    consumer-scoped ones).

    Args: ``op`` — op name; ``arrays`` — the operands whose shape/dtype
    identify the tuned kernel geometry (pass the *local shard* structs when
    tuning under a mesh — see ``local_case_shapes``); ``backend`` /
    ``impl`` — the jax backend and registry impl the timings belong to;
    ``precision`` — the policy name for scaled-path cases. The dispatch
    operands of a scaled case are the same fp32 arrays as the legacy case
    (quantization happens inside the impl), so without the suffix the two
    would collide on one record entry. ``consumer`` — the call-site shape
    class (``"prefill"``/``"decode"``); it rides the key so a serving
    session can hold BOTH a prefill-tuned and a decode-tuned entry for the
    same op without one clobbering the other, even when a suite probes
    them at overlapping operand geometry.
    """
    shapes = ",".join(
        f"{'x'.join(map(str, a.shape))}:{a.dtype}" for a in arrays
    )
    key = f"{op}|{shapes}|{backend}|{impl}"
    if precision is not None:
        key = f"{key}|{precision}"
    return key if consumer is None else f"{key}#{consumer}"


def _time_call(fn, args, *, reps: int, warmup: int = 1) -> float:
    """Median wall-time of ``fn(*args)`` per call in seconds over ``reps``
    measured calls (jit compile paid in ``warmup`` untimed calls)."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def candidate_prior_seconds(case: TuneCase, blocks: dict) -> float:
    """Analytic warm-start prior for one candidate geometry: ``case``'s
    StreamProgram built at ``blocks``, priced as modeled HBM stream time
    ``traffic_bytes() / HBM_BW``.

    Small blocks re-fetch shared operands more often (more grid steps over
    the same data), so per-candidate traffic differs even at fixed problem
    size — exactly the effect measured tuning keeps rediscovering. Pricing
    it analytically lets the search measure candidates cheapest-first and
    lets a trial budget cut the modeled-slow tail instead of a random one.
    """
    from repro.launch import roofline

    return case.program(blocks).traffic_bytes() / roofline.HBM_BW


def autotune_case(
    case: TuneCase,
    *,
    budget_bytes: int = VMEM_BUDGET_BYTES,
    reps: int = 3,
    trial_budget: int | None = None,
    time_candidate: Callable | None = None,
) -> dict:
    """Search one case. Returns the record entry (winner + full audit trail).

    Args: ``case`` — the TuneCase to search (its ``mesh`` field, when set,
    routes every timed call through the sharded dispatch); ``budget_bytes``
    — the VMEM ceiling the analytic prune checks candidates against;
    ``reps`` — measured repetitions per candidate; ``trial_budget`` — when
    set, at most this many candidates are actually timed, taken in
    warm-start order (the default geometry is always timed regardless, so
    the strictly-faster selection keeps its baseline); ``time_candidate
    (case, blocks)`` — may be injected for tests; the default jits a fresh
    wrapper per candidate (a shared jit cache would silently reuse the
    first candidate's compiled geometry).

    Warm start: feasible candidates are timed in ascending order of the
    roofline prior (``candidate_prior_seconds``), so the modeled-best
    geometry is measured first and a trial budget spends its measurements
    on the candidates the analytic model already favours.

    Invariant: a non-default candidate is recorded only if it measured
    strictly faster than the default geometry.
    """
    defaults = registry.block_defaults(case.op, overrides=False)

    # normalize to full dicts, defaults first, order-preserving dedupe
    seen, ordered = set(), []
    for cand in [{}] + list(case.candidates):
        full = {**defaults, **cand}
        sig = tuple(sorted(full.items()))
        if sig not in seen:
            seen.add(sig)
            ordered.append(full)

    pruned, feasible = [], []
    for full in ordered:
        vmem = case.program(full).vmem_bytes()
        if vmem > budget_bytes:
            pruned.append({"blocks": full, "vmem_bytes": vmem})
        else:
            feasible.append(full)

    # warm start: measure in analytic-prior order (stable sort — ties keep
    # the candidate-list order, so the defaults-first convention survives)
    priors = {id(f): candidate_prior_seconds(case, f) for f in feasible}
    feasible.sort(key=lambda f: priors[id(f)])

    skipped = []
    if trial_budget is not None:
        keep = feasible[: max(int(trial_budget), 1)]
        if defaults in feasible and defaults not in keep:
            # the baseline must stay measured even when the prior ranks it
            # below the cut — without it no candidate could be recorded
            keep.append(next(f for f in feasible if f == defaults))
        skipped = [
            {"blocks": f, "prior_s": priors[id(f)]}
            for f in feasible
            if not any(f is k for k in keep)
        ]
        feasible = keep

    if time_candidate is None:

        def time_candidate(case, blocks):
            # fresh wrapper, fresh cache; the mesh (if any) rides the closure
            fn = jax.jit(lambda *a: case.fn(*a, mesh=case.mesh))
            return _time_call(fn, case.args, reps=reps)

    timed = []
    for full in feasible:
        with registry.block_override(case.op, **full):
            timed.append({
                "blocks": full,
                "us_per_call": time_candidate(case, full) * 1e6,
                "prior_s": priors[id(full)],
            })

    default_entry = next(
        (t for t in timed if t["blocks"] == defaults), None
    )
    # strictly-faster-than-default selection: the recorded winner is never
    # worse than the default it replaces (ties keep the default)
    best = default_entry or (timed[0] if timed else None)
    for t in timed:
        if best is None or t["us_per_call"] < best["us_per_call"]:
            best = t
    return {
        "op": case.op,
        "precision": case.precision,
        "consumer": case.consumer,
        "blocks": best["blocks"] if best else defaults,
        "us_per_call": best["us_per_call"] if best else None,
        "default_blocks": defaults,
        "default_us": default_entry["us_per_call"] if default_entry else None,
        "timed": timed,
        "pruned": pruned,
        "skipped_by_budget": skipped,
        "trial_budget": trial_budget,
        "vmem_budget_bytes": budget_bytes,
    }


# ---------------------------------------------------------------------------
# Default suite: one representative call per op with a block table
# ---------------------------------------------------------------------------


def _gemm_case(rng) -> TuneCase:
    from repro.kernels.gemm import gemm_program

    m = k = n = 256
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    def program(bl):
        bm, bk, bn = min(bl["bm"], m), min(bl["bk"], k), min(bl["bn"], n)
        return gemm_program(
            m + (-m) % bm, n + (-n) % bn, k + (-k) % bk, bm, bn, bk,
            a_dtype=a.dtype, b_dtype=b.dtype, out_dtype=a.dtype,
            accum_dtype=jnp.float32,
        )

    return TuneCase(
        "gemm", (a, b), lambda a, b, mesh=None: ops.gemm(a, b, mesh=mesh),
        [{"bm": s, "bk": s, "bn": s} for s in (64, 128, 256)], program,
    )


def _flash_attention_case(rng) -> TuneCase:
    from repro.kernels.flash_attention import flash_attention_program

    B, H, S, D = 1, 4, 256, 64
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
        for _ in range(3)
    )

    def program(bl):
        bq, bk = min(bl["bq"], S), min(bl["bk"], S)
        nq, nk = -(-S // bq), -(-S // bk)
        return flash_attention_program(
            B, H, 1, nq * bq, D, nq, nk, bq, bk, q.dtype, k.dtype, v.dtype,
            scale=1.0, causal=True, window=0, q_offset=0, sk=S,
        )

    return TuneCase(
        "flash_attention", (q, k, v),
        lambda q, k, v, mesh=None: ops.flash_attention(
            q, k, v, causal=True, mesh=mesh),
        [{"bk": s} for s in (32, 64, 128, 256)], program,
    )


def _linear_attention_case(rng) -> TuneCase:
    from repro.kernels.rwkv6 import linear_attention_program

    B, H, T, N = 1, 2, 256, 64
    r, k, v = (
        jnp.asarray(rng.standard_normal((B, H, T, N)), jnp.float32)
        for _ in range(3)
    )
    w = jnp.asarray(-rng.uniform(0.01, 1.0, (B, H, T, N)), jnp.float32)

    def program(bl):
        chunk = min(bl["chunk"], T)
        return linear_attention_program(
            B * H, T + (-T) % chunk, N, N, chunk, ssd=True,
            r_dtype=r.dtype, k_dtype=k.dtype, v_dtype=v.dtype,
            w_dtype=w.dtype, o_dtype=v.dtype,
        )

    return TuneCase(
        "linear_attention", (r, k, v, w),
        lambda r, k, v, w, mesh=None: ops.linear_attention(
            r, k, v, w, mesh=mesh),
        [{"chunk": s} for s in (8, 16, 32)], program,
    )


def _spmm_case(rng) -> TuneCase:
    from repro.core.sparse import random_ell
    from repro.kernels.spmm import ell_spmm_program

    R, C, F = 512, 256, 64
    A = random_ell(rng, R, C, 0.05)
    dense = jnp.asarray(rng.standard_normal((C, F)), jnp.float32)
    L = A.values.shape[1]

    def program(bl):
        bm = min(bl["bm"], R)
        return ell_spmm_program(
            R + (-R) % bm, L, C, F, bm, A.values.dtype, dense.dtype
        )

    return TuneCase(
        "spmm", (A.values, A.cols, dense),
        lambda v, c, d, mesh=None: ops.spmm(v, c, d, mesh=mesh),
        [{"bm": s} for s in (32, 64, 128, 256)], program,
    )


def _bsr_spmm_case(rng) -> TuneCase:
    from repro.core.sparse import dense_to_bsr
    from repro.kernels.spmm import bsr_spmm_program

    R, K, F = 256, 256, 512
    mat = np.zeros((R, K), np.float32)
    mask = rng.random((R, K)) < 0.05
    mat[mask] = rng.standard_normal(mask.sum())
    A = dense_to_bsr(mat, bm=8, bk=128)
    dense = jnp.asarray(rng.standard_normal((K, F)), jnp.float32)
    T, bm, bk = A.tile_values.shape

    def program(bl):
        bf = min(bl["bf"], F)
        return bsr_spmm_program(
            A.tile_rows, A.tile_cols, T, bm, bk, bf, F + (-F) % bf, R,
            A.tile_values.dtype, dense.dtype,
        )

    return TuneCase(
        "bsr_spmm", (A.tile_values, A.tile_rows, A.tile_cols, dense),
        lambda tv, tr, tc, d, mesh=None: ops.bsr_spmm(
            tv, tr, tc, d, R, mesh=mesh),
        [{"bf": s} for s in (128, 256, 512)], program,
        plan_kwargs={"num_rows": R},
    )


def _spmspm_case(rng) -> TuneCase:
    from repro.core.sparse import random_ell
    from repro.kernels.spmspm import spmspm_program

    R, C, K = 128, 128, 256
    A = random_ell(rng, R, K, 0.05)
    B = random_ell(rng, C, K, 0.05)
    La, Lb = A.values.shape[1], B.values.shape[1]

    def program(bl):
        bm, bn = min(bl["bm"], R), min(bl["bn"], C)
        return spmspm_program(
            R + (-R) % bm, C + (-C) % bn, La, Lb, bm, bn,
            A.values.dtype, B.values.dtype,
        )

    return TuneCase(
        "spmspm", (A.values, A.cols, B.values, B.cols),
        lambda av, ac, bv, br, mesh=None: ops.spmspm(
            av, ac, bv, br, K, mesh=mesh),
        [{"bm": m, "bn": n} for m in (8, 16, 32) for n in (64, 128)], program,
        plan_kwargs={"contraction_dim": K},
    )


def _stencil_case(rng) -> TuneCase:
    from repro.kernels.stencil import stencil_program

    X, Y, Z = 64, 32, 32
    grid = jnp.asarray(rng.standard_normal((X, Y, Z)), jnp.float32)
    offsets = np.array(
        [(0, 0, 0), (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0),
         (0, 0, 1), (0, 0, -1)], np.int32,
    )
    weights = np.full(len(offsets), 1.0 / len(offsets), np.float32)

    def program(bl):
        bx = min(bl["bx"], X)
        return stencil_program(X, Y, Z, bx, offsets, weights, grid.dtype)

    return TuneCase(
        "stencil", (grid,),
        lambda g, mesh=None: ops.stencil(g, offsets, weights, mesh=mesh),
        [{"bx": s} for s in (4, 8, 16, 32)], program,
        plan_kwargs={"offsets": offsets, "weights": weights},
    )


def _decode_attention_case(rng) -> TuneCase:
    """the contiguous decode has no StreamProgram (its blocked form is the
    xla impl; the Pallas kernel is paged only), so the VMEM probe uses a
    stream DESCRIPTION of that impl's cache traffic:
    one resident q block plus double-buffered (bs x D) K/V cache tiles per
    grid step — the same footprint the online-softmax scan carries."""
    from repro.core.streams import AffineStream, StreamProgram

    B, H, K, S, D = 2, 8, 4, 1024, 64
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, K, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, K, S, D)), jnp.float32)
    pos = jnp.full((B,), S - 1, jnp.int32)

    def program(bl):
        bs = min(bl["bs"], S)
        Sp = S + (-S) % bs
        cache = AffineStream((B, K, bs, D), lambda i: (0, 0, i, 0), dtype=k.dtype)
        head = AffineStream((B, H, D), lambda i: (0, 0, 0), dtype=q.dtype)
        return StreamProgram(
            name="decode_attention",
            body=lambda *_: None,  # feasibility probe only; never executed
            grid=(Sp // bs,),
            in_streams=(head, cache, cache),
            out_streams=(head,),
            out_shapes=(jax.ShapeDtypeStruct((B, H, D), q.dtype),),
        )

    return TuneCase(
        "decode_attention", (q, k, v, pos),
        lambda q, k, v, p, mesh=None: ops.decode_attention(
            q, k, v, p, mesh=mesh),
        [{"bs": s} for s in (128, 256, 512, 1024)], program,
    )


DEFAULT_SUITE: dict[str, Callable] = {
    "gemm": _gemm_case,
    "flash_attention": _flash_attention_case,
    "linear_attention": _linear_attention_case,
    "spmm": _spmm_case,
    "bsr_spmm": _bsr_spmm_case,
    "spmspm": _spmspm_case,
    "stencil": _stencil_case,
    "decode_attention": _decode_attention_case,
}


def _gemm_precision_case(policy: str) -> Callable:
    """Factory-of-factory for the policy-scoped gemm cases: same operand
    geometry as ``_gemm_case`` but dispatched with ``precision=policy``,
    feasibility-probed through ``gemm_scaled_program`` (whose narrow value
    streams plus fp32 scale streams give the analytic prune and the
    roofline warm-start prior per-policy traffic, not fp32 traffic)."""

    def factory(rng) -> TuneCase:
        from repro.core import precision as prec
        from repro.kernels.gemm import gemm_scaled_program

        p = prec.resolve(policy)
        m = k = n = 256
        a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

        def program(bl):
            bm, bk, bn = min(bl["bm"], m), min(bl["bk"], k), min(bl["bn"], n)
            return gemm_scaled_program(
                m + (-m) % bm, n + (-n) % bn, k + (-k) % bk, bm, bn, bk,
                compute_dtype=p.compute_dtype, out_dtype=jnp.float32,
                accum_dtype=p.accum_dtype,
            )

        return TuneCase(
            "gemm", (a, b),
            lambda a, b, mesh=None: ops.gemm(a, b, precision=p, mesh=mesh),
            [{"bm": s, "bk": s, "bn": s} for s in (64, 128, 256)], program,
            plan_kwargs={"precision": p}, precision=p.name,
        )

    return factory


# policy-scoped cases: the scaled gemm path tuned under fp8 and bf16. Kept
# out of DEFAULT_SUITE so existing records and the CI smoke stay stable;
# ``full_suite()`` is the merged table the analyzer sweeps.
PRECISION_SUITE: dict[str, Callable] = {
    "gemm@fp8": _gemm_precision_case("fp8"),
    "gemm@bf16": _gemm_precision_case("bf16"),
}


def _flash_attention_consumer_case(consumer: str) -> Callable:
    """Factory-of-factory for the consumer-scoped flash cases: the same op
    probed at the shape each serving call site actually dispatches —
    ``prefill`` runs the batched B x S geometry, ``decode`` a one-query-row
    B x 1 geometry (speculative / chunked single-step flash). The two grids
    share no tiling evidence: a bk that wins when 256 query rows amortize
    each K tile streams the whole cache per single row at decode."""

    def factory(rng) -> TuneCase:
        from repro.kernels.flash_attention import flash_attention_program

        B, H, S, D = 1, 4, 256, 64
        sq = S if consumer == "prefill" else 1
        q = jnp.asarray(rng.standard_normal((B, H, sq, D)), jnp.float32)
        k, v = (
            jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
            for _ in range(2)
        )
        q_offset = 0 if consumer == "prefill" else S - 1

        def program(bl):
            bq, bk = min(bl["bq"], sq), min(bl["bk"], S)
            nq, nk = -(-sq // bq), -(-S // bk)
            return flash_attention_program(
                B, H, 1, nq * bq, D, nq, nk, bq, bk, q.dtype, k.dtype,
                v.dtype, scale=1.0, causal=True, window=0,
                q_offset=q_offset, sk=S,
            )

        return TuneCase(
            "flash_attention", (q, k, v),
            lambda q, k, v, mesh=None: ops.flash_attention(
                q, k, v, causal=True, q_offset=q_offset, mesh=mesh),
            [{"bk": s} for s in (32, 64, 128, 256)], program,
            consumer=consumer,
        )

    return factory


def _decode_attention_consumer_case() -> Callable:
    """``decode_attention`` tagged with its (only) consumer class, so the
    serving engine's ``apply_record(consumer="decode")`` picks it up and an
    untagged legacy record entry for the same geometry cannot collide."""

    def factory(rng) -> TuneCase:
        case = _decode_attention_case(rng)
        case.consumer = "decode"
        return case

    return factory


# consumer-scoped cases: the attention ops probed per call-site shape
# class (prefill B x S vs decode B x 1). Same record-stability reasoning
# as PRECISION_SUITE for keeping them out of DEFAULT_SUITE.
CONSUMER_SUITE: dict[str, Callable] = {
    "flash_attention#prefill": _flash_attention_consumer_case("prefill"),
    "flash_attention#decode": _flash_attention_consumer_case("decode"),
    "decode_attention#decode": _decode_attention_consumer_case(),
}


def full_suite() -> dict[str, Callable]:
    """DEFAULT_SUITE plus the policy-scoped PRECISION_SUITE and the
    consumer-scoped CONSUMER_SUITE cases — the complete factory table the
    CLI searches and the ``repro.analysis`` plan rules (vmem-budget,
    accum-dtype-widening) sweep."""
    return {**DEFAULT_SUITE, **PRECISION_SUITE, **CONSUMER_SUITE}


# ---------------------------------------------------------------------------
# Record: search, persist, deterministic re-apply
# ---------------------------------------------------------------------------


def autotune(
    ops_subset=None,
    *,
    budget_bytes: int = VMEM_BUDGET_BYTES,
    reps: int = 3,
    seed: int = 0,
    suite: dict[str, Callable] | None = None,
    mesh: Any = None,
    trial_budget: int | None = None,
    time_candidate: Callable | None = None,
) -> dict:
    """Search every suite case and return the tuning record.

    Args: ``ops_subset`` — restrict to these op names (KeyError on unknown
    names); ``budget_bytes`` — VMEM ceiling for the analytic prune;
    ``reps`` — measured repetitions per candidate; ``seed`` — operand RNG
    seed (records are deterministic given a seed); ``suite`` — factory
    table, defaulting to DEFAULT_SUITE; ``mesh`` — tune through the sharded
    dispatch over this mesh, keying every entry by the LOCAL shard geometry
    (see ``local_case_shapes``); ``trial_budget`` — per-case cap on how
    many candidates are timed, spent in roofline-prior order (the default
    geometry always stays measured); ``time_candidate`` — test injection
    forwarded to ``autotune_case``.

    Returns the record dict (version, backend, impl, mesh tag, entries).
    Winners are NOT yet applied — call ``apply_record``.
    """
    suite = DEFAULT_SUITE if suite is None else suite
    if ops_subset:
        unknown = set(ops_subset) - set(suite)
        if unknown:
            raise KeyError(
                f"unknown autotune ops {sorted(unknown)}; known: {sorted(suite)}"
            )
    backend = jax.default_backend()
    impl = registry.resolve_impl(None)
    rng = np.random.default_rng(seed)
    entries = {}
    for name, factory in suite.items():
        if ops_subset and name not in ops_subset:
            continue
        case = factory(rng)
        case.mesh = mesh
        entry = autotune_case(
            case, budget_bytes=budget_bytes, reps=reps,
            trial_budget=trial_budget, time_candidate=time_candidate,
        )
        key = case_key(case.op, local_case_shapes(case, impl), backend, impl,
                       precision=case.precision, consumer=case.consumer)
        entries[key] = entry
    return {
        "version": RECORD_VERSION,
        "backend": backend,
        "impl": impl,
        "mesh": mesh_tag(mesh),
        "entries": entries,
    }


def save_record(record: dict, path: str) -> None:
    """Persist ``record`` to ``path`` as deterministic (sorted, indented)
    JSON with a trailing newline."""
    with open(path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")


def load_record(path: str) -> dict:
    """Load a tuning record from ``path``; raises ValueError when its
    version is not the RECORD_VERSION this module writes."""
    with open(path) as f:
        record = json.load(f)
    if record.get("version") != RECORD_VERSION:
        raise ValueError(
            f"{path}: tuning record version {record.get('version')!r} != "
            f"{RECORD_VERSION}; re-run the autotuner"
        )
    return record


def record_matches_environment(record: dict, *, mesh: Any = None) -> bool:
    """Was ``record`` tuned for the current (backend, impl) and for ``mesh``?

    Geometry tuned for one impl is not evidence about another; likewise a
    record tuned under one mesh keys (and tuned) the local shard shapes of
    THAT mesh, so it only applies where the same mesh (by ``mesh_tag``) is
    in play. Records predating the mesh field match ``mesh=None``.
    """
    return (
        record.get("backend") == jax.default_backend()
        and record.get("impl") == registry.resolve_impl(None)
        and record.get("mesh") == mesh_tag(mesh)
    )


def apply_record(record: dict, *, force: bool = False,
                 mesh: Any = None,
                 precision: str | None = None,
                 consumer: str | None = None) -> dict[str, dict[str, int]]:
    """Write every recorded winner through ``registry.set_block_override``
    (deterministic: no timing, no search).

    Args: ``record`` — a dict from ``autotune``/``load_record``; ``force``
    — skip the environment check; ``mesh`` — the mesh this session
    dispatches kernels over (None for single-device), matched against the
    record's tuned mesh; ``precision`` — apply only entries tuned under
    this policy name (None = the legacy full-precision entries). The
    registry's block-override table has no precision axis, so a session
    must pick which policy's winners drive it: an fp8-tuned geometry is
    measured through the scaled kernel and is not evidence about the
    unscaled one (and vice versa) — entries never cross-apply.
    ``consumer`` — likewise for the call-site shape axis: apply only
    entries tuned for this consumer class (None = untagged legacy
    entries). A serving engine applies the ``"decode"`` winners before its
    decode loop and the ``"prefill"`` winners around admission prefill;
    a prefill-tuned geometry never leaks into the decode step's one-row
    grid through a shared record. Returns {op: blocks} applied.

    Raises if the record was tuned for a different backend/impl/mesh than
    the one currently dispatching — applying it would silently mistune, the
    exact bug class the tuner exists to remove. ``force=True`` overrides.
    """
    if not force and not record_matches_environment(record, mesh=mesh):
        raise ValueError(
            f"tuning record is for backend={record.get('backend')!r} "
            f"impl={record.get('impl')!r} mesh={record.get('mesh')!r} but "
            f"this session dispatches backend={jax.default_backend()!r} "
            f"impl={registry.resolve_impl(None)!r} mesh={mesh_tag(mesh)!r}; "
            f"re-run the autotuner (or pass force=True)"
        )
    applied = {}
    for entry in record["entries"].values():
        if entry.get("precision") != precision:
            continue
        if entry.get("consumer") != consumer:
            continue
        blocks = {k: int(v) for k, v in entry["blocks"].items()}
        registry.set_block_override(entry["op"], **blocks)
        applied[entry["op"]] = blocks
    return applied


def record_deltas(record: dict) -> dict[str, dict]:
    """Tuned-vs-default summary per op of one tuning ``record`` — the
    perf-harness reporting view. Returns {op: {blocks, default_blocks,
    us_per_call, default_us, delta_pct, non_default}} with None times
    preserved (a case whose candidates were all pruned has no timing)."""
    out = {}
    for entry in record["entries"].values():
        tuned, default = entry["us_per_call"], entry["default_us"]
        delta = (
            (tuned - default) / default * 100.0
            if tuned is not None and default
            else None
        )
        name = entry["op"]
        if entry.get("precision"):
            name = f"{name}@{entry['precision']}"
        if entry.get("consumer"):
            name = f"{name}#{entry['consumer']}"
        out[name] = {
            "blocks": entry["blocks"],
            "default_blocks": entry["default_blocks"],
            "us_per_call": tuned,
            "default_us": default,
            "delta_pct": delta,
            "non_default": entry["blocks"] != entry["default_blocks"],
        }
    return out


def main(argv=None) -> None:
    """CLI entry point: search, persist, and report. ``argv`` defaults to
    sys.argv (see ``--help`` for the flags)."""
    ap = argparse.ArgumentParser(
        description="benchmark-driven block-size autotuner; persists a JSON "
        "tuning record later runs load deterministically"
    )
    ap.add_argument("--out", default="autotune_record.json")
    ap.add_argument("--ops", default=None,
                    help="comma-separated subset of "
                         f"{sorted(full_suite())} (``op@policy`` names are "
                         "the precision-scoped scaled-path cases)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--budget-bytes", type=int, default=VMEM_BUDGET_BYTES)
    ap.add_argument("--budget", type=int, default=None, metavar="N",
                    help="time at most N candidates per case, spent in "
                    "roofline-prior order (the default geometry is always "
                    "measured); unset = time every feasible candidate")
    ap.add_argument("--impl", default=None,
                    help="pin a registry impl for the search (default: the "
                    "normal dispatch resolution)")
    args = ap.parse_args(argv)

    subset = args.ops.split(",") if args.ops else None
    with registry.default_impl(args.impl):
        record = autotune(
            subset, budget_bytes=args.budget_bytes, reps=args.reps,
            trial_budget=args.budget, suite=full_suite(),
        )
    save_record(record, args.out)
    print(f"wrote {args.out}")
    for op, d in sorted(record_deltas(record).items()):
        tuned_us = (
            "n/a (all candidates pruned)" if d["us_per_call"] is None
            else f"{d['us_per_call']:.1f}us"
        )
        default_us = (
            "n/a" if d["default_us"] is None else f"{d['default_us']:.1f}us"
        )
        delta = (
            "n/a" if d["delta_pct"] is None else f"{d['delta_pct']:+.1f}%"
        )
        print(
            f"{op}: {d['blocks']} {tuned_us} "
            f"(default {d['default_blocks']} {default_us}, delta {delta})"
        )


if __name__ == "__main__":
    main()
