"""Mesh-aware kernel partitioning: the third axis of dispatch (paper Fig. 13).

Occamy's hierarchical, symmetric interconnect lets cluster-agnostic kernels
scale across groups, chiplets, and the D2D link with predictable bandwidth
per level. The software analogue: every op in the kernel registry carries a
``PartitionRule`` describing how its operands split over the mesh's
*partition levels* — the chiplet axis (``model``) and, on a multi-pod mesh,
the pod axis (``pod``, the D2D link) jointly above it — which collective
stitches the partials back together at each level, and when the op must
degrade to fewer levels or to replication instead (the same divisibility
contract as ``parallel/sharding.py``).

Layering (parallel to impl selection and block resolution):

  ops.py            resolves the rule once per call — explicit ``mesh=`` kwarg
                    or the mesh from ``sharding.use_mesh`` — and routes here
  partition.py      plan_for(): PartitionRule -> PartitionPlan (specs +
                    local function + per-level collective-cost metadata)
  sharded_call()    wraps WHICHEVER registered impl runs in
                    ``jax.shard_map``, so pallas, interpret, xla and ref all
                    execute the identical sharded program; the single
                    pallas-call-site invariant (core/streams.py) is untouched
  consumers         launch/roofline prices plan.collectives per level with
                    ``topology.collective_seconds`` (on-chiplet vs D2D
                    bandwidth); benchmarks/bench_mesh.py times sharded vs
                    single device

Rule table (the op's logical-axis split over the partition levels):

  gemm              K-sharded (A cols x B rows) over pod×model jointly; the
                    epilogue is a *hierarchical* all-reduce — intra-pod psum
                    then cross-pod psum — so the D2D link carries one
                    already-reduced buffer per pod. Falls back to M-row
                    sharding, then (via the level ladder) to model-only,
                    then replication
  flash_attention   GQA head-sharded (q heads AND kv heads) over pod×model,
                    COMPOSED with the ``data`` level (attention_levels):
                    B over ``data`` when the batch divides it, else the
                    sequence-parallel KV ring — Sq/Sk sharded over ``data``
                    with the K/V chunks rotating through (n-1) ppermute
                    hops, each hop re-entering the registered kernel at its
                    static q_offset and folding through the online-softmax
                    merge (collectives.ring_scan / online_softmax_merge) —
                    the latency-tolerant C4/C5 tile-rotation pattern at
                    mesh scale. TP-hostile head counts keep the data-level
                    composition and drop only the head split
  decode_attention  same composed GQA head × batch rule (cache and
                    position rows ride the batch split); no ring
  linear_attention  head-sharded state/decay streams (u, s0 included),
                    composed with B over ``data``
  spmm              row-sharded ELL value/index streams — rows split across
                    pods, then within each pod — dense replicated
  bsr_spmm          tile-sharded (nnz-parallel), hierarchical ``psum``
                    epilogue over rows
  spmspm            row-sharded A, B replicated
  stencil           x-sharded grid with ``ppermute`` halo exchange; on a
                    multi-pod mesh the intra-pod hops ride the chiplet
                    crossbar and the single pod-boundary hop per direction
                    rides the D2D link (SARIS boundary planes)

**The replication fallback ladder.** ``plan_for`` resolves the mesh's
partition levels outermost-first (``pod`` above ``model``) and offers the
full stack to the op's rule; if the rule's divisibility checks fail, the
outermost level is dropped and the rule is retried, down to a single level
and finally to ``None`` (replication). An op whose heads divide the chiplet
axis but not pod×model therefore still shards intra-pod instead of
replicating outright.

``plan_for`` also accepts a device-free ``MeshSpec`` so the dry-run/roofline
path can cost the per-level collectives without constructing devices;
executing a plan (``sharded_call``) requires a real ``jax.sharding.Mesh``.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.diagnostics import warn_degrade
from repro.kernels import registry
from repro.parallel.collectives import hierarchical_psum

# The complete mesh-axis vocabulary the partition layer ever shards over or
# names in a collective: the D2D pod link, the group interconnect (data),
# and the chiplet crossbar (model). partition_levels / attention_levels
# only ever emit these names, and the repro.analysis axis-name lint rule
# holds every string-literal collective axis in the tree to this list — a
# stray "modle" in a psum is a silent replication bug otherwise.
AXIS_VOCAB = ("pod", "data", "model")


# ---------------------------------------------------------------------------
# Plan objects
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CollectiveCost:
    """One collective a plan fires at one partition level.

    Fields: ``kind`` — the collective, in the vocabulary of
    ``topology.collective_seconds`` ("all_reduce" | "all_gather" |
    "reduce_scatter" | "permute"); ``axis`` — the mesh axis it crosses
    (``"pod"`` prices at the D2D link bandwidth, anything else at the
    on-chiplet ICI bandwidth); ``nbytes`` — the per-device payload;
    ``n`` — the participant count at that level (the ring size the
    bandwidth model uses). ``n=0`` means "the plan's total shard count",
    kept for constructors predating per-level costing.
    """

    kind: str
    axis: str
    nbytes: int
    n: int = 0


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    """A resolved partitioning of one op call over one or more mesh levels.

    Fields: ``op`` — the registry op name; ``levels`` — outer→inner
    ``(axis, size)`` pairs the plan shards over (``(("pod", 2), ("model",
    16))`` for a two-level plan, a single pair otherwise); ``in_specs`` —
    one PartitionSpec per positional operand (entries for operands that are
    ``None`` are ignored); ``out_specs`` — the output spec (or tuple
    thereof); ``local_fn`` — takes the full operand tuple (Nones included)
    and runs the registered impl on the local shard, firing any collective
    epilogue inside ``shard_map``; ``collectives`` — per-level
    ``CollectiveCost`` metadata in firing order (innermost level first);
    ``note`` — a human-readable one-liner for benchmark/roofline rows.

    Latency-tolerance metadata (the overlap cost model reads these):
    ``overlappable`` — the local_fn issues its collectives double-buffered,
    so per-hop D2D time hides behind per-hop compute instead of adding to
    it; ``hops`` — the pipeline depth the overlap model amortises over
    (ring length for the KV ring, 2 for the halo exchange's two
    directions); ``pre`` / ``post`` — optional GLOBAL-array rewrites
    applied by ``sharded_call`` outside shard_map: ``pre(*args) -> args``
    before sharding (the zigzag sequence gather), ``post(out) -> out``
    after (its inverse).

    Invariants: ``n`` (total shard count) is the product of the level
    sizes; ``axis`` is the spec-entry form of the levels — the bare axis
    name for a single level, the axis tuple for a joint split.
    """

    op: str
    levels: tuple
    in_specs: tuple
    out_specs: Any
    local_fn: Callable
    collectives: tuple[CollectiveCost, ...] = ()
    note: str = ""
    overlappable: bool = False
    hops: int = 0
    pre: Callable | None = None
    post: Callable | None = None

    @property
    def axis(self):
        """Spec-entry form of the partition axes: ``"model"`` for a
        single-level plan, ``("pod", "model")`` for a joint two-level one."""
        axes = tuple(a for a, _ in self.levels)
        return axes[0] if len(axes) == 1 else axes

    @property
    def n(self) -> int:
        """Total shard count: the product of every level's size."""
        return math.prod(n for _, n in self.levels)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Device-free mesh descriptor: lets the dry-run/roofline layer resolve
    partition plans (and their per-level D2D costs) without any devices
    existing.

    Fields: ``shape`` — ``{axis_name: size}`` in axis order (a 2-pod
    production mesh is ``{"pod": 2, "data": 16, "model": 16}``).
    """

    shape: dict

    @property
    def axis_names(self) -> tuple:
        """The mesh axis names, in declaration order."""
        return tuple(self.shape)


def partition_axis(mesh) -> str:
    """The innermost axis ops shard over: ``model`` (the chiplet crossbar in
    the C5 mapping) when present, else the last axis of ``mesh`` (a Mesh or
    MeshSpec). Two-level plans stack the ``pod`` axis above this one — see
    ``partition_levels``."""
    names = tuple(mesh.axis_names)
    return "model" if "model" in names else names[-1]


def partition_levels(mesh) -> tuple:
    """The partition-level stack of ``mesh``, outermost first.

    Returns ``(axis, size)`` pairs: ``("pod", P)`` when the mesh has a
    non-trivial ``pod`` axis (the D2D link), then the ``partition_axis``
    (the chiplet crossbar). Size-1 axes are dropped, so a flat mesh yields
    one level and a trivial mesh yields ``()`` (replication). ``mesh`` may
    be a Mesh or a device-free MeshSpec.
    """
    names = tuple(mesh.axis_names)
    inner = partition_axis(mesh)
    levels = []
    if "pod" in names and inner != "pod" and int(mesh.shape["pod"]) > 1:
        levels.append(("pod", int(mesh.shape["pod"])))
    if int(mesh.shape[inner]) > 1:
        levels.append((inner, int(mesh.shape[inner])))
    return tuple(levels)


def attention_levels(mesh) -> tuple:
    """The attention family's level stack: ``partition_levels`` with the
    ``data`` axis (the group-interconnect level) slotted between ``pod``
    and the chiplet axis.

    Attention rules use the extra level for the *batch or sequence*
    dimension — B-sharding when the batch divides it, else the
    sequence-parallel KV ring for ``flash_attention`` — composed with the
    GQA head sharding the remaining levels carry. Size-1 axes are dropped;
    a mesh without a ``data`` axis degenerates to ``partition_levels``.
    """
    names = tuple(mesh.axis_names)
    inner = partition_axis(mesh)
    levels = []
    if "pod" in names and inner != "pod" and int(mesh.shape["pod"]) > 1:
        levels.append(("pod", int(mesh.shape["pod"])))
    if "data" in names and inner != "data" and int(mesh.shape["data"]) > 1:
        levels.append(("data", int(mesh.shape["data"])))
    if int(mesh.shape[inner]) > 1:
        levels.append((inner, int(mesh.shape[inner])))
    return tuple(levels)


def _joint(levels) -> str | tuple:
    """PartitionSpec entry for a joint split over ``levels``: the bare axis
    name for one level, the axis-name tuple for several."""
    axes = tuple(a for a, _ in levels)
    return axes[0] if len(axes) == 1 else axes


def _ntot(levels) -> int:
    return math.prod(n for _, n in levels)


def _levels_note(levels) -> str:
    return "+".join(f"{a}={n}" for a, n in levels)


# ---------------------------------------------------------------------------
# Rule registry
# ---------------------------------------------------------------------------

_RULES: dict[str, Callable] = {}
_LEVEL_FNS: dict[str, Callable] = {}


def register_partition_rule(op: str, *, levels: Callable | None = None) -> Callable:
    """Decorator: ``@register_partition_rule("spmm")`` registers the
    PartitionRule for the registry op named ``op``.

    The rule receives ``(levels, *operands, impl=..., **op_kwargs)`` —
    ``levels`` being the outer→inner ``(axis, size)`` stack ``plan_for``
    offers it — and returns a PartitionPlan, or None when its divisibility
    checks fail at that level count (``plan_for`` then retries with the
    outermost level dropped: the replication fallback ladder).

    ``levels`` selects the op's level vocabulary — the function mapping a
    mesh to the stack ``plan_for`` offers (default ``partition_levels``;
    the attention family uses ``attention_levels``, which adds the ``data``
    axis for batch/sequence parallelism).
    """

    def deco(fn: Callable) -> Callable:
        _RULES[op] = fn
        if levels is not None:
            _LEVEL_FNS[op] = levels
        return fn

    return deco


def partitioned_ops() -> list[str]:
    """Sorted names of every op that registered a PartitionRule."""
    return sorted(_RULES)


# Plan-only keywords: schedule knobs the partition layer consumes, never the
# kernels. ``plan_for`` forwards each one only to rules whose signature
# declares it (rules like gemm's pass **blocks straight to kernel_call, so a
# stray ``overlap=`` would land in an impl); the dispatch seams strip them
# before any direct kernel_call.
PLAN_KWARGS = ("overlap", "zigzag", "remote_copy")


@functools.lru_cache(maxsize=None)
def _rule_plan_params(rule: Callable) -> frozenset:
    """The subset of PLAN_KWARGS a rule's signature declares."""
    try:
        params = inspect.signature(rule).parameters
    except (TypeError, ValueError):  # builtins/C callables: assume none
        return frozenset()
    return frozenset(k for k in PLAN_KWARGS if k in params)


def strip_plan_kwargs(kwargs: dict) -> dict:
    """``kwargs`` without the plan-only schedule keywords — what a plain
    (replicated) ``kernel_call`` may receive."""
    return {k: v for k, v in kwargs.items() if k not in PLAN_KWARGS}


def plan_for(op: str, mesh, *args, impl: str | None = None, **kwargs):
    """Resolve the op's PartitionRule against ``mesh`` (a Mesh or MeshSpec).

    Args: ``op`` — registry op name; ``mesh`` — the mesh (or device-free
    MeshSpec) whose partition levels the rule sees; ``*args`` / ``**kwargs``
    — the op call's operands (arrays or ShapeDtypeStructs; plans resolve
    from shapes alone) and keyword parameters; ``impl`` — the registry impl
    the plan's local function will dispatch to.

    Walks the replication fallback ladder: the full level stack (pod×model
    on a multi-pod mesh) is offered first; each time the rule declines, the
    outermost level is dropped. Returns None — replication — when the op
    has no rule, no non-trivial level exists, or every rung fails (the
    graceful-degradation contract shared with parallel/sharding.py). A
    fully exhausted ladder — a rule that declined every rung of a
    non-trivial stack — emits a one-shot ``ReproDegradeWarning`` naming the
    op and mesh, so silent replication is visible to callers and to the
    ``repro.analysis`` ladder-dead-end check.
    """
    rule = _RULES.get(op)
    if rule is None:
        return None
    accepted = _rule_plan_params(rule)
    kwargs = {
        k: v for k, v in kwargs.items()
        if k not in PLAN_KWARGS or k in accepted
    }
    levels = _LEVEL_FNS.get(op, partition_levels)(mesh)
    offered = levels
    while levels:
        plan = rule(levels, *args, impl=impl, **kwargs)
        if plan is not None:
            return plan
        levels = levels[1:]
    if offered:
        shape = "x".join(f"{a}={s}" for a, s in offered)
        warn_degrade(
            f"partition ladder exhausted for {op!r}: every rung of "
            f"({shape}) declined; replicating the call on all devices",
            key=("ladder_exhausted", op, shape),
        )
    return None


def plan_collective_bytes(plan: PartitionPlan | None) -> int:
    """Total per-device collective payload of ``plan``, summed across every
    level (0 for replication)."""
    if plan is None:
        return 0
    return sum(c.nbytes for c in plan.collectives)


def local_operand_structs(plan: PartitionPlan | None, mesh, args) -> tuple:
    """Per-device shard geometry of each live operand under ``plan``.

    Args: ``plan`` — a plan from ``plan_for`` (None means replication:
    operands pass through whole); ``mesh`` — the Mesh or MeshSpec the plan
    was resolved against; ``args`` — the positional operands (arrays or
    ShapeDtypeStructs; ``None`` entries are skipped, mirroring
    ``sharded_call``).

    Returns one ``jax.ShapeDtypeStruct`` per live operand with every
    sharded dimension divided by the product of its spec axes' sizes — the
    shapes the registered impl actually sees inside ``shard_map``. This is
    what keys autotune records under a mesh: tuned block geometry is only
    valid for the *local* shapes the kernel ran on.
    """
    live = [a for a in args if a is not None]
    if plan is None:
        return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in live)
    out = []
    for a, spec in zip(args, plan.in_specs):
        if a is None:
            continue
        shape = list(a.shape)
        for d, entry in enumerate(tuple(spec)):
            if entry is None:
                continue
            names = (entry,) if isinstance(entry, str) else tuple(entry)
            for name in names:
                shape[d] //= int(mesh.shape[name])
        out.append(jax.ShapeDtypeStruct(tuple(shape), a.dtype))
    return tuple(out)


def sharded_call(op: str, mesh, *args, impl: str | None = None, **kwargs):
    """Run ``op`` sharded over ``mesh`` through whichever registered impl is
    selected, falling back to a plain (replicated) ``kernel_call`` when no
    plan applies.

    Args: ``op`` — registry op name; ``mesh`` — a real ``jax.sharding.Mesh``
    (a MeshSpec resolves plans but cannot execute them); ``*args`` — the
    positional operands (``None`` holes allowed, e.g. linear_attention's
    optional u/s0); ``impl``/``**kwargs`` — forwarded to the registry
    dispatch. Returns exactly what the unsharded op returns.

    This is the single seam ops.py routes mesh-aware calls through — no
    per-call spec plumbing anywhere else.
    """
    impl = registry.resolve_impl(impl, op)
    plan = plan_for(op, mesh, *args, impl=impl, **kwargs)
    if plan is None:
        return registry.kernel_call(
            op, *args, impl=impl, **strip_plan_kwargs(kwargs)
        )
    if not isinstance(mesh, Mesh):
        raise TypeError(
            f"executing a partition plan for {op!r} needs a device mesh; "
            f"got {type(mesh).__name__} (MeshSpec is for plan_for/costing only)"
        )
    if plan.pre is not None:  # global rewrite (zigzag gather) before sharding
        args = plan.pre(*args)
    live = [i for i, a in enumerate(args) if a is not None]
    in_specs = tuple(plan.in_specs[i] for i in live)

    def wrapped(*live_args):
        full = list(args)
        for i, v in zip(live, live_args):
            full[i] = v
        return plan.local_fn(*full)

    fn = jax.shard_map(
        wrapped, mesh=mesh, in_specs=in_specs, out_specs=plan.out_specs,
        check_vma=False,
    )
    out = fn(*(args[i] for i in live))
    return plan.post(out) if plan.post is not None else out


def _nbytes(shape, dtype) -> int:
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def _per_level_psum_costs(levels, shape, dtype) -> tuple:
    """One all_reduce CollectiveCost per level, innermost (intra-pod) first —
    the firing order of ``hierarchical_psum``."""
    return tuple(
        CollectiveCost("all_reduce", axis, _nbytes(shape, dtype), n)
        for axis, n in reversed(tuple(levels))
    )


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------


@register_partition_rule("gemm")
def _gemm_rule(levels, a, b, *, impl=None, out_dtype=None,
               accum_dtype=jnp.float32, precision=None, **blocks):
    """K-sharded GEMM with a hierarchical psum epilogue (the paper's split-K
    over the chiplet axis; on a multi-pod mesh the intra-pod psum runs
    before the cross-pod psum so the D2D link moves one buffer per pod);
    M-row sharding when K resists; the level ladder handles the rest.

    Each shard quantizes its own K-slab under a ``precision`` policy, so
    the per-block scales compose with the sharding by construction (no
    scale arrays cross the shard_map boundary). Sub-fp32 policies also
    narrow the psum payload to bf16 — the ``optim/compression.py``
    error-feedback reduction dtype — halving the D2D bytes the collective
    moves (the intra-shard accumulate stays fp32; only the cross-device
    partial rides narrow)."""
    from repro.core import precision as _prec

    precision = _prec.resolve(precision)
    M, K = a.shape
    N = b.shape[1]
    out_dtype = out_dtype or (
        jnp.float32 if precision is not None else a.dtype
    )
    n = _ntot(levels)
    ax = _joint(levels)
    pk = {} if precision is None else {"precision": precision}
    reduce_dtype = accum_dtype
    if (precision is not None
            and jnp.dtype(precision.compute_dtype).itemsize < 4):
        reduce_dtype = jnp.bfloat16

    if K % n == 0:
        def local(a_l, b_l):
            part = registry.kernel_call(
                "gemm", a_l, b_l, out_dtype=reduce_dtype,
                accum_dtype=accum_dtype, impl=impl, **pk, **blocks,
            )
            return hierarchical_psum(part, levels).astype(out_dtype)

        return PartitionPlan(
            op="gemm", levels=tuple(levels),
            in_specs=(P(None, ax), P(ax, None)),
            out_specs=P(None, None),
            local_fn=local,
            collectives=_per_level_psum_costs(levels, (M, N), reduce_dtype),
            note=f"k-sharded ({K}/{n} per device over {_levels_note(levels)})"
                 ", psum epilogue"
                 + (f", {jnp.dtype(reduce_dtype).name} reduce"
                    if reduce_dtype != accum_dtype else ""),
        )

    if M % n == 0:
        def local(a_l, b_l):
            return registry.kernel_call(
                "gemm", a_l, b_l, out_dtype=out_dtype,
                accum_dtype=accum_dtype, impl=impl, **pk, **blocks,
            )

        return PartitionPlan(
            op="gemm", levels=tuple(levels),
            in_specs=(P(ax, None), P(None, None)),
            out_specs=P(ax, None),
            local_fn=local,
            note=f"m-row-sharded ({M}/{n} per device over "
                 f"{_levels_note(levels)})",
        )
    return None


def _attn_levels_split(levels, batch: int):
    """Split the attention level stack into its parts.

    Returns ``(head_levels, data_level, batch_ok)``: the non-``data``
    levels (the GQA head-sharding stack), the ``("data", n)`` level if
    offered (else None), and whether ``batch`` divides it (B-over-``data``
    composition is legal).
    """
    heads = tuple(lv for lv in levels if lv[0] != "data")
    data = next((lv for lv in levels if lv[0] == "data"), None)
    batch_ok = data is not None and batch % data[1] == 0
    return heads, data, batch_ok


def _attn_used(levels, head_ok: bool, data_used: bool):
    """The subset of ``levels`` a composed attention plan actually shards
    over, preserving mesh (outer→inner) order."""
    return tuple(
        lv for lv in levels
        if (lv[0] == "data" and data_used) or (lv[0] != "data" and head_ok)
    )


def _attn_head_ok(heads, count: int):
    """GQA head divisibility at this rung, or ``None`` to decline it.

    ``count`` heads must divide the whole head stack for the split to
    engage. When they don't but the stack can still shrink (two head
    levels offered), the rule DECLINES the rung instead of settling for a
    data-only plan — the ladder then drops the outermost level and the
    retry may recover an intra-pod head split (e.g. 4 kv heads on
    pod=2 × model=4 head-shard 4-way after the pod level drops). Only a
    minimal (single-level) head stack that still fails degrades to the
    data-only composition.
    """
    ok = bool(heads) and count % _ntot(heads) == 0
    if not ok and len(heads) > 1:
        return None
    return ok


@register_partition_rule("flash_attention", levels=attention_levels)
def _flash_rule(levels, q, k, v, *, impl=None, causal=True, window=0,
                q_offset=0, scale=None, precision=None, return_lse=False,
                overlap=True, zigzag=True, remote_copy=False, **blocks):
    """The attention family's composed rule: GQA head sharding × a ``data``
    level carrying either the batch or the sequence.

    Heads: q heads AND kv heads split together over the non-``data``
    levels (pods first, then the chiplet axis) so every device keeps whole
    (kv-head × group) blocks; TP-hostile counts drop the head split.

    Data level, in preference order:

    - **batch**: ``B % data == 0`` → B-sharding, collective-free;
    - **sequence-parallel KV ring**: the long-context form (B too small to
      split, ``Sq == Sk`` divisible by ``data``). Each device keeps its Q
      chunk resident and the K/V chunks rotate through an (n−1)-hop
      ``ppermute`` ring (``collectives.ring_scan``, double-buffered when
      ``overlap`` so each hop's D2D flight hides behind the hop kernel);
      every hop re-enters the registered kernel and the per-hop partials
      fold through the (m, l, acc)-equivalent ``online_softmax_merge``.

      The unbounded-causal ring additionally stripes Q ownership
      **zigzag** (``zigzag``, default on; see
      ``flash_attention.zigzag_indices``): rank ``r`` owns half-chunks
      ``r`` and ``2d-1-r``, gathered/ungathered globally by the plan's
      ``pre``/``post``. Hop 0 is ONE plain causal kernel call on the
      concatenated local block (order-isomorphic to its global rows); hop
      ``t>0`` is exactly two fully-unmasked ``causal=False`` sub-calls —
      every omitted (q-half × kv-half) pair is provably fully masked — so
      every rank does identical 2·(Sq/2d)² score work per hop and the
      wrapped-hop no-ops of the naive causal ring disappear.

      The legacy (contiguous-chunk) ring remains for windowed/non-causal/
      zigzag-indivisible cases: each hop runs at its static ``q_offset``
      so the mask lands on absolute positions, wrapped hops merge as
      no-ops, and a lookback window prunes whole tail hops statically.
      The ring declines bounded masks at nonzero ``q_offset`` (the wrap
      would alias past positions).

    If neither composition applies at this rung the ladder drops the
    outermost level and retries; ``None`` only once every level is gone.
    """
    from repro.kernels.flash_attention import zigzag_indices, zigzag_inverse
    from repro.parallel.collectives import (
        NEG_LSE, online_softmax_merge, ring_scan,
    )

    B, H, Sq, _ = q.shape
    K, Sk = k.shape[1], k.shape[2]
    # precision quantizes per shard (and per ring hop) inside the impls:
    # each device scales its own rows over D, so no scale arrays ever
    # cross the shard_map boundary and the composition is automatic
    pk = {} if precision is None else {"precision": precision}
    heads, data, batch_ok = _attn_levels_split(levels, B)
    head_ok = _attn_head_ok(heads, K)
    if head_ok is None:
        return None  # decline: a shorter head stack may still divide
    bounded = bool(causal or window)
    ring_ok = (
        data is not None and not batch_ok
        and Sq == Sk and Sq % data[1] == 0
        and not (bounded and q_offset != 0)
    )
    if not head_ok and not batch_ok and not ring_ok:
        return None
    ax = _joint(heads) if head_ok else None
    used = _attn_used(levels, head_ok, batch_ok or ring_ok)
    notes = []
    if head_ok:
        notes.append(
            f"head-sharded ({K}/{_ntot(heads)} kv heads over "
            f"{_levels_note(heads)})"
        )

    if batch_ok or not ring_ok:
        dt = "data" if batch_ok else None
        h4 = P(dt, ax, None, None)

        def local(q_l, k_l, v_l):
            return registry.kernel_call(
                "flash_attention", q_l, k_l, v_l, causal=causal,
                window=window, q_offset=q_offset, scale=scale,
                return_lse=return_lse, impl=impl, **pk, **blocks,
            )

        if batch_ok:
            notes.append(f"batch-sharded (B={B}/{data[1]} over data)")
        return PartitionPlan(
            op="flash_attention", levels=used,
            in_specs=(h4, h4, h4),
            out_specs=(h4, P(dt, ax, None)) if return_lse else h4,
            local_fn=local,
            note=" + ".join(notes),
        )

    # sequence-parallel ring: Sq/Sk over `data`, KV rotating
    d = data[1]
    c = Sq // d  # per-device chunk length (static)
    hops = d
    if window:
        # hop t's nearest k sits c*t - (c-1) behind the earliest q; hops
        # entirely beyond every row's lookback are pruned statically
        hops = min(d, max(1, -(-(window + c - 1) // c)))
    zig = bool(
        zigzag and causal and not window and q_offset == 0
        and Sq % (2 * d) == 0
    )

    if zig:
        c2 = Sq // (2 * d)  # half-chunk length: rank r owns chunks r, 2d-1-r

        def local(q_l, k_l, v_l):
            me = jax.lax.axis_index("data")
            o0 = jnp.zeros(q_l.shape, jnp.float32)
            lse0 = jnp.full(q_l.shape[:-1], NEG_LSE, jnp.float32)

            def step(carry, kv, t):
                o, lse = carry
                k_b, v_b = kv
                if t == 0:
                    # resident hop: the local block is order-isomorphic to
                    # its global rows, so a plain causal call IS the global
                    # causal mask restricted to them
                    o_t, lse_t = registry.kernel_call(
                        "flash_attention", q_l, k_b, v_b, causal=True,
                        window=0, q_offset=0, scale=scale,
                        return_lse=True, impl=impl, **pk, **blocks,
                    )
                    return online_softmax_merge(o, lse, o_t, lse_t)
                # hop t>0: the resident KV left rank s = me - t (mod d).
                # Of the four (q-half × kv-half) pairs, q_tail × k_head is
                # always fully valid; up-ranks (me >= t, s < me) also get
                # q_head × k_head, down-ranks (wrapped, s > me) also get
                # q_tail × k_tail — every pair fully valid, every omitted
                # pair fully masked, so both sub-calls run unmasked
                # (causal=False) and each rank does the same 2·c2² work.
                up = me >= t
                q_head, q_tail = q_l[:, :, :c2], q_l[:, :, c2:]
                k_head, v_head = k_b[:, :, :c2], v_b[:, :, :c2]
                k_tail, v_tail = k_b[:, :, c2:], v_b[:, :, c2:]
                o_full, lse_full = registry.kernel_call(
                    "flash_attention", q_tail, k_head, v_head,
                    causal=False, window=0, q_offset=0, scale=scale,
                    return_lse=True, impl=impl, **pk, **blocks,
                )
                o_sel, lse_sel = registry.kernel_call(
                    "flash_attention",
                    jnp.where(up, q_head, q_tail),
                    jnp.where(up, k_head, k_tail),
                    jnp.where(up, v_head, v_tail),
                    causal=False, window=0, q_offset=0, scale=scale,
                    return_lse=True, impl=impl, **pk, **blocks,
                )
                # head rows: up-ranks take the sel partial, down-ranks none
                o_h = jnp.where(up, o_sel.astype(jnp.float32), 0.0)
                lse_h = jnp.where(up, lse_sel, NEG_LSE)
                # tail rows: the always-valid full partial, plus (down
                # ranks only) the sel partial over k_tail
                o_m, lse_m = online_softmax_merge(
                    o_full.astype(jnp.float32), lse_full,
                    jnp.where(up, 0.0, o_sel.astype(jnp.float32)),
                    jnp.where(up, NEG_LSE, lse_sel),
                )
                o_t = jnp.concatenate([o_h, o_m], axis=2)
                lse_t = jnp.concatenate([lse_h, lse_m], axis=2)
                return online_softmax_merge(o, lse, o_t, lse_t)

            o, lse = ring_scan(
                step, (o0, lse0), (k_l, v_l), "data", d,
                hops=d, overlap=overlap, remote_copy=remote_copy,
            )
            o = o.astype(q_l.dtype)
            return (o, lse) if return_lse else o

        idx, inv = zigzag_indices(Sq, d), zigzag_inverse(Sq, d)

        def pre(q_g, k_g, v_g):
            return tuple(jnp.take(x, idx, axis=2) for x in (q_g, k_g, v_g))

        def post(out):
            if return_lse:
                o_g, lse_g = out
                return jnp.take(o_g, inv, axis=2), jnp.take(lse_g, inv, axis=2)
            return jnp.take(out, inv, axis=2)

    else:
        pre = post = None

        def local(q_l, k_l, v_l):
            me = jax.lax.axis_index("data")
            o0 = jnp.zeros(q_l.shape, jnp.float32)
            lse0 = jnp.full(q_l.shape[:-1], NEG_LSE, jnp.float32)

            def step(carry, kv, t):
                o, lse = carry
                k_b, v_b = kv
                o_t, lse_t = registry.kernel_call(
                    "flash_attention", q_l, k_b, v_b, causal=causal,
                    window=window, q_offset=q_offset + t * c, scale=scale,
                    return_lse=True, impl=impl, **pk, **blocks,
                )
                if bounded and t:
                    # ranks me < t hold a wrapped (future) KV chunk this
                    # hop: causal/window semantics mask it entirely, so
                    # the partial merges as a no-op
                    valid = me >= t
                    lse_t = jnp.where(valid, lse_t, NEG_LSE)
                    o_t = jnp.where(valid, o_t.astype(jnp.float32), 0.0)
                return online_softmax_merge(o, lse, o_t, lse_t)

            o, lse = ring_scan(
                step, (o0, lse0), (k_l, v_l), "data", d,
                hops=hops, overlap=overlap, remote_copy=remote_copy,
            )
            o = o.astype(q_l.dtype)
            return (o, lse) if return_lse else o

    h4 = P(None, ax, "data", None)
    kv_local_bytes = _nbytes(
        (B, (K // _ntot(heads)) if head_ok else K, Sk // d, k.shape[-1]),
        k.dtype,
    )
    notes.append(
        f"ring seq-parallel{' zigzag' if zig else ''} "
        f"(Sq={Sq}/{d} per device over data={d}, {hops - 1} kv hops)"
    )
    return PartitionPlan(
        op="flash_attention", levels=used,
        in_specs=(h4, h4, h4),
        out_specs=(h4, P(None, ax, "data")) if return_lse else h4,
        local_fn=local,
        collectives=tuple(
            CollectiveCost("permute", "data", kv_local_bytes, d)
            for _ in range(2 * (hops - 1))  # k and v, per hop
        ),
        note=" + ".join(notes),
        overlappable=bool(overlap and hops > 1),
        hops=hops,
        pre=pre,
        post=post,
    )


@register_partition_rule("decode_attention", levels=attention_levels)
def _decode_rule(levels, q, k, v, position, *, impl=None, **kwargs):
    """Same composed GQA head × batch rule as flash_attention: heads over
    the non-``data`` levels, B (queries AND their cache/position rows) over
    ``data`` when it divides. No sequence ring — decode is one query token
    against a resident cache."""
    if kwargs.get("block_table") is not None:
        # paged pools carry no batch dim and shard by cache pages, not by
        # B/heads — the serving layer's ring_decode owns that distribution
        return None
    B, K = q.shape[0], k.shape[1]
    heads, data, batch_ok = _attn_levels_split(levels, B)
    head_ok = _attn_head_ok(heads, K)
    if head_ok is None:
        return None  # decline: a shorter head stack may still divide
    if not head_ok and not batch_ok:
        return None
    ax = _joint(heads) if head_ok else None
    dt = "data" if batch_ok else None

    def local(q_l, k_l, v_l, pos_l):
        return registry.kernel_call(
            "decode_attention", q_l, k_l, v_l, pos_l, impl=impl, **kwargs
        )

    notes = []
    if head_ok:
        notes.append(f"head-sharded ({K}/{_ntot(heads)} kv heads over "
                     f"{_levels_note(heads)})")
    if batch_ok:
        notes.append(f"batch-sharded (B={B}/{data[1]} over data)")
    return PartitionPlan(
        op="decode_attention", levels=_attn_used(levels, head_ok, batch_ok),
        in_specs=(P(dt, ax, None), P(dt, ax, None, None),
                  P(dt, ax, None, None), P(dt)),
        out_specs=P(dt, ax, None),
        local_fn=local,
        note=" + ".join(notes),
    )


@register_partition_rule("linear_attention", levels=attention_levels)
def _linear_attention_rule(levels, r, k, v, w_log, u=None, s0=None, *,
                           impl=None, **kwargs):
    """Head-sharded chunked state scan composed with B over ``data``: every
    stream (r/k/v/decay, the carried state) splits on H across the
    non-``data`` levels and on B across ``data``; the u bonus is per-head
    only. The recurrence stays embarrassingly parallel across devices: no
    collective epilogue at all."""
    B, H = r.shape[0], r.shape[1]
    heads, data, batch_ok = _attn_levels_split(levels, B)
    head_ok = _attn_head_ok(heads, H)
    if head_ok is None:
        return None  # decline: a shorter head stack may still divide
    if not head_ok and not batch_ok:
        return None
    ax = _joint(heads) if head_ok else None
    dt = "data" if batch_ok else None

    def local(r_l, k_l, v_l, w_l, u_l, s0_l):
        return registry.kernel_call(
            "linear_attention", r_l, k_l, v_l, w_l, u_l, s0_l,
            impl=impl, **kwargs,
        )

    h4 = P(dt, ax, None, None)
    notes = []
    if head_ok:
        notes.append(f"head-sharded ({H}/{_ntot(heads)} heads over "
                     f"{_levels_note(heads)})")
    if batch_ok:
        notes.append(f"batch-sharded (B={B}/{data[1]} over data)")
    return PartitionPlan(
        op="linear_attention", levels=_attn_used(levels, head_ok, batch_ok),
        in_specs=(h4, h4, h4, h4, P(ax, None), h4),
        out_specs=(h4, h4),
        local_fn=local,
        note=" + ".join(notes),
    )


@register_partition_rule("spmm")
def _spmm_rule(levels, values, cols, dense, *, impl=None, **kwargs):
    """Row-sharded ELL: rows split across pods, then across the chiplet axis
    within each pod; each device streams its own value/index rows against
    the replicated dense operand — the chiplet-local SU indirection."""
    R = values.shape[0]
    n = _ntot(levels)
    if R % n != 0:
        return None
    ax = _joint(levels)

    def local(v_l, c_l, d_l):
        return registry.kernel_call("spmm", v_l, c_l, d_l, impl=impl, **kwargs)

    return PartitionPlan(
        op="spmm", levels=tuple(levels),
        in_specs=(P(ax, None), P(ax, None), P(None, None)),
        out_specs=P(ax, None),
        local_fn=local,
        note=f"row-sharded ({R}/{n} ELL rows per device over "
             f"{_levels_note(levels)})",
    )


@register_partition_rule("bsr_spmm")
def _bsr_rule(levels, tile_values, tile_rows, tile_cols, dense, *,
              num_rows, impl=None, **kwargs):
    """Tile-sharded BSR (nnz-parallel): devices own disjoint tile subsets,
    each scatter-accumulates a full-height partial, and a hierarchical psum
    stitches the rows back — intra-pod first, so the D2D crossing moves one
    reduced partial per pod."""
    T = tile_values.shape[0]
    n = _ntot(levels)
    if T % n != 0 or T == 0:
        return None
    F = dense.shape[1]
    bm_tile = tile_values.shape[1]
    ax = _joint(levels)

    def local(tv_l, tr_l, tc_l, d_l):
        part = registry.kernel_call(
            "bsr_spmm", tv_l, tr_l, tc_l, d_l, num_rows=num_rows,
            impl=impl, **kwargs,
        )
        # the stream kernel only initialises output blocks whose row id
        # appears in ITS tile subset; rows all of whose tiles live on other
        # devices stay uninitialised locally, so mask them before the psum
        present = jnp.zeros((num_rows // bm_tile,), bool).at[tr_l].set(True)
        row_mask = jnp.repeat(present, bm_tile)[:, None]
        return hierarchical_psum(jnp.where(row_mask, part, 0.0), levels)

    return PartitionPlan(
        op="bsr_spmm", levels=tuple(levels),
        in_specs=(P(ax, None, None), P(ax), P(ax), P(None, None)),
        out_specs=P(None, None),
        local_fn=local,
        collectives=_per_level_psum_costs(levels, (num_rows, F), jnp.float32),
        note=f"tile-sharded ({T}/{n} nnz tiles per device over "
             f"{_levels_note(levels)}), psum epilogue",
    )


@register_partition_rule("spmspm")
def _spmspm_rule(levels, a_values, a_cols, b_values, b_rows, *,
                 contraction_dim, impl=None, **kwargs):
    """A-row-sharded sparse×sparse: A's rows split across pods then within,
    B replicated; each device intersects its own rows independently."""
    R = a_values.shape[0]
    n = _ntot(levels)
    if R % n != 0:
        return None
    ax = _joint(levels)

    def local(av_l, ac_l, bv_l, br_l):
        return registry.kernel_call(
            "spmspm", av_l, ac_l, bv_l, br_l,
            contraction_dim=contraction_dim, impl=impl, **kwargs,
        )

    return PartitionPlan(
        op="spmspm", levels=tuple(levels),
        in_specs=(P(ax, None), P(ax, None), P(None, None), P(None, None)),
        out_specs=P(ax, None),
        local_fn=local,
        note=f"a-row-sharded ({R}/{n} rows per device over "
             f"{_levels_note(levels)})",
    )


def _halo_block(width: int, cap: int, halo: int) -> int:
    """Largest block <= cap that divides the padded local extent and still
    covers the halo reach (the pallas kernel requires max|dx| <= bx)."""
    for d in range(min(cap, width), 0, -1):
        if width % d == 0 and d >= halo:
            return d
    return width


@register_partition_rule("stencil")
def _stencil_rule(levels, grid, *, offsets, weights, impl=None, bx=None,
                  overlap=True, **kwargs):
    """X-sharded grid with ppermute halo exchange (the SARIS boundary planes).

    Each device pads its slab with ``h`` neighbour planes per side — the
    ring wrap IS the periodic boundary — then runs the registered impl on
    the padded slab; offsets never reach past the halo, so the impl's own
    periodic wrap never engages inside the slab.

    With ``overlap`` (default, when the slab is deep enough: ``lx >= 2h``)
    the exchange is double-buffered: both halo ppermutes are issued first,
    the interior rows — which never reach the halo — are computed directly
    on the unpadded slab while the planes fly, and only the two ``h``-row
    boundary strips wait on the transfers. Row-for-row the same values in
    the same accumulation order as the synchronous path (bit-identical);
    only the issue order differs. ``overlap=False`` keeps the synchronous
    pad-then-kernel schedule as the correctness oracle.

    On a two-level mesh the slab order is pod-major: most neighbours sit on
    the same pod, so the exchange is an intra-pod ``ppermute`` ring over the
    chiplet axis, plus ONE cross-pod boundary hop per direction — an extra
    ``ppermute`` over the pod axis whose payload replaces the intra-pod
    wrap value exactly at the pod-edge devices (its own ring wrap carries
    the global periodic boundary across the D2D link).
    """
    import numpy as np

    X, Y, Z = grid.shape
    offs = np.asarray(offsets)
    h = int(np.abs(offs[:, 0]).max(initial=0))
    n = _ntot(levels)
    if X % n != 0:
        return None
    lx = X // n
    if h > lx:
        return None  # halo wider than a slab: drop a level rather than multi-hop
    padded_x = lx + 2 * h
    bx_cap = registry.resolve_blocks("stencil", bx=bx)["bx"]
    bx_local = _halo_block(padded_x, bx_cap, max(h, 1))
    ax = _joint(levels)
    inner_axis, tp = levels[-1]
    outer = levels[:-1]  # () or the single ("pod", P) level above
    fwd = [(i, (i + 1) % tp) for i in range(tp)]
    bwd = [(i, (i - 1) % tp) for i in range(tp)]
    if outer:
        (pod_axis, pods), = outer
        pod_fwd = [(i, (i + 1) % pods) for i in range(pods)]
        pod_bwd = [(i, (i - 1) % pods) for i in range(pods)]
    overlapped = bool(overlap and h and lx >= 2 * h)

    def exchange(g_l):
        lo = jax.lax.ppermute(g_l[-h:], inner_axis, fwd)  # left tail
        hi = jax.lax.ppermute(g_l[:h], inner_axis, bwd)  # right head
        if outer:
            # pod-edge devices got the intra-pod wrap; what they need is
            # the neighbouring pod's boundary slab, one D2D hop away
            m = jax.lax.axis_index(inner_axis)
            lo = jnp.where(m == 0,
                           jax.lax.ppermute(lo, pod_axis, pod_fwd), lo)
            hi = jnp.where(m == tp - 1,
                           jax.lax.ppermute(hi, pod_axis, pod_bwd), hi)
        return lo, hi

    if overlapped:
        bx_int = _halo_block(lx, bx_cap, max(h, 1))
        bx_strip = _halo_block(3 * h, bx_cap, max(h, 1))

        def local(g_l):
            # issue both halo transfers, then compute the interior while
            # they fly: rows [h, lx-h) reach at most the slab edges, so
            # the unpadded kernel's periodic wrap never touches them (the
            # wrap-polluted edge rows are discarded and recomputed below)
            lo, hi = exchange(g_l)
            interior = registry.kernel_call(
                "stencil", g_l, offsets, weights, impl=impl, bx=bx_int,
                **kwargs,
            )[h:lx - h]
            # boundary strips: h output rows each, padded to 3h input rows
            # so every stencil reach stays inside the strip
            top = registry.kernel_call(
                "stencil", jnp.concatenate([lo, g_l[:2 * h]], axis=0),
                offsets, weights, impl=impl, bx=bx_strip, **kwargs,
            )[h:2 * h]
            bottom = registry.kernel_call(
                "stencil", jnp.concatenate([g_l[-2 * h:], hi], axis=0),
                offsets, weights, impl=impl, bx=bx_strip, **kwargs,
            )[h:2 * h]
            return jnp.concatenate([top, interior, bottom], axis=0)

    else:
        def local(g_l):
            if h:
                lo, hi = exchange(g_l)
                padded = jnp.concatenate([lo, g_l, hi], axis=0)
            else:
                padded = g_l
            out = registry.kernel_call(
                "stencil", padded, offsets, weights, impl=impl, bx=bx_local,
                **kwargs,
            )
            return out[h:h + lx] if h else out

    halo_bytes = _nbytes((h, Y, Z), grid.dtype)
    colls = []
    if h:
        colls += [CollectiveCost("permute", inner_axis, halo_bytes, tp)] * 2
        if outer:
            colls += [CollectiveCost("permute", pod_axis, halo_bytes, pods)] * 2
    return PartitionPlan(
        op="stencil", levels=tuple(levels),
        in_specs=(P(ax, None, None),),
        out_specs=P(ax, None, None),
        local_fn=local,
        collectives=tuple(colls),
        note=f"x-sharded ({lx} planes per device over {_levels_note(levels)})"
             f", halo h={h} via ppermute"
             + (" + pod boundary hop" if h and outer else "")
             + (" (overlapped)" if overlapped else ""),
        overlappable=overlapped,
        hops=2 if overlapped else 0,
    )
