"""Explicit collective patterns: expert-parallel all-to-all MoE, the
hierarchical psum, and the ppermute ring primitives behind sequence
parallelism (paper C5's D2D traffic patterns as jax.lax collectives under
shard_map).

The default MoE keeps all experts TP-sharded on d_ff (weights resident
everywhere); this module provides the EP alternative — experts partitioned
across the `model` axis with token all-to-alls — used in the §Perf hillclimb
where it trades weight all-gathers for activation exchange.

The ring family (``ring_scan``, ``ring_scan_carry``,
``online_softmax_merge``) is the latency-tolerant tile-rotation pattern the
paper's C4/C5 interconnect overlaps with compute: a resident operand stays
put while its partner shard hops rank→rank over ``ppermute``, (n−1) hops
total — ring flash attention (``kernels/partition.py``) and the
sequence-parallel linear-recurrence carry are both built on it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.diagnostics import warn_degrade

# matches the flash kernels' masked-score floor: fully-masked softmax rows
# carry lse ~= NEG, which the online merge weights to exp(NEG - NEG) ~ 1
# against a zero accumulator instead of producing -inf - -inf NaNs
NEG_LSE = -1e30


def hierarchical_psum(x, levels):
    """Reduce ``x`` across a hierarchy of mesh axes, innermost level first.

    ``levels`` is an outer→inner tuple of ``(axis_name, size)`` pairs (the
    ``PartitionPlan.levels`` vocabulary from ``kernels/partition.py``): for
    Occamy's two-level pod×model plans this fires the intra-pod (chiplet
    crossbar) psum before the cross-pod (D2D link) psum, so the narrow D2D
    hop carries one already-reduced buffer per pod instead of one per device
    — the hierarchical all-reduce the paper's Fig. 13 scaling relies on.

    Args: ``x`` — the per-device partial (any array); ``levels`` — the
    ``((axis, n), ...)`` hierarchy, outermost first. Size-1 levels are
    skipped. Returns the fully reduced array, replicated across every level's
    axis. Must run inside a ``shard_map`` whose mesh names all the axes.
    """
    for axis, n in reversed(tuple(levels)):
        if n > 1:
            x = jax.lax.psum(x, axis)
    return x


def ep_expert_ffn(disp, wi, wg, wo, act, mesh, dp, *, ep_axis="model"):
    """Expert-parallel FFN on capacity-dispatched tokens.

    disp: (B, E, C, d) batch-sharded over dp, replicated over ep_axis.
    weights: (E, d, f) etc. with E sharded over ep_axis (requires E %
    mesh[ep_axis] == 0, checked by the caller).
    Inside shard_map: all_to_all swaps the (E, local-batch) layout so each
    ep-rank holds ALL batch rows for ITS experts, runs the dense FFN, and
    all_to_alls back — two activation exchanges instead of streaming every
    expert's weights through every rank.
    """
    ep = mesh.shape[ep_axis]

    def local(disp_l, wi_l, wg_l, wo_l):
        # disp_l: (b, E, C, d) with b = B/|dp|; E global here, experts local
        b, E, C, d = disp_l.shape
        e_loc = wi_l.shape[0]  # E / ep
        # regroup (b, E, C, d) -> (ep, b, e_loc, C, d) and exchange over ep
        x = disp_l.reshape(b, ep, e_loc, C, d).transpose(1, 0, 2, 3, 4)
        x = jax.lax.all_to_all(x, ep_axis, split_axis=0, concat_axis=1,
                               tiled=False)
        # x: (ep*b, e_loc, C, d) — every rank now owns its experts' tokens
        h = jnp.einsum("becd,edf->becf",
                       x.reshape(ep * b, e_loc, C, d), wi_l,
                       preferred_element_type=jnp.float32)
        if wg_l is not None:
            g = jnp.einsum("becd,edf->becf", x.reshape(ep * b, e_loc, C, d),
                           wg_l, preferred_element_type=jnp.float32)
            h = act(g) * h
        h = h.astype(disp_l.dtype)
        y = jnp.einsum("becf,efd->becd", h, wo_l,
                       preferred_element_type=jnp.float32).astype(disp_l.dtype)
        # exchange back: (ep*b, e_loc, C, d) -> (b, E, C, d)
        y = y.reshape(ep, b, e_loc, C, d)
        y = jax.lax.all_to_all(y, ep_axis, split_axis=0, concat_axis=0,
                               tiled=False)
        return y.reshape(ep, b, e_loc, C, d).transpose(1, 0, 2, 3, 4).reshape(
            b, E, C, d
        )

    has_gate = wg is not None
    if has_gate:
        return jax.shard_map(
            lambda d_, wi_, wg_, wo_: local(d_, wi_, wg_, wo_),
            mesh=mesh,
            in_specs=(P(dp, None, None, None), P(ep_axis, None, None),
                      P(ep_axis, None, None), P(ep_axis, None, None)),
            out_specs=P(dp, None, None, None),
            check_vma=False,
        )(disp, wi, wg, wo)
    return jax.shard_map(
        lambda d_, wi_, wo_: local(d_, wi_, None, wo_),
        mesh=mesh,
        in_specs=(P(dp, None, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None)),
        out_specs=P(dp, None, None, None),
        check_vma=False,
    )(disp, wi, wo)


def _ring_fwd(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def _hop_send(axis: str, n: int, remote_copy: bool):
    """One ring hop as a leaf function: ``ppermute`` by default; the pallas
    async-remote-copy fast path (``core.streams.remote_ring_hop``, the RDMA
    engine the SU double-buffer hands its D2D hops to) when ``remote_copy``
    is set AND the backend is a real TPU. Anywhere else the request falls
    back to ``ppermute`` — the inter-chip DMA engine simply does not exist
    on host/GPU backends, and the two paths move identical bytes — with a
    one-shot ``ReproDegradeWarning`` so the degraded overlap is visible.
    """
    if remote_copy:
        if jax.default_backend() == "tpu":
            from repro.core.streams import remote_ring_hop

            return lambda x: remote_ring_hop(x, axis, n)
        warn_degrade(
            f"remote_copy=True requested on backend "
            f"{jax.default_backend()!r}: no inter-chip DMA engine here, "
            f"falling back to ppermute (identical bytes; the hop overlaps "
            f"via XLA collective-permute scheduling instead of the SU "
            f"double-buffer DMA)",
            key=("remote_copy_fallback", jax.default_backend()),
        )
    perm = _ring_fwd(n)
    return lambda x: jax.lax.ppermute(x, axis, perm)


@dataclasses.dataclass(frozen=True)
class HopEvent:
    """One event of a ring hop schedule, in issue order.

    Fields: ``kind`` — ``"send"`` (issue hop ``hop``'s transfer),
    ``"dma_start"`` / ``"dma_wait"`` (the remote-copy form of a send: the
    async DMA issue and its receive-semaphore wait), or ``"fold"`` (consume
    hop ``hop``'s resident block into the carry); ``hop`` — the hop index
    the event serves (``fold`` at hop t reads the block that has travelled
    t ranks); ``src`` — the buffer id the event reads (the resident block
    for sends and folds); ``dst`` — the buffer id a transfer lands in
    (None for folds; ``dma_wait`` records the landing buffer it fences).
    """

    kind: str
    hop: int
    src: int | None = None
    dst: int | None = None


def ring_schedule(hops: int, *, overlap: bool = True,
                  remote_copy: bool = False) -> tuple:
    """The ring hop schedule as data: the exact event order ``ring_scan``
    executes, checkable without devices.

    Args: ``hops`` — fold count (``ring_scan``'s ``hops``); ``overlap`` —
    double-buffered order (hop t+1's transfer issued BEFORE hop t's fold)
    vs the synchronous oracle (transfer only after the fold); ``remote_copy``
    — expand each send into its DMA pair (``dma_start`` + ``dma_wait``, the
    ``remote_ring_hop`` semantics) so the analyzer can verify the semaphore
    wait is ordered before the consuming fold.

    Returns a tuple of ``HopEvent``. Blocks live in two alternating buffers
    (hop t resides in buffer ``t % 2``) — the double-buffer discipline that
    keeps hop t+1's landing buffer disjoint from the one hop t's fold still
    reads. ``repro.analysis``'s ``overlap-schedule`` rule replays this very
    schedule through its hazard checker; ``ring_scan`` drives its jax calls
    off it, so the checked artifact is the executed artifact.
    """
    events = []

    def send(t):
        src, dst = (t - 1) % 2, t % 2
        if remote_copy:
            events.append(HopEvent("dma_start", t, src, dst))
            events.append(HopEvent("dma_wait", t, None, dst))
        else:
            events.append(HopEvent("send", t, src, dst))

    for t in range(hops):
        if overlap and t + 1 < hops:
            send(t + 1)
        events.append(HopEvent("fold", t, t % 2))
        if not overlap and t + 1 < hops:
            send(t + 1)
    return tuple(events)


def ring_scan(step_fn, carry, block, axis: str, n: int, *,
              hops: int | None = None, overlap: bool = True,
              remote_copy: bool = False):
    """Rotate ``block`` through an n-rank ``ppermute`` ring, folding it into
    ``carry`` at every hop — the primitive under ring flash attention.

    Args: ``step_fn(carry, block, t) -> carry`` — called once per hop; at
    hop ``t`` the resident ``block`` is the one originally owned by rank
    ``(axis_index - t) % n``; ``carry`` — the running accumulator; ``block``
    — the rotating operand (any pytree; every leaf hops together); ``axis``
    — the mesh axis the ring lives on; ``n`` — the ring size (static);
    ``hops`` — stop after this many steps (default ``n``: every shard
    visits every rank; a lookback window lets ring attention prune the
    tail). The permutation always spans the full ``n``-rank ring
    regardless of ``hops``.

    ``overlap`` (default) double-buffers the ring: hop ``t+1``'s transfer
    is issued BEFORE hop ``t``'s fold, so the scheduler can fly the D2D
    hop behind ``step_fn``'s compute — the software form of the SU
    double-buffer the paper's C4/C5 interconnect overlaps with FPU work.
    ``overlap=False`` keeps the synchronous schedule (permute only after
    the fold) as the correctness oracle; both orders fold bit-identical
    values, only issue order differs. ``remote_copy`` opts the hop into
    the pallas async-remote-copy path on TPU backends (see ``_hop_send``).

    Fires exactly ``hops - 1`` ppermutes — the block is consumed in place
    on the final hop, never sent home. Must run inside a ``shard_map``
    naming ``axis``. Returns the folded carry.

    The issue order is not re-derived here: the jax calls replay
    ``ring_schedule(hops, overlap=...)`` event by event (sends depend only
    on the resident block, never on ``step_fn``'s result, so an
    overlap-ordered send lets the hop fly while the kernel/merge runs).
    ``remote_copy`` swaps the transport of each send (``_hop_send``), not
    the event order — ``remote_ring_hop`` fuses its DMA start/wait pair
    inside one kernel.
    """
    hops = n if hops is None else hops
    send = _hop_send(axis, n, remote_copy)
    buffers = {0: block}
    for ev in ring_schedule(hops, overlap=overlap):
        if ev.kind == "send":
            buffers[ev.dst] = jax.tree_util.tree_map(send, buffers[ev.src])
        else:  # fold
            carry = step_fn(carry, buffers[ev.src], ev.hop)
    return carry


def online_softmax_merge(o_acc, lse_acc, o, lse):
    """Merge one attention partial into a running online-softmax accumulator.

    Args: ``o_acc`` / ``lse_acc`` — the running (unnormalised-by-partner)
    output and log-sum-exp (init ``o_acc = 0``, ``lse_acc = NEG_LSE``);
    ``o`` / ``lse`` — a new partial: softmax-normalised output and its lse
    over the same query rows, as the kernels' ``return_lse=True`` path
    emits them (``lse`` has one fewer trailing dim than ``o``).

    Returns the merged ``(o, lse)``: each side is reweighted by
    ``exp(lse_side - lse_merged)``, the exact rescaling the flash kernels
    apply per KV block — so folding ring partials in any order reproduces
    the single-device softmax. Rows fully masked in BOTH sides stay 0 (the
    NEG_LSE floor keeps every weight finite).
    """
    lse_new = jnp.logaddexp(lse_acc, lse)
    w_acc = jnp.exp(lse_acc - lse_new)[..., None]
    w = jnp.exp(lse - lse_new)[..., None]
    return (
        o_acc.astype(jnp.float32) * w_acc + o.astype(jnp.float32) * w,
        lse_new,
    )


def ring_scan_carry(chunk_fn, xs_l, s0, axis: str, n: int, *,
                    overlap: bool = True):
    """Sequence-parallel linear-recurrence carry over a ppermute ring: rank
    ``r`` scans its local chunk with the TRUE carry produced by rank
    ``r - 1`` (the D2D-pipelined version of the SSM chunk scan).

    Args: ``chunk_fn(state, xs_local) -> (state_out, ys_local)`` — the
    per-chunk scan; ``xs_l`` — this rank's chunk; ``s0`` — the global
    initial state (only rank 0's is consumed); ``axis`` / ``n`` — the ring
    axis and its (static) size; ``overlap`` — issue hop ``t+1``'s permute
    the moment ``chunk_fn`` produces its state, BEFORE the keep-merges, so
    the hop flies while the where-folds run (the carry chain itself is
    inherently serial — permute -> chunk_fn -> permute — so unlike
    ``ring_scan`` only the merge arithmetic can hide the hop here);
    ``overlap=False`` keeps the synchronous oracle order.

    Runs inside ``shard_map``. The carry threads hop by hop: after hop
    ``t`` the state that left rank ``t`` arrives at rank ``t + 1``, which
    re-scans its chunk with it — so every rank's kept result is computed
    from the exact sequential prefix state, unlike the pre-fix version
    whose single ppermute delivered each rank only its LEFT neighbour's
    locally-seeded scan. SPMD cost is ``n`` chunk evaluations per rank
    (the recurrence is inherently a depth-``n`` pipeline; the extra
    evaluations are the dead pipeline slots).

    Returns ``(ys, s_out)``: this rank's output chunk and end state (rank
    ``n - 1``'s ``s_out`` is the global final state).
    """
    me = jax.lax.axis_index(axis)
    perm = _ring_fwd(n)
    s_new, ys = chunk_fn(s0, xs_l)
    s_keep = s_new  # correct on rank 0 after hop 0; later ranks fixed below
    s_in = jax.lax.ppermute(s_new, axis, perm) if n > 1 else None
    for t in range(1, n):
        s_new, ys_t = chunk_fn(s_in, xs_l)
        if overlap and t != n - 1:
            s_in = jax.lax.ppermute(s_new, axis, perm)
        keep = me == t
        ys = jax.tree_util.tree_map(
            lambda a, b: jnp.where(keep, b, a), ys, ys_t
        )
        s_keep = jax.tree_util.tree_map(
            lambda a, b: jnp.where(keep, b, a), s_keep, s_new
        )
        if not overlap and t != n - 1:
            s_in = jax.lax.ppermute(s_new, axis, perm)
    return ys, s_keep
