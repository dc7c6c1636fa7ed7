"""Streaming-unit programming model (paper C1/C2) as the kernel substrate.

Occamy's SUs map *streams* — ≤4D affine address sequences or index-driven
indirect sequences — onto FP register reads/writes, so the issue slots carry
only compute. The TPU translation: a stream is a (block_shape, index_map)
pair; the Pallas grid pipeline performs the address generation and the
double-buffered HBM->VMEM copies, and the kernel body carries only compute.

This module makes that correspondence explicit and first-class:

  AffineStream(block, loop)    ~ SU 4D affine stream descriptor (Fig. 4a)
  IndirectStream(block, idx)   ~ SU indirect stream (Fig. 4b): a scalar-
                                 prefetched index array drives the index_map
  StreamProgram(...)           ~ a full SU configuration: grid (the FREP loop
                                 nest), bound streams, and the compute body
  stream_compute(program, ...) ~ FREP + SU setup: executes the program with
                                 operands bound to its streams

Every production kernel (kernels/*.py) builds a StreamProgram and executes it
here — this is the only module that calls ``pl.pallas_call``, so backend
concerns (compiler params, scalar prefetch plumbing, interpret mode) live in
exactly one place.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dtype_bytes(dtype) -> int:
    # dtype is cost metadata only; streams without it are counted at the
    # 4-byte float32 default (see StreamProgram.traffic_bytes)
    return jnp.dtype(dtype or jnp.float32).itemsize


@dataclasses.dataclass(frozen=True)
class AffineStream:
    """≤4D affine stream: block_shape + an index_map over the grid ids.

    ``dtype`` is cost metadata (the element width the stream carries); it lets
    a StreamProgram report per-step traffic without running the kernel.
    """

    block_shape: tuple
    index_map: Callable  # (*grid_ids) -> block coords
    dtype: Any = None

    @property
    def block_elems(self) -> int:
        return math.prod(self.block_shape)

    @property
    def bytes_per_step(self) -> int:
        """HBM<->VMEM bytes one grid step of this stream moves."""
        return self.block_elems * _dtype_bytes(self.dtype)

    def spec(self, n_prefetch: int = 0) -> pl.BlockSpec:
        if n_prefetch == 0:
            return pl.BlockSpec(self.block_shape, self.index_map)
        # scalar-prefetch grids pass the prefetch refs after the grid ids;
        # an affine map never reads them, so truncate.
        fn = self.index_map
        return pl.BlockSpec(
            self.block_shape, lambda *a: fn(*a[: len(a) - n_prefetch])
        )


@dataclasses.dataclass(frozen=True)
class IndirectStream:
    """Index-driven stream: ``index_map`` may read the scalar-prefetched index
    arrays (passed as trailing args), Occamy's 8/16/32-bit index streams."""

    block_shape: tuple
    index_map: Callable  # (*grid_ids, *prefetch_refs) -> block coords
    dtype: Any = None

    @property
    def block_elems(self) -> int:
        return math.prod(self.block_shape)

    @property
    def bytes_per_step(self) -> int:
        return self.block_elems * _dtype_bytes(self.dtype)

    def spec(self, n_prefetch: int = 0) -> pl.BlockSpec:
        return pl.BlockSpec(self.block_shape, self.index_map)


Stream = AffineStream | IndirectStream


@dataclasses.dataclass(frozen=True)
class StreamProgram:
    """A complete SU configuration: the FREP loop nest (grid), the streams
    feeding/draining the body, and the body itself.

    ``index_args`` are scalar-prefetched (SMEM-resident) index arrays,
    available to every IndirectStream's index_map and to the body as leading
    refs. ``dimension_semantics`` annotates each grid axis as "parallel" or
    "arbitrary" (sequential) for the TPU pipeliner. ``trace_name``, where
    set, names the kernel's instruction in the compiled program, and so its
    ops in the profiler's trace.
    """

    name: str
    body: Callable
    grid: tuple
    in_streams: tuple[Stream, ...]
    out_streams: tuple[Stream, ...]
    out_shapes: tuple[jax.ShapeDtypeStruct, ...]
    index_args: tuple = ()
    scratch: tuple = ()
    dimension_semantics: tuple | None = None
    trace_name: str | None = None

    @property
    def steps(self) -> int:
        """Grid steps — the SU's total stream-advance count."""
        return math.prod(self.grid)

    def traffic_bytes(self) -> int:
        """Upper-bound HBM traffic: every stream refetches per grid step.

        The Pallas pipeliner elides refetches when an index_map repeats a
        block across consecutive steps, so this is the no-reuse bound — the
        numerator of the paper's per-kernel operational-intensity figures.
        Streams built without a dtype are counted at 4 bytes/element; pass
        dtypes on every stream for exact figures.
        """
        per_step = sum(
            s.bytes_per_step for s in (*self.in_streams, *self.out_streams)
        )
        return per_step * self.steps

    def validate(self, *, strict: bool = False) -> list[str]:
        """Structural invariants of the program, as a list of problem strings
        (empty when well-formed) — the resolve-time check ``repro.analysis``
        runs over every registered kernel's program builder.

        Always checked: the grid is a non-empty tuple of positive ints,
        every stream's block_shape is all-positive, and ``out_shapes``
        pairs one shape per out stream. With ``strict`` the index_map
        arity is also checked: an AffineStream's map must accept exactly
        one argument per grid axis (an IndirectStream's at least that many
        — it may also read the scalar-prefetch refs). Returns problems
        instead of raising so the analyzer can report every violation of a
        seeded-bad program at once.
        """
        problems = []
        if not self.grid or not all(
            isinstance(g, int) and g > 0 for g in self.grid
        ):
            problems.append(f"grid must be positive ints, got {self.grid!r}")
        if len(self.out_shapes) != len(self.out_streams):
            problems.append(
                f"{len(self.out_streams)} out_streams but "
                f"{len(self.out_shapes)} out_shapes"
            )
        for role, streams in (("in", self.in_streams),
                              ("out", self.out_streams)):
            for i, s in enumerate(streams):
                if not all(isinstance(b, int) and b > 0 for b in s.block_shape):
                    problems.append(
                        f"{role}_streams[{i}] block_shape {s.block_shape!r} "
                        f"has a non-positive extent"
                    )
                if strict:
                    code = getattr(s.index_map, "__code__", None)
                    if code is not None and not (code.co_flags & 0x04):
                        nargs = code.co_argcount
                        want = len(self.grid)
                        affine = isinstance(s, AffineStream)
                        if (affine and nargs != want) or nargs < want:
                            problems.append(
                                f"{role}_streams[{i}] index_map takes "
                                f"{nargs} args for a {want}-axis grid"
                            )
        return [f"{self.name}: {p}" for p in problems]

    def vmem_bytes(self) -> int:
        """Estimated VMEM residency of the pipelined program.

        Every in/out stream holds one block double-buffered (the C4 SPM
        discipline: compute on one buffer while DMA fills the other), scratch
        buffers are single, persistent allocations. This is the analytic
        feasibility bound the block-size autotuner checks against the VMEM
        budget before compiling a candidate geometry.
        """
        stream_bytes = 2 * sum(
            s.bytes_per_step for s in (*self.in_streams, *self.out_streams)
        )
        scratch_bytes = sum(
            math.prod(s.shape) * _dtype_bytes(getattr(s, "dtype", None))
            for s in self.scratch
        )
        return stream_bytes + scratch_bytes


def stream_compute(program: StreamProgram, *operands, interpret: bool = False):
    """Execute a StreamProgram (the FREP + SU launch).

    ``operands`` bind positionally to ``program.in_streams``; scalar-prefetch
    index args come from the program itself. This is the single pallas_call
    site in the codebase.
    """
    if len(operands) != len(program.in_streams):
        raise ValueError(
            f"{program.name}: got {len(operands)} operands for "
            f"{len(program.in_streams)} in_streams"
        )
    n_pre = len(program.index_args)
    in_specs = [s.spec(n_pre) for s in program.in_streams]
    out_specs = [s.spec(n_pre) for s in program.out_streams]
    single = len(program.out_streams) == 1
    if single:
        out_specs, out_shapes = out_specs[0], program.out_shapes[0]
    else:
        out_shapes = list(program.out_shapes)

    kwargs: dict = {"out_shape": out_shapes, "interpret": interpret}
    if program.trace_name:
        kwargs["name"] = program.trace_name
    if program.dimension_semantics is not None and not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=tuple(program.dimension_semantics)
        )

    if n_pre:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre,
            grid=program.grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=list(program.scratch),
        )
        return pl.pallas_call(program.body, grid_spec=grid_spec, **kwargs)(
            *program.index_args, *operands
        )
    return pl.pallas_call(
        program.body,
        grid=program.grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=list(program.scratch),
        **kwargs,
    )(*operands)


def remote_ring_hop(x: jax.Array, axis: str, n: int) -> jax.Array:
    """One forward ring hop as a pallas async remote copy (RDMA).

    The D2D analogue of the SU double-buffer: instead of routing the hop
    through XLA's ``collective-permute``, the kernel programs the
    inter-chip DMA engine directly — ``make_async_remote_copy`` pushes the
    local buffer to rank ``(me + 1) % n`` and blocks on the receive
    semaphore until the left neighbour's push lands. Semantically identical
    to ``ppermute(x, axis, ring_fwd)``; the win is scheduling: the copy is
    a plain DMA the pipeliner can overlap like any other stream.

    TPU-only (the DMA engine and semaphores are TPU hardware); callers gate
    on ``jax.default_backend() == "tpu"`` and fall back to ``ppermute``
    (``parallel.collectives._hop_send``). Assumes the ring spans the whole
    ``axis`` with logical device ids matching axis order — the layout
    ``shard_map`` meshes give a single ring axis. Must run inside a
    ``shard_map`` naming ``axis``.
    """

    def body(x_ref, y_ref, send_sem, recv_sem):
        me = jax.lax.axis_index(axis)
        copy = pltpu.make_async_remote_copy(
            src_ref=x_ref,
            dst_ref=y_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=((me + 1) % n,),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        copy.start()
        copy.wait()

    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=0
        ),
    )(x)


def gemm_streams(
    M: int, N: int, K: int, bm: int, bn: int, bk: int, dtype=None
):
    """The paper's Fig. 4a GEMM loop nest as three affine streams."""
    a = AffineStream((bm, bk), lambda i, j, k: (i, k), dtype=dtype)
    b = AffineStream((bk, bn), lambda i, j, k: (k, j), dtype=dtype)
    o = AffineStream((bm, bn), lambda i, j, k: (i, j), dtype=dtype)
    grid = (M // bm, N // bn, K // bk)
    return grid, [a, b], o
