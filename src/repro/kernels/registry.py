"""Kernel registry: one dispatch layer for every op in the stack.

Each op registers up to four implementations:

  - ``pallas``:    the TPU StreamProgram kernel (built on core.streams)
  - ``interpret``: the same kernel body interpreted on CPU (tests)
  - ``xla``:       a blocked jnp implementation of the *same algorithm* —
                   lowering-representative (same FLOPs / memory behaviour),
                   used by the multi-pod dry-run where Pallas cannot lower
  - ``ref``:       the naive oracle from ref.py

Selection precedence: explicit ``impl=`` argument > ``set_default_impl()`` >
``REPRO_KERNEL_IMPL`` env var > auto. ``auto`` resolves per op: on a TPU
backend, ``pallas`` where the op registers a real kernel and ``xla`` where it
does not; ``xla`` on every other backend. No name stands for another form:
an op without a ``pallas`` kernel has no ``pallas`` entry, and asking for one
raises.

The registry also owns the per-op default block-size table with an override
layer (``set_block_override``) — the seam a future autotuner writes through —
and the ``unroll_inner`` flag the roofline dry-run uses to trade lax.scan
inner loops for cost-countable python unrolls.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable

import jax

VALID_IMPLS = ("auto", "pallas", "interpret", "xla", "ref")

_REGISTRY: dict[str, dict[str, Callable]] = {}
_default_impl: str | None = None  # process-wide override set by set_default_impl()


# ---------------------------------------------------------------------------
# Registration
# ---------------------------------------------------------------------------


def register_kernel(op: str, *, impl: str) -> Callable:
    """Decorator: ``@register_kernel("spmm", impl="pallas")``."""
    if impl not in VALID_IMPLS or impl == "auto":
        raise ValueError(f"cannot register impl {impl!r}; one of {VALID_IMPLS[1:]}")

    def deco(fn: Callable) -> Callable:
        _REGISTRY.setdefault(op, {})[impl] = fn
        return fn

    return deco


def register_stream_kernel(op: str) -> Callable:
    """Register a StreamProgram-backed kernel under both ``pallas`` and
    ``interpret`` (the interpret entry is the same program, interpreted)."""

    def deco(fn: Callable) -> Callable:
        _REGISTRY.setdefault(op, {})["pallas"] = fn
        _REGISTRY[op]["interpret"] = functools.partial(fn, interpret=True)
        return fn

    return deco


def registered_ops() -> list[str]:
    return sorted(_REGISTRY)


def implementations(op: str) -> list[str]:
    if op not in _REGISTRY:
        raise KeyError(
            f"unknown kernel op {op!r}; registered ops: {registered_ops()}"
        )
    return sorted(_REGISTRY[op])


# ---------------------------------------------------------------------------
# Implementation selection
# ---------------------------------------------------------------------------


def set_default_impl(impl: str | None) -> None:
    global _default_impl
    if impl is not None and impl not in VALID_IMPLS:
        raise ValueError(f"unknown impl {impl!r}; one of {VALID_IMPLS}")
    _default_impl = impl


@contextlib.contextmanager
def default_impl(impl: str | None):
    """Scoped form of ``set_default_impl``: restores the previous default on
    exit, so harnesses and tests don't leak process-global impl state."""
    global _default_impl
    if impl is not None and impl not in VALID_IMPLS:
        raise ValueError(f"unknown impl {impl!r}; one of {VALID_IMPLS}")
    old = _default_impl
    _default_impl = impl
    try:
        yield
    finally:
        _default_impl = old


def resolve_impl(impl: str | None = None, op: str | None = None) -> str:
    """The impl name a call dispatches to. With ``op``, ``auto`` resolves
    for that op (``pallas`` only where the op has a kernel); without it,
    ``auto`` names the backend's preferred form."""
    impl = impl or _default_impl or os.environ.get("REPRO_KERNEL_IMPL", "auto")
    if impl not in VALID_IMPLS:
        raise ValueError(f"unknown impl {impl!r}; one of {VALID_IMPLS}")
    if impl == "auto":
        if jax.default_backend() != "tpu":
            return "xla"
        if op is None:
            return "pallas"
        return "pallas" if "pallas" in implementations(op) else "xla"
    return impl


def kernel_call(op: str, *args, impl: str | None = None, **kwargs):
    """Dispatch ``op`` to its registered implementation."""
    if op not in _REGISTRY:
        raise KeyError(
            f"unknown kernel op {op!r}; registered ops: {registered_ops()}"
        )
    impl = resolve_impl(impl, op)
    fn = _REGISTRY[op].get(impl)
    if fn is None:
        raise NotImplementedError(
            f"kernel {op!r} has no {impl!r} implementation; "
            f"available: {implementations(op)}"
        )
    return fn(*args, **kwargs)


# ---------------------------------------------------------------------------
# Block-size defaults + override table (autotuning groundwork)
# ---------------------------------------------------------------------------

_BLOCK_DEFAULTS: dict[str, dict[str, int]] = {
    "gemm": {"bm": 256, "bk": 256, "bn": 256},
    "flash_attention": {"bq": 128, "bk": 128},
    "linear_attention": {"chunk": 32},
    "spmm": {"bm": 128},
    "bsr_spmm": {"bf": 512},
    "spmspm": {"bm": 8, "bn": 128},
    "stencil": {"bx": 8},
    # bs: the contiguous cache's scan tile; pages: table columns per grid
    # step of the paged kernel (the pool's page extent pins its tile)
    "decode_attention": {"bs": 512, "pages": 8},
}
_block_overrides: dict[str, dict[str, int]] = {}


def block_defaults(op: str, *, overrides: bool = True) -> dict[str, int]:
    """Per-op block sizes: the static defaults merged with any override.

    ``overrides=False`` returns the pristine table defaults — the autotuner's
    baseline, measured regardless of what overrides are currently active.
    """
    if not overrides:
        return dict(_BLOCK_DEFAULTS.get(op, {}))
    return {**_BLOCK_DEFAULTS.get(op, {}), **_block_overrides.get(op, {})}


def set_block_override(op: str, **sizes: int) -> None:
    """Override default block sizes for ``op`` (e.g. from an autotuner)."""
    known = _BLOCK_DEFAULTS.get(op)
    if known is None:
        raise KeyError(
            f"op {op!r} has no block-size table; known: {sorted(_BLOCK_DEFAULTS)}"
        )
    bad = set(sizes) - set(known)
    if bad:
        raise ValueError(f"{op!r} has no block parameters {sorted(bad)}")
    _block_overrides.setdefault(op, {}).update(sizes)


def clear_block_overrides(op: str | None = None) -> None:
    if op is None:
        _block_overrides.clear()
    else:
        _block_overrides.pop(op, None)


def resolve_blocks(op: str, **explicit: int | None) -> dict[str, int]:
    """The single block-geometry resolution path, every impl's source of
    truth: explicit kwarg > ``set_block_override`` > static default.

    ``explicit`` entries that are None fall through to the override/default
    layers; unknown parameter names raise (same contract as
    ``set_block_override``). Returns the complete block dict for ``op``, so
    pallas, interpret, and xla implementations of one call all receive
    identical geometry.
    """
    known = _BLOCK_DEFAULTS.get(op)
    if known is None:
        raise KeyError(
            f"op {op!r} has no block-size table; known: {sorted(_BLOCK_DEFAULTS)}"
        )
    bad = set(explicit) - set(known)
    if bad:
        raise ValueError(f"{op!r} has no block parameters {sorted(bad)}")
    resolved = {**known, **_block_overrides.get(op, {})}
    resolved.update({k: v for k, v in explicit.items() if v is not None})
    return resolved


@contextlib.contextmanager
def block_override(op: str, **sizes: int):
    """Scoped ``set_block_override``: the autotuner times each candidate
    under this so a failed or aborted search never leaks geometry."""
    old = dict(_block_overrides.get(op, {}))
    had = op in _block_overrides
    set_block_override(op, **sizes)
    try:
        yield
    finally:
        if had:
            _block_overrides[op] = old
        else:
            _block_overrides.pop(op, None)


# ---------------------------------------------------------------------------
# Roofline unroll flag (consumed by the xla implementations)
# ---------------------------------------------------------------------------

# When True, the xla paths replace their inner lax.scan (KV blocks / decay
# chunks) with python loops. XLA's HloCostAnalysis counts while-loop bodies
# ONCE regardless of trip count, so roofline-term extraction (launch/dryrun)
# traces small unrolled variants to get true FLOP/byte/collective counts.
_unroll_inner = False


def unroll_inner_enabled() -> bool:
    return _unroll_inner


@contextlib.contextmanager
def unroll_inner():
    global _unroll_inner
    old, _unroll_inner = _unroll_inner, True
    try:
        yield
    finally:
        _unroll_inner = old
