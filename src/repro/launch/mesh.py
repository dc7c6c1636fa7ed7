"""Production mesh construction (Occamy hierarchy -> TPU mesh axes).

Axis mapping (DESIGN.md C5): `model` = intra-chiplet crossbar (TP),
`data` = group level (DP/FSDP/SP), `pod` = D2D link (second DP axis).
A FUNCTION, not a module constant: importing this module never touches jax
device state.

Every mesh in the repository is built by ``make_mesh``: its axes are
``Auto``, so shardings propagate through the compiler and
``with_sharding_constraint`` may name any axis. (``jax.make_mesh`` itself
defaults to ``Explicit`` axes, which the sharding rules here do not use.)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.diagnostics import warn_degrade


def make_mesh(shape: tuple, axes: tuple, *, devices=None):
    """A mesh of ``shape`` named ``axes`` with ``Auto`` axis types, over
    ``devices`` (default: every local device, in ``jax.make_mesh`` order)."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def host_device_mesh(tp: int = 1, pods: int = 1):
    """Whatever devices exist locally, as (data, model) — or, when ``pods``
    is requested, as the three-axis (pod, data, model) hierarchy.

    Args: ``tp`` — the model-axis (chiplet-crossbar) size; ``pods`` — the
    pod-axis (D2D-link) size. ``pods=1`` keeps the historical two-axis
    shape; any other value yields a three-axis mesh (the pod axis is kept
    even if it degrades to size 1, so callers written for the pod axis see
    a stable set of axis names).

    When ``pods * tp`` does not divide the device count, degrades with a
    warning — the largest dividing ``pods`` first, then the largest ``tp``
    that divides the per-pod remainder — the same graceful-degradation
    contract as ``parallel/sharding.py``. Raises ``ValueError`` when no
    valid factorisation exists at all (``tp < 1`` or ``pods < 1``).
    """
    n = len(jax.devices())
    if tp < 1 or pods < 1:
        raise ValueError(
            f"host_device_mesh: tp={tp}, pods={pods} is not a valid mesh "
            f"factorisation (need 1 <= pods and 1 <= tp, have {n} devices)"
        )
    want_tp, want_pods = tp, pods
    if n % pods != 0:
        pods = max(p for p in range(1, min(pods, n) + 1) if n % p == 0)
    per_pod = n // pods
    if per_pod % tp != 0:
        tp = max(t for t in range(1, min(tp, per_pod) + 1) if per_pod % t == 0)
    if (tp, pods) != (want_tp, want_pods):
        warn_degrade(
            f"host_device_mesh: pods={want_pods} x tp={want_tp} does not "
            f"divide {n} devices; degrading to tp={tp}, pods={pods}",
        )
    if want_pods == 1:
        return make_mesh((n // tp, tp), ("data", "model"))
    return make_mesh((pods, per_pod // tp, tp), ("pod", "data", "model"))
