"""Fault tolerance & straggler mitigation (paper C7).

Occamy's D2D link calibrates once, disables faulty PHYs, and reshuffles
traffic over the survivors with linear degradation. The framework analogue:

- StragglerMonitor: per-step wall-clock EWMA; a step exceeding k x the EWMA
  flags a straggle event. At scale each host reports its own timing on the
  control plane (kept OUT of the hot loop, like the narrow 64-bit network).
- elastic_remesh: rebuild a smaller/larger mesh after failures (shrink the
  `data` axis — drop the bad "lanes") and re-shard the training state onto
  it from host memory or the last checkpoint. Batch is re-sharded too;
  throughput degrades linearly with lost data-parallel rank, exactly the
  channel-allocator contract.
- FailureInjector: deterministic fault schedule for tests/examples.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np


@dataclasses.dataclass
class StragglerMonitor:
    threshold: float = 2.5  # x EWMA counts as a straggle
    alpha: float = 0.1
    ewma: float | None = None
    events: int = 0
    steps: int = 0

    def observe(self, step_seconds: float) -> bool:
        self.steps += 1
        if self.ewma is None:
            self.ewma = step_seconds
            return False
        straggled = step_seconds > self.threshold * self.ewma
        if straggled:
            self.events += 1
        else:  # do not pollute the EWMA with outliers
            self.ewma = (1 - self.alpha) * self.ewma + self.alpha * step_seconds
        return straggled

    @property
    def should_exclude(self) -> bool:
        """A host persistently straggling gets excluded at the next elastic
        boundary (3 events within any 100-step window)."""
        return self.events >= 3


class FailureInjector:
    """Deterministic failure schedule: {step: kind}; kinds: 'crash' (the loop
    must restart from checkpoint), 'straggle' (sleep multiplier)."""

    def __init__(self, schedule: dict[int, str] | None = None):
        self.schedule = schedule or {}
        self.triggered: list[tuple[int, str]] = []

    def check(self, step: int) -> str | None:
        kind = self.schedule.get(step)
        if kind:
            self.triggered.append((step, kind))
        return kind


def elastic_remesh(data_parallel: int, model_parallel: int, lost_ranks: int = 0):
    """Rebuild the mesh with `lost_ranks` fewer data-parallel rows using
    whatever devices remain. Returns (mesh, new_data_parallel)."""
    new_dp = data_parallel - lost_ranks
    assert new_dp >= 1, "cannot shrink below one data-parallel rank"
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((new_dp, model_parallel), ("data", "model"),
                     devices=jax.devices()[: new_dp * model_parallel])
    return mesh, new_dp


def reshard_state(state, cfg, mesh, mode="train"):
    """Re-device_put a state pytree onto a (new) mesh (elastic restart)."""
    from repro.parallel import sharding as sh

    pspecs = sh.param_specs(cfg, state["params"], mesh, mode)
    specs = {
        "params": pspecs,
        "opt": {"m": pspecs, "v": pspecs,
                "step": jax.sharding.PartitionSpec()},
    }
    shardings = sh.named(mesh, specs)
    return jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(jax.device_get(x)), s),
        state, shardings,
    )
