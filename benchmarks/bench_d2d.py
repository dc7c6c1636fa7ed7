"""Fig. 13: D2D-link behaviour — (a) linear bandwidth degradation as lanes
are disabled, (b) effective bandwidth vs transfer size.

The TPU analogue of the D2D link is the pod axis. (a) maps to the elastic
re-mesh contract (throughput ~ surviving data-parallel ranks); (b) to the
ring-collective efficiency model from core/topology (latency-vs-bandwidth
regime, like the paper's 96% utilization at 16 kB transfers).

The pod-allreduce rows are MEASURED when the process sees more than one
device (benchmarks/run.py ``--mesh DxM`` forces a host-device mesh): a real
``shard_map`` psum runs over all devices as a 1-D pod axis, and the analytic
ring number rides along as ``model=`` metadata — Fig. 13b's measured-vs-model
column. Single-device runs keep the analytic rows (tagged accordingly).
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import row, timeit
from repro.core.topology import POD_LINK_BW, collective_seconds
from repro.launch.mesh import make_mesh

LINK_LATENCY = 1e-6  # per-hop launch overhead (the paper's 61-cycle analogue)


def _measured_allreduce_rows():
    """psum over every host device as a 1-D pod axis; per-device buffer
    sizes kept CPU-friendly (the analytic model scales linearly anyway)."""
    n = jax.device_count()
    mesh = make_mesh((n,), ("pod",))
    for mbytes in (1, 4, 16):
        per_dev = mbytes * (1 << 20)
        elems = per_dev // 4
        x = jnp.ones((n * elems,), jnp.float32)
        f = jax.jit(
            jax.shard_map(
                lambda v: jax.lax.psum(v, "pod"),
                mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                check_vma=False,
            )
        )
        t = timeit(f, x, reps=3)
        model = collective_seconds("all_reduce", per_dev, "pod", n)
        eff = 2 * per_dev * (n - 1) / n / t  # ring bytes actually moved
        yield (
            f"fig13b_pod_allreduce_{mbytes}MBx{n}", t,
            f"{eff / 1e9:.2f} GB/s measured;model={model * 1e6:.1f}us;"
            f"model_bw={POD_LINK_BW / 1e9:.0f}GB/s",
        )


def run():
    # (a) lane disabling -> linear degradation (38 PHYs in the paper)
    lanes = 38
    for disabled in (0, 8, 16, 24):
        frac = (lanes - disabled) / lanes
        row(f"fig13a_d2d_disable_{disabled}", LINK_LATENCY,
            f"{frac * POD_LINK_BW / 1e9:.2f} GB/s;linear_frac={frac:.2f}")

    # (b) effective bandwidth vs transfer size (latency-bound -> bw-bound)
    for size in (1024, 4096, 16384, 65536, 262144, 1048576):
        t = LINK_LATENCY + size / POD_LINK_BW
        eff = size / t
        row(f"fig13b_d2d_xfer_{size}B", t,
            f"{eff / 1e9:.2f} GB/s;util={eff / POD_LINK_BW:.2%}")

    # pod-axis gradient all-reduce (the framework's real D2D traffic):
    # measured over the forced host-device mesh when one exists, with the
    # analytic ring model alongside; analytic-only on a single device.
    if jax.device_count() > 1:
        for name, t, derived in _measured_allreduce_rows():
            row(name, t, derived)
    else:
        for gbytes in (0.1, 1.0, 2.45):  # up to grok-1's per-device params
            t = collective_seconds("all_reduce", gbytes * 1e9, "pod", 2)
            row(f"fig13_pod_allreduce_{gbytes}GB", t,
                f"{2 * gbytes / t:.1f} GB/s effective;model=analytic-only")
