"""Compile a cell's decode step and its largest prefill program for a
described TPU v5e, without the chip, and print ``memory_analysis``.

    JAX_PLATFORMS=cpu python chipbench/compile_check.py --workload gptj-chat

It builds the engine's own jitted programs (``PagedModel``) over shapes
only: the weights, the page pool at the cell's size and the batch. On the
CPU the kernel registry's ``auto`` would pick the XLA forms, so this script
makes the registry see a TPU backend while it lowers, as the chip would.
The numbers are the compiler's, per program; they are no chip run.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness, traffic, weights
    from repro.kernels import registry
    from repro.serving.engine import PagedModel
    from repro.serving.paged_cache import PagedKVCache

    bench = harness.load_bench()
    cell = harness.load_cell(bench, args.workload)
    cfg = harness.program_config(cell["model_file"])
    m, geo = cell["model_file"]["model"], cell["engine"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    dt = jnp.dtype(m["dtype"])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    params = {"layers": {}}
    for name, (shape, stacked, _, _) in weights.leaf_specs(m).items():
        if stacked:
            params["layers"][name.split("/", 1)[1]] = sds(
                (m["num_layers"], *shape), dt)
        else:
            params[name] = sds(shape, dt)
    pool = (m["num_layers"], geo["num_blocks"], m["num_kv_heads"],
            geo["block_size"], m["head_dim"])
    cache = PagedKVCache(sds(pool, dt), sds(pool, dt), None, None,
                         geo["block_size"], None)
    B, NB = geo["max_slots"], geo["max_blocks_per_seq"]
    model = PagedModel(cfg, params, num_blocks=2,
                       block_size=geo["block_size"], max_slots=B,
                       max_blocks_per_seq=NB)
    batch = {"token": sds((B,), jnp.int32), "position": sds((B,), jnp.int32),
             "block_table": sds((B, NB), jnp.int32)}
    sb = traffic.buckets(
        [traffic.max_length(cell["mix"]["prompt"])], geo["block_size"])[0]
    programs = {
        "decode": (model._decode_jit, (params, cache, batch)),
        f"prefill_{sb}": (model._prefill_fn(sb), (
            params, cache, sds((1, sb), jnp.int32),
            sds((sb // geo["block_size"],), jnp.int32), sds((), jnp.int32))),
    }
    real_backend = jax.default_backend
    registry.jax.default_backend = lambda: "tpu"
    try:
        for name, (fn, args_) in programs.items():
            compiled = fn.lower(*args_).compile()
            ma = compiled.memory_analysis()
            kernels = compiled.as_text().count("tpu_custom_call")
            print(f"{args.workload} {name}: arguments "
                  f"{ma.argument_size_in_bytes} B, outputs "
                  f"{ma.output_size_in_bytes} B, aliased "
                  f"{ma.alias_size_in_bytes} B, temporaries "
                  f"{ma.temp_size_in_bytes} B, Pallas kernels {kernels}")
    finally:
        registry.jax.default_backend = real_backend


if __name__ == "__main__":
    main()
