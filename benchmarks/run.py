"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see benchmarks/common.py for the
CPU-timing caveats and the derived figure-of-merit definitions).

Implementation selection is registry-global: the harness pins the ``xla``
impls (the lowering-representative blocked forms — Pallas cannot lower on
CPU) once here, scoped via ``registry.default_impl`` so nothing leaks past
the run. Override with ``REPRO_BENCH_IMPL=interpret`` etc.

``--autotune`` (or ``REPRO_AUTOTUNE=1``) runs the block-size autotuner
(repro.launch.autotune) before the benchmarks: if the tuning record already
exists it is loaded and applied deterministically — no re-search — otherwise
the search runs and persists it. Tuned-vs-default ``us_per_call`` deltas are
emitted as ``autotune_<op>`` CSV rows, and the benchmarks then run under the
tuned overrides.

``--mesh DxM`` backs a (data, model) mesh — and ``--mesh PxDxM`` the
three-axis (pod, data, model) hierarchy, where kernel partition plans
resolve two-level with per-level collective costing — with forced
host-platform devices (the flag must be decided before jax imports, which
is why argument parsing precedes the jax import here) and emits per-op
sharded-vs-single rows (benchmarks/bench_mesh.py), including the
``mesh_overlap_*`` rows comparing the overlapped ring/halo schedules
against their synchronous oracles. ``--mesh-only`` stops after those rows
(CI smoke for the multi-device job). When ``--autotune`` and ``--mesh``
combine, the tuner searches through the sharded dispatch and keys its
record by the local shard geometry (see repro/launch/autotune.py);
``--autotune-budget N`` caps how many candidates each case measures,
spent in roofline-prior order.

``--json PATH`` additionally writes every emitted row as machine-readable
JSON (structured op/mesh/impl/overlap metadata alongside the measured
microseconds) — the committed ``BENCH_mesh.json`` host-backend baseline
is produced by ``python -m benchmarks.run --mesh 4x2 --mesh-only --json
BENCH_mesh.json``.
"""
import argparse
import math
import os


def _parse_mesh(spec: str) -> tuple[int, ...]:
    """``DxM`` -> a (data, model) mesh; ``PxDxM`` -> (pod, data, model)."""
    try:
        dims = tuple(int(x) for x in spec.lower().split("x"))
    except ValueError:
        dims = ()
    if len(dims) not in (2, 3):
        raise SystemExit(
            f"--mesh expects DxM or PxDxM (e.g. 2x4 or 2x2x2), got {spec!r}"
        )
    if any(d < 1 for d in dims):
        raise SystemExit(f"--mesh axes must be >= 1, got {spec!r}")
    return dims


_MESH_AXES = {2: ("data", "model"), 3: ("pod", "data", "model")}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--autotune", action="store_true",
                    help="tune block sizes first (or load the existing record)")
    ap.add_argument("--autotune-record", default="autotune_record.json")
    ap.add_argument("--autotune-reps", type=int, default=3)
    ap.add_argument("--autotune-budget", type=int, default=None, metavar="N",
                    help="time at most N candidates per autotune case, "
                    "spent in roofline-prior order (the default geometry "
                    "is always measured)")
    ap.add_argument("--autotune-only", action="store_true",
                    help="emit the autotune rows and stop (CI smoke)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write every row as machine-readable JSON "
                    "(benchmarks/common.py emit_json) to PATH on exit")
    ap.add_argument("--mesh", default=None, metavar="DxM|PxDxM",
                    help="(data, model) or (pod, data, model) mesh for the "
                         "sharded-vs-single rows; forces that many host "
                         "devices on CPU")
    ap.add_argument("--mesh-only", action="store_true",
                    help="emit the mesh rows and stop (CI smoke)")
    args = ap.parse_args(argv)
    if args.mesh_only and not args.mesh:
        raise SystemExit("--mesh-only needs --mesh DxM")

    mesh_shape = _parse_mesh(args.mesh) if args.mesh else None
    mesh_devices = math.prod(mesh_shape) if mesh_shape else 0
    if mesh_shape is not None:
        # the shared append-only bootstrap (launch/xla_flags.py): caller
        # flags survive, and a caller-chosen device count wins
        from repro.launch.xla_flags import ensure_host_device_count

        ensure_host_device_count(mesh_devices)

    import jax

    from repro.kernels import registry
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    tune = (args.autotune or args.autotune_only
            or os.environ.get("REPRO_AUTOTUNE") == "1")

    impl = os.environ.get("REPRO_BENCH_IMPL")
    if impl is None:
        # xla is the CPU stand-in; on TPU let auto pick the Pallas kernels
        impl = "xla" if jax.default_backend() != "tpu" else "auto"

    mesh = None
    if mesh_shape is not None:
        from repro.launch.mesh import make_mesh

        if jax.device_count() < mesh_devices:
            raise SystemExit(
                f"--mesh {args.mesh} needs {mesh_devices} devices, have "
                f"{jax.device_count()} (is XLA_FLAGS already set?)"
            )
        mesh = make_mesh(mesh_shape, _MESH_AXES[len(mesh_shape)])

    def finish():
        # --json: dump every row recorded through benchmarks/common.row
        # (shared by all exit paths, including the --*-only CI smokes)
        if args.json:
            from benchmarks.common import emit_json

            emit_json(args.json)

    with registry.default_impl(impl):
        print("name,us_per_call,derived")
        if tune:
            from repro.launch import autotune as at

            record = None
            source = "loaded"
            if os.path.exists(args.autotune_record):
                record = at.load_record(args.autotune_record)
                if not at.record_matches_environment(record, mesh=mesh):
                    # tuned for a different backend/impl/mesh: re-search
                    # rather than silently mistune this one
                    record = None
            if record is None:
                # tuning under the mesh keys each entry by the LOCAL shard
                # geometry, so the record stays valid for the kernels the
                # sharded dispatch actually runs
                record = at.autotune(reps=args.autotune_reps, mesh=mesh,
                                     trial_budget=args.autotune_budget)
                at.save_record(record, args.autotune_record)
                source = "searched"
            at.apply_record(record, mesh=mesh)
            for op, d in sorted(at.record_deltas(record).items()):
                delta = ("n/a" if d["delta_pct"] is None
                         else f"{d['delta_pct']:+.1f}%")
                default_us = ("n/a" if d["default_us"] is None
                              else f"{d['default_us']:.1f}")
                tuned_us = ("n/a" if d["us_per_call"] is None
                            else f"{d['us_per_call']:.1f}")
                print(
                    f"autotune_{op},{tuned_us},"
                    f"default_us={default_us};delta={delta};"
                    f"blocks={'/'.join(f'{k}={v}' for k, v in sorted(d['blocks'].items()))};"
                    f"{source}",
                    flush=True,
                )
            if args.autotune_only:
                return finish()

        if mesh is not None:
            from benchmarks import bench_mesh

            bench_mesh.run(mesh)
            if args.mesh_only:
                return finish()

        from benchmarks import (bench_d2d, bench_gcn, bench_gemm, bench_gptj,
                                bench_precision, bench_spmm, bench_spmspm,
                                bench_stencil)

        for mod in (bench_gemm, bench_precision, bench_stencil, bench_spmm,
                    bench_spmspm, bench_gcn, bench_gptj, bench_d2d):
            mod.run()
        finish()


if __name__ == "__main__":
    main()
