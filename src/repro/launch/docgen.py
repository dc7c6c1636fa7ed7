"""Generated op-reference: the kernel registry rendered as markdown.

``python -m repro.launch.docgen`` regenerates ``docs/op-reference.md`` from
the live registry — per op: the registered impls, the default block
geometry from ``registry.resolve_blocks``, and the partition rule resolved
against both production meshes (single-pod 16×16 and two-pod 2×16×16
device-free MeshSpecs), including its per-level collectives and halo
metadata. The representative operand shapes are the shared
``launch.op_cases.op_roofline_cases`` table (GPT-J / Fig. 9 scale), so the
doc shows the same plans the roofline cells cost.

The output is deterministic (sorted ops, no timestamps); CI regenerates it
with ``--check`` and fails on drift, so the committed doc can never lag the
registry.
"""
from __future__ import annotations

import argparse
import sys

HEADER = """\
# Op reference

<!-- GENERATED FILE - do not edit by hand.
     Regenerate with:  PYTHONPATH=src python -m repro.launch.docgen
     CI runs `python -m repro.launch.docgen --check` and fails on drift. -->

Every op dispatches through `kernels/ops.py` along three axes: **impl**
(pallas / interpret / xla / ref, resolved per op by `registry.resolve_impl`;
`auto` is pallas on TPU where the op has a kernel, xla otherwise),
**block geometry** (`registry.resolve_blocks`: explicit kwarg >
`set_block_override` > table default), and **partitioning**
(`kernels/partition.py`: the op's `PartitionRule` resolved against the
`mesh=` kwarg or the `sharding.use_mesh` context). See
[docs/partitioning.md](partitioning.md) for how plans resolve and
[ARCHITECTURE.md](../ARCHITECTURE.md) for the layering.

Partition columns below show each rule resolved at a representative
operand geometry (the dry-run's op-roofline cases) against the production
meshes: single-pod `data=16, model=16` and two-pod `pod=2, data=16,
model=16`, where plans resolve two-level with per-level collectives
(intra-pod at ICI bandwidth, cross-pod at D2D bandwidth).
"""


def _collectives_cell(plan) -> str:
    if plan is None:
        return "—"
    if not plan.collectives:
        return "none"
    # run-length encode: a ring plan fires dozens of identical per-hop
    # permutes; "30× permute@data(...)" reads, thirty repeats don't
    parts, runs = [], []
    for c in plan.collectives:
        cell = f"{c.kind}@{c.axis}(n={c.n}, {c.nbytes} B)"
        if runs and runs[-1][0] == cell:
            runs[-1][1] += 1
        else:
            runs.append([cell, 1])
    for cell, count in runs:
        parts.append(cell if count == 1 else f"{count}× {cell}")
    return "; ".join(parts)


def _partition_cell(plan) -> str:
    if plan is None:
        return "replicated"
    return plan.note


def _overlap_cell(plan) -> str:
    # the overlap-capability column: which plans run the double-buffered
    # ring/halo schedule (kernels/partition.py `overlappable`), and over
    # how many pipeline stages the transfers hide
    if plan is None or not plan.overlappable:
        return "—"
    return f"yes ({plan.hops} hops)"


def generate() -> str:
    """Render the op-reference markdown (deterministic; returns the text)."""
    from repro.core import precision
    from repro.kernels import ops as _ops  # noqa: F401  (registers the ops)
    from repro.kernels import partition, registry
    from repro.launch.op_cases import op_roofline_cases

    cases = {c[0]: c for c in op_roofline_cases()}
    single = partition.MeshSpec({"data": 16, "model": 16})
    multi = partition.MeshSpec({"pod": 2, "data": 16, "model": 16})

    lines = [HEADER]
    lines.append("## Dispatch table\n")
    lines.append("| op | impls | default blocks | precisions |")
    lines.append("|---|---|---|---|")
    for op in registry.registered_ops():
        impls = ", ".join(registry.implementations(op))
        blocks = registry.block_defaults(op)
        blocks_s = ", ".join(f"{k}={v}" for k, v in sorted(blocks.items()))
        blocks_s = blocks_s or "—"
        precs = ", ".join(precision.supported_policies(op))
        lines.append(f"| `{op}` | {impls} | {blocks_s} | {precs} |")
    lines.append("")
    lines.append(
        "The precisions column lists the `core/precision.py` policies each "
        "op's kernels accept via `precision=` (fp32 is the `precision=None` "
        "legacy path; everything else dispatches the block-scaled "
        "quantized kernels — see the precision ladder section in "
        "[ARCHITECTURE.md](../ARCHITECTURE.md)). Ops listing only fp32 "
        "have no scaled path.\n"
    )

    for mesh, title, tag in (
        (single, "Partitioning on the single-pod mesh (`data=16, model=16`)",
         "one level: the chiplet crossbar (`model`)"),
        (multi, "Partitioning on the two-pod mesh (`pod=2, data=16, "
         "model=16`)",
         "two levels: pods (D2D link) above the chiplet crossbar"),
    ):
        lines.append(f"## {title}\n")
        lines.append(f"Plans resolve over {tag}.\n")
        lines.append("| op | partition plan | levels | overlap | collectives |")
        lines.append("|---|---|---|---|---|")
        for op in registry.registered_ops():
            if op not in cases:
                lines.append(f"| `{op}` | (no representative case) | | | |")
                continue
            _, args, kwargs, _, _ = cases[op]
            plan = partition.plan_for(op, mesh, *args, **kwargs)
            levels = (
                ", ".join(f"{a}={n}" for a, n in plan.levels)
                if plan else "—"
            )
            lines.append(
                f"| `{op}` | {_partition_cell(plan)} | {levels} | "
                f"{_overlap_cell(plan)} | {_collectives_cell(plan)} |"
            )
        lines.append("")

    lines.append(
        "The overlap column marks plans that run the double-buffered "
        "latency-tolerant schedule (`overlap=True`, the default): the next "
        "hop's transfer is issued before the current hop's kernel, so up "
        "to `hops - 1` transfers hide behind compute "
        "(`roofline.overlapped_seconds`). Pass `overlap=False` on the op "
        "call for the synchronous oracle schedule.\n"
    )
    lines.append(
        "Collective cells read `kind@axis(n=ring size, payload bytes)`; "
        "`pod`-axis entries are priced at the D2D link bandwidth, all "
        "others at on-chiplet ICI bandwidth "
        "(`core/topology.py::collective_seconds`). An op that resolves to "
        "fewer levels than the mesh offers walked the replication fallback "
        "ladder (its dimensions divide the chiplet axis but not "
        "pod×model).\n"
    )
    return "\n".join(lines)


def main(argv=None) -> int:
    """CLI: write (default) or drift-check the generated op reference.

    ``argv`` defaults to sys.argv. ``--out`` picks the target file
    (default docs/op-reference.md); ``--check`` regenerates in memory,
    compares against the committed file, and returns exit code 2 on drift
    (the CI gate).
    """
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="docs/op-reference.md")
    ap.add_argument("--check", action="store_true",
                    help="fail (exit 2) if the committed file is stale")
    args = ap.parse_args(argv)

    text = generate()
    if args.check:
        try:
            with open(args.out) as f:
                committed = f.read()
        except FileNotFoundError:
            print(f"docgen --check: {args.out} does not exist; run "
                  f"`python -m repro.launch.docgen` and commit it",
                  file=sys.stderr)
            return 2
        if committed != text:
            print(f"docgen --check: {args.out} is stale; regenerate with "
                  f"`PYTHONPATH=src python -m repro.launch.docgen` and "
                  f"commit the result", file=sys.stderr)
            return 2
        print(f"docgen --check: {args.out} is up to date")
        return 0
    with open(args.out, "w") as f:
        f.write(text)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
