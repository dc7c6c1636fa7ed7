"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name in ``BENCHMARK.json`` (see ``harness``). The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit. The same numbers end
standard error. With no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    try:
        sys.exit(harness.main(t_start=T_START))
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        sys.exit(2)
