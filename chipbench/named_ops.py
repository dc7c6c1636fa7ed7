"""Device time of the ops of a named kernel, per call of the engine.

The trace names an op by its HLO text (``trace.Trace.ops``), which starts
with the instruction's name. A Pallas kernel built with a name
(``pallas_call(name=...)``) is an instruction of that name, and the
compiler names the grouped matmuls of ``jax.lax.ragged_dot`` ``ragged-dot``
(its Mosaic kernels ``ragged-dot-metadata`` and ``ragged-dot-none``). So
a kernel's ops are those whose instruction name (``trace.op_name``)
begins with a given prefix; an op that only takes such a kernel's output
as an operand is not one of them.
"""
from __future__ import annotations

import bisect

from chipbench import trace as t


def per_call(tr: t.Trace, inside: str, prefix: str, device: int = 0) -> list:
    """For each host span ``chipbench.<inside>`` that lies wholly in the
    window, in order: the summed device time, in seconds, of the ops on
    ``device`` whose instruction name begins with ``prefix`` and whose
    midpoint falls in the span."""
    lo, hi = tr.window
    spans = sorted((s, e) for s, e, n in tr.spans
                   if n == t.SPAN_PREFIX + inside and s >= lo and e <= hi)
    starts = [s for s, _ in spans]
    out = [0] * len(spans)
    for s, e, text in tr.ops.get(device, []):
        if not t.op_name(text).startswith(prefix):
            continue
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < spans[i][1]:
            out[i] += e - s
    return [x / 1e9 for x in out]
