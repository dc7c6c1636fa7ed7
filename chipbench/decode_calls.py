"""The device's idle time inside each decode call, split at the run of the
call's decode program.

A decode call is a host span around ``PagedModel.decode``: the
benchmark's ``chipbench.decode`` (``trace.Trace.spans``) or the program's
own ``serve.decode`` (``read_program``); both wrap the same call, a few
microseconds apart. Its decode program is the ``XLA Modules`` event named
``jit_serve_decode`` on device 0 whose midpoint falls in the span; a call
with none, or with more than one, is left out, and so is a call that does
not lie wholly inside the window. The idle time of a call then splits
into three parts that sum to the idle inside its span:

- ``launch``: from the span's start to the program's start (the batch's
  upload and the dispatch);
- ``program``: from the program's start to its end (gaps between the
  program's own ops);
- ``sample``: from the program's end to the span's end (the argmax and
  the tokens' read-back).

Device events are on the host's clock, as ``trace.read_xplane`` moves
them; the program's spans are host events and need no move.
"""
from __future__ import annotations

import bisect

from chipbench import trace as t

DECODE_PROGRAM = "jit_serve_decode"
PARTS = ("launch", "program", "sample")


def read_program(path: str) -> list:
    """[(start_ns, end_ns, name, args)] of the program's own host spans
    (``serve.<what>``) in an ``.xplane.pb``, in order of start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns, e.end_ns, e.name, dict(e.stats))
                           for e in line.events
                           if e.name.startswith("serve."))
    return sorted(out, key=lambda s: s[:2])


def idle_within(gaps: list, ends: list, lo: int, hi: int) -> int:
    """Nanoseconds of ``gaps`` (``trace.idle_gaps``: in order, disjoint;
    ``ends`` their ends) that lie in [lo, hi]."""
    total, i = 0, bisect.bisect_right(ends, lo)
    while i < len(gaps) and gaps[i][0] < hi:
        total += min(gaps[i][1], hi) - max(gaps[i][0], lo)
        i += 1
    return total


def decode_calls(tr: t.Trace, spans) -> list:
    """[(span_start, span_end, program_start, program_end)] of the decode
    calls (``spans``: (start_ns, end_ns, ...)) that lie in the window and
    hold exactly one decode program."""
    lo, hi = tr.window
    mods = [(s, e) for s, e, name in tr.modules.get(0, [])
            if name == DECODE_PROGRAM]
    out = []
    for s, e, *_ in spans:
        if s < lo or e > hi:
            continue
        inside = [m for m in mods if s <= (m[0] + m[1]) // 2 < e]
        if len(inside) == 1:
            out.append((s, e, *inside[0]))
    return out


def decode_gaps(tr: t.Trace, spans) -> list:
    """[(launch_ns, program_ns, sample_ns)]: the idle parts of each call
    of ``decode_calls``, the program's run clipped to its span."""
    gaps = t.idle_gaps(tr)
    ends = [e for _, e in gaps]
    out = []
    for s, e, ms, me in decode_calls(tr, spans):
        ms, me = max(ms, s), min(me, e)
        out.append(tuple(idle_within(gaps, ends, lo, hi)
                         for lo, hi in ((s, ms), (ms, me), (me, e))))
    return out


def benchmark_spans(tr: t.Trace) -> list:
    """The benchmark's decode spans, ``chipbench.decode``."""
    return [s for s in tr.spans if s[2] == t.SPAN_PREFIX + "decode"]


def mean_gap_ms(tr: t.Trace, part: str):
    """The mean over the benchmark's decode calls of one idle ``part``
    (``PARTS``), in ms; None where no call holds its decode program."""
    gaps = decode_gaps(tr, benchmark_spans(tr))
    if not gaps:
        return None
    i = PARTS.index(part)
    return sum(g[i] for g in gaps) / len(gaps) / 1e6
