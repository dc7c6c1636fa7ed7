"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain tuples; everything after that is pure Python over those tuples, so
the tests can check it on a small recorded trace.

- A device plane is one named ``/device:TPU:<n>``; its ops are the events
  on the line ``XLA Ops``.
- Busy time is the union of the op intervals inside the traced window,
  averaged over the devices; idle is the rest of the window.
- An op's event name is its HLO text; ``op_name`` shortens it to the
  instruction's name and opcode. The breakdown lists ops under the name of
  the program (``XLA Modules``) they ran in, and leaves out the loops and
  calls whose events enclose other ops.
- A kernel's time is the summed duration of the ops whose HLO text
  contains a given string, optionally only of those that ran inside a
  given host span. A program's time is the duration of its runs (``XLA
  Modules``) that ran inside one host span: the device's own time for
  that call, without the host's part of it. A call whose program the trace
  does not hold is left out, with its work, rather than counted as free.
- Host spans are the benchmark's own ``TraceAnnotation`` events, named
  ``chipbench.<what>``; an idle gap is put down to the innermost span open
  at its midpoint.
- The device's clock runs apart from the host's by about a millisecond.
  ``read_xplane`` moves the device's events onto the host's clock by the
  least lag between a program's end on the device (``XLA Modules``) and
  the host's ``CompleteCallbacks`` of the same ``run_id``: an upper bound
  of the offset, tight to the host's reaction time.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    """``ops``: device id -> [(start_ns, end_ns, HLO text)]; ``modules``:
    device id -> [(start_ns, end_ns, program name)]; ``spans``: [(start_ns,
    end_ns, name)] of the benchmark's host spans; ``window``: (start_ns,
    end_ns) of the measured window, taken from its span."""

    ops: dict
    spans: list
    window: tuple
    modules: dict = dataclasses.field(default_factory=dict)


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(files)}")
    return files[0]


def read_xplane(path: str, window_span: str = "chipbench.window") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict = {}
    modules: dict = {}
    spans = []
    ends = {}  # (device, run_id) -> end of the program on the device
    done = {}  # (device, run_id) -> host's CompleteCallbacks start
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            dev = int(plane.name[12:])
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.start_ns, e.end_ns, e.name)
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        run = dict(e.stats).get("run_id")
                        ends[(dev, run)] = e.end_ns
                        modules.setdefault(dev, []).append(
                            (e.start_ns, e.end_ns, e.name.split("(")[0]))
            ops[dev] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.end_ns, e.name))
                    elif e.name == "CompleteCallbacks":
                        st = dict(e.stats)
                        done[(st.get("device_ordinal"), st.get("run_id"))] = (
                            e.start_ns)
    lags = [done[k] - ends[k] for k in ends if k in done]
    skew = min(lags) if lags else 0.0
    ops, modules = ({d: [(s + skew, e + skew, n) for s, e, n in evs]
                     for d, evs in x.items()} for x in (ops, modules))
    win = [s for s in spans if s[2] == window_span]
    if not win:
        raise RuntimeError(f"no {window_span!r} span in the trace")
    return Trace(ops=ops, spans=spans, window=(win[0][0], win[0][1]),
                 modules=modules)


def _union(intervals, lo, hi):
    """Merged intervals clipped to [lo, hi], in order."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(tr: Trace) -> float:
    """Seconds in which some op ran, averaged over the devices."""
    lo, hi = tr.window
    if not tr.ops:
        return 0.0
    total = sum(sum(e - s for s, e in _union(evs, lo, hi))
                for evs in tr.ops.values())
    return total / len(tr.ops) / 1e9


def window_seconds(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


def idle_gaps(tr: Trace, device: int = 0):
    """[(start_ns, end_ns)] of the window in which ``device`` ran nothing."""
    lo, hi = tr.window
    gaps, t = [], lo
    for s, e in _union(tr.ops.get(device, []), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[..] fusion(...), ...`` -> ``fusion.3 fusion``."""
    head, _, rest = text.partition(" = ")
    m = re.search(r"\s([a-z][a-z0-9-]*)\(", rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def _inside(spans, name: str):
    """A test of whether a time lies in a host span ``chipbench.<name>``."""
    index = sorted((s, e) for s, e, n in spans if n == SPAN_PREFIX + name)
    starts = [s for s, _ in index]

    def test(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < index[i][1]

    return test


def kernel_seconds(tr: Trace, text: str, inside: str | None = None) -> float:
    """Summed device time, inside the window and averaged over the
    devices, of the ops whose HLO text contains ``text``; with
    ``inside``, only of those whose midpoint falls in a host span named
    ``chipbench.<inside>``."""
    lo, hi = tr.window
    within = _inside(tr.spans, inside or "")
    total = 0
    for evs in tr.ops.values():
        total += sum(min(e, hi) - max(s, lo) for s, e, name in evs
                     if text in name and e > lo and s < hi
                     and (inside is None or within((s + e) // 2)))
    return total / max(len(tr.ops), 1) / 1e9


def program_seconds(tr: Trace, inside: str) -> list:
    """For each host span named ``chipbench.<inside>`` that lies in the
    window, in order: the device time of the programs (``XLA Modules``,
    device 0) whose midpoint falls in it, or None where the trace holds
    none (an event the profiler dropped, or one put beside its span)."""
    lo, hi = tr.window
    spans = sorted((s, e) for s, e, n in tr.spans
                   if n == SPAN_PREFIX + inside and s >= lo and e <= hi)
    starts = [s for s, _ in spans]
    out = [0] * len(spans)
    for s, e, _ in tr.modules.get(0, []):
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < spans[i][1]:
            out[i] += e - s
    return [t / 1e9 if t else None for t in out]


def paired(tr: Trace, inside: str, calls: list) -> list:
    """[(call, device seconds)]: the benchmark's own records of the calls
    that began in the traced part of the window (``calls``, in order),
    each beside the device time of its span (``program_seconds``), where
    the trace holds one. Empty where the two do not count alike."""
    times = program_seconds(tr, inside)
    if len(times) != len(calls):
        return []
    return [(c, t) for c, t in zip(calls, times) if t]


def idle_inside(tr: Trace, inside: str, device: int = 0) -> float:
    """Seconds of the window in which ``device`` ran nothing while a host
    span named ``chipbench.<inside>`` was open."""
    spans = _union([(s, e) for s, e, n in tr.spans
                    if n == SPAN_PREFIX + inside], *tr.window)
    gaps = idle_gaps(tr, device)
    total, i, j = 0, 0, 0
    while i < len(gaps) and j < len(spans):  # both sorted and disjoint
        (gs, ge), (s, e) = gaps[i], spans[j]
        total += max(0, min(ge, e) - max(gs, s))
        if ge < e:
            i += 1
        else:
            j += 1
    return total / 1e9


ENCLOSING = ("while", "conditional", "call")


def top_ops(tr: Trace, n: int = 10):
    """[[program/op, seconds]] of the ops that took most device time in the
    window, summed by name, averaged over the devices; loops and calls,
    whose events enclose their body's ops, are left out."""
    lo, hi = tr.window
    by = defaultdict(int)
    for dev, evs in tr.ops.items():
        mods = sorted(tr.modules.get(dev, []))
        starts = [m[0] for m in mods]
        for s, e, name in evs:
            short = op_name(name)
            if e <= lo or s >= hi or short.rsplit(" ", 1)[-1] in ENCLOSING:
                continue
            i = bisect.bisect_right(starts, (s + e) // 2) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= e else "?"
            by[f"{prog}/{short}"] += min(e, hi) - max(s, lo)
    k = max(len(tr.ops), 1)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / k / 1e9] for name, t in ranked]


def _by_name(spans):
    """name -> (starts, spans) sorted by start; spans of one name do not
    overlap one another."""
    out = defaultdict(list)
    for s in spans:
        out[s[2]].append(s)
    return {k: ([s[0] for s in v], v)
            for k, v in ((k, sorted(v)) for k, v in out.items())}


def host_at(index, t: int) -> str:
    """The innermost benchmark span open at ``t`` (``index`` from
    ``_by_name``), without its prefix; ``none`` outside every span."""
    best = None
    for starts, spans in index.values():
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and spans[i][0] <= t < spans[i][1]:
            s, e, name = spans[i]
            if best is None or e - s < best[1] - best[0]:
                best = spans[i]
    return "none" if best is None else best[2][len(SPAN_PREFIX):]


def idle_by_host(tr: Trace, n: int = 10):
    """[[host span, seconds]]: the device's idle time in the window, summed
    by what the host was doing at each gap's midpoint, largest first."""
    index = _by_name(tr.spans)
    by = defaultdict(int)
    for s, e in idle_gaps(tr):
        by[host_at(index, (s + e) // 2)] += e - s
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / 1e9] for name, t in ranked]
