"""Decode program: model operations of the decode steps in the traced part
of the window (``counting.decode_flops``) over the device time of the
programs that ran inside those decode calls (``trace.paired``)
times the chips' peak, in percent."""

from chipbench import counting


def read(run):
    if run.trace is None:
        return None
    from chipbench import trace

    calls = trace.paired(run.trace, "decode", run.traced(run.rec.decode))
    if not calls:
        return None
    busy = sum(t for _, t in calls)
    flops = sum(counting.decode_flops(run.m, ctx) for (_, _, ctx), _ in calls)
    return 100.0 * flops / (busy * run.chips * run.peaks["bf16_flops_per_s"])
