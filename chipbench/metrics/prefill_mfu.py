"""Prefill program: model operations of the prompts prefilled in the traced
part of the window (``counting.prefill_flops``) over the device time of the
programs that ran inside those prefill calls (``trace.paired``) times the
chips' peak, in percent. The host's part of each call (building and
uploading the prompt, reading the first token back) is left out."""

from chipbench import counting


def read(run):
    if run.trace is None:
        return None
    from chipbench import trace

    calls = trace.paired(run.trace, "prefill", run.traced(run.rec.prefill))
    if not calls:
        return None
    busy = sum(t for _, t in calls)
    flops = sum(counting.prefill_flops(run.m, n) for (*_, n), _ in calls)
    return 100.0 * flops / (busy * run.chips * run.peaks["bf16_flops_per_s"])
