"""Model: operations of all the work the window served (prefills and
decode steps, ``counting``) over the window times the chips' peak, in
percent."""

from chipbench import counting


def read(run):
    c, m = counting, run.m
    flops = (sum(c.prefill_flops(m, n) for *_, n in run.rec.prefill)
             + sum(c.decode_flops(m, ctx) for _, _, ctx in run.rec.decode))
    if flops == 0:
        return None
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["bf16_flops_per_s"])
