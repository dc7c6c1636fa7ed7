"""Decode program: the least time the decode steps of the traced part of
the window need at the chip's peaks, the larger of the compute and the
memory bound (every weight once, the live tokens' KV, the new KV written;
``counting.decode_bytes``), over the device time of the programs that ran
inside those decode calls (``trace.paired``), in percent. The
host's part of each call (building and uploading the batch and the tables,
reading the sampled tokens back) is left out: it is the engine host
path's."""

from chipbench import counting


def read(run):
    if run.trace is None:
        return None
    from chipbench import trace

    calls = trace.paired(run.trace, "decode", run.traced(run.rec.decode))
    if not calls:
        return None
    busy = sum(t for _, t in calls)
    c, m, pk = counting, run.m, run.peaks
    need = sum(c.roofline_seconds(c.decode_flops(m, ctx),
                                  c.decode_bytes(m, ctx), pk)
               for (_, _, ctx), _ in calls)
    return 100.0 * need / (busy * run.chips)
