"""Prefill program: prompt tokens over the summed prefill spans (each span
ends in the host's read of the first token, so it holds the device time)."""


def read(run):
    spans = run.rec.prefill
    busy = sum(b - a for a, b, *_ in spans)
    if not spans or busy <= 0:
        return None
    return sum(n for *_, n in spans) / busy
