"""Decode program: the mean decode span in milliseconds (from the call to
the host's read of the sampled tokens)."""


def read(run):
    spans = run.rec.decode
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b, _ in spans) / len(spans)
