"""Device: the share of the traced window in which no op ran on the chip
(1 - the union of the op intervals over the window), in percent."""


def read(run):
    if run.trace is None:
        return None
    from chipbench import trace

    win = trace.window_seconds(run.trace)
    if win <= 0 or not run.trace.ops:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(run.trace) / win)
