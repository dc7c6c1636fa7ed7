"""Engine host path: the share of the traced window in which the chip ran
nothing while ``ServingEngine.step()`` was running, in percent: the host's
admission, block growth, building and uploading of the batch and the
tables, reading of the sampled tokens and bookkeeping, inside the model's
calls or around them. The device waits on all of it alike."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    from chipbench import trace

    win = trace.window_seconds(run.trace)
    if win <= 0:
        return None
    return 100.0 * trace.idle_inside(run.trace, "engine_step") / win
