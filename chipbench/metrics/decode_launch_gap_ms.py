"""Engine host path: the chip's idle time in each traced decode call
before its decode program starts (the batch's upload and the program's
dispatch), mean over the calls, in ms (``decode_calls``)."""


def read(run):
    if run.trace is None:
        return None
    from chipbench import decode_calls

    return decode_calls.mean_gap_ms(run.trace, "launch")
