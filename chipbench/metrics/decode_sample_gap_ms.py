"""Engine host path: the chip's idle time in each traced decode call
after its decode program ends (the argmax and the tokens' read-back),
mean over the calls, in ms (``decode_calls``)."""


def read(run):
    if run.trace is None:
        return None
    from chipbench import decode_calls

    return decode_calls.mean_gap_ms(run.trace, "sample")
