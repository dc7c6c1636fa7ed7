"""Decode program: the chip's idle time in each traced decode call while
its decode program runs (gaps between the program's ops), mean over the
calls, in ms (``decode_calls``)."""


def read(run):
    if run.trace is None:
        return None
    from chipbench import decode_calls

    return decode_calls.mean_gap_ms(run.trace, "program")
