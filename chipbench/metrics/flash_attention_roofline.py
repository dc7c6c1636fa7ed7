"""Kernels: the least time the prefill attention of the traced part of the
window needs (``counting.flash_attention_cost`` at the chip's peaks) over the
device time of the ``flash_attention`` kernel in the trace, in percent.

The program does not name its Pallas kernels in the trace yet: every one
is a ``tpu_custom_call`` op named for its caller. Until one is named
``flash_attention``, the kernel's time is that of the Pallas ops that ran
inside the benchmark's prefill spans; the serving path's one Pallas kernel
in prefill is flash attention (decode attention has no Pallas form)."""

from chipbench import counting


PALLAS = 'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None or not run.rec.prefill:
        return None
    from chipbench import trace

    busy = (trace.kernel_seconds(run.trace, "flash_attention")
            or trace.kernel_seconds(run.trace, PALLAS, inside="prefill"))
    if busy <= 0:
        return None
    c = counting
    need = sum(c.roofline_seconds(*c.flash_attention_cost(run.m, n),
                                  run.peaks)
               for *_, n in run.traced(run.rec.prefill))
    return 100.0 * need / busy
