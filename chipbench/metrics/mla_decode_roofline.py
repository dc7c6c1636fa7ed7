"""Kernels: the least time the absorbed latent decode attention of the
traced decode calls needs at the chip's peaks, over the device time of its
Pallas kernel, in percent.

The need of a call is the block's ``latent_attention_cost`` over its live
rows: each live token's latent row (576 values of 2 bytes for each of the
27 layers) read once, and 2 x 16 x (576 + 512) operations a live token and
layer. The kernel is the instruction named ``mla_decode_attention``
(``kernels/paged_attention.py``) on chip 0, inside each of the decode
calls that began in the traced part of the window (``named_ops``); a call
whose kernel the trace does not hold is left out with its need. None where
the block has no latent attention or the trace no such kernel."""

KERNEL = "mla_decode_attention"


def read(run):
    cost = getattr(run.block, "latent_attention_cost", None)
    if run.trace is None or cost is None:
        return None
    from chipbench import counting, named_ops

    calls = run.traced(run.rec.decode)
    times = named_ops.per_call(run.trace, "decode", KERNEL)
    if not calls or len(times) != len(calls):
        return None
    pairs = [(ctx, s) for (_, _, ctx), s in zip(calls, times) if s > 0]
    busy = sum(s for _, s in pairs)
    if busy <= 0:
        return None
    need = sum(counting.roofline_seconds(*cost(run.m, ctx), run.peaks)
               for ctx, _ in pairs)
    return 100.0 * need / busy
