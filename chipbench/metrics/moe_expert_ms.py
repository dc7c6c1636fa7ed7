"""Expert layer: chip 0's device time, per decode call, of the held
experts' grouped matmuls, in ms: the ops the compiler names ``ragged-dot``
(``jax.lax.ragged_dot`` inside the ``moe_experts`` scope of
``models/moe.py``; the trace's HLO text carries instruction names and no
scopes, and no other op of the decode program is a grouped matmul), mean
over the decode calls that lie wholly in the traced window
(``named_ops``). The router, the sort and gather of the rows and the
combine, also in that scope, are small element-wise and sort ops that the
trace does not name. None where the trace holds no such op."""

PREFIX = "ragged-dot"


def read(run):
    if run.trace is None:
        return None
    from chipbench import named_ops

    times = named_ops.per_call(run.trace, "decode", PREFIX)
    if not times or not any(times):
        return None
    return 1e3 * sum(times) / len(times)
