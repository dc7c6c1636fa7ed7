"""Named spans of the serving engine, written into the profiler's trace.

Each span is a ``jax.profiler.TraceAnnotation`` named ``serve.<what>``;
its keyword arguments arrive in the trace as the event's stats. The
profiler is the only sink: with no profiler session running a span costs
about a microsecond, and with one, the spans sit on the host's clock
beside the device's events (XProf's trace viewer, or
``jax.profiler.ProfileData``).

- ``serve.step`` (a step marker), ``ServingEngine.step``: ``step_num``,
  and ``running`` and ``queued`` at the step's start;
- ``serve.prefill``, ``PagedModel.prefill``: ``rid``, ``tokens`` (the
  prompt's length);
- ``serve.decode``, ``PagedModel.decode``: ``live`` (rows decoded),
  ``slots`` (rows in the batch), ``pages`` (table columns the decode
  attention reads, summed over the rows: ``position // block_size + 1``
  each, fewer under a lookback window) and ``width`` (the table's columns
  per row); inside it ``serve.decode.launch`` (the
  batch's upload and the program's dispatch) and ``serve.decode.sample``
  (the argmax over the logits and the tokens' read-back);
- under a grouped MoE (``router="sigmoid_bias"``) only:
  ``serve.decode.experts``, inside ``serve.decode`` after the read-back:
  ``experts_hit``, the (layer, held expert) pairs that computed at least
  one row of the step, which the decode program returns beside the
  logits and the read-back brings with the tokens; and
  ``serve.prefill.experts``, inside ``serve.prefill``: ``held_rows``, the
  prompt's (layer, token, choice) rows that the held experts computed.

The decode program is ``jit_serve_decode`` in the device trace and each
prefill program ``jit_serve_prefill``. Inside them ``jax.named_scope``
marks ``mla_decode`` (absorbed latent attention), ``moe_route`` (the
router's scores and top-k), ``moe_experts`` (the held experts' sort,
grouped matmuls and combine) and ``moe_shared`` (the shared experts) in
the ops' metadata; the latent kernel's instruction is named
``mla_decode_attention``, and the grouped matmuls' ``ragged-dot``.
"""
from __future__ import annotations

from jax.profiler import StepTraceAnnotation, TraceAnnotation

PREFIX = "serve."


def span(what: str, **args) -> TraceAnnotation:
    return TraceAnnotation(PREFIX + what, **args)


def step_span(step_num: int, **args) -> StepTraceAnnotation:
    return StepTraceAnnotation(PREFIX + "step", step_num=step_num, **args)
