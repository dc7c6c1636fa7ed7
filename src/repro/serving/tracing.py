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
  ``slots`` (rows in the batch); inside it ``serve.decode.launch`` (the
  batch's upload and the program's dispatch) and ``serve.decode.sample``
  (the argmax over the logits and the tokens' read-back).

The decode program is ``jit_serve_decode`` in the device trace and each
prefill program ``jit_serve_prefill``.
"""
from __future__ import annotations

from jax.profiler import StepTraceAnnotation, TraceAnnotation

PREFIX = "serve."


def span(what: str, **args) -> TraceAnnotation:
    return TraceAnnotation(PREFIX + what, **args)


def step_span(step_num: int, **args) -> StepTraceAnnotation:
    return StepTraceAnnotation(PREFIX + "step", step_num=step_num, **args)
