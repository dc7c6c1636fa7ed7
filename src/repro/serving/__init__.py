"""Serving layer: continuous batching over a paged KV cache.

The package splits along the host/device boundary:

  - ``scheduler``   — pure-Python request scheduler + block allocator (no
                      jax import: the analysis plan rule replays it
                      device-free)
  - ``paged_cache`` — the ``PagedKVCache`` pytree (physical KV block pools,
                      optionally fp8-quantized) and its pure write helpers
  - ``ring_decode`` — cache-sharded decode over the ``data`` axis
                      (per-shard partials folded through
                      ``collectives.ring_scan`` + ``online_softmax_merge``)
  - ``engine``      — the continuous-batching loop wiring the scheduler to
                      jitted paged prefill/decode steps (imports the model
                      stack; import it explicitly)
  - ``tracing``     — the engine's named spans (``serve.<what>``) in the
                      profiler's trace
"""
from repro.serving.paged_cache import PagedKVCache, NULL_BLOCK
from repro.serving.scheduler import (
    BlockAllocator,
    ContinuousBatchingScheduler,
    Request,
)

__all__ = [
    "BlockAllocator",
    "ContinuousBatchingScheduler",
    "NULL_BLOCK",
    "PagedKVCache",
    "Request",
]
