"""Serving-engine benchmark: continuous batching under Poisson load.

Drives the ``repro.serving`` engine — continuous-batching scheduler over a
paged KV cache — with a seeded open-loop arrival process and reports the
serving figures of merit: decode throughput (tok/s), preemption and
admission counts, and the block-ledger audit (leaked blocks must be 0).
Request latency in seconds on the chip is the chip benchmark's
(``chipbench/``).

Arrivals are Poisson in virtual time: request r arrives at step
``cumsum(Exp(1/lam))_r`` — deterministic given ``--seed``. EOS is disabled,
so retirement timing is pure scheduler arithmetic and the admission trace
``(step, rid, slot)*`` is a machine-independent function of the seed; the
committed ``BENCH_serve.json`` pins its hash and CI re-asserts it without
devices (same seed -> same admission trace, on any machine).

The committed baseline is produced by::

    PYTHONPATH=src python -m benchmarks.bench_serve --json BENCH_serve.json

``--smoke`` asserts the CI serving-job invariants (every request
completed, zero leaked blocks) and exits nonzero on violation.
"""
import argparse
import hashlib
import time

import jax
import numpy as np

from benchmarks.common import emit_json, row
from repro.configs.base import get_config
from repro.models import registry as model_registry
from repro.serving.engine import Request, ServingEngine


def poisson_requests(rng, *, n, lam, vocab, prompt_lens=(4, 24),
                     gen_lens=(4, 16), priorities=(0, 0, 0, 1)):
    """Seeded open-loop workload: ``n`` requests with Exp(1/lam)
    inter-arrival steps (a Poisson process in virtual time), uniform
    prompt/gen lengths and a priority mix. Deterministic given ``rng``."""
    t = 0.0
    reqs = []
    for rid in range(n):
        t += rng.exponential(1.0 / lam)
        plen = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        reqs.append(Request(
            rid=rid,
            prompt=tuple(int(x) for x in rng.integers(1, vocab, plen)),
            max_new_tokens=int(rng.integers(gen_lens[0], gen_lens[1] + 1)),
            priority=int(priorities[rng.integers(0, len(priorities))]),
            arrival=int(t),
        ))
    return reqs


def trace_hash(engine) -> str:
    """SHA-256 over the admission trace — the reproducibility artifact."""
    return hashlib.sha256(
        repr(engine.scheduler.admission_trace()).encode()
    ).hexdigest()


def run(args):
    cfg = get_config(args.arch, reduced=True)
    params = model_registry.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(args.seed)
    reqs = poisson_requests(rng, n=args.requests, lam=args.rate,
                            vocab=cfg.vocab_size)

    engine = ServingEngine.with_model(
        cfg, params,
        num_blocks=args.num_blocks, block_size=args.block_size,
        max_slots=args.slots, max_blocks_per_seq=args.max_blocks_per_seq,
        eos_id=None,  # no EOS: the trace is scheduler arithmetic only
    )
    for r in reqs:
        engine.submit(r)

    t0 = time.perf_counter()
    engine.run(max_steps=args.max_steps)
    wall = time.perf_counter() - t0

    tokens = sum(len(v) for v in engine.completed.values())
    events = engine.scheduler.events
    preempts = sum(1 for e in events if e[0] == "preempt")
    leaked = engine.leaked_blocks()
    thash = trace_hash(engine)

    row("serve/throughput", wall / max(tokens, 1),
        f"{tokens / wall:.1f} tok/s",
        tokens=tokens, wall_s=wall, arch=args.arch, seed=args.seed,
        requests=args.requests, completed=len(engine.completed),
        steps=engine.step_count)
    row("serve/latency", wall / max(engine.step_count, 1),
        f"{preempts} preemptions", preemptions=preempts,
        leaked_blocks=leaked, trace_sha256=thash,
        num_blocks=args.num_blocks, block_size=args.block_size,
        slots=args.slots)

    print(f"completed={len(engine.completed)}/{args.requests} "
          f"tokens={tokens} steps={engine.step_count} "
          f"preemptions={preempts} leaked={leaked}")
    print(f"trace_sha256={thash}")

    if args.smoke:
        assert len(engine.completed) > 0, "smoke: no requests completed"
        assert leaked == 0, f"smoke: {leaked} leaked blocks"
        assert len(engine.completed) == args.requests, (
            f"smoke: only {len(engine.completed)}/{args.requests} finished"
        )
        print("smoke OK")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=1.5,
                    help="mean arrivals per engine step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--num-blocks", type=int, default=12)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-blocks-per-seq", type=int, default=6)
    ap.add_argument("--max-steps", type=int, default=5000)
    ap.add_argument("--json", default=None)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    rc = run(args)
    if args.json:
        emit_json(args.json)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
