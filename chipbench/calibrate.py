"""Readings that set a cell's limit and rate, many seeds in one process.

    python3 chipbench/calibrate.py --workload gptj-chat --seconds 20 \
        --seeds 12 [--sweep 1.6,2.0,2.4]

Builds the cell once and warms it once, for the prompt lengths of every
rate it will serve. For each seed it draws the weights anew, serves the mix
at the cell's rate for ``--seconds`` through the same window and check as a
run, frees the weights, and reads:

- ``program``: the widest and the mean gap by which a served token's
  float32 reference logit lies below the reference's best (what a run
  compares with the cell's ``limits``): the lower readings;
- ``control``: the same gaps for the token the fp8 reference puts first
  at each of those positions (the reference one precision step below the
  served bfloat16, in the program's place): the upper readings.

With ``--sweep`` it then serves each listed rate (requests per second)
once, on the first seed, and reports the queue over the window and the
tails: the knee is the highest rate whose queue does not grow.

One JSON object per reading goes to standard output. It does not replace
a run: the benchmark's own runs never compute the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
FIRST_SEED = 7_000_000_001  # apart from the seeds the runs were tried on


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--sweep", default="",
                    help="comma-separated rates to serve after the seeds")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import harness, traffic, weights
    from repro.serving.engine import ServingEngine

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = harness.load_bench()
    cell = harness.load_cell(bench, args.workload)
    harness.require_devices(cell["chips"])
    mix, geo, check = cell["mix"], cell["engine"], cell["check"]
    length = geo["max_blocks_per_seq"] * geo["block_size"]
    seeds = [FIRST_SEED + 1_000_003 * i for i in range(args.seeds)]
    rates = [float(r) for r in args.sweep.split(",") if r]

    engine, m = harness.build(cell, seeds[0])
    model = engine.model
    plain = (model.prefill, model.decode)
    harness.warm(engine, [q for r in [cell["rate_per_s"], *rates]
                          for q in traffic.plan(mix, r, args.seconds,
                                                seeds[0], m["vocab"])],
                 geo["block_size"])

    def serve(seed, rate):
        model.prefill, model.decode = plain
        eng = ServingEngine(model, **geo)
        rec = harness.Recorder()
        harness.instrument(eng, rec)
        queue = []
        step = eng.step

        def step_q():
            out = step()
            sc = eng.scheduler
            queue.append((time.perf_counter(), len(sc.pending)
                          + sum(len(q) for q in sc.queues.values())))
            return out

        eng.step = step_q
        planned = traffic.plan(mix, rate, args.seconds, seed, m["vocab"])
        compiles = harness.CompileCounter()
        win = harness.serve_window(eng, planned, args.seconds, rec, compiles)
        win["compiles"] = compiles.n
        return eng, rec, win, planned, queue

    def emit(obj):
        print(json.dumps(obj), flush=True)

    for i, seed in enumerate(seeds):
        if i:
            model.params = weights.make_params(m, seed,
                                               jax.numpy.dtype(m["dtype"]))
        eng, rec, win, planned, _ = serve(seed, cell["rate_per_s"])
        e2e = harness.end_to_end(win, rec, planned)
        pairs = harness.sample(eng, planned, seed, check["requests"])
        model.params = None
        gc.collect()
        f32, where = harness.reference_logits(m, seed, pairs, length,
                                              check["requests"])
        fp8, _ = harness.reference_logits(m, seed, pairs, length,
                                          check["requests"], fp8=True)
        prog = harness.gaps(f32, [t for _, _, t in where])
        ctl = harness.gaps(f32, fp8.argmax(1))
        emit({"seed": seed, "tokens": len(prog),
              "program": harness.gap_numbers(prog),
              "control": harness.gap_numbers(ctl),
              "program_flips": float((prog > 0).mean()),
              "control_flips": float((ctl > 0).mean()), **e2e})
    model.params = weights.make_params(m, seeds[0],
                                       jax.numpy.dtype(m["dtype"]))
    for rate in rates:
        eng, rec, win, planned, queue = serve(seeds[0], rate)
        t0, t1 = win["t0"], win["t1"]
        half = [q for t, q in queue if t <= t0 + (t1 - t0) / 2]
        emit({"rate_per_s": rate, "due": len(planned),
              "finished": sum(q.rid in eng.completed for q in planned),
              "queue_mid": half[-1] if half else 0,
              "queue_end": queue[-1][1] if queue else 0,
              "queue_max": max((q for _, q in queue), default=0),
              "running_end": len(eng.scheduler.running),
              "preemptions": sum(e[0] == "preempt"
                                 for e in eng.scheduler.events),
              "compiles_inside": win["compiles"],
              **harness.end_to_end(win, rec, planned),
              "decode_step_ms": 1e3 * float(np.mean(
                  [b - a for a, b, _ in rec.decode])) if rec.decode else None})


if __name__ == "__main__":
    main()
