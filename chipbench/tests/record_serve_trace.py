"""Records ``data/v5e_serve_small.xplane.pb`` and ``data/v5e_serve_small.json``
on one TPU: a few steps of the engine at a tiny width (one layer), under
the benchmark's spans (``harness.instrument``) and the engine's own
(``serve.<what>``), with the benchmark's profiler options.

    python3 chipbench/tests/record_serve_trace.py --out <dir>   # on the chip
    python3 chipbench/tests/record_serve_trace.py --prune <dir>  # anywhere

``--prune`` keeps what the readers read and drops the rest, which would
be most of the file: the programs' HLO (the ``/host:metadata`` plane) and
every host event but the spans (``chipbench.``, ``serve.``), the jitted
calls (``PjitFunction(...)``) and ``CompleteCallbacks``. It needs
TensorFlow's ``xplane_pb2``.

The JSON holds what a per-layer reader needs beside the trace: the
Recorder's lists, the window's times, the requests' due times and the
model's sizes. Two requests are admitted in the first traced step and
one more in the second, and they finish after 3, 5 and 4 tokens: four
decode calls of 2, 3, 2 and 2 live rows.
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench import trace as tr  # noqa: E402

GEOMETRY = {"max_slots": 4, "block_size": 16, "max_blocks_per_seq": 4,
            "num_blocks": 24}
# (prompt length, tokens to generate, step at which it is submitted)
REQUESTS = [(20, 3, 0), (25, 5, 0), (30, 4, 1)]
SEED = 2**33 + 17
NAME = "v5e_serve_small"


def small_config(cell):
    from repro.configs.base import get_config

    arch = cell["model_file"]["program"]["arch"]
    return get_config(arch, reduced=True).replace(
        dtype=cell["model_file"]["model"]["dtype"], num_layers=1)


def record(out: Path) -> dict:
    import jax

    from repro.serving.engine import Request

    bench = harness.load_bench()
    cell = harness.load_cell(bench, "gptj-chat")
    cell.update(engine=GEOMETRY,
                mix=dict(cell["mix"],
                         prompt={"dist": "uniform", "min": 17, "max": 32},
                         output={"dist": "uniform", "min": 3, "max": 5}))
    engine, m = harness.build(cell, SEED, small_config(cell))
    rec = harness.Recorder()
    harness.instrument(engine, rec)
    rng = np.random.default_rng(SEED)

    def prompt(n):
        return tuple(int(x) for x in rng.integers(1, m["vocab"], n))

    # warm every program the traced steps run
    for i, (n, _, _) in enumerate(REQUESTS):
        engine.submit(Request(rid=100 + i, prompt=prompt(n),
                              max_new_tokens=2, arrival=engine.step_count))
    engine.run(max_steps=engine.step_count + 50)
    jax.block_until_ready(engine.model.cache)

    log_dir = tempfile.mkdtemp(prefix="serve-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    window = jax.profiler.TraceAnnotation("chipbench.window")
    window.__enter__()
    t0 = time.perf_counter()
    due = {}
    rec.on = True
    first = engine.step_count
    while not engine.scheduler.idle() or len(due) < len(REQUESTS):
        for rid, (n, new, at) in enumerate(REQUESTS):
            if rid not in due and engine.step_count - first >= at:
                engine.submit(Request(rid=rid, prompt=prompt(n),
                                      max_new_tokens=new,
                                      arrival=engine.step_count))
                due[rid] = time.perf_counter()
        engine.step()
    t1 = time.perf_counter()
    window.__exit__(None, None, None)
    rec.on = False
    jax.block_until_ready(engine.model.cache)
    jax.profiler.stop_trace()

    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(tr.find_xplane(log_dir), out / f"{NAME}.xplane.pb")
    shutil.rmtree(log_dir, ignore_errors=True)
    data = {
        "device_kind": jax.devices()[0].device_kind, "chips": 1, "m": m,
        "win": {"t0": t0, "t1": t1, "trace_t0": t0,
                "due": {str(k): v for k, v in due.items()}},
        "rec": {"prefill": rec.prefill, "decode": rec.decode,
                "step": rec.step,
                "admitted": {str(k): v for k, v in rec.admitted.items()},
                "tokens": {str(k): v for k, v in rec.tokens.items()}},
        "completed": {str(k): list(v) for k, v in engine.completed.items()
                      if k < 100},
    }
    with open(out / f"{NAME}.json", "w") as f:
        json.dump(data, f, indent=1)
    return data


def prune(path: Path) -> None:
    """Rewrites the trace at ``path`` with only what the readers read."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(path.read_bytes())
    for plane in list(space.planes):
        if plane.name == "/host:metadata":
            space.planes.remove(plane)
        elif plane.name.startswith("/host:"):
            meta = plane.event_metadata
            for line in plane.lines:
                keep = [e for e in line.events
                        if meta[e.metadata_id].name.startswith(
                            ("chipbench.", "serve.", "PjitFunction("))
                        or meta[e.metadata_id].name == "CompleteCallbacks"]
                del line.events[:]
                line.events.extend(keep)
            used = {e.metadata_id for line in plane.lines
                    for e in line.events}
            for k in [k for k in meta if k not in used]:
                del meta[k]
    path.write_bytes(space.SerializeToString())


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--out", help="record into this directory (a TPU)")
    mode.add_argument("--prune", help="prune the trace in this directory")
    args = ap.parse_args()
    if args.out:
        harness.require_devices(1)
        record(Path(args.out))
    else:
        prune(Path(args.prune) / f"{NAME}.xplane.pb")
    for p in sorted(Path(args.out or args.prune).glob(f"{NAME}.*")):
        print(p, p.stat().st_size)
