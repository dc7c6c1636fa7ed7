"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one cell is found by name in ``BENCHMARK.json``:
the workload names a configuration (``configs``: its ``file``) and a traffic
mix (``chipbench/mixes/<traffic>.json``: the length distributions), and has
a file of its own (``chipbench/cells/<workload>.json``: the rate it is
offered, the engine geometry and the check's sample and limits, which
follow from the model and the chip); each per-layer metric is read by
``chipbench/metrics/<name>.py``. Adding a cell, a configuration, a mix or a
metric is adding files and entries; nothing here names one.

A run:

1. checks the devices (a TPU, as many chips as the cell asks for) and
   looks up their peaks in ``peaks.json`` by ``device_kind``;
2. draws the weights on the device from the seed (``weights``), builds the
   engine with the cell's geometry, and warms the prefill programs of the
   prompt lengths the window will send and the decode step;
3. drives ``ServingEngine.step()`` from an open-loop client for
   ``seconds``: each request is submitted once its due time has passed,
   and every latency is taken from that due time;
4. reads the peak memory, frees the engine, and checks a sample of the
   finished requests against the float32 reference (``reference``): the
   widest gap by which a served token's reference logit lies below the
   reference's best must stay within the cell's limit.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from chipbench import traffic

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
WARM_RID0 = 1 << 40  # request ids of the warm-up, apart from the window's
CHECK_SALT = 0x5EED  # the check's sample is drawn from seed ^ CHECK_SALT
NOT_READ = 1e30  # the gap reported when none could be read
# A traced run traces only the window's last seconds: over a whole long
# window the profiler drops device events, and busy time reads low.
TRACE_SECONDS = 8.0


class BenchError(RuntimeError):
    """A run that cannot give a result (wrong devices, bad cell)."""


# -- the cell, by name --------------------------------------------------------


def load_bench(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(bench: dict, workload: str, root: Path = ROOT) -> dict:
    """The workload entry with its configuration file, its mix and its
    cell file (``rate_per_s``, ``engine``, ``check``) resolved."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = dict(cells[workload])
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[cell["config"]]["file"]) as f:
        cell["model_file"] = json.load(f)
    cell["mix"] = traffic.load_mix(HERE / "mixes" / f"{cell['traffic']}.json")
    with open(HERE / "cells" / f"{workload}.json") as f:
        own = json.load(f)
    cell.update({k: own[k] for k in ("rate_per_s", "engine", "check")})
    return cell


def metrics_for(bench: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this workload reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def load_reader(name: str):
    """``chipbench/metrics/<name>.py``'s ``read(run)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    with open(HERE / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise BenchError(f"device_kind {kind!r} is not in peaks.json "
                         f"(have {sorted(table)})")
    return table[kind]


def require_devices(chips: int):
    """The devices of the cell: a TPU and at least ``chips`` of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def sizes_of(cfg) -> dict:
    """A ``ModelConfig`` in the vocabulary of a configuration file's
    ``model`` block."""
    return {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
            "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.resolved_head_dim(), "d_ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "activation": cfg.activation,
            "gated": cfg.activation in ("swiglu", "geglu"),
            "parallel_block": cfg.parallel_block,
            "tie_embeddings": cfg.tie_embeddings,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
            "dtype": cfg.dtype}


def program_config(model_file: dict):
    """The engine's ``ModelConfig``: the registry arch with the file's
    replacements, cross-checked against the file's ``model`` sizes."""
    from repro.configs.base import get_config

    prog = model_file["program"]
    cfg = get_config(prog["arch"]).replace(**prog.get("replace", {}))
    m = model_file["model"]
    wrong = {k: (v, m[k]) for k, v in sizes_of(cfg).items() if m[k] != v}
    if wrong or cfg.qkv_bias or cfg.qk_norm or cfg.num_experts:
        raise BenchError(f"program config {prog} differs from the file's "
                         f"model sizes: {wrong}")
    return cfg


# -- instrumentation ----------------------------------------------------------


class Recorder:
    """The benchmark's own spans and bookkeeping around the engine.

    ``prefill``: [(t0, t1, rid, prompt_len)]; ``decode``: [(t0, t1,
    contexts)] where contexts are the keys each live row's new token sees;
    ``step``: [(t0, t1)]; ``tokens``: rid -> [times of its tokens];
    ``admitted``: rid -> time its prefill began. Times are perf_counter
    seconds."""

    def __init__(self):
        self.prefill, self.decode, self.step = [], [], []
        self.tokens: dict = {}
        self.admitted: dict = {}
        self.pending: list = []  # rids given a token during this step
        self.on = False


def instrument(engine, rec: Recorder):
    """Wrap the engine's step, its model's prefill and decode, and the
    scheduler's token record, in place. Each span is also a
    ``TraceAnnotation``, which costs nothing while no trace is taken."""
    import jax

    def span(name):
        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    model, sc = engine.model, engine.scheduler
    prefill, decode, step, record = (model.prefill, model.decode, engine.step,
                                     sc.record_token)

    def prefill_w(seq, block_ids):
        t0 = time.perf_counter()
        with span("prefill"):
            out = prefill(seq, block_ids)
        if rec.on:
            rec.prefill.append((t0, time.perf_counter(), seq.rid,
                                len(seq.req.prompt)))
            rec.admitted.setdefault(seq.rid, t0)
        return out

    def decode_w(tokens, positions, tables, active):
        t0 = time.perf_counter()
        with span("decode"):
            out = decode(tokens, positions, tables, active)
        if rec.on:
            ctx = [int(p) + 1 for p, a in zip(positions, active) if a]
            rec.decode.append((t0, time.perf_counter(), ctx))
        return out

    def step_w():
        t0 = time.perf_counter()
        with span("engine_step"):
            out = step()
        t1 = time.perf_counter()
        if rec.on:
            rec.step.append((t0, t1))
            for rid in rec.pending:
                rec.tokens.setdefault(rid, []).append(t1)
        rec.pending = []
        return out

    def record_w(seq, token):
        rec.pending.append(seq.rid)
        return record(seq, token)

    model.prefill, model.decode, engine.step = prefill_w, decode_w, step_w
    sc.record_token = record_w


class CompileCounter:
    """Counts JAX tracing and compilation events while ``on``."""

    def __init__(self):
        import jax

        self.on, self.n = False, 0

        def listen(event, *a, **k):
            if self.on and ("compile" in event or "jaxpr_trace" in event):
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


# -- set-up -------------------------------------------------------------------


def warm(engine, planned, block_size: int) -> int:
    """Serve one request per prefill length the window will use (the
    longest prompt of each bucket of ``block_size``), two tokens each, so
    that every prefill program and the decode step are compiled and loaded
    before the window opens. Returns the number of warm-up requests."""
    from repro.serving.engine import Request

    by_bucket = {}
    for p in planned:
        b = traffic.buckets([len(p.prompt)], block_size)[0]
        if len(p.prompt) >= len(by_bucket.get(b, ())):
            by_bucket[b] = p.prompt
    for i, b in enumerate(sorted(by_bucket)):
        engine.submit(Request(rid=WARM_RID0 + i, prompt=by_bucket[b],
                              max_new_tokens=2, arrival=engine.step_count))
    engine.run(max_steps=engine.step_count + 100 * (len(by_bucket) + 1))
    return len(by_bucket)


# -- the window ---------------------------------------------------------------


def serve_window(engine, planned, seconds: float, rec: Recorder,
                 compiles: CompileCounter, trace_dir: str | None = None
                 ) -> dict:
    """Drive the engine open loop for ``seconds``. Returns the window's
    start and end, the due and submit time of each request, and where a
    ``trace_dir`` is given, ``trace_t0``: when the profiler began tracing
    the window's last ``TRACE_SECONDS`` (the caller stops it)."""
    import jax

    from repro.serving.engine import Request

    due, submitted = {}, {}
    i, n = 0, len(planned)
    ctx, trace_t0 = None, None
    rec.on = compiles.on = True
    t0 = time.perf_counter()
    t_end = t0 + seconds
    trace_at = (t0 + max(0.0, seconds - TRACE_SECONDS) if trace_dir
                else float("inf"))
    for p in planned:
        due[p.rid] = t0 + p.due_s
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if ctx is None and now >= trace_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            ctx = jax.profiler.TraceAnnotation("chipbench.window")
            ctx.__enter__()
            trace_t0 = time.perf_counter()
        while i < n and due[planned[i].rid] <= now:
            p = planned[i]
            engine.submit(Request(rid=p.rid, prompt=p.prompt,
                                  max_new_tokens=p.max_new,
                                  arrival=engine.step_count))
            submitted[p.rid] = time.perf_counter()
            i += 1
        if engine.scheduler.idle():
            nxt = due[planned[i].rid] if i < n else t_end
            wake = min(nxt, t_end, trace_at if ctx is None else t_end)
            with jax.profiler.TraceAnnotation("chipbench.client_wait"):
                time.sleep(max(0.0, wake - now))
            continue
        engine.step()
    t1 = max(time.perf_counter(), t_end)
    if ctx is not None:
        ctx.__exit__(None, None, None)
    rec.on = compiles.on = False
    return {"t0": t0, "t1": t1, "due": due, "submitted": submitted,
            "trace_t0": trace_t0}


def p(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(win: dict, rec: Recorder, planned) -> dict:
    """ttft_p90_s, itl_p95_s and tokens_per_s of the window."""
    t0, t1, due = win["t0"], win["t1"], win["due"]
    ttft = []
    for q in planned:
        times = rec.tokens.get(q.rid)
        ttft.append((times[0] if times else t1) - due[q.rid])
    gaps = [b - a for times in rec.tokens.values()
            for a, b in zip(times, times[1:])]
    prompt_tokens = sum(n for _, _, _, n in rec.prefill)
    generated = sum(len(v) for v in rec.tokens.values())
    return {
        "ttft_p90_s": p(ttft, 90),
        "itl_p95_s": p(gaps, 95) if gaps else None,
        "tokens_per_s": (prompt_tokens + generated) / (t1 - t0),
    }


class Run:
    """What a per-layer reader sees: ``m`` (the model's sizes), ``peaks``,
    ``chips``, ``rec`` (the Recorder), ``win`` (the window's times),
    ``planned`` (the requests) and ``trace`` (a ``trace.Trace`` in a traced
    run, else None)."""

    def __init__(self, m, peaks, chips, rec, win, planned, trace=None):
        self.m, self.peaks, self.chips = m, peaks, chips
        self.rec, self.win, self.planned, self.trace = rec, win, planned, trace

    @property
    def window_s(self) -> float:
        return self.win["t1"] - self.win["t0"]

    def traced(self, spans) -> list:
        """The spans of ``rec`` that began in the traced part of the
        window."""
        t0 = self.win.get("trace_t0")
        return [] if t0 is None else [s for s in spans if s[0] >= t0]


# -- the check ----------------------------------------------------------------


def sample(engine, planned, seed: int, n: int):
    """Up to ``n`` finished requests of the window, drawn from the seed,
    the one with the longest sequence always among them: [(prompt,
    served tokens)]."""
    done = [q for q in planned if q.rid in engine.completed]
    if not done:
        return []
    longest = max(done, key=lambda q: len(q.prompt) + q.max_new)
    rest = [q for q in done if q is not longest]
    rng = np.random.default_rng((int(seed) ^ CHECK_SALT) % (1 << 64))
    picked = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        : n - 1]]
    return [(q.prompt, engine.completed[q.rid]) for q in picked]


def served_positions(pairs):
    """[(row, position, token)]: each served token and the position whose
    logits chose it (the one before it in the sequence)."""
    out = []
    for r, (prompt, served) in enumerate(pairs):
        for j, tok in enumerate(served):
            out.append((r, len(prompt) - 1 + j, int(tok)))
    return out


def reference_logits(m: dict, seed, pairs, length: int, rows: int,
                     fp8: bool = False):
    """(logits, where): the reference's logits at every served position of
    ``pairs`` (``served_positions``), teacher-forced over each whole
    sequence padded to ``length``, in a batch of ``rows`` sequences."""
    from chipbench import reference

    seqs = [tuple(pr) + tuple(sv) for pr, sv in pairs]
    tokens = reference.pack(seqs + [()] * (rows - len(seqs)), length)
    where = served_positions(pairs)
    logits = reference.logits_at(m, seed, tokens,
                                 [(r, c) for r, c, _ in where], fp8=fp8)
    return logits, where


def gaps(logits, tokens) -> np.ndarray:
    """How far each token's logit lies below its row's best (infinite for
    a token outside the vocabulary)."""
    tok = np.asarray(tokens)
    ok = (tok >= 0) & (tok < logits.shape[1])
    got = logits[np.arange(len(tok)), np.where(ok, tok, 0)]
    return np.where(ok, logits.max(1) - got, np.inf)


def gap_numbers(g) -> dict:
    """The numbers a check can compare, from the gaps of every served
    token: the widest, and the mean over them."""
    return {"widest_logit_gap": min(float(g.max()), NOT_READ),
            "mean_logit_gap": min(float(g.mean()), NOT_READ)}


def check_sample(m: dict, seed, pairs, length: int, rows: int) -> tuple:
    """(``gap_numbers``, tokens compared, widest gap per sampled request):
    by how much each served token's reference logit lies below the best
    one."""
    logits, where = reference_logits(m, seed, pairs, length, rows)
    g = gaps(logits, [t for _, _, t in where])
    per_req = {}
    for (r, _, _), x in zip(where, g):
        per_req[r] = max(per_req.get(r, 0.0), float(x))
    return gap_numbers(g), len(g), per_req


# -- one run ------------------------------------------------------------------


def build(cell: dict, seed, cfg=None):
    """(engine, sizes): the weights drawn from ``seed`` and the engine built
    with the cell's geometry. ``cfg`` replaces the program config (the tests
    run a small one on the CPU), and the sizes follow it."""
    import jax

    from chipbench import weights
    from repro.models import transformer
    from repro.serving.engine import ServingEngine

    mf, mix = cell["model_file"], cell["mix"]
    m = dict(mf["model"])
    if cfg is None:
        cfg = program_config(mf)
    else:
        m.update(sizes_of(cfg))
    geo = cell["engine"]
    longest = (traffic.max_length(mix["prompt"])
               + traffic.max_length(mix["output"]))
    if longest > geo["max_blocks_per_seq"] * geo["block_size"]:
        raise BenchError(f"mix {cell['traffic']}: {longest} tokens do not fit"
                         f" {geo['max_blocks_per_seq']} pages")
    params = weights.make_params(m, seed, jax.numpy.dtype(m["dtype"]))
    want = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0)))
    have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    if jax.tree.map(lambda x: (x.shape, x.dtype), want) != have:
        raise BenchError("the drawn weights do not match the program's "
                         "parameter tree")
    return ServingEngine.with_model(cfg, params, **geo), m


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices, peaks: dict, cfg=None,
             log=print) -> dict:
    """Set-up, window, check. ``cfg`` replaces the program config (tests run
    a small one on the CPU). Returns the result line's object."""
    import jax

    from chipbench import trace as tr

    geo, check = cell["engine"], cell["check"]
    compiles = CompileCounter()
    phases = {"start": time.perf_counter() - t_start}
    engine, m = build(cell, seed, cfg)
    jax.block_until_ready(engine.model.params)
    phases["weights"] = time.perf_counter() - t_start
    rec = Recorder()
    instrument(engine, rec)
    planned = traffic.plan(cell["mix"], cell["rate_per_s"], seconds, seed,
                           m["vocab"])
    n_warm = warm(engine, planned, geo["block_size"])
    jax.block_until_ready(engine.model.cache)
    phases["warm"] = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    setup_s = time.perf_counter() - t_start
    win = serve_window(engine, planned, seconds, rec, compiles, trace_dir)
    if trace:
        jax.block_until_ready(engine.model.cache)
        jax.profiler.stop_trace()
    e2e = end_to_end(win, rec, planned)
    e2e["setup_s"] = setup_s
    stats = [d.memory_stats() or {} for d in devices]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    lateness = [win["submitted"][r] - win["due"][r] for r in win["submitted"]]

    trace_obj = None
    if trace:
        trace_obj = tr.read_xplane(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(m, peaks, cell["chips"], rec, win, planned, trace_obj)
    per_layer = {}
    if trace:
        for spec in metrics_for(cell["bench"], cell["name"], "per_layer"):
            v = load_reader(spec["name"])(run)
            if v is not None:
                per_layer[spec["name"]] = {"value": v, "unit": spec["unit"]}

    # free the engine before the reference takes the chip
    leaked = engine.leaked_blocks()
    pairs = sample(engine, planned, seed, check["requests"])
    n_finished = sum(q.rid in engine.completed for q in planned)
    preempted = sum(1 for e in engine.scheduler.events if e[0] == "preempt")
    del engine
    gc.collect()

    length = geo["max_blocks_per_seq"] * geo["block_size"]
    t_ref = time.perf_counter()
    if pairs:
        numbers, n_tok, per_req = check_sample(m, seed, pairs, length,
                                               check["requests"])
    else:
        numbers = dict.fromkeys(("widest_logit_gap", "mean_logit_gap"),
                                NOT_READ)
        n_tok, per_req = 0, {}
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in check["limits"].items()}
    checks["tokens_compared"] = {"value": n_tok, "limit": check["min_tokens"]}
    checks["leaked_blocks"] = {"value": leaked, "limit": 0}
    correct = (all(numbers[k] <= lim for k, lim in check["limits"].items())
               and n_tok >= check["min_tokens"] and leaked == 0)

    log(f"setup: {n_warm} prefill lengths warmed, setup_s {setup_s!r}; "
        f"seconds from the start to the end of each phase {phases}")
    log(f"window: {win['t1'] - win['t0']!r} s, {len(planned)} requests due, "
        f"{n_finished} finished, {len(rec.step)} steps, "
        f"{len(rec.prefill)} prefills, {len(rec.decode)} decodes, "
        f"{preempted} preemptions, {compiles.n} compile events inside")
    if lateness:
        log(f"client lateness: median {p(lateness, 50)!r} s, max "
            f"{max(lateness)!r} s")
    log(f"end to end: {json.dumps(e2e)}")
    log(f"peak_bytes_in_use: {peak} (bytes_limit "
        f"{max((s.get('bytes_limit', 0) for s in stats), default=0)})")
    log(f"check: {numbers}; widest gap per sampled request {per_req}; the "
        f"reference took {time.perf_counter() - t_ref!r} s")

    if trace:
        metrics = per_layer
    else:
        metrics = {}
        for spec in metrics_for(cell["bench"], cell["name"], "end_to_end"):
            v = e2e.get(spec["name"])
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(planned),
           "failed": 0 if correct else max(1, len(pairs)),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr.busy_seconds(trace_obj)
        device["window_s"] = tr.window_seconds(trace_obj)
        out["breakdown"] = {"device_ops": tr.top_ops(trace_obj),
                            "idle_gaps": tr.idle_by_host(trace_obj)}
    out["checks"] = checks
    return out


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_bench()
    cell = load_cell(bench, args.workload)
    cell["bench"] = bench
    os.environ.pop("REPRO_KERNEL_IMPL", None)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = require_devices(cell["chips"])[: cell["chips"]]
    peaks = load_peaks(devices[0].device_kind)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start, devices=devices, peaks=peaks, log=log)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0
