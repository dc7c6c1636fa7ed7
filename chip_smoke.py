"""Bring-up check on the chip: full-width GPT-J served through the engine.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --four-chips  # ring decode over a (data=4) mesh

One chip. Builds the published ``occamy-gptj`` (GPT-J-6B: 28 layers,
d_model 4096, 16 heads of 256, d_ff 16384, vocab 50400) with random weights
drawn from ``--seed`` on the device, and serves 8 requests (prompts of 32 to
200 tokens, 16 new tokens each) through ``ServingEngine.with_model`` twice:
the first pass includes compilation, the second is warm. It checks that
every request completes with in-vocabulary tokens, that no cache block
leaks, that both passes give the same tokens, that the main path's
last-position logits are finite and match a ``ref``-impl prefill within a
bf16 tolerance, and that the Pallas flash attention and the paged decode
attention match ``ref`` at GPT-J shapes.

Four chips (``--four-chips``). Serves the same 8 requests through the engine
with ring decode over a (data=4) mesh, weights replicated and page pools
sharded, and compares them with the same engine on one chip: the worst
decode-logits error and the count of token mismatches. Nothing else runs.

Every result line names its numbers; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
With no TPU, or when a check fails, the script exits non-zero and prints no
such line. It runs in one process and reads no tuning record; the kernel
choice is the registry's ``auto`` (``REPRO_KERNEL_IMPL`` is ignored).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "occamy-gptj"
NUM_REQUESTS = 8
PROMPT_LENS = (32, 200)  # spread evenly over this range, inclusive
MAX_NEW = 16
ENGINE = dict(num_blocks=128, block_size=16, max_slots=8, max_blocks_per_seq=16)
N_CHECKED = 3  # prompts whose logits are compared with the ref prefill
FLASH_SEQ = 256  # flash attention check length (one full prefill bucket)
# Errors are max|x - ref| / max|ref|. bf16 keeps 8 significant bits
# (2^-8 = 3.9e-3 relative): one attention call may be a few roundings off,
# and 28 layers of bf16 activations may grow that a few times more.
KERNEL_TOL = 2e-2
LOGITS_TOL = 5e-2
MAIN_PATH = {"flash_attention": "pallas", "decode_attention": "pallas"}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def make_prompts(vocab: int, seed: int) -> list[tuple]:
    rng = np.random.default_rng(seed)
    lens = np.linspace(*PROMPT_LENS, NUM_REQUESTS).astype(int)
    return [tuple(int(t) for t in rng.integers(0, vocab, n)) for n in lens]


def init_params(cfg, seed: int, sharding=None):
    """Random weights made on the device by one compiled program; with a
    ``sharding``, each device draws its own copy in place."""
    import jax

    from repro.models import registry as mreg

    def init(key):
        return mreg.init_params(cfg, key)

    placed = {} if sharding is None else {"out_shardings": sharding}
    params = jax.jit(init, **placed)(jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def submit(engine, prompts, rid0: int) -> list[int]:
    from repro.serving.engine import Request

    rids = [rid0 + i for i in range(len(prompts))]
    for rid, p in zip(rids, prompts):
        engine.submit(Request(rid=rid, prompt=p, max_new_tokens=MAX_NEW))
    return rids


def serve(engine, prompts, rid0: int):
    """One pass of the prompts through ``engine.run()``. Returns the
    generated tokens in prompt order and the wall seconds."""
    import jax

    rids = submit(engine, prompts, rid0)
    t0 = time.perf_counter()
    done = engine.run()
    jax.block_until_ready(engine.model.cache)
    seconds = time.perf_counter() - t0
    return [done.get(r) for r in rids], seconds


def serve_by_step(engine, prompts):
    """Like ``serve`` but step by step, keeping each decode step's logits
    of the slots it served: (tokens, [(slots, logits)], seconds)."""
    import jax

    from repro.serving.engine import NULL_BLOCK

    rids = submit(engine, prompts, 0)
    vocab = engine.model.vocab
    steps = []
    t0 = time.perf_counter()
    while not engine.scheduler.idle():
        if engine.step():
            slots = np.flatnonzero(engine.model.tables[:, 0] != NULL_BLOCK)
            logits = np.asarray(engine.model.last_logits, np.float32)
            steps.append((slots, logits[slots, :vocab]))
    jax.block_until_ready(engine.model.cache)
    seconds = time.perf_counter() - t0
    return [engine.completed.get(r) for r in rids], steps, seconds


def check_outputs(tokens, vocab: int, engine) -> None:
    check(all(t is not None and len(t) == MAX_NEW for t in tokens),
          f"every request completes with {MAX_NEW} tokens: "
          f"{[None if t is None else len(t) for t in tokens]}")
    flat = np.concatenate([np.asarray(t) for t in tokens])
    check(bool(np.all((flat >= 0) & (flat < vocab))),
          f"token ids in [0, {vocab})")
    check(engine.leaked_blocks() == 0,
          f"no leaked cache blocks ({engine.leaked_blocks()})")


def last_logits(cfg, params, prompts, impl: str | None):
    """Last-position logits of each prompt from ``transformer.prefill_step``
    under a fresh jit (traced under ``impl``). The prompts share one padded
    length; causal attention keeps the padding out of every real row.
    Returns (logits (n, vocab) fp32, Pallas kernels in the program)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import registry
    from repro.models import transformer

    bs = ENGINE["block_size"]
    S = bs * -(-max(len(p) for p in prompts) // bs)
    tokens = np.zeros((len(prompts), S), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, : len(p)] = p
    last = np.asarray([len(p) - 1 for p in prompts], np.int32)

    def run(params, tokens, last):
        logits, _ = transformer.prefill_step(params, cfg, {"tokens": tokens},
                                             max_len=S)
        return logits[jnp.arange(len(prompts)), last, : cfg.vocab_size]

    fn = jax.jit(run)
    with registry.default_impl(impl):
        kernels = fn.lower(params, tokens, last).as_text().count(
            "tpu_custom_call")
        out = fn(params, tokens, last)
    return np.asarray(out, np.float32), kernels


def check_kernels(cfg, seed: int) -> None:
    """Flash attention (pallas) and paged decode attention (auto) against
    ``ref`` at the model's head shapes and the engine's cache geometry."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim()
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(kk, (1, n, FLASH_SEQ, D), dt)
               for kk, n in zip(keys[:3], (H, K, K)))
    flash = {impl: jax.jit(functools.partial(ops.flash_attention, impl=impl))
             for impl in ("pallas", "ref")}
    err = rel_err(flash["pallas"](q, k, v), flash["ref"](q, k, v))
    print(f"flash_attention pallas vs ref (1,{H},{FLASH_SEQ},{D}) "
          f"{dt.name}: worst rel err {err!r}")
    check(err < KERNEL_TOL, f"flash_attention error {err} < {KERNEL_TOL}")

    B, P, bs, NB = (ENGINE["max_slots"], ENGINE["num_blocks"],
                    ENGINE["block_size"], ENGINE["max_blocks_per_seq"])
    rng = np.random.default_rng(seed)
    table = rng.integers(1, P, (B, NB)).astype(np.int32)
    pos = rng.integers(bs, NB * bs, B).astype(np.int32)
    qd = jax.random.normal(keys[3], (B, H, D), dt)
    kp, vp = (jax.random.normal(kk, (P, K, bs, D), dt) for kk in keys[4:])

    def decode(impl):
        return jax.jit(lambda q, kp, vp, pos, tbl: ops.decode_attention(
            q, kp, vp, pos, paged=True, block_table=tbl, impl=impl))

    err = rel_err(decode(None)(qd, kp, vp, pos, table),
                  decode("ref")(qd, kp, vp, pos, table))
    print(f"paged decode_attention auto vs ref ({B} slots, {P} pages of "
          f"{bs}, {NB} per slot): worst rel err {err!r}")
    check(err < KERNEL_TOL, f"decode_attention error {err} < {KERNEL_TOL}")


def one_chip(cfg, seed: int) -> None:
    import jax

    import repro.kernels.ops  # noqa: F401  (registers every kernel)
    from repro.kernels import registry
    from repro.serving.engine import ServingEngine

    resolved = {op: registry.resolve_impl(None, op) for op in MAIN_PATH}
    print(f"impls: {resolved}")
    check(resolved == MAIN_PATH, f"main-path impls {resolved} == {MAIN_PATH}")

    t0 = time.perf_counter()
    params = init_params(cfg, seed)
    print(f"init: {time.perf_counter() - t0!r} s (compile included)")

    engine = ServingEngine.with_model(cfg, params, **ENGINE)
    prompts = make_prompts(cfg.vocab_size, seed)
    first, t_first = serve(engine, prompts, 0)
    warm, t_warm = serve(engine, prompts, NUM_REQUESTS)
    print(f"serve {NUM_REQUESTS} requests x {MAX_NEW} tokens, prompts "
          f"{[len(p) for p in prompts]}: first pass {t_first!r} s "
          f"(compile included), warm pass {t_warm!r} s, compile "
          f"{t_first - t_warm!r} s (first minus warm)")
    check_outputs(first, cfg.vocab_size, engine)
    check(first == warm, "both passes generate the same tokens")
    print(f"engine: {engine.step_count} steps over both passes, 0 leaked blocks")

    checked = [prompts[i] for i in
               np.linspace(0, NUM_REQUESTS - 1, N_CHECKED).astype(int)]
    main, n_kernels = last_logits(cfg, params, checked, None)
    ref, _ = last_logits(cfg, params, checked, "ref")
    print(f"prefill program: {n_kernels} Pallas kernel calls")
    check(n_kernels > 0, "the main-path prefill runs the Pallas kernel")
    check(bool(np.all(np.isfinite(main))), "main-path logits are finite")
    errs = [rel_err(m, r) for m, r in zip(main, ref)]
    print(f"last-position logits vs ref prefill, prompts "
          f"{[len(p) for p in checked]}: rel err {errs!r}, worst "
          f"{max(errs)!r}")
    check(max(errs) < LOGITS_TOL, f"logits error {max(errs)} < {LOGITS_TOL}")
    agree = sum(int(np.argmax(m) == np.argmax(r)) for m, r in zip(main, ref))
    print(f"argmax agrees with ref on {agree}/{len(checked)} prompts")

    check_kernels(cfg, seed)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")


def four_chips(cfg, seed: int) -> None:
    import gc

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.serving.engine import ServingEngine

    n = len(jax.devices())
    check(n == 4, f"four chips visible ({n})")
    prompts = make_prompts(cfg.vocab_size, seed)

    # the comparison: the same engine on one chip; dropped before the mesh
    # run so that no device ever holds two copies of the weights
    engine = ServingEngine.with_model(cfg, init_params(cfg, seed), **ENGINE)
    want, want_steps, t_one = serve_by_step(engine, prompts)
    check_outputs(want, cfg.vocab_size, engine)
    del engine
    gc.collect()

    mesh = make_mesh((n,), ("data",))
    params = init_params(cfg, seed, NamedSharding(mesh, P()))
    engine = ServingEngine.with_model(cfg, params, mesh=mesh, **ENGINE)
    got, got_steps, t_ring = serve_by_step(engine, prompts)
    check_outputs(got, cfg.vocab_size, engine)
    print(f"serve {NUM_REQUESTS} requests x {MAX_NEW} tokens (compile "
          f"included): one chip {t_one!r} s, ring decode over data={n} "
          f"{t_ring!r} s")

    mismatches = sum(int(a != b) for w, g in zip(want, got)
                     for a, b in zip(w, g))
    check(len(want_steps) == len(got_steps)
          and all(np.array_equal(a[0], b[0])
                  for a, b in zip(want_steps, got_steps)),
          "both runs decode the same slots at every step")
    # logits are comparable while both runs feed the same tokens: up to the
    # first step whose argmax differs anywhere
    errs = []
    for (_, w), (_, g) in zip(want_steps, got_steps):
        errs += [rel_err(gi, wi) for gi, wi in zip(g, w)]
        if np.any(np.argmax(w, -1) != np.argmax(g, -1)):
            break
    worst = max(errs)
    print(f"ring decode vs one chip: worst decode-logits rel err {worst!r} "
          f"over {len(errs)} slot-steps, {mismatches} token mismatches of "
          f"{NUM_REQUESTS * MAX_NEW}")
    check(worst < LOGITS_TOL, f"ring decode logits error {worst} < {LOGITS_TOL}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only ring decode over a (data=4) mesh vs one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    os.environ.pop("REPRO_KERNEL_IMPL", None)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {dev.platform!r}")

    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    print(f"device: {dev.device_kind} x{len(jax.devices())}; model {cfg.name}"
          f" ({cfg.num_layers}L d={cfg.d_model} H={cfg.num_heads}x"
          f"{cfg.resolved_head_dim()} ff={cfg.d_ff} vocab={cfg.vocab_size})")
    if args.four_chips:
        four_chips(cfg, args.seed)
    else:
        one_chip(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
