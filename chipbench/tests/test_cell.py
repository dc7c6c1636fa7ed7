"""A whole run of a cell on the CPU at a small width, through the same
harness path as on the chip, with the look for a chip skipped.

A sound run comes out correct; a run whose timed path is broken
underneath comes out not correct, once for each fault a serving cell can
have: a decode step that leaves the KV cache unchanged, half of the batch
left out of a decode step, and a token altered where it is produced."""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, traffic

SECONDS = 3.0
SEED = 2**33 + 17  # wider than 32 bits, as seeds of a check may be


def small_cell():
    bench = harness.load_bench()
    cell = harness.load_cell(bench, bench["workloads"][0]["name"])
    cell["bench"] = bench
    cell["mix"] = dict(cell["mix"],
                       prompt={"dist": "uniform", "min": 17, "max": 60},
                       output={"dist": "uniform", "min": 8, "max": 24})
    cell.update(rate_per_s=4.0,
                engine={"max_slots": 4, "block_size": 16,
                        "max_blocks_per_seq": 8, "num_blocks": 40},
                check=dict(cell["check"], min_tokens=20))
    return cell


def small_config(cell):
    from repro.configs.base import get_config

    arch = cell["model_file"]["program"]["arch"]
    return get_config(arch, reduced=True).replace(
        dtype=cell["model_file"]["model"]["dtype"], num_layers=2)


def run(cell, trace=False):
    return harness.run_cell(
        cell, SEED, SECONDS, trace, t_start=time.perf_counter(),
        devices=jax.devices(), peaks=harness.load_peaks("TPU v5 lite"),
        cfg=small_config(cell), log=lambda s: print(s, file=sys.stderr))


def test_sound_run_is_correct():
    out = run(small_cell())
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"ttft_p90_s", "itl_p95_s", "tokens_per_s",
                                   "setup_s"}
    assert out["attempted"] == traffic.count(small_cell()["rate_per_s"],
                                             SECONDS)
    assert out["device"]["count"] == len(jax.devices())
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics():
    out = run(small_cell(), trace=True)
    assert out["correct"], out["checks"]
    specs = harness.metrics_for(harness.load_bench(), small_cell()["name"],
                                "per_layer")
    names = {m["name"] for m in specs}
    # the device metrics need a TPU plane in the trace; the CPU has none
    host_only = {m["name"] for m in specs if m["source"] != "device_trace"}
    assert host_only <= set(out["metrics"])
    assert set(out["metrics"]) <= names
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _kv_unchanged(model):
    jit = model._decode_jit

    def broken(p, cache, batch):
        keep = jax.tree.map(jnp.copy, cache)
        logits, _ = jit(p, cache, batch)
        return logits, keep

    model._decode_jit = broken


def _half_batch(model):
    decode = model.decode

    def broken(tokens, positions, tables, active):
        out = decode(tokens, positions, tables, active)
        out[np.flatnonzero(active)[::2]] = 0  # every other live row
        return out

    model.decode = broken


def _token_altered(model):
    decode = model.decode

    def broken(tokens, positions, tables, active):
        out = decode(tokens, positions, tables, active)
        return (out + 1) % model.vocab

    model.decode = broken


@pytest.mark.parametrize("fault", [_kv_unchanged, _half_batch,
                                   _token_altered],
                         ids=["kv_unchanged", "half_batch", "token_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.serving import engine as eng

    real = eng.PagedModel.__init__

    def init(self, *a, **k):
        real(self, *a, **k)
        fault(self)

    monkeypatch.setattr(eng.PagedModel, "__init__", init)
    out = run(small_cell())
    assert not out["correct"], out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_same_work_for_every_seed():
    mix = small_cell()["mix"]
    a = traffic.plan(mix, 4.0, 10.0, 1, 100)
    b = traffic.plan(mix, 4.0, 10.0, 2**40 + 3, 100)
    assert [(len(q.prompt), q.max_new, q.due_s) for q in a] == [
        (len(q.prompt), q.max_new, q.due_s) for q in b]
    assert [q.prompt for q in a] != [q.prompt for q in b]
    assert a == traffic.plan(mix, 4.0, 10.0, 1, 100)
    assert len(a) == traffic.count(4.0, 10.0) and a[-1].due_s < 10.0
