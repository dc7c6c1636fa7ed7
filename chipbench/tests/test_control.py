"""The control comes out not correct: the float32 reference computed in
fp8 (float8_e4m3fn, one step below the served bfloat16) and put in the
program's place, at a size a CPU test run can hold.

For each cell's configuration at a small width, a short window is served
through the harness, and at each served position of the sample the control
reads the reference's gap for the token that the fp8 reference puts
first. That widest gap has to exceed the cell's limit, while the program's
own stays within it. Where the head is tied to the embedding, the
embedding's scale grows with the width cut, so that the logits keep the
spread they have at the published width. On the chip the same readings, at the cells' own
sizes and on a dozen seeds, are ``calibrate.py``'s."""
import sys

import pytest

from chipbench import harness, traffic

SEEDS = (11, 2**35 + 5, 987654321)


def cell_at_small_width(name):
    from repro.configs.base import get_config

    bench = harness.load_bench()
    cell = harness.load_cell(bench, name)
    cell["bench"] = bench
    cell["mix"] = dict(cell["mix"],
                       prompt={"dist": "uniform", "min": 40, "max": 200},
                       output={"dist": "uniform", "min": 16, "max": 48})
    cell.update(rate_per_s=4.0,
                engine={"max_slots": 4, "block_size": 16,
                        "max_blocks_per_seq": 16, "num_blocks": 80},
                check=dict(cell["check"], min_tokens=40))
    full = cell["model_file"]["model"]
    if full["tie_embeddings"]:
        # a tied head's logits grow with sqrt(d_model): keep their scale
        cell["model_file"] = dict(cell["model_file"], model=dict(
            full, embed_scale=full["embed_scale"] * (full["d_model"] / 256)
            ** 0.5))
    arch = cell["model_file"]["program"]["arch"]
    cfg = get_config(arch, reduced=True).replace(
        dtype=cell["model_file"]["model"]["dtype"], num_layers=4,
        d_model=256, num_heads=4, head_dim=64, d_ff=1024, vocab_size=4096,
        num_kv_heads=cell["model_file"]["model"]["num_kv_heads"] // 4 or 1)
    return cell, cfg


@pytest.mark.parametrize("name", [w["name"] for w in
                                  harness.load_bench()["workloads"]])
def test_control_fails_the_limit(name):
    cell, cfg = cell_at_small_width(name)
    limits = cell["check"]["limits"]
    m = dict(cell["model_file"]["model"], **harness.sizes_of(cfg))
    geo = cell["engine"]
    length = geo["max_blocks_per_seq"] * geo["block_size"]
    program, control = [], []
    for seed in SEEDS:
        engine, _ = harness.build(cell, seed, cfg)
        rec = harness.Recorder()
        harness.instrument(engine, rec)
        planned = traffic.plan(cell["mix"], cell["rate_per_s"], 3.0, seed,
                               m["vocab"])
        harness.warm(engine, planned, geo["block_size"])
        harness.serve_window(engine, planned, 3.0, rec,
                             harness.CompileCounter())
        pairs = harness.sample(engine, planned, seed, 6)
        del engine
        f32, where = harness.reference_logits(m, seed, pairs, length, 6)
        fp8, _ = harness.reference_logits(m, seed, pairs, length, 6,
                                          fp8=True)
        program.append(harness.gap_numbers(
            harness.gaps(f32, [t for _, _, t in where])))
        control.append(harness.gap_numbers(harness.gaps(f32, fp8.argmax(1))))
    print(f"{name}: program {program}, control {control}, limits {limits}",
          file=sys.stderr)
    for k, lim in limits.items():
        assert max(p[k] for p in program) <= lim
    # the control fails one of the cell's numbers on every seed
    assert all(any(c[k] > lim for k, lim in limits.items()) for c in control)

