"""The trace reduction, on a small trace recorded on one TPU v5e and on
hand-made ones.

The recorded trace (``data/v5e_small.xplane.pb``) holds three rounds of a
Pallas flash attention call (1x4x256x128 bf16) inside a benchmark
``prefill`` span and a 512x512 matmul inside a ``decode`` span, 10 ms
apart, all inside a ``window`` span."""
from pathlib import Path

import pytest

from chipbench import trace as t

DATA = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"
PALLAS = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def recorded():
    return t.read_xplane(str(DATA))


def test_recorded_window_and_busy(recorded):
    assert t.window_seconds(recorded) == pytest.approx(0.036596209, abs=1e-12)
    # no two ops overlap, so the union is the sum of their durations:
    # three flash calls (~10 us each), three matmul fusions (1.82 us) and
    # their copies; the first flash call lies inside the window only once
    # the device's clock is moved onto the host's
    ops = recorded.ops[0]
    lo, hi = recorded.window
    inside = [e - s for s, e, _ in ops if s >= lo and e <= hi]
    assert len(inside) == 12
    assert t.busy_seconds(recorded) == pytest.approx(sum(inside) / 1e9)
    assert t.busy_seconds(recorded) == pytest.approx(3.8655e-05, rel=1e-6)


def test_recorded_kernel_time_inside_prefill(recorded):
    flash = t.kernel_seconds(recorded, PALLAS, inside="prefill")
    assert flash == pytest.approx(3.0065e-05, rel=1e-6)
    assert t.kernel_seconds(recorded, PALLAS) == flash
    assert t.kernel_seconds(recorded, PALLAS, inside="decode") == 0.0
    assert t.kernel_seconds(recorded, "flash_attention") == 0.0
    # the program (XLA Modules) encloses its kernel
    assert sum(t.program_seconds(recorded, "prefill")) == pytest.approx(
        3.0076e-05, rel=1e-6)
    assert sum(t.program_seconds(recorded, "decode")) == pytest.approx(
        8.606e-06, rel=1e-6)


def test_recorded_breakdown(recorded):
    top = t.top_ops(recorded)
    assert top[0] == ["jit__lambda/_lambda_.1 custom-call",
                      pytest.approx(3.0065e-05)]
    assert [n for n, _ in top[1:3]] == [
        "jit__lambda/convolution_reduce_fusion fusion",
        "jit__lambda/copy-done copy-done"]
    idle = dict(t.idle_by_host(recorded))
    assert set(idle) == {"window", "prefill", "decode"}
    busy = t.busy_seconds(recorded)
    assert sum(idle.values()) == pytest.approx(
        t.window_seconds(recorded) - busy)
    assert t.idle_inside(recorded, "window") == pytest.approx(
        t.window_seconds(recorded) - busy)


def _made(ops, spans):
    win = [s for s in spans if s[2] == "chipbench.window"][0]
    return t.Trace(ops={0: ops}, spans=spans, window=(win[0], win[1]))


def test_union_clips_and_merges():
    tr = _made([(0, 30, "a"), (20, 50, "b"), (60, 70, "c"), (95, 130, "d")],
               [(10, 100, "chipbench.window")])
    # busy: [10, 50] + [60, 70] + [95, 100]
    assert t.busy_seconds(tr) == pytest.approx(55e-9)
    assert t.idle_gaps(tr) == [(50, 60), (70, 95)]


def test_idle_gaps_go_to_the_innermost_host_span():
    spans = [(0, 100, "chipbench.window"), (0, 40, "chipbench.engine_step"),
             (5, 30, "chipbench.decode"), (60, 90, "chipbench.client_wait")]
    tr = _made([(5, 20, "x"), (45, 50, "y")], spans)
    # gaps: [0,5] at 2 in engine_step, [20,45] at 32 in engine_step,
    # [50,100] at 75 in client_wait
    assert t.idle_by_host(tr) == [["client_wait", 50e-9],
                                  ["engine_step", 30e-9]]


def test_program_time_of_each_call():
    spans = [(0, 100, "chipbench.window"), (10, 40, "chipbench.prefill"),
             (50, 70, "chipbench.decode"), (72, 80, "chipbench.decode"),
             (82, 95, "chipbench.decode")]
    tr = _made([], spans)
    tr.modules = {0: [(12, 30, "jit_run"), (35, 45, "jit_run"),
                      (52, 60, "jit_decode"), (61, 66, "jit_argmax"),
                      (84, 92, "jit_decode"), (96, 110, "jit_decode")]}
    # midpoints 21 and 40: the second falls past the prefill span; the
    # second decode call's program is not in the trace; the last program
    # ran after every span
    assert t.program_seconds(tr, "prefill") == [pytest.approx(18e-9)]
    assert t.program_seconds(tr, "decode") == [
        pytest.approx(13e-9), None, pytest.approx(8e-9)]
    # the calls the benchmark recorded, paired with their device time; a
    # call the trace lost is left out, and a count that differs pairs none
    assert t.paired(tr, "decode", ["a", "b", "c"]) == [
        ("a", pytest.approx(13e-9)), ("c", pytest.approx(8e-9))]
    assert t.paired(tr, "decode", ["a", "b"]) == []


def test_idle_inside_a_span():
    spans = [(0, 100, "chipbench.window"), (0, 40, "chipbench.engine_step"),
             (50, 90, "chipbench.engine_step")]
    tr = _made([(5, 20, "x"), (30, 60, "y")], spans)
    # idle [0,5], [20,30] and [60,100]; of it inside the steps 5 + 10 + 30
    assert t.idle_inside(tr, "engine_step") == pytest.approx(45e-9)
    assert t.idle_inside(tr, "decode") == 0.0


def test_breakdown_leaves_out_enclosing_loops():
    spans = [(0, 100, "chipbench.window")]
    tr = _made([(10, 60, "%while.3 = (s32[]) while((s32[]) %t)"),
                (12, 30, "%fusion.1 = f32[2]{0} fusion(f32[2]{0} %a)"),
                (30, 58, "%fusion.1 = f32[2]{0} fusion(f32[2]{0} %a)")],
               spans)
    tr.modules = {0: [(5, 65, "jit_decode")]}
    assert t.top_ops(tr) == [["jit_decode/fusion.1 fusion", 46e-9]]
    assert t.busy_seconds(tr) == pytest.approx(50e-9)


def test_op_name():
    text = ('%fusion.12 = bf16[16,4096]{1,0:T(8,128)(2,1)} fusion(bf16[16,'
            '4096]{1,0} %p), kind=kLoop, calls=%fused_computation.3')
    assert t.op_name(text) == "fusion.12 fusion"
    assert t.op_name("%copy-start = (bf16[2]{0}, u32[]{:S(2)}) copy-start("
                     "bf16[2]{0} %x)") == "copy-start copy-start"
