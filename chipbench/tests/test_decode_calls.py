"""The split of each decode call's device idle (``decode_calls``) and its
three readers, on hand-made traces, and the program's spans beside the
benchmark's in a small engine run on the CPU."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import decode_calls as dc
from chipbench import harness
from chipbench import trace as t

WINDOW = (0, 1000, "chipbench.window")
GAP_READERS = {"launch": "decode_launch_gap_ms",
               "program": "decode_program_gap_ms",
               "sample": "decode_sample_gap_ms"}


def _made(ops, spans, modules):
    win = [s for s in spans if s[2] == "chipbench.window"][0]
    return t.Trace(ops={0: ops}, spans=spans, window=(win[0], win[1]),
                   modules={0: modules})


def _decode(s, e):
    return (s, e, "chipbench.decode")


def test_idle_within_clips_to_the_interval():
    gaps = [(0, 10), (20, 30), (40, 50)]
    ends = [e for _, e in gaps]
    assert dc.idle_within(gaps, ends, 5, 45) == 5 + 10 + 5
    assert dc.idle_within(gaps, ends, 10, 20) == 0
    assert dc.idle_within(gaps, ends, 60, 70) == 0
    assert dc.idle_within(gaps, ends, 0, 50) == 30


@pytest.fixture
def handmade():
    spans = [WINDOW,
             _decode(100, 200),  # paired: idle before, inside and after
             _decode(300, 400),  # no decode program: left out
             _decode(500, 600),  # two decode programs: left out
             _decode(700, 800),  # the parent's program name: left out
             _decode(950, 1050)]  # straddles the window's end: left out
    modules = [(120, 180, "jit_serve_decode"), (185, 190, "jit_argmax"),
               (510, 540, "jit_serve_decode"), (550, 590, "jit_serve_decode"),
               (710, 790, "jit_decode"), (960, 990, "jit_serve_decode")]
    ops = [(120, 140, "a"), (150, 180, "b"), (185, 190, "argmax"),
           (510, 540, "c"), (550, 590, "d"), (710, 790, "e"),
           (960, 990, "f")]
    return _made(ops, spans, modules)


def test_each_call_is_paired_with_its_one_program(handmade):
    calls = dc.decode_calls(handmade, dc.benchmark_spans(handmade))
    assert calls == [(100, 200, 120, 180)]
    # launch [100, 120]; program [140, 150]; sample [180, 185] + [190, 200]
    assert dc.decode_gaps(handmade, dc.benchmark_spans(handmade)) == [
        (20, 10, 15)]


def test_the_parts_sum_to_the_idle_inside_the_call(handmade):
    gaps = t.idle_gaps(handmade)
    ends = [e for _, e in gaps]
    (parts,) = dc.decode_gaps(handmade, dc.benchmark_spans(handmade))
    assert sum(parts) == dc.idle_within(gaps, ends, 100, 200)


def test_a_program_put_before_its_span_is_clipped_to_it():
    # a device clock moved by slightly too little puts the program's start
    # before its call's: no launch gap, and nothing counted twice
    tr = _made([(95, 150, "a"), (160, 180, "b")],
               [WINDOW, _decode(100, 200)], [(95, 180, "jit_serve_decode")])
    assert dc.decode_gaps(tr, dc.benchmark_spans(tr)) == [(0, 10, 20)]


def test_program_spans_are_read_like_the_benchmark_spans(handmade):
    prog = [(s + 2, e - 2, "serve.decode", {"live": 3, "slots": 3})
            for s, e, name in handmade.spans if name == "chipbench.decode"]
    assert dc.decode_gaps(handmade, prog) == [(18, 10, 13)]


def test_readers_give_the_mean_in_ms(handmade):
    for part, name in GAP_READERS.items():
        read = harness.load_reader(name)
        want = dict(zip(dc.PARTS, (20, 10, 15)))[part] / 1e6
        assert read(SimpleNamespace(trace=handmade)) == pytest.approx(want)
        assert read(SimpleNamespace(trace=None)) is None


def test_readers_find_nothing_in_a_program_without_the_names():
    # the parent's program names its decode step jit_decode: nothing to read
    tr = _made([(120, 180, "a")], [WINDOW, _decode(100, 200)],
               [(120, 180, "jit_decode")])
    for name in GAP_READERS.values():
        assert harness.load_reader(name)(SimpleNamespace(trace=tr)) is None


@pytest.fixture(scope="module")
def recorded_on_cpu(tmp_path_factory):
    import record_serve_trace

    out = tmp_path_factory.mktemp("serve")
    data = record_serve_trace.record(out)
    path = str(out / f"{record_serve_trace.NAME}.xplane.pb")
    return data, t.read_xplane(path), dc.read_program(path)


def test_program_spans_lie_inside_the_benchmark_spans(recorded_on_cpu):
    # one host clock: each program span inside the benchmark's span of the
    # same call, one to one and in order
    _, tr, prog = recorded_on_cpu
    for bench, ours in (("engine_step", "step"), ("prefill", "prefill"),
                        ("decode", "decode")):
        outer = sorted(s for s in tr.spans
                       if s[2] == t.SPAN_PREFIX + bench)
        inner = [s for s in prog if s[2] == "serve." + ours]
        assert len(inner) == len(outer) > 0, ours
        for (s, e, *_), (os_, oe, _) in zip(inner, outer):
            assert os_ <= s and e <= oe, (ours, (s, e), (os_, oe))


def test_program_span_args_match_the_benchmark_records(recorded_on_cpu):
    data, _, prog = recorded_on_cpu
    decode = [s[3] for s in prog if s[2] == "serve.decode"]
    assert [d["live"] for d in decode] == [len(c) for *_, c in
                                           data["rec"]["decode"]]
    assert {d["slots"] for d in decode} == {4}
    assert len({d["live"] for d in decode}) > 1
    assert [(s[3]["rid"], s[3]["tokens"]) for s in prog
            if s[2] == "serve.prefill"] == [
        (rid, n) for *_, rid, n in data["rec"]["prefill"]]


# -- a trace recorded on one TPU v5e (``record_serve_trace.py``) -------------

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def on_chip():
    path = str(DATA / "v5e_serve_small.xplane.pb")
    with open(DATA / "v5e_serve_small.json") as f:
        data = json.load(f)
    return data, t.read_xplane(path), dc.read_program(path)


def _program(prog, name):
    return [s for s in prog if s[2] == "serve." + name]


def test_recorded_programs_run_inside_their_calls(on_chip):
    # the device's clock, moved onto the host's, puts each decode program
    # after the host's call that dispatched it began, and before the
    # tokens were read back
    _, tr, prog = on_chip
    lo, hi = tr.window
    mods = sorted(m for m in tr.modules[0]
                  if m[2] == dc.DECODE_PROGRAM and lo <= m[0] < hi)
    launch = _program(prog, "decode.launch")
    sample = _program(prog, "decode.sample")
    path = str(DATA / "v5e_serve_small.xplane.pb")
    from jax.profiler import ProfileData

    calls = sorted(e.start_ns for p in ProfileData.from_file(path).planes
                   for line in p.lines for e in line.events
                   if e.name == "PjitFunction(serve_decode)")
    dispatch = [min(c for c in calls if a[0] <= c < a[1]) for a in launch]
    assert len(mods) == len(launch) == len(sample) == 4
    for m, d, b in zip(mods, dispatch, sample):
        assert d <= m[0] and m[1] <= b[1], (m, d, b)


def test_recorded_gaps_of_program_and_benchmark_spans(on_chip):
    _, tr, prog = on_chip
    ours = _program(prog, "decode")
    theirs = sorted(dc.benchmark_spans(tr))
    mine, bench = dc.decode_gaps(tr, ours), dc.decode_gaps(tr, theirs)
    assert len(mine) == len(bench) == len(ours) == 4
    gaps = t.idle_gaps(tr)
    ends = [e for _, e in gaps]
    for o, b, x, y in zip(ours, theirs, mine, bench):
        assert sum(x) == dc.idle_within(gaps, ends, o[0], o[1])
        # the benchmark's span encloses the program's: the same program
        # gap, and launch and sample gaps longer by at most the time
        # between the two spans' starts and ends
        assert y[1] == x[1]
        assert x[0] <= y[0] <= x[0] + o[0] - b[0]
        assert x[2] <= y[2] <= x[2] + b[1] - o[1]
    assert [d[3]["live"] for d in ours] == [2, 3, 2, 2]


def _recorded_run(data, trace):
    rec = harness.Recorder()
    rec.prefill, rec.decode, rec.step = (data["rec"][k] for k in
                                         ("prefill", "decode", "step"))
    rec.admitted = {int(k): v for k, v in data["rec"]["admitted"].items()}
    rec.tokens = {int(k): v for k, v in data["rec"]["tokens"].items()}
    win = dict(data["win"], due={int(k): v
                                 for k, v in data["win"]["due"].items()})
    planned = [SimpleNamespace(rid=r) for r in sorted(win["due"])]
    return harness.Run(data["m"], harness.load_peaks(data["device_kind"]),
                       data["chips"], rec, win, planned, trace)


RECORDED = {"decode_launch_gap_ms": 1.933606,
            "decode_mfu": 0.006908758516333173,
            "decode_program_gap_ms": 0.0003955,
            "decode_sample_gap_ms": 0.5922355,
            "decode_step_ms": 2.566304749999304,
            "decode_step_roofline": 0.7872829616100521,
            "device_idle_share": 99.30085140420827,
            "engine_host_share": 97.42862493330138,
            "flash_attention_roofline": 0.8147080258287905,
            "mfu": 0.00018422739421563631,
            "prefill_mfu": 0.06551358790406017,
            "prefill_tokens_per_s": 10503.188137732748,
            "queue_wait_p90_s": 0.002212343999998723}


def test_every_reader_on_the_recorded_run(on_chip):
    # pinned, so that a change to the reduction shows in every metric
    data, tr, _ = on_chip
    run = _recorded_run(data, tr)
    names = sorted(p.stem for p in (DATA.parent.parent / "metrics").glob(
        "*.py"))
    got = {n: harness.load_reader(n)(run) for n in names}
    assert got == pytest.approx(RECORDED, rel=1e-9)
