"""The serving engine's own spans (``serve.<what>``, see
``repro.serving.tracing``), read back from a profiler trace of a small
engine run on the CPU: where each span sits, and what its arguments
count."""
import glob

import jax
import pytest

from repro.serving.engine import Request, ServingEngine

# (prompt length, tokens to generate, arrival step): five requests on
# three slots, so that the live rows and the queue change from step to step
REQUESTS = [(5, 3, 0), (9, 6, 0), (3, 4, 1), (7, 2, 1), (4, 3, 3)]
SLOTS = 3


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from jax.profiler import ProfileData

    from repro.configs.base import get_config
    from repro.models import registry as mreg

    cfg = get_config("gemma-2b", reduced=True)
    params = mreg.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine.with_model(cfg, params, num_blocks=24, block_size=4,
                                   max_slots=SLOTS, max_blocks_per_seq=6)
    for rid, (n, new, at) in enumerate(REQUESTS):
        eng.submit(Request(rid=rid, prompt=tuple(range(1, n + 1)),
                           max_new_tokens=new, arrival=at))
    seen = {"step": [], "decode": []}
    step, decode, sc = eng.step, eng.model.decode, eng.scheduler

    def step_counted():
        seen["step"].append((len(sc.running), len(sc.pending)
                             + sum(map(len, sc.queues.values()))))
        return step()

    def decode_counted(*a):
        seen["decode"].append(len(sc.running))
        return decode(*a)

    eng.step, eng.model.decode = step_counted, decode_counted
    log_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(log_dir)):
        eng.run(max_steps=100)
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events.extend((e.start_ns, e.end_ns, e.name, dict(e.stats))
                          for e in line.events
                          if e.name.startswith(("serve.", "PjitFunction(")))
    return eng, seen, events


def _named(events, name):
    return sorted((e for e in events if e[2] == name), key=lambda e: e[:2])


def _within(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_spans_nest_inside_their_step(traced):
    eng, seen, events = traced
    steps = _named(events, "serve.step")
    decodes = _named(events, "serve.decode")
    assert len(steps) == eng.step_count
    assert len(decodes) == len(seen["decode"]) > 0
    for name in ("serve.prefill", "serve.decode"):
        for s in _named(events, name):
            assert sum(_within(s, st) for st in steps) == 1, (name, s)
    for name in ("serve.decode.launch", "serve.decode.sample"):
        inner = _named(events, name)
        assert len(inner) == len(decodes)
        for s, d in zip(inner, decodes):
            assert _within(s, d), (name, s, d)
    # the sampling starts once the launch has returned
    for a, b in zip(_named(events, "serve.decode.launch"),
                    _named(events, "serve.decode.sample")):
        assert a[1] <= b[0]


def test_step_span_counts_running_and_queued(traced):
    eng, seen, events = traced
    steps = _named(events, "serve.step")
    assert [s[3]["step_num"] for s in steps] == list(range(eng.step_count))
    assert [(s[3]["running"], s[3]["queued"]) for s in steps] == seen["step"]
    assert max(q for _, q in seen["step"]) > 0


def test_decode_span_counts_live_rows(traced):
    _, seen, events = traced
    decodes = _named(events, "serve.decode")
    assert [d[3]["live"] for d in decodes] == seen["decode"]
    assert {d[3]["slots"] for d in decodes} == {SLOTS}
    assert len(set(seen["decode"])) > 1


def test_prefill_span_counts_the_admitted_prompt(traced):
    eng, _, events = traced
    admitted = [e[2] for e in eng.scheduler.events if e[0] == "admit"]
    assert sorted(admitted) == list(range(len(REQUESTS)))
    assert [(s[3]["rid"], s[3]["tokens"])
            for s in _named(events, "serve.prefill")] == [
        (rid, REQUESTS[rid][0]) for rid in admitted]


def test_programs_are_named_and_dispatched_inside_their_spans(traced):
    _, _, events = traced
    for span, program in (("serve.decode.launch", "serve_decode"),
                          ("serve.prefill", "serve_prefill")):
        calls = _named(events, f"PjitFunction({program})")
        spans = _named(events, span)
        assert calls and spans
        assert all(any(_within(c, s) for s in spans) for c in calls)
        assert all(any(_within(c, s) for c in calls) for s in spans)
