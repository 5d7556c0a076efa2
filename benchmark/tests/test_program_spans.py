"""The program's own spans as the benchmark reads them: interval arithmetic
on hand-made spans, and the small trace recorded on a v5e with the serving
engine's spans in it (fixtures/small_serve_v5e.xplane.pb: two requests
through a two-layer engine, every step in a `bench.step` after a 2 ms sleep in
`bench.put`; fixtures/small_serve_v5e.expected.json holds what the readers
read from it when it was recorded)."""

import json
import os

import pytest

from benchmark import manifest as manifest_mod
from benchmark import program_spans as ps
from benchmark import trace_reduce as tr

FIX = os.path.join(manifest_mod.HERE, "fixtures")
SERVE = os.path.join(FIX, "small_serve_v5e.xplane.pb")
DEV = "/device:TPU:0"
DECODE_ONLY = {"has": ["decode"], "lacks": ["prefill"]}


def _ops(*rows):
    return [tr.Op(n, s, e) for n, s, e in rows]


def _readers(hand_made_clock=False):
    """The two readers, each a module of its own as the harness loads
    them.  The hand-made times are whole numbers, with clocks up to 1.0
    apart: the offsets tried there are -1.0 to 1.0 in steps of 0.1."""
    man = manifest_mod.Manifest()
    idle = man.module("readers", "idle_by_program_span_ms")
    if hand_made_clock:
        idle.OFFSETS_S = [0.1 * k for k in range(-10, 11)]
    return man.module("readers", "program_span_ms"), idle


HOST_SPANS = (
    ("serve_step", 0.0, 10.0), ("prefill", 1.0, 3.0),
    ("dispatch", 1.2, 1.4), ("device_wait", 2.0, 3.0),
    ("decode", 4.0, 9.0), ("dispatch", 4.3, 4.5),
    ("device_wait", 5.0, 8.5), ("step_emit", 9.0, 9.5),
    ("serve_step", 11.0, 20.0), ("decode", 12.0, 19.0),
    ("dispatch", 12.3, 12.5), ("device_wait", 13.0, 18.5),
    ("step_emit", 19.0, 19.5),
    ("serve_step", 30.0, 31.0))                # outside the window
SPLIT = dict(after="device_wait", until="dispatch")


def _hand_made(monkeypatch, device_clock_ahead=0.0):
    """Two steps on one device.  Step 1 [0, 10): a prefill [1, 3) that
    calls its program in [1.2, 1.4) and waits for it in [2, 3), a decode
    [4, 9) that calls in [4.3, 4.5) and waits in [5, 8.5).  Step 2 [11, 20):
    a decode [12, 19), call [12.3, 12.5), wait [13, 18.5).  The device runs
    [1.5, 2.5), [4.5, 8) and [12.5, 18) on the host's clock."""
    spans = ps.ProgramSpans(_ops(*HOST_SPANS))
    d = device_clock_ahead
    red = tr.Reduced(
        {DEV: _ops(("fusion.1", 1.5 + d, 2.5 + d),
                   ("fusion.2", 4.5 + d, 8.0 + d),
                   ("fusion.2", 12.5 + d, 18.0 + d))}, {DEV: []},
        _ops(("bench.step", 0.0, 10.0), ("bench.put", 10.0, 11.0),
             ("bench.step", 11.0, 20.0)), {DEV: []})
    monkeypatch.setattr(ps, "of_run", lambda reduced: spans.clipped(
        reduced.window()))
    return {"trace": red}


@pytest.fixture
def hand_made(monkeypatch):
    return _hand_made(monkeypatch)


def test_spans_by_name_inside_a_parent_and_clipped():
    spans = ps.ProgramSpans(_ops(
        ("serve_step", 0.0, 10.0), ("decode", 4.0, 9.0),
        ("serve_step", 11.0, 20.0), ("decode", 12.0, 19.0),
        ("device_wait", 2.0, 3.0), ("device_wait", 5.0, 8.5),
        ("device_wait", 13.0, 18.5)))
    one, two = spans.named("serve_step")
    assert [(w.start, w.end) for w in spans.inside("device_wait", one)] == \
        [(2.0, 3.0), (5.0, 8.5)]
    assert [(w.start, w.end) for w in spans.inside("device_wait", two)] == \
        [(13.0, 18.5)]
    assert spans.inside("prefill", one) == [] == spans.named("prefill")
    cut = spans.clipped((0.0, 12.5))
    assert len(cut.named("serve_step")) == 1 and len(cut.named("decode")) == 1
    assert ps.where_passes(spans, two, DECODE_ONLY)
    assert ps.where_passes(spans, one, None)
    assert not ps.where_passes(spans, one, {"has": ["prefill"]})


def test_span_ms_self_time_and_the_steps_where_chooses(hand_made):
    span_ms, _ = _readers()
    # serve_step less device_wait: (10 - 4.5) and (9 - 5.5), in ms
    assert span_ms.read(hand_made, span="serve_step",
                        less=["device_wait"]) \
        == pytest.approx(1e3 * (5.5 + 3.5) / 2)
    # ... and less the calls as well: 0.4 and 0.2 more
    assert span_ms.read(hand_made, span="serve_step",
                        less=["device_wait", "dispatch"]) \
        == pytest.approx(1e3 * (5.1 + 3.3) / 2)
    # decode-only steps: the second alone
    assert span_ms.read(hand_made, span="serve_step", less=["device_wait"],
                        where=DECODE_ONLY) == pytest.approx(3500.0)
    assert span_ms.read(hand_made, span="device_wait", where=DECODE_ONLY) \
        == pytest.approx(5500.0)
    assert span_ms.read(hand_made, span="serve_step", where=DECODE_ONLY) \
        == pytest.approx(9000.0)
    assert span_ms.read(hand_made, span="decode", less=["device_wait"]) \
        == pytest.approx(1e3 * (1.5 + 1.5) / 2)
    # a span the program never recorded: a step's 0; no step: nothing
    assert span_ms.read(hand_made, span="spec_verify") == 0.0
    assert span_ms.read(hand_made, span="serve_step",
                        where={"has": ["spec_verify"]}) is None
    assert span_ms.read(hand_made, span="decode", per="train_step") is None


@pytest.mark.parametrize("ahead", [0.0, 0.4, -0.4, 0.7])
def test_idle_split_is_the_hosts_stretch_whatever_the_clocks_offset(
        monkeypatch, ahead):
    """The host's part of an idle gap is the stretch from the end of a wait
    to the start of the next program call, on the host's clock: 3 -> 4.3 in
    the gap that begins at 2.5 and 8.5 -> 12.3 in the one that begins at 8
    (the last wait has no call after it).  A device clock 0.4 ahead of or
    behind the host's moves every gap and not the split, nor does one 0.7
    ahead, by which a wait seems to end before its gap begins."""
    _, idle = _readers(hand_made_clock=True)
    ctx = _hand_made(monkeypatch, ahead)
    red = ctx["trace"]
    host = idle.read(ctx, host=True, **SPLIT)
    rest = idle.read(ctx, host=False, **SPLIT)
    assert host == pytest.approx(1e3 * (1.3 + 3.8) / 2)
    assert rest == pytest.approx(1e3 * (10.0 - 5.1) / 2)
    assert host + rest == pytest.approx(
        1e3 * (red.window_seconds() - red.busy_seconds()) / 2)
    assert idle.read(ctx, host=True, per="train_step", **SPLIT) is None
    # the offset found (host clock less device clock) is one at which both
    # stretches lie inside their gaps: from 0.2 under to 0.5 over the truth
    gaps = tr.subtract([red.window()], red.busy[DEV])
    off = idle.clock_offset([(3.0, 4.3), (8.5, 12.3)], gaps)
    assert -0.2 - 1e-9 <= off + ahead <= 0.5 + 1e-9


def test_idle_split_takes_no_more_than_the_gap_it_fell_in(hand_made):
    _, idle = _readers(hand_made_clock=True)
    # no call after any wait, or no wait: nothing to read
    assert idle.read(hand_made, host=True, after="device_wait",
                     until="spec_verify") is None
    assert idle.read(hand_made, host=True, after="spec_verify",
                     until="dispatch") is None
    # the host's stretch is capped by the gap it fell in: 3 -> 4.3 fits
    # in a gap of 1.5 and not in one of 1.0
    hand_made["trace"].busy[DEV] = [(1.5, 2.5), (4.0, 8.0), (12.5, 18.0)]
    assert idle.read(hand_made, host=True, **SPLIT) == pytest.approx(
        1e3 * (1.3 + 3.8) / 2)
    hand_made["trace"].busy[DEV] = [(1.5, 2.5), (3.5, 8.0), (12.5, 18.0)]
    assert idle.read(hand_made, host=True, **SPLIT) == pytest.approx(
        1e3 * (1.0 + 3.8) / 2)
    # a stretch that meets no gap at any offset tried claims nothing
    hand_made["trace"].busy[DEV] = [(0.0, 20.0)]
    assert idle.read(hand_made, host=True, **SPLIT) == 0.0


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    """The parent of the PR that added the spans: its trace has `prefill`
    and `decode` and no `serve_step`; and a run that left no trace file."""
    span_ms, idle = _readers()
    red = tr.Reduced({DEV: _ops(("fusion.1", 1.0, 2.0))}, {DEV: []},
                     _ops(("bench.step", 0.0, 3.0)), {DEV: []})
    old = ps.ProgramSpans(_ops(("decode", 0.5, 2.5)))
    monkeypatch.setattr(ps, "of_run", lambda reduced: old)
    assert span_ms.read({"trace": red}, span="serve_step",
                        less=["device_wait"]) is None
    assert idle.read({"trace": red}, after="device_wait", until="dispatch",
                     host=True) is None
    monkeypatch.undo()
    monkeypatch.setattr(ps, "TRACES", os.path.join(FIX, "no-such-*", "*.pb"))
    assert ps.of_run(red) is None
    assert span_ms.read({"trace": red}, span="serve_step") is None
    assert idle.read({"trace": red}, after="device_wait", until="dispatch",
                     host=False) is None


# --------------------------------------------------- the recorded v5e trace
@pytest.fixture
def recorded(monkeypatch):
    if not os.path.isfile(SERVE):
        pytest.skip("no recorded trace")
    monkeypatch.setattr(ps, "TRACES", SERVE)  # found as a run's trace is
    with open(os.path.join(FIX, "small_serve_v5e.expected.json")) as f:
        want = json.load(f)
    return {"trace": tr.reduce_file(SERVE)}, want


def test_recorded_serve_trace_structure(recorded):
    ctx, want = recorded
    assert os.path.getsize(SERVE) < 200_000
    red = ctx["trace"]
    assert red.devices() == [DEV]
    spans = ps.of_run(red)
    steps = spans.named("serve_step")
    assert len(steps) == want["steps"] == len(red.span_list("bench.step"))
    # one program step inside each of the benchmark's, in order
    for mine, bench in zip(steps, red.span_list("bench.step")):
        assert bench.start <= mine.start and mine.end <= bench.end
    assert {n: len(s) for n, s in spans.by_name.items()} == \
        want["span_counts"]
    for step in steps:
        assert len(spans.inside("step_admit", step)) == 1
        waits = spans.inside("device_wait", step)
        assert len(waits) == len(spans.inside("decode", step)) + sum(
            1 for p in spans.inside("prefill", step)
            if any(p.start <= w.start and w.end <= p.end for w in waits))
    # the program's names only: nothing of the benchmark's, no runtime event
    assert set(spans.by_name) <= ps.SERVE_SPANS
    for step in steps:  # one call in every phase, before the phase's wait
        phases = spans.inside("prefill", step) + spans.inside("decode", step)
        assert len(spans.inside("dispatch", step)) == len(phases)
        for p in phases:
            (call,) = spans.inside("dispatch", p)
            assert all(call.end <= w.start
                       for w in spans.inside("device_wait", p))
    assert any(ps.where_passes(spans, s, DECODE_ONLY) for s in steps)
    assert not all(ps.where_passes(spans, s, DECODE_ONLY) for s in steps)


def test_recorded_serve_trace_numbers_repeat(recorded):
    ctx, want = recorded
    span_ms, idle = _readers()
    red = ctx["trace"]
    assert red.window_seconds() == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_seconds() == pytest.approx(want["busy_s"], rel=1e-9)
    host = span_ms.read(ctx, span="serve_step", less=["device_wait"],
                        where=DECODE_ONLY)
    wait = span_ms.read(ctx, span="device_wait", where=DECODE_ONLY)
    whole = span_ms.read(ctx, span="serve_step", where=DECODE_ONLY)
    assert host == pytest.approx(want["step_host_ms.decode_only"], rel=1e-9)
    assert host + wait == pytest.approx(whole, rel=1e-9)
    # every step, and less the program calls as well
    assert span_ms.read(ctx, span="serve_step",
                        less=["device_wait", "dispatch"]) == \
        pytest.approx(want["step_host_ms"], rel=1e-9)
    split = dict(after="device_wait", until="dispatch")
    inside = idle.read(ctx, host=False, **split)
    outside = idle.read(ctx, host=True, **split)
    assert inside == pytest.approx(want["idle_in_device_wait_ms"], rel=1e-9)
    assert outside == pytest.approx(want["idle_outside_device_wait_ms"],
                                    rel=1e-9)
    assert inside + outside == pytest.approx(
        1e3 * (red.window_seconds() - red.busy_seconds()) / want["steps"],
        rel=1e-9)
    # the 2 ms sleeps in bench.put are the host's: between a wait and the
    # next call
    assert outside > 2.0 and inside > 0.0
    # this trace's host clock is 0.9 - 2.0 ms ahead of its device clock: the
    # first program starts 0.9 ms before its call, the first wait ends
    # 2.1 ms after the device went idle
    spans = ps.of_run(red)
    calls = [c.start for c in spans.named("dispatch")]
    stretches = [(w.end, min(c for c in calls if c >= w.end))
                 for w in spans.named("device_wait") if w.end <= calls[-1]]
    gaps = tr.subtract([red.window()], red.busy[DEV])
    assert len(stretches) == 5
    assert 0.8e-3 < idle.clock_offset(stretches, gaps) < 2.1e-3


def test_the_five_metrics_read_through_their_files(recorded):
    """Each new layer_metrics file, through the reader and arguments it
    names, as harness.result_line calls it."""
    ctx, _want = recorded
    man = manifest_mod.Manifest()
    new = [m["name"] for m in man.data["per_layer"]
           if m["source"] == "program_span"]
    assert len(new) == 5
    for name in new:
        spec = man.layer_metric(name)
        value = man.module("readers", spec["reader"]).read(
            ctx, **spec.get("args", {}))
        assert value is not None and value >= 0.0, name
