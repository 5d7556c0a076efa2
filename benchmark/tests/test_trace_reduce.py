"""The trace reducer: interval arithmetic on hand-made events, and the small
trace recorded on a v5e (fixtures/small_v5e.xplane.pb: three `bench.step`
spans of a jitted scan of two flash-attention calls and a matmul, each after a
2 ms `bench.next_batch` sleep; fixtures/small_v5e.expected.json holds what the
reducer read from it when it was recorded)."""

import json
import os

import pytest

from benchmark import manifest as manifest_mod
from benchmark import trace_reduce as tr

FIX = os.path.join(manifest_mod.HERE, "fixtures")


def _ops(*rows):
    return [tr.Op(n, s, e) for n, s, e in rows]


def test_interval_arithmetic():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.total([(0, 2), (3, 4)]) == 3
    assert tr.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 1), (2, 3)], [(0, 3)]) == []
    assert tr.short_name("%fusion.3 = bf16[2,4]{1,0} fusion(%p), kind=kLoop") \
        == "fusion.3"
    assert tr.short_name("dstpu_flash_fwd.7") == "dstpu_flash_fwd.7"
    assert tr.is_collective("all-gather-start.4")
    assert not tr.is_collective("fusion.4")


def test_self_time_busy_gaps_and_exposed_collectives():
    dev = "/device:TPU:0"
    ops = _ops(
        ("while.1", 1.0, 5.0),                 # holds the next three
        ("fusion.1", 1.0, 2.0),
        ("dstpu_flash_fwd.2", 2.0, 3.5),
        ("all-gather.3", 3.5, 5.0),            # half hidden under async copy
        ("fusion.9", 7.0, 8.0),
        ("all-reduce.4", 8.0, 9.0),
    )
    spans = _ops(("bench.next_batch", 0.0, 0.5), ("bench.step", 0.5, 6.5),
                 ("bench.step", 6.5, 10.0))
    red = tr.Reduced({dev: ops}, {dev: []}, spans, {dev: []})
    by = {o.name: o.self_s for o in red.device_ops[dev]}
    assert by["while.1"] == pytest.approx(0.0)
    assert by["dstpu_flash_fwd.2"] == pytest.approx(1.5)
    assert red.window() == (0.0, 10.0)
    assert red.busy_seconds() == pytest.approx(6.0)           # [1,5] + [7,9]
    assert sum(by.values()) == pytest.approx(red.busy_seconds())
    assert red.op_seconds(lambda n: "dstpu_" in n) == pytest.approx(1.5)
    assert red.busy_inside(0.5, 6.5) == pytest.approx(4.0)
    # gaps: [0,1] began inside next_batch; [5,7] and [9,10] inside a step
    gaps = red.idle_gaps()
    assert gaps == {"bench.next_batch": pytest.approx(1.0),
                    "bench.step": pytest.approx(3.0)}
    # collectives [3.5,5] and [8,9]; nothing else runs then: all exposed
    assert red.exposed_collective_seconds() == pytest.approx(2.5)
    top = red.breakdown()
    assert top["device_ops"][0][0] in ("dstpu_flash_fwd.2", "all-gather.3")
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10


def test_gap_outside_any_span_is_named():
    dev = "/device:TPU:0"
    red = tr.Reduced({dev: _ops(("fusion.1", 0.0, 1.0), ("fusion.2", 4.0, 5.0))},
                     {dev: []}, _ops(("bench.step", 0.0, 2.0),
                                     ("bench.step", 3.0, 5.0)), {dev: []})
    assert red.idle_gaps() == {"bench.step": pytest.approx(3.0)}
    red2 = tr.Reduced({dev: _ops(("fusion.1", 0.0, 1.0), ("fusion.2", 4.0, 5.0))},
                      {dev: []}, _ops(("bench.step", 0.0, 0.5),
                                      ("bench.step", 4.5, 5.0)), {dev: []})
    assert red2.idle_gaps() == {tr.NO_SPAN: pytest.approx(3.0)}


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(FIX, "small_v5e.xplane.pb")
    if not os.path.isfile(path):
        pytest.skip("no recorded trace")
    return tr.reduce_file(path)


def test_recorded_trace_structure(recorded):
    assert recorded.devices() == ["/device:TPU:0"]
    assert [s.name for s in recorded.spans].count("bench.step") == 3
    assert [s.name for s in recorded.spans].count("bench.next_batch") == 3
    ops = recorded.ops_in_window()
    flash = [o for o in ops if o.name.startswith("dstpu_flash_fwd")]
    assert len(flash) == 6                     # two calls a step, three steps
    assert all(" = " not in o.name and not o.name.startswith("%") for o in ops)
    busy, window = recorded.busy_seconds(), recorded.window_seconds()
    assert 0 < busy < window
    assert sum(o.self_s for o in ops) == pytest.approx(busy, rel=1e-9)
    # brute force: the union by sweeping the sorted end points
    lo, hi = recorded.window()
    pts = sorted([(o.start, 1) for o in ops] + [(o.end, -1) for o in ops])
    depth, last, total = 0, None, 0.0
    for t, d in pts:
        if depth > 0:
            total += t - last
        depth, last = depth + d, t
    assert total == pytest.approx(busy, rel=1e-6)
    # the 2 ms sleeps are idle time attributed to the span they began in
    assert recorded.idle_gaps().get("bench.next_batch", 0.0) > 0.004
    assert any(m.name.startswith("jit_step") for m in recorded.modules[
        "/device:TPU:0"])


def test_recorded_trace_numbers_repeat(recorded):
    with open(os.path.join(FIX, "small_v5e.expected.json")) as f:
        want = json.load(f)
    assert recorded.window_seconds() == pytest.approx(want["window_s"], rel=1e-9)
    assert recorded.busy_seconds() == pytest.approx(want["busy_s"], rel=1e-9)
    assert recorded.op_seconds(lambda n: "dstpu_flash_fwd" in n) == \
        pytest.approx(want["flash_fwd_s"], rel=1e-9)
    got = recorded.breakdown()
    assert [k for k, _ in got["device_ops"]] == \
        [k for k, _ in want["breakdown"]["device_ops"]]


def test_module_share_is_a_share_of_program_time_and_zero_is_a_reading(recorded):
    reader = manifest_mod.Manifest().module("readers", "module_share")
    assert reader.read({"trace": recorded}, contains="jit_step") == 100.0
    # programs ran and none had the name: 0, the reading a control wants
    assert reader.read({"trace": recorded}, contains="decode") == 0.0
    # no program on record at all: nothing to read
    dev = "/device:TPU:0"
    bare = tr.Reduced({dev: _ops(("fusion.1", 0.0, 1.0))}, {dev: []},
                      _ops(("bench.step", 0.0, 2.0)), {dev: []})
    assert reader.read({"trace": bare}, contains="decode") is None
