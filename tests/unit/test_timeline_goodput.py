"""Measured step-time attribution (`telemetry/timeline.py`) and the
run-level goodput ledger (`telemetry/goodput.py`).

Covers the trace-event categorizer (synthetic fixtures per category;
unknown ops land in `unscoped`, never dropped), the interval-sweep
decomposition (categories sum to wall by construction, overlap
attribution, clock-skew scaling, pipe-bubble carve), goodput bucket
arithmetic on a fake clock (buckets sum to lifetime, restart
attribution through the union run file, overflow-skip steps are
productive), the CPU capture fallback (`measured: false`, honest), and
the flight-dump integration (timeline + goodput records land before the
snapshot; a capture that raises mid-step propagates without leaving a
torn record).
"""

import json
import os

import pytest

from deepspeed_tpu.telemetry.flight import FlightRecorder
from deepspeed_tpu.telemetry.goodput import (BUCKETS, GoodputLedger,
                                             set_goodput_ledger)
from deepspeed_tpu.telemetry.registry import MetricsRegistry
from deepspeed_tpu.telemetry.timeline import (StepTimeline, capture_thunk,
                                              categorize_op,
                                              decompose_events)


# ------------------------------------------------------------ categorizer
@pytest.mark.parametrize("name,cat", [
    ("all-reduce.17", "all_reduce"),
    ("fusion.all_reduce.3", "all_reduce"),
    ("all-gather-start", "all_gather"),
    ("reduce-scatter.2", "reduce_scatter"),
    ("all-to-all.1", "all_to_all"),
    ("collective-permute.9", "collective_permute"),
    ("ppermute", "collective_permute"),
    ("custom-call.flash_attention", "attention"),
    ("dstpu_flash_fwd.3", "attention"),     # the names the kernels have
    ("dstpu_paged_decode.6", "attention"),  # in a v5e trace
    # XLA's own operations are named by no pattern (on a TPU a gemm is
    # fusion.167): what no region table knows is unscoped, never dropped
    # (tests/unit/test_regions.py has the tables)
    ("dynamic-update-slice.2", "unscoped"),
    ("some_op_nobody_has_heard_of", "unscoped"),
])
def test_categorize_op(name, cat):
    assert categorize_op(name) == cat


def test_collective_shadows_compute_in_fused_names():
    # a fusion name embedding BOTH signals must categorize as the
    # collective: that is the scarcer (and perf-relevant) signal
    assert categorize_op("fusion.dot.all-reduce.1") == "all_reduce"


# ---------------------------------------------------------- decomposition
def test_decompose_sums_to_wall_and_splits_overlap():
    events = [
        {"name": "dstpu_flash_fwd.1", "ts": 0.0, "dur": 0.4},
        {"name": "all-reduce.1", "ts": 0.2, "dur": 0.4},   # 0.2 hidden, 0.2 exposed
        {"name": "fusion.1", "ts": 0.7, "dur": 0.1},
    ]
    d = decompose_events(events, wall_s=1.0)
    cats = d["categories"]
    assert abs(sum(cats.values()) - 1.0) < 1e-9
    assert abs(cats["attention"] - 0.4) < 1e-9
    assert abs(cats["all_reduce"] - 0.2) < 1e-9      # only the exposed part
    assert abs(cats["unscoped"] - 0.1) < 1e-9
    assert abs(cats["host_gap"] - 0.3) < 1e-9        # 1.0 - 0.7 device busy
    assert abs(d["exposed_collective_seconds"] - 0.2) < 1e-9
    assert abs(d["overlapped_collective_seconds"] - 0.2) < 1e-9


def test_decompose_unknown_ops_never_dropped():
    d = decompose_events([{"name": "mystery", "ts": 0.0, "dur": 0.5}], 1.0)
    assert abs(d["categories"]["unscoped"] - 0.5) < 1e-9
    assert abs(sum(d["categories"].values()) - 1.0) < 1e-9


def test_decompose_scales_on_clock_skew():
    # device busy (2.0s) exceeding the host wall (1.0s) is clock skew:
    # everything scales down so the identity still holds
    d = decompose_events([{"name": "dot", "ts": 0.0, "dur": 2.0}], 1.0)
    assert d["scale"] == pytest.approx(0.5)
    assert d["categories"]["unscoped"] == pytest.approx(1.0)
    assert sum(d["categories"].values()) == pytest.approx(1.0)


def test_decompose_pipe_bubble_carved_from_gap():
    d = decompose_events([{"name": "dot", "ts": 0.0, "dur": 0.4}], 1.0,
                         pipe_bubble_fraction=0.25)
    assert d["categories"]["pipe_bubble"] == pytest.approx(0.25)
    assert d["categories"]["host_gap"] == pytest.approx(0.35)
    assert sum(d["categories"].values()) == pytest.approx(1.0)
    # the bubble can never exceed the measured gap, whatever the claim
    d2 = decompose_events([{"name": "dot", "ts": 0.0, "dur": 0.9}], 1.0,
                          pipe_bubble_fraction=0.5)
    assert d2["categories"]["pipe_bubble"] == pytest.approx(0.1)
    assert d2["categories"]["host_gap"] == pytest.approx(0.0)


def test_decompose_empty_trace_is_all_gap():
    d = decompose_events([], 2.0)
    assert d["categories"]["host_gap"] == pytest.approx(2.0)
    assert sum(d["categories"].values()) == pytest.approx(2.0)


# -------------------------------------------------------- goodput ledger
class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_goodput_buckets_sum_to_lifetime():
    clk = _Clock()
    led = GoodputLedger(registry=MetricsRegistry(), now_fn=clk)
    led.observe_step(2.0, step=1)
    led.observe_phase("checkpoint_save", 0.5)
    led.observe_phase("eval", 0.25)
    clk.t += 10.0
    s = led.summary()
    assert set(s["buckets"]) == set(BUCKETS)
    assert sum(s["buckets"].values()) == pytest.approx(s["lifetime_seconds"])
    assert s["buckets"]["step"] == pytest.approx(2.0)
    assert s["buckets"]["idle"] == pytest.approx(10.0 - 2.75)
    assert s["goodput_fraction"] == pytest.approx(0.2)
    assert s["productive_steps"] == 1


def test_goodput_stall_and_skip_classification():
    led = GoodputLedger(registry=MetricsRegistry(), now_fn=_Clock())
    led.observe_step(1.0, step=1, stalled=True)   # whole step is badput
    led.observe_step(1.0, step=2, skipped=True)   # overflow skip: productive
    s = led.summary()
    assert s["buckets"]["stall"] == pytest.approx(1.0)
    assert s["buckets"]["step"] == pytest.approx(1.0)
    assert s["productive_steps"] == 1


def test_goodput_rejects_step_idle_and_unknown_phases():
    led = GoodputLedger(registry=MetricsRegistry(), now_fn=_Clock())
    for bad in ("step", "idle", "lunch"):
        with pytest.raises(ValueError):
            led.observe_phase(bad, 1.0)


def test_goodput_override_reroutes_phases():
    led = GoodputLedger(registry=MetricsRegistry(), now_fn=_Clock())
    with led.override("restart"):
        led.observe_phase("checkpoint_load", 0.75)
    s = led.summary()
    assert s["buckets"]["restart"] == pytest.approx(0.75)
    assert s["buckets"]["checkpoint_load"] == pytest.approx(0.0)


def test_goodput_union_run_file_restart_attribution(tmp_path):
    run = str(tmp_path / "goodput_run.json")
    # attempt 1: steps 1..3 productive, then dies (no close())
    a1 = GoodputLedger(registry=MetricsRegistry(), run_file=run,
                       now_fn=_Clock())
    for st in (1, 2, 3):
        a1.observe_step(1.0, step=st)
    rec = json.load(open(run))
    assert rec["high_water"] == 3 and rec["productive_steps"] == 3
    assert rec["attempts"] == 1
    # attempt 2: resumes behind the high water — step 3 is recompute
    # (restart badput), steps 4..5 are fresh progress
    a2 = GoodputLedger(registry=MetricsRegistry(), run_file=run,
                       now_fn=_Clock())
    a2.observe_step(1.0, step=3)
    for st in (4, 5):
        a2.observe_step(1.0, step=st)
    rec = json.load(open(run))
    assert rec["attempts"] == 2
    assert rec["high_water"] == 5
    assert rec["recomputed_steps"] == 1
    assert rec["buckets"]["restart"] == pytest.approx(1.0)
    # union productive matches an uninterrupted 5-step run
    assert rec["productive_steps"] == 5
    assert rec["buckets"]["step"] == pytest.approx(5.0)


def test_goodput_publish_folds_into_registry():
    reg = MetricsRegistry()
    clk = _Clock()
    led = GoodputLedger(registry=reg, now_fn=clk)
    led.observe_step(2.0, step=1)
    clk.t += 4.0
    led.close()
    sec = reg.get("deepspeed_tpu_goodput_seconds_total")
    frac = reg.get("deepspeed_tpu_goodput_fraction")
    assert sec is not None and sec.total() == pytest.approx(4.0)
    assert frac is not None and frac.value() == pytest.approx(0.5)


# ------------------------------------------------- capture + flight dump
def test_capture_thunk_cpu_fallback_is_honest(tmp_path):
    import jax.numpy as jnp

    from deepspeed_tpu.telemetry.spans import span

    tl = StepTimeline(every_n_steps=0, artifact_dir=str(tmp_path / "art"),
                      registry=MetricsRegistry())

    def work():
        with span("timeline_test_work"):
            return float(jnp.asarray([1.0, 2.0]).sum())

    out, rec = capture_thunk(work, step=5, timeline=tl)
    assert out == 3.0
    assert rec is not None and rec["step"] == 5
    import jax

    if jax.default_backend() == "cpu":
        # no device timeline on CPU: the record must say so, not guess
        assert rec["measured"] is False
    cats = rec["categories"]
    assert sum(cats.values()) == pytest.approx(rec["wall_seconds"], abs=1e-6)
    # the merged Chrome-trace artifact parses and carries events
    arts = os.listdir(str(tmp_path / "art"))
    assert arts
    trace = json.load(open(str(tmp_path / "art" / arts[0])))
    assert trace.get("traceEvents")


def test_capture_exception_propagates_without_torn_record():
    tl = StepTimeline(every_n_steps=0, registry=MetricsRegistry())
    before = tl.last_record()

    class Boom(RuntimeError):
        pass

    tl.force_next()
    with pytest.raises(Boom):
        with tl.capture(step=1):
            raise Boom("step died mid-capture")
    # the failed capture never publishes a half-built record
    assert tl.last_record() == before
    # and the timeline is reusable afterwards (not stuck "active")
    assert tl.should_capture(0) is False
    tl.force_next()
    assert tl.should_capture(0) is True


def test_flight_dump_carries_timeline_and_goodput(tmp_path):
    from deepspeed_tpu.telemetry import timeline as tl_mod

    tl_mod._set_last_record({"step": 7, "measured": False,
                             "categories": {"host_gap": 1.0},
                             "wall_seconds": 1.0})
    clk = _Clock()
    led = GoodputLedger(registry=MetricsRegistry(), now_fn=clk)
    led.observe_step(1.0, step=1)
    clk.t += 2.0
    set_goodput_ledger(led)
    try:
        fr = FlightRecorder(path=str(tmp_path), registry=MetricsRegistry())
        path = fr.dump(reason="manual:test")
        kinds = [json.loads(line)["kind"] for line in open(path)]
        assert "timeline" in kinds and "goodput" in kinds
        # both land BEFORE the final snapshot, like the memory section
        assert kinds.index("timeline") < kinds.index("snapshot")
        assert kinds.index("goodput") < kinds.index("snapshot")
        recs = [json.loads(line) for line in open(path)]
        tl_rec = next(r for r in recs if r["kind"] == "timeline")
        assert tl_rec["step"] == 7 and tl_rec["measured"] is False
        gp_rec = next(r for r in recs if r["kind"] == "goodput")
        assert gp_rec["buckets"]["step"] == pytest.approx(1.0)
    finally:
        set_goodput_ledger(None)
