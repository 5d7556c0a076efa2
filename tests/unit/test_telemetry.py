"""Telemetry subsystem tests.

Fast tier: registry semantics (counter/gauge/histogram + percentile
math), Prometheus exposition round-trip, JSONL event schema, timer sync
behavior, CSV monitor handle reuse, stall watchdog, MFU helpers, and the
training engine's registry wiring on the tiny MLP.  Slow tier: serving
metrics emission from InferenceEngineV2 on a tiny CPU llama.
"""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry import (JSONLWriter, MetricsRegistry,
                                     PrometheusFileExporter, StallWatchdog,
                                     mfu, parse_prometheus_text,
                                     peak_flops_for_kind, to_prometheus_text)


# ----------------------------- registry semantics ---------------------------
def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("deepspeed_tpu_t_requests_total", "h", labelnames=("op",))
    c.inc(op="a")
    c.inc(2.5, op="a")
    c.inc(op="b")
    assert c.value(op="a") == 3.5 and c.value(op="b") == 1.0
    assert c.total() == 4.5
    with pytest.raises(ValueError):
        c.inc(-1, op="a")  # counters only go up
    with pytest.raises(ValueError):
        c.inc(1)  # missing label
    g = reg.gauge("deepspeed_tpu_t_depth")
    g.set(7)
    g.dec(2)
    assert g.value() == 5.0
    # get-or-create: same name+type returns the same object
    assert reg.counter("deepspeed_tpu_t_requests_total",
                       labelnames=("op",)) is c
    # same name, different type: loud failure
    with pytest.raises(ValueError):
        reg.gauge("deepspeed_tpu_t_requests_total")
    # label-set mismatch on re-registration: loud failure
    with pytest.raises(ValueError):
        reg.counter("deepspeed_tpu_t_requests_total", labelnames=("other",))


def test_metric_name_validation():
    reg = MetricsRegistry()
    for bad in ("loss", "deepspeed_tpu_CamelCase", "deepspeed_tpu_",
                "other_ns_loss", "deepspeed_tpu_x-y"):
        with pytest.raises(ValueError):
            reg.gauge(bad)


def test_histogram_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("deepspeed_tpu_t_latency_seconds", "h",
                      buckets=(0.1, 0.2, 0.4, 0.8, 1.6))
    # 100 uniform samples on (0, 1]: p50 ~ 0.5, p95 ~ 0.95, p99 ~ 0.99,
    # each within its owning bucket's interpolation error
    for i in range(1, 101):
        h.observe(i / 100.0)
    assert h.count() == 100
    assert h.sum() == pytest.approx(50.5)
    assert 0.4 <= h.quantile(0.5) <= 0.8  # p50 interpolated in (0.4, 0.8]
    p = h.percentiles()
    assert 0.8 <= p["p95"] <= 1.6 and 0.8 <= p["p99"] <= 1.6
    assert p["p50"] <= p["p95"] <= p["p99"]
    # +Inf bucket clamps to the top finite bound
    h2 = reg.histogram("deepspeed_tpu_t_big_seconds", buckets=(1.0, 2.0))
    h2.observe(100.0)
    assert h2.quantile(0.99) == 2.0
    # empty series: NaN, not a crash
    assert math.isnan(h.quantile(0.5, **{})) is False  # has data
    h3 = reg.histogram("deepspeed_tpu_t_empty_seconds")
    assert math.isnan(h3.quantile(0.5))


def test_histogram_exact_bucket_math():
    """Deterministic check of the interpolation formula: 10 samples in
    [0, 1) bucket, 10 in [1, 2) bucket (bounds 1 and 2): the median rank
    10 falls exactly at the first bucket's upper bound."""
    reg = MetricsRegistry()
    h = reg.histogram("deepspeed_tpu_t_exact_seconds", buckets=(1.0, 2.0))
    for _ in range(10):
        h.observe(0.5)
    for _ in range(10):
        h.observe(1.5)
    assert h.quantile(0.5) == pytest.approx(1.0)
    assert h.quantile(0.25) == pytest.approx(0.5)
    assert h.quantile(0.75) == pytest.approx(1.5)


def test_snapshot_events():
    reg = MetricsRegistry()
    reg.counter("deepspeed_tpu_t_x_total").inc(3)
    h = reg.histogram("deepspeed_tpu_t_h_seconds", labelnames=("phase",))
    h.observe(0.1, phase="fwd")
    events = reg.snapshot_events(step=7)
    tags = {t for t, _v, _s in events}
    assert ("deepspeed_tpu_t_x_total", 3.0, 7) in events
    assert "deepspeed_tpu_t_h_seconds/phase=fwd/p50" in tags
    assert "deepspeed_tpu_t_h_seconds/phase=fwd/count" in tags


# ----------------------------- exposition round-trip ------------------------
def test_prometheus_round_trip(tmp_path):
    reg = MetricsRegistry()
    c = reg.counter("deepspeed_tpu_t_ops_total", "ops so far",
                    labelnames=("op", "axis"))
    c.inc(5, op="all_reduce", axis="data")
    c.inc(2, op="all_gather", axis="d,x\"y")  # label escaping
    reg.gauge("deepspeed_tpu_t_util", "utilization").set(0.54)
    h = reg.histogram("deepspeed_tpu_t_lat_seconds", buckets=(0.5, 1.0))
    h.observe(0.2)
    h.observe(0.7)
    h.observe(3.0)

    text = to_prometheus_text(reg)
    assert "# TYPE deepspeed_tpu_t_ops_total counter" in text
    assert "# HELP deepspeed_tpu_t_ops_total ops so far" in text
    assert "# TYPE deepspeed_tpu_t_lat_seconds histogram" in text

    parsed = parse_prometheus_text(text)
    assert parsed[("deepspeed_tpu_t_ops_total",
                   (("axis", "data"), ("op", "all_reduce")))] == 5.0
    assert parsed[("deepspeed_tpu_t_ops_total",
                   (("axis", 'd,x"y'), ("op", "all_gather")))] == 2.0
    assert parsed[("deepspeed_tpu_t_util", ())] == pytest.approx(0.54)
    # histogram: cumulative buckets, +Inf == count, sum preserved
    assert parsed[("deepspeed_tpu_t_lat_seconds_bucket",
                   (("le", "0.5"),))] == 1.0
    assert parsed[("deepspeed_tpu_t_lat_seconds_bucket",
                   (("le", "1.0"),))] == 2.0
    assert parsed[("deepspeed_tpu_t_lat_seconds_bucket",
                   (("le", "+Inf"),))] == 3.0
    assert parsed[("deepspeed_tpu_t_lat_seconds_count", ())] == 3.0
    assert parsed[("deepspeed_tpu_t_lat_seconds_sum", ())] == pytest.approx(3.9)

    # file exporter writes the same bytes atomically
    path = tmp_path / "m.prom"
    PrometheusFileExporter(str(path), reg).write()
    assert parse_prometheus_text(path.read_text()) == parsed


def test_jsonl_event_schema(tmp_path):
    reg = MetricsRegistry()
    reg.gauge("deepspeed_tpu_t_v").set(1.25)
    h = reg.histogram("deepspeed_tpu_t_s_seconds")
    h.observe(0.01)
    path = tmp_path / "events.jsonl"
    w = JSONLWriter(str(path))
    w.emit("run_started", run="demo", size=3)
    w.emit_snapshot(reg, step=11)
    w.close()
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(lines) == 2
    ev, snap = lines
    assert ev["kind"] == "event" and ev["name"] == "run_started"
    assert ev["run"] == "demo" and ev["size"] == 3 and "ts" in ev
    assert snap["kind"] == "snapshot" and snap["step"] == 11 and "ts" in snap
    assert snap["metrics"]["deepspeed_tpu_t_v"][0]["value"] == 1.25
    hrow = snap["metrics"]["deepspeed_tpu_t_s_seconds"][0]
    assert {"count", "sum", "p50", "p95", "p99"} <= set(hrow)
    # writes after close are dropped, not a crash
    w.emit("late")


# ----------------------------- timer sync + sink ----------------------------
def test_timer_sync_blocks_and_reports():
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer

    seen = []
    timers = SynchronizedWallClockTimer(sink=lambda n, dt: seen.append((n, dt)))
    t = timers("fwd")
    t.start()
    x = jnp.ones((256, 256)) @ jnp.ones((256, 256))  # dispatched async work
    t.stop(sync=True)  # must block on a device sentinel, not effects_barrier
    assert not t.started and t.count == 1
    assert t.elapsed(reset=False) > 0.0
    assert len(seen) == 1 and seen[0][0] == "fwd" and seen[0][1] > 0.0
    np.asarray(x)  # keep the computation alive to its end


def test_timer_sync_uses_device_sentinel(monkeypatch):
    """The old implementation leaned on jax.effects_barrier, which does
    NOT wait on pending computations; the fix must go through a
    block_until_ready'd device sentinel instead."""
    from deepspeed_tpu.utils import timer as timer_mod

    called = {"sync": 0}
    monkeypatch.setattr(timer_mod, "_device_sync",
                        lambda: called.__setitem__("sync", called["sync"] + 1))
    t = timer_mod._Timer("x")
    t.start()
    t.stop(sync=True)
    assert called["sync"] == 1
    t.start()
    t.stop(sync=False)
    assert called["sync"] == 1  # unsynced stop stays cheap


# ----------------------------- CSV monitor handles --------------------------
def test_csv_monitor_persistent_handles(tmp_path):
    from deepspeed_tpu.monitor.monitor import CSVMonitor

    mon = CSVMonitor(str(tmp_path), "job")
    mon.write_events([("Train/loss", 1.5, 0)])
    first_handle = mon._files["Train/loss"]
    mon.write_events([("Train/loss", 1.2, 1), ("Train/loss", 1.1, 2)])
    # the handle is reused, not reopened per event
    assert mon._files["Train/loss"] is first_handle
    files = list(tmp_path.rglob("*.csv"))
    assert len(files) == 1
    rows = files[0].read_text().splitlines()
    # header exactly once, then one row per event (flushed without close)
    assert rows[0] == "step,Train/loss"
    assert len(rows) == 4
    assert sum(1 for r in rows if r.startswith("step,")) == 1
    mon.close()
    assert not mon._files
    # writing after close reopens cleanly and does NOT re-write the header
    mon.write_events([("Train/loss", 1.0, 3)])
    mon.close()
    rows = files[0].read_text().splitlines()
    assert len(rows) == 5
    assert sum(1 for r in rows if r.startswith("step,")) == 1


def test_monitor_master_close_and_registry_fanout(tmp_path):
    from deepspeed_tpu.monitor.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 1,
        "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                        "job_name": "j"}})
    master = MonitorMaster(cfg)
    reg = MetricsRegistry()
    reg.gauge("deepspeed_tpu_t_fanout").set(3.5)
    h = reg.histogram("deepspeed_tpu_t_fan_seconds", labelnames=("phase",))
    h.observe(0.2, phase="fwd")
    master.write_registry(reg, step=4)
    master.close()
    master.close()  # idempotent
    tags = {f.name for f in tmp_path.rglob("*.csv")}
    assert "deepspeed_tpu_t_fanout.csv" in tags
    assert any("deepspeed_tpu_t_fan_seconds" in t and "p50" in t for t in tags)


# ----------------------------- watchdog + MFU -------------------------------
def test_stall_watchdog_flags_outlier():
    reg = MetricsRegistry()
    wd = StallWatchdog(multiple=3.0, window=16, min_samples=5, name="t",
                       registry=reg)
    for _ in range(10):
        assert not wd.observe(0.1)
    assert wd.observe(1.0)  # 10x the median
    assert wd.stall_count == 1
    assert not wd.observe(0.1)  # recovery
    # the stall itself joined the window but the median is robust to it
    assert not wd.observe(0.12)
    assert reg.get("deepspeed_tpu_stall_ratio").value(loop="t") < 3.0


def test_mfu_helpers():
    assert peak_flops_for_kind("TPU v4") == 275e12
    assert peak_flops_for_kind("TPU v5e") == 197e12
    assert peak_flops_for_kind("TPU v5 lite") == 197e12  # what a v5e reports
    assert peak_flops_for_kind("cpu") == 1e12  # nominal, test tier only
    with pytest.raises(ValueError, match="made-up"):
        peak_flops_for_kind("made-up")  # an assumed peak is an error
    assert mfu(1e12, 1.0, n_chips=1, peak_flops=2e12) == 0.5
    assert mfu(1e12, 1.0, n_chips=2, peak_flops=1e12) == 0.5
    assert mfu(1e12, 0.0, peak_flops=1e12) == 0.0  # degenerate inputs


# ----------------------------- engine wiring (fast) -------------------------
def test_engine_telemetry_wiring(tmp_path):
    import deepspeed_tpu
    from tests.unit.simple_model import random_batch, simple_mlp_spec

    prom = tmp_path / "metrics.prom"
    jsonl = tmp_path / "events.jsonl"
    engine, *_ = deepspeed_tpu.initialize(
        model=simple_mlp_spec(),
        config={"train_micro_batch_size_per_gpu": 4,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 2,
                "telemetry": {"enabled": True,
                              "prometheus_path": str(prom),
                              "jsonl_path": str(jsonl),
                              "export_interval": 2}})
    # the registry is the shared process default — another telemetry-
    # enabled test's train_batches land in the same phase series, so
    # assert the DELTA this engine contributes, not the absolute count
    ph = engine.telemetry.registry.get("deepspeed_tpu_train_phase_seconds")
    ph_before = ph.count(phase="train_batch")
    for i in range(4):
        engine.train_batch(random_batch(batch_size=4, gas=1, seed=i))
    engine.close()

    reg = engine.telemetry.registry
    assert reg.get("deepspeed_tpu_train_steps_total").value() >= 4
    assert ph.count(phase="train_batch") - ph_before == 4
    assert reg.get("deepspeed_tpu_train_loss").value() > 0
    assert reg.get("deepspeed_tpu_train_samples_per_second").value() > 0
    # MFU gauge set from the XLA cost analysis fallback (no token batch)
    assert reg.get("deepspeed_tpu_train_mfu").value() > 0

    parsed = parse_prometheus_text(prom.read_text())
    assert any(n == "deepspeed_tpu_train_phase_seconds_bucket"
               for n, _l in parsed)
    lines = [json.loads(line) for line in jsonl.read_text().splitlines()]
    assert any(rec["kind"] == "snapshot" for rec in lines)


# ----------------------------- serving wiring (slow) ------------------------
@pytest.mark.slow
def test_engine_v2_serving_metrics():
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig,
                                            RaggedRequest)
    from deepspeed_tpu.models.llama import llama_model
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    dec = reg.histogram("deepspeed_tpu_serving_decode_seconds")
    pre = reg.histogram("deepspeed_tpu_serving_prefill_seconds")
    dec0, pre0 = dec.count(), pre.count()
    gen = reg.counter("deepspeed_tpu_serving_tokens_generated_total")
    adm = reg.counter("deepspeed_tpu_serving_prefill_admitted_tokens_total")
    gen0, adm0 = gen.value(), adm.value()

    model = llama_model("tiny", max_seq_len=64)
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=16, max_seqs=2,
        max_pages_per_seq=4))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, model.config.vocab_size, 9).tolist()
               for _ in range(2)]
    got = eng.generate_all([RaggedRequest(prompt_ids=p, max_new_tokens=3)
                            for p in prompts])
    assert all(len(v) == 3 for v in got.values())

    assert pre.count() - pre0 == 2        # one prefill per request
    assert dec.count() - dec0 >= 2        # batched decode steps
    assert gen.value() - gen0 >= 2        # decode-program tokens
    assert adm.value() - adm0 == sum(len(p) for p in prompts)
    assert reg.get("deepspeed_tpu_serving_queue_depth").value() == 0
    assert reg.get("deepspeed_tpu_serving_batch_occupancy").value() <= 1.0
    p = pre.percentiles()
    assert p["p50"] <= p["p95"] <= p["p99"]
    # cache_stats keeps its per-engine face on top of the registry
    stats = eng.cache_stats()
    assert stats["prefill_admitted_tokens"] == sum(len(p) for p in prompts)


# ----------------------------- comms busbw ----------------------------------
def test_comms_logger_bus_bandwidth():
    from deepspeed_tpu.comm.comms_logger import CommsLogger, bus_factor

    assert bus_factor("all_reduce", 8) == pytest.approx(2 * 7 / 8)
    assert bus_factor("all_gather", 8) == pytest.approx(7 / 8)
    assert bus_factor("reduce_scatter", 4) == pytest.approx(3 / 4)
    assert bus_factor("all_reduce", 1) == 0.0  # no wire traffic on 1 rank

    cl = CommsLogger(enabled=True)
    cl.append("all_reduce", "data", 1000)
    cl.append("all_reduce", "data", 1000)
    cl.append("all_gather", "model", 500)
    out = cl.log_summary(axis_sizes={"data": 8, "model": 4}, elapsed_s=2.0)
    assert "busbw GB/s" in out and "bus MB" in out
    assert "all_reduce" in out and "all_gather" in out

    reg = MetricsRegistry()
    cl.publish(reg, axis_sizes={"data": 8, "model": 4})
    ops = reg.get("deepspeed_tpu_comm_ops_total")
    byts = reg.get("deepspeed_tpu_comm_bytes_total")
    bus = reg.get("deepspeed_tpu_comm_bus_bytes_total")
    assert ops.value(op="all_reduce", axis="data") == 2
    assert byts.value(op="all_reduce", axis="data") == 2000
    assert bus.value(op="all_reduce", axis="data") == pytest.approx(
        2000 * 2 * 7 / 8)
    # re-publish without new traffic: deltas only, no double count
    cl.publish(reg, axis_sizes={"data": 8, "model": 4})
    assert ops.value(op="all_reduce", axis="data") == 2
    cl.append("all_reduce", "data", 100)
    cl.publish(reg, axis_sizes={"data": 8})
    assert byts.value(op="all_reduce", axis="data") == 2100
