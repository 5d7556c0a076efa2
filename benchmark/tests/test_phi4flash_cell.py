"""What the Phi-4-mini-flash cell added: the generator kind that takes the
reference, the state leaf and its layout from the configuration and a
``negative_control`` from the traffic file; the operation and byte counts of
the scan, window and shared-pool kernels; readers that read nothing (and do
not raise) where the program has no such counter or the trace no such kernel,
and roofline shares that cannot pass 100 %."""

import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import manifest as manifest_mod
from benchmark import roofline, sambay_counts, trace_reduce

ROOT = os.path.dirname(manifest_mod.HERE)
CELL = "phi4flash-assist-saturated"
PEAK = roofline.peaks("TPU v5 lite")
#: the configuration's published sizes, and its engine's
INNER, STATE, HEADS, KVH, D, PS, WINDOW = 5120, 16, 40, 20, 64, 16, 512
DESC = {"ssm_inner": INNER, "ssm_state": STATE, "num_attention_heads": HEADS,
        "num_key_value_heads": KVH, "head_dim": D,
        "runs": [[["mamba", "swa"], 8], [["mamba", "dattn"], 1],
                 [["gmu", "xattn"], 7]]}


def _rehearse(*more):
    p = subprocess.run(
        [sys.executable, os.path.join(manifest_mod.HERE, "rehearse.py"),
         "--workload", CELL, "--seed", "3000000029", "--seconds", "1",
         "--trace", "0", *more], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_cell_rehearses_against_its_own_reference():
    line, said = _rehearse()
    assert line["correct"] and line["failed"] == 0
    assert "largest regret against the float32 reference 0.000e+00" in said
    assert "state_bf16_share 0.000e+00" in said
    assert "NEGATIVE CONTROL" not in said
    assert line["counts"]["preempted"] == 0


@pytest.mark.parametrize("control", [
    {"reference": {"weights_dtype": "float8_e4m3fn"}},
    {"reference": {"window": 48}},      # twice the tiny window, as 1024 is
    {"reference": {"lambda_scale": 0.0}},
    {"program": {"state_dtype": "bfloat16"}}],
    ids=["float8_weights", "window_doubled", "second_softmax_dropped",
         "program_state_bfloat16"])
def test_each_negative_control_comes_out_not_correct(control, tmp_path):
    """A planted fault in the program's place, under the limits the program
    has just passed: ``correct`` is false."""
    man = manifest_mod.Manifest()
    traffic = man.traffic(man.cell(CELL)["traffic"])
    assert "negative_control" not in traffic  # no committed file has it
    traffic["negative_control"] = control
    os.makedirs(tmp_path / "benchmark" / "traffic")
    with open(tmp_path / "benchmark" / "traffic"
              / (man.cell(CELL)["traffic"] + ".json"), "w") as f:
        json.dump(traffic, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(man.data, f)
    line, said = _rehearse("--manifest", str(tmp_path / "BENCHMARK.json"))
    assert "NEGATIVE CONTROL" in said
    assert line["correct"] is False and line["failed"] == 0


def test_the_configuration_is_the_published_one_uncut():
    man = manifest_mod.Manifest()
    entry = next(c for c in man.data["configs"]
                 if c["name"] == man.cell(CELL)["config"])
    cfg = man.config(entry["name"])
    assert entry["reduced"] == cfg["reduced"] == []
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert cfg["assumed_sizes"] == {"d_inner": 2 * 2560, "d_state": 16,
                                    "d_conv": 4, "dt_rank": 160}
    assert cfg["reference"] == "sambay_lm"
    assert cfg["state"] == {"leaf": "ssm_s", "layout": "state_major"}
    desc = man.module("families", cfg["family"]).describe(cfg)
    assert desc["runs"] == DESC["runs"] and desc["head_dim"] == 64
    e = cfg["engine"]
    assert e["max_seqs"] * e["max_pages_per_seq"] >= e["num_pages"]
    assert cfg["sliding_window"] % e["page_size"] == 0
    # the traffic the issue names, to the digit
    tr = man.traffic(man.cell(CELL)["traffic"])
    assert tr["arrivals"] == {"process": "backlog", "count": 960}
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 768,
                                   "sigma": 0.8, "min": 128, "max": 8192}
    assert tr["output_tokens"] == {"dist": "lognormal", "median": 256,
                                   "sigma": 0.7, "min": 32, "max": 2048}
    assert tr["check_prompt_tokens"] == [320, 640, 1500]
    assert (tr["check_decode_steps"], tr["tpot_min_gaps"]) == (16, 16)
    assert max(tr["prompt_tokens"]["max"] + tr["output_tokens"]["max"],
               1500 + 18) <= e["page_size"] * e["max_pages_per_seq"]


def test_counts_are_a_hand_count_of_the_least_that_moves():
    state = 4 * STATE * INNER                     # one row's float32 state
    # one decoded row of one layer: its state in and out, dt, u, y, B, C,
    # and the decay matrix once for the call
    ops, nbytes = sambay_counts.ssm_step_ops_bytes(1, 1, INNER, STATE)
    assert nbytes == 2 * state + 4 * (3 * INNER + 2 * STATE) + state
    assert ops == 7 * STATE * INNER
    _, more = sambay_counts.ssm_step_ops_bytes(128, 1, INNER, STATE)
    assert more - nbytes == 127 * (2 * state + 4 * (3 * INNER + 2 * STATE))
    # a chunk call: a state in, a state out, the decay matrix; 61,568 B a token
    _, one = sambay_counts.ssm_chunk_ops_bytes(512, 1, INNER, STATE)
    _, two = sambay_counts.ssm_chunk_ops_bytes(512, 2, INNER, STATE)
    assert two - one == 3 * state
    assert one == 3 * state + 512 * 4 * (3 * INNER + 2 * STATE)
    # a cached position of one layer: 20 K heads and 20 V heads of 64 in bf16
    ops, nbytes = sambay_counts.attend_ops_bytes(1, HEADS, KVH, D)
    assert nbytes == 5120
    assert ops == 2 * HEADS * (D + 2 * D)


def _steps(rows, contexts, chunk_tokens=0, chunks=0):
    """A step record as the generator leaves it, from the engine's own rule:
    ``contexts`` the decoded rows' visible tokens."""
    return {"decode_rows": rows, "ssm_rows": rows, "xdec_rows": rows,
            "window_tokens": sum(min(c, WINDOW) for c in contexts),
            "shared_kv_pages": sum(-(-c // PS) for c in contexts),
            "chunk_tokens": chunk_tokens, "recompute_tokens": 0,
            "chunks": chunks}


class _Trace:
    """A trace in which the named kernel took ``seconds``."""

    def __init__(self, seconds, spans=1):
        self.seconds, self.spans = seconds, spans

    def span_list(self, _name):
        return [object()] * self.spans

    def op_seconds(self, _match):
        return self.seconds

    def devices(self):
        return ["d0"]


@pytest.mark.parametrize("what,kernel", [
    ("ssm_step", "dstpu_ssm_step"), ("ssm_chunk", "dstpu_ssm_chunk"),
    ("window_decode", "dstpu_window_decode"),
    ("shared_pages", "dstpu_paged_decode")])
def test_a_roofline_share_cannot_pass_100_percent(what, kernel):
    """Over the counter values the program can produce, the counted bytes are
    at most what the kernel's calls must move at the least — every decoded
    row's state once each way, every visible position's K and V once a layer
    that reads it, a chunk's real tokens — so the least time is at most the
    time those bytes take at the peak, which no measured time can be under."""
    reader = manifest_mod.Manifest().module("readers", "sambay_roofline")
    hbm = PEAK["hbm_bytes_per_s"]
    for rows, ctx_len, tokens in itertools.product(
            (1, 5, 128), (1, 17, 511, 512, 513, 10240), (1, 100, 512)):
        steps = [_steps(rows, [ctx_len] * rows, tokens, 1)]
        least = reader.least_seconds(what, steps, DESC, {"page_size": PS},
                                     PEAK)
        moved = {
            # 9 layers: the rows' states both ways, their float32 rows and
            # columns, and the decay matrix a call
            "ssm_step": 9 * (rows * (8 * STATE * INNER
                                     + 4 * (3 * INNER + 2 * STATE))
                             + 4 * STATE * INNER),
            "ssm_chunk": 9 * (12 * STATE * INNER
                              + tokens * 4 * (3 * INNER + 2 * STATE)),
            # 8 window layers, never more than the ring holds
            "window_decode": 8 * rows * min(ctx_len, WINDOW) * 5120,
            # 8 readers of the one pool layer, whole pages
            "shared_pages": 8 * rows * -(-ctx_len // PS) * PS * 5120,
        }[what]
        assert least == pytest.approx(moved / hbm)   # memory-bound, all four
        # the reader's share at exactly that time is 100, and under it above
        ctx = {"trace": _Trace(moved / hbm), "device": {"kind": "TPU v5 lite"},
               "result": {"steps": steps, "desc": DESC,
                          "engine_config": {"page_size": PS}}}
        assert reader.read(ctx, what, kernel, "bench.step") == \
            pytest.approx(100.0)
        ctx["trace"] = _Trace(3 * moved / hbm)
        assert reader.read(ctx, what, kernel, "bench.step") == \
            pytest.approx(100.0 / 3)


def test_readers_read_nothing_where_the_program_counts_nothing():
    """A parent's step records lack the new keys, and a trace without the new
    kernels has no time to divide by: no reading, no raise.  The recorded
    serving fixture (a one-layer dense engine, PR 25) holds none of them."""
    man = manifest_mod.Manifest()
    reader = man.module("readers", "sambay_roofline")
    old = [{"decode_rows": 4, "chunks": 1, "chunk_tokens": 9,
            "recompute_tokens": 0, "decode_pages": 7}] * 3
    ctx = {"trace": _Trace(1.0, spans=3), "device": {"kind": "TPU v5 lite"},
           "result": {"steps": old, "desc": DESC,
                      "engine_config": {"page_size": PS}}}
    for what in ("ssm_step", "window_decode", "shared_pages"):
        assert reader.read(ctx, what, "dstpu_x", "bench.step") is None
    # another family's description has no runs to count layers from
    ctx["result"]["desc"] = {"period": ["gqa", "kda"]}
    assert reader.read(ctx, "ssm_chunk", "dstpu_x", "bench.step") is None
    assert man.module("readers", "step_ratio").read(
        ctx, "bench.step", "xdec_prefill_rows", "chunk_tokens") is None
    assert man.module("readers", "step_percentile").read(
        ctx, "bench.step", "state_slots_in_use", 50) is None
    recorded = trace_reduce.reduce_file(os.path.join(
        manifest_mod.HERE, "fixtures", "small_serve_v5e.xplane.pb"))
    ctx = {"trace": recorded, "device": {"kind": "TPU v5 lite"},
           "result": {"steps": [_steps(2, [60, 40], 25, 1)] * 4, "desc": DESC,
                      "engine_config": {"page_size": PS}}}
    for name in ("ssm_step_roofline", "ssm_chunk_roofline",
                 "window_decode_roofline", "ssm_step_ms_per_step",
                 "ssm_chunk_ms_per_ktok", "window_decode_ms_per_step"):
        spec = man.layer_metric(name)
        assert man.module("readers", spec["reader"]).read(
            ctx, **spec["args"]) is None, name
    # what the recorded trace does hold is read by the data files this cell
    # brings for readers that were there: its programs' time, and a ratio of
    # the step records
    for name in ("chunk_device_ms_per_ktok.steady",
                 "chunk_share_of_step.steady"):
        spec = man.layer_metric(name)
        got = man.module("readers", spec["reader"]).read(ctx, **spec["args"])
        assert got is not None and got > 0, name
    spec = man.layer_metric("xdec_prefill_share")
    ctx["result"]["steps"] = [dict(_steps(2, [60, 40], 100, 1),
                                   xdec_prefill_rows=1)] * 4
    assert man.module("readers", spec["reader"]).read(
        ctx, **spec["args"]) == 0.01


def test_every_new_metric_names_the_cell_and_moves_tpot():
    man = manifest_mod.Manifest()
    listed = {m["name"]: m for m in man.per_layer(CELL)}
    for name in ("ssm_step_ms_per_step", "ssm_step_roofline",
                 "ssm_chunk_ms_per_ktok", "ssm_chunk_roofline",
                 "window_decode_roofline", "paged_decode_roofline.shared",
                 "xdec_prefill_share"):
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "tpot_p50_ms"
        assert man.layer_metric(name)["name"] == name
    # a later cell that the same reader reads is appended to these
    for name in ("window_decode_ms_per_step",
                 "chunk_device_ms_per_ktok.steady",
                 "chunk_share_of_step.steady"):
        assert CELL in listed[name]["workloads"]
        assert listed[name]["moves"] == "tpot_p50_ms"
        assert man.layer_metric(name)["name"] == name
    # the accepted readers that count the model's or the period's layers, and
    # the metrics of another end-to-end metric, stay off this cell
    for name in ("paged_decode_roofline", "paged_decode_roofline.period",
                 "prefill_device_ms_per_ktok", "kda_step_roofline"):
        assert name not in listed
    assert [m["name"] for m in man.end_to_end(CELL)] == ["tpot_p50_ms",
                                                        "setup_s"]


def test_state_readings_follow_the_layout_the_configuration_states():
    import ml_dtypes

    gen = manifest_mod.Manifest().module("generators", "serve_requests_state")
    rng = np.random.default_rng(0)
    ref = [rng.normal(size=(32, 8)).astype(np.float32) for _ in range(3)]
    kept = np.stack([r.T for r in ref])               # [state, inner]
    exact = gen.state_readings(kept, ref, "state_major")
    assert exact["state_error"] == 0.0 and exact["state_bf16_share"] < 0.01
    assert gen.state_readings(np.stack(ref), ref,
                              "as_reference")["state_error"] == 0.0
    rounded = kept.astype(ml_dtypes.bfloat16).astype(np.float32)
    low = gen.state_readings(rounded, ref, "state_major")
    assert low["state_bf16_share"] == 1.0
    assert 1e-4 < low["state_error"] < 1e-2
    with pytest.raises(ValueError, match="layout"):
        gen.state_readings(kept, ref, "sideways")
