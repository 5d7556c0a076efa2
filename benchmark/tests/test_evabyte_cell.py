"""What the EvaByte cell added: a configuration whose cut is depth alone, a
reference with a control for each mechanism of EVA attention, the least
operations and bytes of the attention over summaries and an open window
(``eva_counts.py``: from the program's counters and the configuration's
shapes, not from what implements it), and a reader of the two kernels' shares
of that roofline that reads nothing (and does not raise) where the program
has no such counter, and cannot pass 100 %."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import eva_counts, roofline
from benchmark import manifest as manifest_mod

ROOT = os.path.dirname(manifest_mod.HERE)
CELL = "evabyte-pp4-bytedoc-saturated"
PEAK = roofline.peaks("TPU v5 lite")
#: the configuration's published widths and its depth here
HEADS, DIM, LAYERS = 32, 128, 8
DESC = {"num_attention_heads": HEADS, "head_dim": DIM, "window_size": 2048,
        "chunk_size": 16, "hidden_size": 4096, "num_pred_heads": 8,
        "vocab_size": 320, "intermediate_size": 11008}
NEW = ("eva_decode_ms_per_step", "eva_decode_roofline",
       "eva_chunk_attn_ms_per_ktok", "flash_prefill_roofline.eva",
       "eva_summary_ms_per_ktok", "eva_glue_ms_per_step",
       "eva_rows_in_use_p50")


def _rehearse(*more):
    p = subprocess.run(
        [sys.executable, os.path.join(manifest_mod.HERE, "rehearse.py"),
         "--workload", CELL, "--seed", "3000000060", "--seconds", "1",
         "--trace", "0", *more], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_cell_rehearses_against_its_own_reference():
    line, said = _rehearse()
    assert line["correct"] and line["failed"] == 0
    assert "largest regret against the float32 reference 0.000e+00" in said
    assert "rows_error" in said and "NEGATIVE CONTROL" not in said
    assert "heads_mean_regret 0.000e+00" in said
    assert line["counts"]["preempted"] == 0
    assert line["counts"]["compiles_in_window"] == 0


@pytest.mark.parametrize("control", [
    {"reference": {"summaries": False}},
    {"reference": {"pool": "mean"}},
    {"reference": {"mu": False}},
    {"reference": {"exact": True}},
    {"reference": {"weights_dtype": "float8_e4m3fn"}}],
    ids=["no_summaries", "mean_pool", "no_mu", "exact_attention",
         "float8_weights"])
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


def test_the_manifest_finds_every_new_file():
    man = manifest_mod.Manifest()
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte-6.5b-pp4-serve", "bytedoc-saturated", 1)
    cfg, tr = man.config(cell["config"]), man.traffic(cell["traffic"])
    assert man.module("families", cfg["family"]).describe(cfg)
    assert hasattr(man.module("reference", cfg["reference"]), "forward")
    assert hasattr(man.module("generators", tr["kind"]), "run")
    assert hasattr(man.module("readers", "eva_roofline"), "read")
    for name in NEW:
        spec = man.layer_metric(name)
        assert spec["name"] == name
        assert hasattr(man.module("readers", spec["reader"]), "read")


def test_the_configuration_is_the_catalogs_cut_in_depth_alone():
    man = manifest_mod.Manifest()
    entry = next(c for c in man.data["configs"]
                 if c["name"] == man.cell(CELL)["config"])
    cfg = man.config(entry["name"])
    cut = {"num_hidden_layers": 8}
    assert entry["reduced"] == cfg["reduced"] == list(cut)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == cut.get(key, value), key
        if key in cut:
            assert cfg["published"][key] == value
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_pred_heads"], cfg["window_size"],
            cfg["chunk_size"]) == (4096, 32, 11008, 320, 8, 2048, 16)
    assert cfg["reference"] == "eva_lm"
    assert cfg["cache"] == {"accessor": "read_eva"}
    for key in ("reduced_why", "deployment", "engine_why"):
        assert len(cfg[key]) > 200, key
    for key in ("modeling_code", "pooling", "mu", "rotary", "own_window",
                "fp32_skip_add", "heads", "norm", "initialisation"):
        assert key in cfg["assumed"], key
    desc = man.module("families", cfg["family"]).describe(cfg)
    for key, want in DESC.items():
        assert desc[key] == want   # what the readers read
    e = cfg["engine"]
    assert e["max_seqs"] in (24, 32)
    assert (e["page_size"], e["dtype"], e["decode_horizon"],
            e["enable_prefix_cache"]) == (16, "bf16", 1, False)
    assert 2048 % e["prefill_chunk"] == 0 and e["prefill_chunk"] % 256 == 0
    # the traffic the issue names, to the digit
    tr = man.traffic(man.cell(CELL)["traffic"])
    assert tr["kind"] == "serve_requests_bytes"
    assert tr["arrivals"]["process"] == "trace"
    assert tr["arrivals"]["preroll_s"] >= 30
    assert tr["arrivals"]["rate_per_s"] * 2 == int(
        tr["arrivals"]["rate_per_s"] * 2)   # rounded to 0.5/s
    assert tr["ttft_share"] == 0
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                   "sigma": 0.7, "min": 1024, "max": 28672}
    assert tr["output_tokens"] == {"dist": "lognormal", "median": 768,
                                   "sigma": 0.6, "min": 128, "max": 3072}
    assert tr["check_prompt_tokens"] == [700, 4090, 9000]
    assert (tr["check_decode_steps"], tr["tpot_min_gaps"],
            tr["schedule_seed"]) == (24, 16, 0)
    assert 28672 + 3072 < e["page_size"] * e["max_pages_per_seq"] <= 32768
    for key in ("regret", "mean_regret", "heads_regret", "heads_mean_regret",
                "rows_error", "rows_error_first"):
        assert len(tr[key + "_tolerance_why"]) > 200, key


def test_counts_are_a_hand_count_of_the_least_that_moves():
    # one decoded row that attends one row, one layer: a key row and a value
    # row of 32 x 128 bfloat16 values, the query in and the output out
    ops, nbytes = eva_counts.eva_decode_ops_bytes(1, 1, 1, HEADS, DIM)
    assert nbytes == 2 * 4096 * (2 + 2) == 32768
    assert ops == 32 * 4 * 128 == 16384
    # ISSUE 60's step: 32 rows at (1,024 open + 640 summary) rows, 8 layers:
    # 7.0 GB of cache a decode step, 8.5 ms at the HBM's peak
    ops, nbytes = eva_counts.eva_decode_ops_bytes(32 * 1664, 32, LAYERS,
                                                  HEADS, DIM)
    assert nbytes == pytest.approx(6.98e9, rel=1e-2)
    assert roofline.roofline_seconds(ops, nbytes, PEAK) == (
        pytest.approx(8.5e-3, rel=2e-2), "memory")
    # a chunk of 2,048 bytes behind 512 summaries: every token sees the 512
    # and the chunk's tokens up to itself
    ops, nbytes = eva_counts.eva_chunk_ops_bytes(
        2048, 2048 * 512, 2048 * 2049 // 2, 512, LAYERS, HEADS, DIM)
    assert ops == (2048 * 512 + 2048 * 2049 // 2) * LAYERS * 32 * 512
    assert nbytes == LAYERS * 2 * 4096 * (2 * (512 + 2048) + 2 * 2048)
    assert roofline.roofline_seconds(ops, nbytes, PEAK)[1] == "compute"


def _step(rows=0, attended=0, calls=()):
    return {"decode_rows": rows, "eva_rows_attended": attended,
            "chunk_tokens": sum(n for n, _ in calls), "recompute_tokens": 0,
            "chunks": len(calls), "ctx_tokens": sum(c for _, c in calls),
            "eva_chunk_tokens": sum(n for n, _ in calls),
            "eva_chunk_tokens_x_ctx": sum(n * c for n, c in calls),
            "eva_chunk_causal_pairs": sum(n * (n + 1) // 2
                                          for n, _ in calls),
            "eva_rows_in_use": 50000}


class _Trace:
    def __init__(self, spans, seconds):
        self.spans, self.seconds = spans, seconds

    def span_list(self, _name):
        return [object()] * self.spans

    def devices(self):
        return ["d0"]

    def op_seconds(self, match):
        return sum(s for name, s in self.seconds.items() if match(name))


@pytest.mark.parametrize("what,kernel", [("decode", "dstpu_eva_decode"),
                                         ("chunk", "dstpu_flash_fwd")])
def test_the_roofline_shares_cannot_pass_100_percent(what, kernel):
    """The counted work is what any form of the attention must do: a kernel
    at the peak reads 100 %, one that takes 3.7 times as long its share."""
    man = manifest_mod.Manifest()
    reader = man.module("readers", "eva_roofline")
    steps = [_step(32, 32 * 1700, [(2048, 640), (900, 128)])] * 3
    least, bound = reader.least_seconds(what, steps, DESC, LAYERS, PEAK)
    assert bound == {"decode": "memory", "chunk": "compute"}[what]
    for slower, want in ((1.0, 100.0), (3.7, 100.0 / 3.7)):
        ctx = {"trace": _Trace(3, {kernel: slower * least, "fusion.1": 9.0}),
               "device": {"kind": "TPU v5 lite"},
               "result": {"steps": steps, "desc": DESC, "n_layers": LAYERS}}
        spec = next(man.layer_metric(n) for n in NEW
                    if man.layer_metric(n)["reader"] == "eva_roofline"
                    and man.layer_metric(n)["args"]["what"] == what)
        assert spec["args"]["kernel"] == kernel
        assert reader.read(ctx, **spec["args"]) == pytest.approx(want)


def test_readers_read_a_number_or_nothing_and_never_raise():
    """A parent records no such counter and runs no such kernel, another
    family has no window: no reading, no raise."""
    man = manifest_mod.Manifest()
    reader = man.module("readers", "eva_roofline")
    steps = [_step(32, 32 * 1700, [(2048, 640)])] * 2
    base = {"trace": _Trace(2, {"dstpu_eva_decode": 1.0,
                                "dstpu_flash_fwd": 1.0}),
            "device": {"kind": "TPU v5 lite"},
            "result": {"steps": steps, "desc": DESC, "n_layers": LAYERS}}
    for what, kernel in (("decode", "dstpu_eva_decode"),
                         ("chunk", "dstpu_flash_fwd")):
        args = {"what": what, "kernel": kernel, "span": "bench.step"}
        assert reader.read(base, **args) > 0
        # no such kernel in the trace
        assert reader.read(dict(base, trace=_Trace(2, {"fusion.1": 1.0})),
                           **args) is None
        # step records without the counters (a parent's)
        old = dict(base, result=dict(base["result"],
                                     steps=[{"chunks": 1, "decode_rows": 3}]))
        assert reader.read(old, **args) is None
        # another family's description
        other = dict(base, result=dict(base["result"],
                                       desc={"period": ["gqa", "kda"]}))
        assert reader.read(other, **args) is None
    # the other new metrics are data on accepted readers
    for name, reader_name in (("eva_decode_ms_per_step", "op_ms"),
                              ("eva_chunk_attn_ms_per_ktok",
                               "op_ms_per_unit"),
                              ("eva_summary_ms_per_ktok", "part_ms"),
                              ("eva_glue_ms_per_step", "part_ms"),
                              ("eva_rows_in_use_p50", "step_percentile")):
        assert man.layer_metric(name)["reader"] == reader_name
    empty = dict(base, trace=_Trace(2, {}), part_ops=None,
                 result=dict(base["result"], steps=[{"chunks": 1}] * 2))
    for name in NEW:
        spec = man.layer_metric(name)
        assert man.module("readers", spec["reader"]).read(
            dict(empty), **spec["args"]) is None, name


def test_every_new_metric_names_the_cell_and_moves_tpot():
    man = manifest_mod.Manifest()
    listed = {m["name"]: m for m in man.per_layer(CELL)}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "tpot_p50_ms"
    for name in ("eva_decode_roofline", "flash_prefill_roofline.eva"):
        assert listed[name]["unit"] == "%" and \
            listed[name]["better"] == "higher"
    # the accepted lists whose readers read this cell
    for name in ("decode_step_device_ms", "chunk_device_ms_per_ktok.steady",
                 "chunk_share_of_step.steady", "xla_unscoped_share.steady",
                 "peak_hbm_gb.steady", "step_host_ms.steady",
                 "idle_in_device_wait_ms.steady",
                 "idle_outside_device_wait_ms.steady",
                 "compiles_in_window.steady", "setup_compile_s",
                 "decode_dense_gemm_ms_per_step",
                 "decode_head_sample_ms_per_step"):
        assert CELL in listed[name]["workloads"], name
    # no paged K/V kernel under its own name, no latent, no expert runs here
    for name in ("paged_decode_ms_per_step", "mla_decode_ms_per_step",
                 "moe_experts_ms_per_step", "latent_tokens_in_use_p50"):
        assert name not in listed, name
    assert [m["name"] for m in man.end_to_end(CELL)] == ["tpot_p50_ms",
                                                        "setup_s"]
    cells = man.data["workloads"]
    assert len(cells) >= 13 and sum(c["chips"] == 4 for c in cells) == 1
