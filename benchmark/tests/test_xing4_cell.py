"""What the Xing4.0 cell added: a configuration whose cut is depth alone, a
reference with controls of the mixing, the least bytes a residual of several
streams must move (``mhc_counts.py``) and a reader of the ``mhc`` part's share
of that roofline that counts the work from the step records and the widths —
not from what implements it — reads nothing (and does not raise) where the
program has no such part, and cannot pass 100 %."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as manifest_mod
from benchmark import mhc_counts, roofline

ROOT = os.path.dirname(manifest_mod.HERE)
CELL = "xing4-pp7-longrag-saturated"
PEAK = roofline.peaks("TPU v5 lite")
#: the configuration's published widths, its depth here, and its engine's page
HIDDEN, STREAMS, LAYERS, PS = 3584, 4, 6, 16
DESC = {"num_attention_heads": 32, "kv_lora_rank": 512,
        "qk_rope_head_dim": 64, "hidden_size": HIDDEN, "expert_width": 1024,
        "hc_mult": STREAMS}
NEW = ("mla_decode_ms_per_step.hc", "mla_decode_roofline.hc",
       "latent_tokens_in_use_p50.hc", "chunk_mhc_ms_per_ktok.steady",
       "decode_mhc_ms_per_step", "mhc_stream_roofline")


def _rehearse(*more):
    p = subprocess.run(
        [sys.executable, os.path.join(manifest_mod.HERE, "rehearse.py"),
         "--workload", CELL, "--seed", "3000000058", "--seconds", "1",
         "--trace", "0", *more], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_cell_rehearses_against_its_own_reference():
    line, said = _rehearse()
    assert line["correct"] and line["failed"] == 0
    assert "largest regret against the float32 reference 0.000e+00" in said
    assert "latent_error" in said and "NEGATIVE CONTROL" not in said
    assert line["counts"]["preempted"] == 0


@pytest.mark.parametrize("control", [
    {"reference": {"mhc": "static"}},
    {"reference": {"mhc": "one_round"}},
    {"reference": {"mhc": "post_unscaled"}},
    {"reference": {"weights_dtype": "float8_e4m3fn"}},
    {"reference": {"router": "softmax"}}],
    ids=["mhc_static", "mhc_one_round", "mhc_post_unscaled", "float8_weights",
         "router_softmax"])
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


def test_the_configuration_is_the_catalogs_cut_in_depth_alone():
    man = manifest_mod.Manifest()
    entry = next(c for c in man.data["configs"]
                 if c["name"] == man.cell(CELL)["config"])
    cfg = man.config(entry["name"])
    cut = {"num_hidden_layers": 6, "first_k_dense_replace": 1}
    assert entry["reduced"] == cfg["reduced"] == list(cut)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == cut.get(key, value), key
        if key in cut:
            assert cfg["published"][key] == value
    # no width among the cuts, every expert and the whole vocabulary held
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in cut)
    assert (cfg["n_routed_experts"], cfg["vocab_size"]) == (64, 131072)
    assert cfg["reference"] == "mhc_mla_moe_lm"
    assert cfg["cache"] == {"accessor": "read_latent"}
    for key in ("reduced_why", "deployment", "engine_why"):
        assert len(cfg[key]) > 200, key
    for key in ("sinkhorn", "mixing_norm", "entry_and_exit", "rotary",
                "softmax_scale", "mtp", "initialisation"):
        assert key in cfg["assumed"], key
    desc = man.module("families", cfg["family"]).describe(cfg)
    for key, want in DESC.items():
        assert desc[key] == want   # what the readers read
    assert (desc["experts_held"], desc["experts_routed"],
            desc["dense_layers"], desc["hc_sinkhorn_iters"]) == (64, 64, 1, 20)
    e = cfg["engine"]
    assert (e["max_seqs"], e["page_size"], e["max_pages_per_seq"],
            e["prefill_chunk"], e["dtype"]) == (48, PS, 2113, 2048, "bf16")
    # the traffic the issue names, to the digit
    tr = man.traffic(man.cell(CELL)["traffic"])
    assert tr["kind"] == "serve_requests_latent"
    assert tr["arrivals"]["process"] == "trace"
    assert tr["arrivals"]["preroll_s"] == 40
    assert tr["arrivals"]["rate_per_s"] * 2 == int(
        tr["arrivals"]["rate_per_s"] * 2)   # rounded to 0.5/s
    assert tr["ttft_share"] == 0
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 6144,
                                   "sigma": 0.7, "min": 1024, "max": 32768}
    assert tr["output_tokens"] == {"dist": "lognormal", "median": 256,
                                   "sigma": 0.6, "min": 32, "max": 1024}
    assert tr["check_prompt_tokens"] == [320, 2500, 8448]
    assert (tr["check_decode_steps"], tr["tpot_min_gaps"],
            tr["schedule_seed"], tr["balance_group"],
            tr["trace_seconds"]) == (16, 16, 0, 16, 30)
    assert 32768 + 1024 < PS * e["max_pages_per_seq"]
    for key in ("regret", "mean_regret", "latent_error",
                "latent_error_first"):
        assert len(tr[key + "_tolerance_why"]) > 200, key


def test_counts_are_a_hand_count_of_the_least_that_moves():
    # one token, one layer: two sublayers, each the four streams of 3584
    # bfloat16 values read twice and written once, y read and h written
    one = mhc_counts.mhc_stream_bytes(1, 1, STREAMS, HIDDEN)
    assert one == 2 * 2 * (3 * 4 * 3584 + 2 * 3584) == 200704
    # ISSUE 58's "265 MB a sublayer" is a chunk of 2048 tokens with one pass
    # more over the streams; the least is 205.5 MB a sublayer, 0.50 ms a
    # layer at the HBM's peak
    chunk = mhc_counts.mhc_stream_bytes(2048, 1, STREAMS, HIDDEN)
    assert chunk / 2 == 2048 * 100352 == pytest.approx(205.5e6, rel=1e-3)
    assert chunk / PEAK["hbm_bytes_per_s"] == pytest.approx(0.502e-3,
                                                            rel=1e-2)
    # linear in tokens and layers; one stream costs the read, y, h and write
    assert mhc_counts.mhc_stream_bytes(7, LAYERS, STREAMS, HIDDEN) == \
        7 * LAYERS * one
    assert mhc_counts.mhc_stream_bytes(1, 1, 1, 64) == 2 * 2 * 5 * 64


def _step(rows=0, chunk_tokens=0):
    return {"decode_rows": rows, "chunk_tokens": chunk_tokens,
            "recompute_tokens": 0, "chunks": int(chunk_tokens > 0),
            "latent_kv_tokens": 1000 * rows, "latent_tokens_in_use": 5000}


class _Op:
    def __init__(self, part, self_s):
        self.part, self.self_s = part, self_s


class _Trace:
    def __init__(self, spans=1):
        self.spans = spans

    def span_list(self, _name):
        return [object()] * self.spans

    def devices(self):
        return ["d0"]


def test_the_roofline_share_cannot_pass_100_percent():
    """The counted bytes are what any form of the mixing must move for the
    window's tokens: a mixing that ran at the memory's peak reads 100 %, one
    that keeps float32 copies reads its share."""
    reader = manifest_mod.Manifest().module("readers", "mhc_roofline")
    hbm = PEAK["hbm_bytes_per_s"]
    for rows, tokens in ((48, 0), (48, 2048), (1, 4096), (0, 2048)):
        steps = [_step(rows, tokens)] * 3
        moved = 3 * (rows + tokens) * LAYERS * 200704
        assert reader.least_seconds(steps, DESC, LAYERS, PEAK) == \
            pytest.approx(moved / hbm)
        for slower, want in ((1.0, 100.0), (3.7, 100.0 / 3.7)):
            ctx = {"trace": _Trace(3), "device": {"kind": "TPU v5 lite"},
                   "result": {"steps": steps, "desc": DESC,
                              "n_layers": LAYERS},
                   "part_ops": [_Op("mhc", slower * moved / hbm),
                                _Op("mlp", 1.0), _Op("stack", 2.0)]}
            assert reader.read(ctx, "bench.step") == pytest.approx(want)


def test_readers_read_a_number_or_nothing_and_never_raise():
    """A parent has no ``mhc`` part and another family no streams: no
    reading, no raise."""
    man = manifest_mod.Manifest()
    reader = man.module("readers", "mhc_roofline")
    steps = [_step(48, 2048)] * 2
    base = {"trace": _Trace(2), "device": {"kind": "TPU v5 lite"},
            "result": {"steps": steps, "desc": DESC, "n_layers": LAYERS}}
    # no table at all (a program from before the tables)
    assert reader.read(dict(base, part_ops=None), "bench.step") is None
    # tables, and no operation of the part (the parent's programs)
    assert reader.read(dict(base, part_ops=[_Op("mlp", 1.0)]),
                       "bench.step") is None
    # another family's description, and step records without the counters
    other = dict(base, part_ops=[_Op("mhc", 1.0)])
    other["result"] = dict(base["result"], desc={"period": ["gqa", "kda"]})
    assert reader.read(other, "bench.step") is None
    old = dict(base, part_ops=[_Op("mhc", 1.0)])
    old["result"] = dict(base["result"], steps=[{"chunks": 1}] * 2)
    assert reader.read(old, "bench.step") is None
    # the two part metrics are data on the accepted reader
    for name in ("chunk_mhc_ms_per_ktok.steady", "decode_mhc_ms_per_step"):
        spec = man.layer_metric(name)
        assert spec["reader"] == "part_ms" and spec["args"]["parts"] == ["mhc"]
        assert man.module("readers", spec["reader"]).read(
            dict(base, part_ops=None), **spec["args"]) is None
    # the .hc metrics are the accepted readers and arguments under a new name
    for name in ("mla_decode_ms_per_step", "mla_decode_roofline",
                 "latent_tokens_in_use_p50"):
        mine, theirs = (man.layer_metric(n) for n in (name + ".hc", name))
        assert {k: v for k, v in mine.items() if k != "name"} == \
            {k: v for k, v in theirs.items() if k != "name"}


def test_every_new_metric_names_the_cell_and_moves_tpot():
    man = manifest_mod.Manifest()
    listed = {m["name"]: m for m in man.per_layer(CELL)}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "tpot_p50_ms"
        assert man.layer_metric(name)["name"] == name
    for name in ("mhc_stream_roofline", "mla_decode_roofline.hc"):
        assert listed[name]["unit"] == "%" and \
            listed[name]["better"] == "higher"
    # the accepted lists whose readers read this cell
    for name in ("decode_step_device_ms", "chunk_device_ms_per_ktok.steady",
                 "chunk_share_of_step.steady", "moe_experts_ms_per_step",
                 "moe_experts_roofline", "moe_pad_share",
                 "flash_prefill_ms_per_ktok", "prefill_ctx_tokens_per_token",
                 "decode_glue_ms_per_step", "chunk_route_ms_per_ktok.steady",
                 "xla_unscoped_share.steady", "peak_hbm_gb.steady",
                 "step_host_ms.steady", "idle_in_device_wait_ms.steady",
                 "idle_outside_device_wait_ms.steady",
                 "compiles_in_window.steady", "setup_compile_s"):
        assert CELL in listed[name]["workloads"], name
    # Mistral's lists stay Mistral's (benchmark/tests/test_mistral4_cell.py),
    # no row kernel serves a bfloat16 row of 14 word-sublanes, and no paged
    # K/V kernel runs here
    for name in ("mla_decode_ms_per_step", "mla_decode_roofline",
                 "latent_tokens_in_use_p50", "moe_dispatch_ms_per_step",
                 "moe_dispatch_ms_per_step.typed", "paged_decode_ms_per_step",
                 "prefill_device_ms_per_ktok"):
        assert name not in listed, name
    assert [m["name"] for m in man.end_to_end(CELL)] == ["tpot_p50_ms",
                                                        "setup_s"]
    cells = man.data["workloads"]
    assert len(cells) == 12 and sum(c["chips"] == 4 for c in cells) == 1
