"""What the Mistral-Small-4 cell added: the generator kind that takes the
reference and the cache accessor from the configuration and a
``negative_control`` from the traffic file; the operation and byte counts of
the latent decode kernel; readers that read nothing (and do not raise) where
the program has no such counter or the trace no such kernel, and a roofline
share that cannot pass 100 %."""

import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import manifest as manifest_mod
from benchmark import mla_counts, roofline, trace_reduce

ROOT = os.path.dirname(manifest_mod.HERE)
CELL = "mistralsmall4-ep8-docqa-saturated"
PEAK = roofline.peaks("TPU v5 lite")
#: the configuration's published widths, its depth here, and its engine's page
HEADS, RANK, ROPE, LAYERS, PS = 32, 256, 64, 8, 16
DESC = {"num_attention_heads": HEADS, "kv_lora_rank": RANK,
        "qk_rope_head_dim": ROPE, "hidden_size": 4096, "expert_width": 2048}


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
    assert "latent_error" in said and "NEGATIVE CONTROL" not in said
    assert line["counts"]["preempted"] == 0


@pytest.mark.parametrize("control", [
    {"reference": {"weights_dtype": "float8_e4m3fn"}},
    {"reference": {"rope": "plain"}},
    {"reference": {"softmax_scale": "plain"}},
    {"reference": {"router": "softmax"}},
    {"program": {"latent_dtype": "float8_e4m3fn"}}],
    ids=["float8_weights", "rope_plain", "softmax_scale_plain",
         "router_softmax", "program_latent_float8"])
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


def test_the_configuration_is_the_catalogs_cut_as_the_issue_cuts_it():
    man = manifest_mod.Manifest()
    entry = next(c for c in man.data["configs"]
                 if c["name"] == man.cell(CELL)["config"])
    cfg = man.config(entry["name"])
    cut = {"num_hidden_layers": 8, "n_routed_experts": 16,
           "vocab_size": 16384}
    assert entry["reduced"] == cfg["reduced"] == list(cut)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mistral-Small-4-119B-2603")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == cut.get(key, value), key
        if key in cut:
            assert cfg["published"][key] == value
    assert cfg["deployment_share"] == {
        "chips_sharing_a_layer": 8, "n_routed_experts_published": 128,
        "first_expert": 0, "vocab_first_row": 0}
    assert cfg["reference"] == "mla_moe_lm"
    assert cfg["cache"] == {"accessor": "read_latent"}
    desc = man.module("families", cfg["family"]).describe(cfg)
    for key in ("hidden_size", "expert_width", "kv_lora_rank",
                "qk_rope_head_dim", "num_attention_heads"):
        assert desc[key] == DESC[key]   # what the accepted readers read
    e = cfg["engine"]
    assert (e["max_seqs"], e["page_size"], e["max_pages_per_seq"]) == (
        128, PS, 1089)
    assert e["prefill_chunk"] in (512, 1024, 2048)
    # the traffic the issue names, to the digit
    tr = man.traffic(man.cell(CELL)["traffic"])
    # R = 2.0 x the 6.06 requests/s the finished change completes with all
    # slots taken (PERF.md section 6), rounded to 0.5
    assert tr["arrivals"] == {"process": "trace", "rate_per_s": 12.0,
                              "preroll_s": 30}
    assert tr["ttft_share"] == 0
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                   "sigma": 0.7, "min": 512, "max": 16384}
    assert tr["output_tokens"] == {"dist": "lognormal", "median": 192,
                                   "sigma": 0.6, "min": 32, "max": 1024}
    assert tr["check_prompt_tokens"] == [320, 1500, 8448]
    assert (tr["check_decode_steps"], tr["tpot_min_gaps"],
            tr["schedule_seed"]) == (16, 16, 0)
    assert max(16384 + 1024, 8448 + 18) <= PS * e["max_pages_per_seq"]


def test_counts_are_a_hand_count_of_the_least_that_moves():
    # one decoded row that sees one cached position, one layer: the row of
    # 256 + 64 bfloat16 values read once (key and value both), the row's 32
    # absorbed queries of 320 in and 32 latents of 256 out
    ops, nbytes = mla_counts.mla_decode_ops_bytes(1, 1, 1, HEADS, RANK, ROPE)
    assert nbytes == 640 + 2 * HEADS * (320 + 256)
    assert ops == HEADS * (2 * 320 + 2 * 256)
    # 57.6 operations a cached byte: under the chip's 240, the bytes bound it
    assert ops / 640 == pytest.approx(57.6)
    assert PEAK["bf16_flops_per_s"] / PEAK["hbm_bytes_per_s"] > 200
    # a cached token costs 640 B a layer, 5,120 B over the cell's 8
    _, one = mla_counts.mla_decode_ops_bytes(1000, 4, LAYERS, HEADS, RANK,
                                             ROPE)
    _, two = mla_counts.mla_decode_ops_bytes(1001, 4, LAYERS, HEADS, RANK,
                                             ROPE)
    assert two - one == 5120
    # tiny sizes: 4 heads over a latent of 32 + 8, 4 layers, 3 rows, 50 tokens
    ops, nbytes = mla_counts.mla_decode_ops_bytes(50, 3, 4, 4, 32, 8)
    assert ops == 50 * 4 * 4 * (2 * 40 + 2 * 32)
    assert nbytes == 4 * 2 * (50 * 40 + 3 * 4 * (40 + 32))


def _step(contexts, chunk_tokens=0, ctx_tokens=0, in_use=0):
    """A step record as the generator leaves it, from the engine's own rule:
    ``contexts`` the decoded rows' visible tokens."""
    return {"decode_rows": len(contexts), "latent_kv_tokens": sum(contexts),
            "latent_tokens_in_use": in_use, "chunk_tokens": chunk_tokens,
            "recompute_tokens": 0, "chunks": int(chunk_tokens > 0),
            "ctx_tokens": ctx_tokens, "moe_local_picks": 64,
            "moe_experts_touched": 16, "moe_padded_rows": 128,
            "moe_layer_calls": 8}


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


def test_the_roofline_share_cannot_pass_100_percent():
    """Over the counter values the program can produce, the counted bytes are
    at most what the kernel's calls must move: every visible position's row
    once a layer at its stated width — the kernel moves that or, with the
    lane padding, more — and the rows' queries and outputs."""
    reader = manifest_mod.Manifest().module("readers", "mla_roofline")
    hbm = PEAK["hbm_bytes_per_s"]
    for rows, ctx_len in itertools.product((1, 5, 128),
                                           (1, 17, 255, 256, 257, 17408)):
        steps = [_step([ctx_len] * rows)]
        least, bound = reader.bound(steps, DESC, LAYERS, PEAK)
        moved = LAYERS * (rows * ctx_len * 640
                          + rows * HEADS * 2 * (320 + 256))
        assert bound == "memory" and least == pytest.approx(moved / hbm)
        # what the kernel moves as the pool is laid out is never less
        assert LAYERS * rows * ctx_len * 768 >= LAYERS * rows * ctx_len * 640
        ctx = {"trace": _Trace(moved / hbm), "device": {"kind": "TPU v5 lite"},
               "result": {"steps": steps, "desc": DESC, "n_layers": LAYERS}}
        assert reader.read(ctx, "dstpu_mla_decode", "bench.step") == \
            pytest.approx(100.0)
        ctx["trace"] = _Trace(3 * moved / hbm)
        assert reader.read(ctx, "dstpu_mla_decode", "bench.step") == \
            pytest.approx(100.0 / 3)


def test_readers_read_a_number_or_nothing_and_never_raise():
    """A parent's step records lack the new keys, and a trace without the new
    kernel has no time to divide by: no reading, no raise.  The recorded
    serving fixture (a one-layer dense engine, PR 25) holds no latent kernel;
    what it does hold — programs, flash calls — the new data files read."""
    man = manifest_mod.Manifest()
    reader = man.module("readers", "mla_roofline")
    old = [{"decode_rows": 4, "chunks": 1, "chunk_tokens": 9,
            "recompute_tokens": 0, "decode_pages": 7}] * 3
    ctx = {"trace": _Trace(1.0, spans=3), "device": {"kind": "TPU v5 lite"},
           "result": {"steps": old, "desc": DESC, "n_layers": LAYERS}}
    assert reader.read(ctx, "dstpu_mla_decode", "bench.step") is None
    # another family's description has no latent widths
    ctx["result"]["desc"] = {"period": ["gqa", "kda"]}
    ctx["result"]["steps"] = [_step([60, 40])] * 3
    assert reader.read(ctx, "dstpu_mla_decode", "bench.step") is None
    for name in ("prefill_ctx_tokens_per_token", "latent_tokens_in_use_p50"):
        spec = man.layer_metric(name)
        ctx["result"]["steps"] = old
        assert man.module("readers", spec["reader"]).read(
            ctx, **spec["args"]) is None, name
    recorded = trace_reduce.reduce_file(os.path.join(
        manifest_mod.HERE, "fixtures", "small_serve_v5e.xplane.pb"))
    steps = [_step([60, 40], chunk_tokens=25, ctx_tokens=50, in_use=160)] * 4
    ctx = {"trace": recorded, "device": {"kind": "TPU v5 lite"},
           "result": {"steps": steps, "desc": DESC, "n_layers": LAYERS}}
    for name in ("mla_decode_ms_per_step", "mla_decode_roofline"):
        spec = man.layer_metric(name)   # no such kernel in that trace
        assert man.module("readers", spec["reader"]).read(
            ctx, **spec["args"]) is None, name
    want = {"prefill_ctx_tokens_per_token": 2.0,
            "latent_tokens_in_use_p50": 160.0}
    for name, value in want.items():
        spec = man.layer_metric(name)
        assert man.module("readers", spec["reader"]).read(
            ctx, **spec["args"]) == value, name
    # the fixture's chunk program ran the flash kernel
    spec = man.layer_metric("flash_prefill_ms_per_ktok")
    got = man.module("readers", spec["reader"]).read(ctx, **spec["args"])
    assert got is None or got > 0


def test_every_new_metric_names_the_cell_and_moves_tpot():
    man = manifest_mod.Manifest()
    listed = {m["name"]: m for m in man.per_layer(CELL)}
    for name in ("mla_decode_ms_per_step", "mla_decode_roofline",
                 "latent_tokens_in_use_p50"):
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "tpot_p50_ms"
        assert man.layer_metric(name)["name"] == name
    # a later cell that the same reader reads is appended to these
    for name in ("flash_prefill_ms_per_ktok", "prefill_ctx_tokens_per_token",
                 "moe_experts_roofline", "moe_pad_share",
                 "chunk_device_ms_per_ktok.steady", "setup_compile_s"):
        assert CELL in listed[name]["workloads"]
    # no paged K/V kernel runs here, and metrics of another end-to-end metric
    # stay off this cell
    for name in ("paged_decode_ms_per_step", "paged_decode_roofline",
                 "prefill_device_ms_per_ktok", "kda_step_roofline"):
        assert name not in listed
    assert [m["name"] for m in man.end_to_end(CELL)] == ["tpot_p50_ms",
                                                        "setup_s"]


def test_latent_errors_are_each_layers_relative_error():
    gen = manifest_mod.Manifest().module("generators",
                                         "serve_requests_latent")
    rng = np.random.default_rng(0)
    ref = [rng.normal(size=(12, 40)).astype(np.float32) for _ in range(3)]
    kept = np.stack([r[:9] for r in ref])     # the cache is one token short
    assert gen.latent_errors(kept, ref) == [0.0, 0.0, 0.0]
    kept[1] *= 1.01
    assert gen.latent_errors(kept, ref) == pytest.approx([0.0, 0.01, 0.0],
                                                         rel=1e-3)
    # what the first layer's limit tells apart: rows kept in bfloat16 from
    # rows rounded through a float8's 3 mantissa bits, scaled or not
    import ml_dtypes

    cache = kept.astype(ml_dtypes.bfloat16)
    through = cache.astype(ml_dtypes.float8_e4m3fn).astype(ml_dtypes.bfloat16)
    scaled = ((cache.astype(np.float32) * 3.7).astype(ml_dtypes.float8_e4m3fn)
              .astype(np.float32) / 3.7).astype(ml_dtypes.bfloat16)
    kept[1] /= 1.01
    assert gen.latent_errors(cache, ref)[0] < 3e-3
    assert gen.latent_errors(through, ref)[0] > 2e-2
    assert gen.latent_errors(scaled, ref)[0] > 2e-2
