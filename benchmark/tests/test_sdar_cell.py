"""What the SDAR-30B-A3B cell added: the generator kind whose requests carry a
quality tier and whose check replays every pass from the engine's own block
states; the ``tiers`` distribution; a ``negative_control`` from the traffic
file (the reference in a lower precision, a causal mask inside the block, a
commit that is never written); the operation and byte counts of the paged
kernel with a block folded into its heads and of the flash kernel under the
block mask; readers that read nothing (and do not raise) where the program has
no such counter or the trace no such operation, and roofline shares that
cannot pass 100 %."""

import itertools
import json
import os
import subprocess
import sys

import pytest

from benchmark import block_counts as counts
from benchmark import manifest as manifest_mod
from benchmark import roofline, trace_reduce

ROOT = os.path.dirname(manifest_mod.HERE)
CELL = "sdar30b-pp8-blockgen-saturated"
PEAK = roofline.peaks("TPU v5 lite")
#: the configuration's published widths
DESC = {"num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "hidden_size": 2048, "expert_width": 768, "vocab_size": 151936,
        "block_length": 4, "mask_token_id": 151669}
LAYERS = 6


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
    assert "kv_error_blocks" in said and "NEGATIVE CONTROL" not in said
    assert "row-passes a delivered token" in said
    assert line["counts"]["preempted"] == 0
    assert line["counts"]["compiles_in_window"] == 0


@pytest.mark.parametrize("control", [
    {"reference": {"weights_dtype": "float8_e4m3fn"}},
    {"reference": {"mask": "causal"}},
    {"program": {"commit": "stale"}},
    {"program": {"reveal": "least"}}],
    ids=["float8_weights", "causal_inside_the_block", "stale_commit",
         "least_confident_revealed"])
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
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == (6 if key == "num_hidden_layers" else value), key
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["reference"] == "block_diffusion_moe_lm"
    assert set(cfg["assumed"]) >= {"block_length", "schedule", "qk_norm",
                                   "logits", "mask_token_id", "last_block"}
    desc = man.module("families", cfg["family"]).describe(cfg)
    for key, value in DESC.items():
        assert desc[key] == value, key  # what the readers read
    e = cfg["engine"]
    assert (e["max_seqs"], e["page_size"], e["prefill_chunk"]) == (256, 16,
                                                                   2048)
    assert not e["enable_prefix_cache"] and e["dtype"] == "bf16"
    # the traffic the issue names, to the digit
    tr = man.traffic(man.cell(CELL)["traffic"])
    arr = tr["arrivals"]
    assert (arr["process"], tr["ttft_share"], tr["schedule_seed"]) == (
        "trace", 0, 0)
    assert arr["rate_per_s"] * 2 == int(arr["rate_per_s"] * 2)
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                   "sigma": 0.8, "min": 32, "max": 4096}
    assert tr["output_tokens"] == {"dist": "tiers", "values": [256, 512, 1024],
                                   "shares": [0.4, 0.4, 0.2]}
    assert tr["denoising_steps"] == {"dist": "tiers", "values": [4, 2],
                                     "shares": [0.7, 0.3]}
    assert (tr["balance_group"], tr["tpot_min_gaps"], tr["check_blocks"]) == (
        16, 16, 4)
    assert sorted(n % 4 for n in tr["check_prompt_tokens"]) == [0, 1, 2, 3]
    assert max(tr["check_prompt_tokens"]) > e["prefill_chunk"]
    assert set(tr["check_steps"]) == {2, 4}
    assert 4096 + 1024 < e["page_size"] * e["max_pages_per_seq"]


@pytest.mark.parametrize("seed", [1, 3000000029])
def test_the_tiers_have_their_shares_and_no_seed_changes_who_gets_which(seed):
    man = manifest_mod.Manifest()
    gen = man.module("generators", "serve_requests_block")
    serve = man.module("generators", "serve_requests")
    tr = man.traffic(man.cell(CELL)["traffic"])
    serve._lengths = gen.tiered_lengths(serve._lengths)
    make = gen.tiered_requests(serve.make_requests, serve._lengths, 151669)
    reqs, other = make(tr, seed, 51.0, 151936), make(tr, seed + 1, 51.0,
                                                     151936)
    for key in ("want", "steps", "due"):
        assert [r[key] for r in reqs] == [r[key] for r in other]
    assert [len(r["prompt"]) for r in reqs] == [len(r["prompt"])
                                                for r in other]
    assert reqs[0]["prompt"] != other[0]["prompt"]  # the ids follow the seed
    assert all(r["prompt"].steps == r["steps"] for r in reqs)
    assert max(max(r["prompt"]) for r in reqs[:200]) < 151669
    n = len(reqs)
    wants = [r["want"] for r in reqs]
    assert {w: round(wants.count(w) / n, 2) for w in set(wants)} == {
        256: 0.4, 512: 0.4, 1024: 0.2}
    steps = [r["steps"] for r in reqs]
    assert steps.count(4) / n == pytest.approx(0.7, abs=0.01)
    assert steps.count(2) + steps.count(4) == n
    # three prompts in four have a remainder that opens the first block
    assert sum(len(r["prompt"]) % 4 > 0 for r in reqs) / n == pytest.approx(
        0.75, abs=0.04)
    # every run of balance_group arrivals holds the tiers in their shares
    g = int(tr["balance_group"])
    pre = sum(1 for r in reqs if r["due"] < 0)
    ordered = sorted(reqs, key=lambda r: r["index"])
    for part in (ordered[:pre], ordered[pre:]):
        for i in range(0, len(part) - g + 1, g):
            run = [r["steps"] for r in part[i:i + g]]
            assert abs(run.count(2) - 0.3 * g) <= 1.5
    # a distribution the kind does not add goes where it went
    assert serve._lengths({"dist": "fixed", "value": 7}, 3, [0, 1, 2]) == [
        7, 7, 7]
    # the mix's passes a delivered token, were every block whole: 1.25 and
    # 0.75 by tier, weighted by what each tier delivers
    per = sum((1.25 if r["steps"] == 4 else 0.75) * r["want"] for r in reqs)
    assert per / sum(wants) == pytest.approx(1.10, abs=0.01)


def test_counts_are_a_hand_count_of_the_least_that_moves():
    # one row whose block ends at position 100: a layer keeps 4 x 2 x 128
    # bfloat16 values a position = 2,048 B, and the row's 4 x 32 queries of
    # 128 come in and as many outputs go out
    ops, nbytes = counts.block_pass_ops_bytes(DESC, 1, 100, 1)
    assert nbytes == 100 * 2048 + 2 * (4 * 32 * 128) * 2
    assert ops == 100 * 4 * 32 * 4 * 128
    # 32 operations a cached byte (4 x 8 query rows share a fetch): under the
    # chip's 240, the bytes bound it
    assert 4 * 32 * 4 * 128 / 2048 == 32
    assert PEAK["bf16_flops_per_s"] / PEAK["hbm_bytes_per_s"] > 200
    _, one = counts.block_pass_ops_bytes(DESC, LAYERS, 1000, 4)
    _, two = counts.block_pass_ops_bytes(DESC, LAYERS, 1001, 4)
    assert two - one == 6 * 2048      # a cached token over the 6 layers
    # a chunk's pairs under the block mask: a query of the chunk's b-th block
    # sees ctx + 4 (b + 1) keys
    assert counts.chunk_pairs(8, 10, 4) == 4 * 14 + 4 * 18
    assert counts.chunk_pairs(4, 0, 4) == 16
    # never fewer than the causal mask's, never more than every pair
    for tokens, ctx in ((4, 0), (64, 12), (2048, 4096)):
        causal = tokens * (ctx + (tokens + 1) / 2.0)
        assert causal < counts.chunk_pairs(tokens, ctx, 4) <= tokens * (
            ctx + tokens)
    ops, nbytes = counts.flash_ops_bytes(DESC, 2, [(8, 12)])
    assert ops == 2 * 4 * 32 * 128 * (8 * 12 + 16 * 3)
    assert nbytes == 2 * 2 * (8 * 2 * 32 * 128 + 20 * 2 * 4 * 128)
    # 2,048 tokens behind 2 k of context: the pairs bound it
    ops, nbytes = counts.flash_ops_bytes(DESC, LAYERS, [(2048, 2048)])
    assert roofline.roofline_seconds(ops, nbytes, PEAK)[1] == "compute"


def _step(contexts, chunks=(), commits=0, out=0):
    """A step record as the generator leaves it: ``contexts`` the positions
    through each row's block, ``chunks`` the step's ``(tokens, start)``."""
    return {"decode_rows": len(contexts), "row_passes": len(contexts),
            "block_passes": int(bool(contexts)), "commit_row_passes": commits,
            "tokens_committed": out, "tokens_revealed": out,
            "block_kv_tokens": sum(contexts), "page_tokens_in_use": 4096,
            "chunk_tokens": sum(t for t, _ in chunks), "recompute_tokens": 0,
            "chunks": len(chunks), "chunk_spans": [list(c) for c in chunks]}


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

    def window(self):
        return (0.0, 1.0)


def test_no_roofline_share_can_pass_100_percent():
    """Over the counter values the program can produce, the counted bytes and
    operations are at most what the kernels' calls must move and compute: a
    kernel that takes exactly that long reads 100 %."""
    reader = manifest_mod.Manifest().module("readers", "block_roofline")
    hbm, flops = PEAK["hbm_bytes_per_s"], PEAK["bf16_flops_per_s"]
    for rows, ctx_len in itertools.product((1, 5, 256), (4, 36, 2048, 5120)):
        steps = [_step([ctx_len] * rows)]
        least, bound = reader.bound("paged", steps, DESC, LAYERS, PEAK)
        moved = LAYERS * 2 * (rows * ctx_len * 1024 + rows * 4 * 2 * 4096)
        assert bound == "memory" and least == pytest.approx(moved / hbm)
        for slow in (1, 3):
            ctx = {"trace": _Trace(slow * moved / hbm),
                   "device": {"kind": "TPU v5 lite"},
                   "result": {"steps": steps, "desc": DESC,
                              "n_layers": LAYERS}}
            assert reader.read(ctx, "paged", "dstpu_paged_decode",
                               "bench.step") == pytest.approx(100.0 / slow)
    for tokens, ctx_len in itertools.product((4, 200, 2048), (0, 100, 4096)):
        steps = [_step([], [(tokens, ctx_len)])]
        least, _ = reader.bound("flash", steps, DESC, LAYERS, PEAK)
        ops, nbytes = counts.flash_ops_bytes(DESC, LAYERS, [(tokens, ctx_len)])
        assert least == pytest.approx(max(ops / flops, nbytes / hbm))
        ctx = {"trace": _Trace(least), "device": {"kind": "TPU v5 lite"},
               "result": {"steps": steps, "desc": DESC, "n_layers": LAYERS}}
        assert reader.read(ctx, "flash", "dstpu_flash_fwd", "bench.step") \
            == pytest.approx(100.0)


def test_readers_read_a_number_or_nothing_and_never_raise():
    """A parent's step records lack the new keys, a trace without the kernels
    has no time to divide by, and a run that left no trace file has no
    instruction text to read: no reading, no raise."""
    man = manifest_mod.Manifest()
    reader = man.module("readers", "block_roofline")
    old = [{"decode_rows": 4, "chunks": 1, "chunk_tokens": 9,
            "recompute_tokens": 0, "decode_pages": 7}] * 3
    ctx = {"trace": _Trace(1.0, spans=3), "device": {"kind": "TPU v5 lite"},
           "result": {"steps": old, "desc": DESC, "n_layers": LAYERS}}
    for what in ("paged", "flash"):
        assert reader.read(ctx, what, "dstpu_x", "bench.step") is None
    # another family's description has no block length to count with
    ctx["result"] = {"steps": [_step([60, 40])] * 3, "n_layers": 4,
                     "desc": {"period": ["gqa", "kda"]}}
    assert reader.read(ctx, "paged", "dstpu_x", "bench.step") is None
    for name in ("row_passes_per_committed_token",
                 "commit_share_of_row_passes", "block_sample_ms_per_step",
                 "block_head_ms_per_step"):
        spec = man.layer_metric(name)
        ctx["result"] = {"steps": old, "desc": {}, "n_layers": 4}
        assert man.module("readers", spec["reader"]).read(
            ctx, **spec["args"]) is None, name
    # the recorded serving fixture (a dense engine, PR 25) ran the flash and
    # the paged kernel: over it the new shares read a number
    recorded = trace_reduce.reduce_file(os.path.join(
        manifest_mod.HERE, "fixtures", "small_serve_v5e.xplane.pb"))
    steps = [_step([60, 900], [(24, 48)], commits=1, out=4)] * 4
    ctx = {"trace": recorded, "device": {"kind": "TPU v5 lite"},
           "result": {"steps": steps, "desc": DESC, "n_layers": LAYERS}}
    for name in ("paged_decode_roofline.block",
                 "flash_prefill_roofline.block"):
        spec = man.layer_metric(name)
        got = man.module("readers", spec["reader"]).read(ctx, **spec["args"])
        assert got is None or got > 0, name
    want = {"row_passes_per_committed_token": 0.5,
            "commit_share_of_row_passes": 0.5}
    for name, value in want.items():
        spec = man.layer_metric(name)
        assert man.module("readers", spec["reader"]).read(
            ctx, **spec["args"]) == value, name


def test_the_vocabulary_wide_operations_are_told_apart_by_their_text(
        tmp_path, monkeypatch):
    """``vocab_ops_ms`` over a made-up profile: the head's product, the
    sampler's reductions, the embedding's gather (vocabulary first: neither)
    and a loop that contains them (left out)."""
    import types

    from benchmark import program_spans

    reader = manifest_mod.Manifest().module("readers", "vocab_ops_ms")

    def ev(text, start_ms, ms):
        return types.SimpleNamespace(name=text, start_ns=start_ms * 1e6,
                                     duration_ns=ms * 1e6)

    events = [
        ev("%convolution_fusion.1 = bf16[1024,151936]{1,0} fusion(bf16[1024,"
           "2048]{1,0} %a, bf16[2048,151936]{1,0} %w), kind=kOutput", 10, 3),
        ev("%reduce_fusion.2 = (f32[1024]{0}, s32[1024]{0}) fusion(bf16[1024,"
           "151936]{1,0} %l), kind=kInput", 14, 2),
        ev("%fusion.9 = bf16[1024,2048]{1,0} fusion(bf16[151936,2048]{1,0} "
           "%tok, s32[1024]{0} %ids), kind=kLoop", 5, 1),
        ev("%while.3 = (s32[], bf16[1024,151936]{1,0}) while(%t), body=%b",
           9, 8),
        ev("%reduce_fusion.2 = (f32[1024]{0}, s32[1024]{0}) fusion(bf16[1024,"
           "151936]{1,0} %l), kind=kInput", 2000, 2),  # outside the window
    ]
    plane = types.SimpleNamespace(
        name="/device:TPU:0",
        lines=[types.SimpleNamespace(name="XLA Ops", events=events)])
    import jax.profiler

    monkeypatch.setattr(program_spans, "newest_trace", lambda: "x.pb")
    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file",
        staticmethod(lambda path: types.SimpleNamespace(planes=[plane])))
    ctx = {"trace": _Trace(0.0, spans=2), "result": {"desc": DESC}}
    assert reader.read(ctx, "head", "bench.step") == pytest.approx(1.5)
    assert reader.read(ctx, "sample", "bench.step") == pytest.approx(1.0)
    ctx["result"] = {"desc": dict(DESC, vocab_size=7)}
    assert reader.read(ctx, "head", "bench.step") is None


def test_every_new_metric_names_the_cell_and_moves_tpot():
    man = manifest_mod.Manifest()
    listed = {m["name"]: m for m in man.per_layer(CELL)}
    for name in ("row_passes_per_committed_token",
                 "commit_share_of_row_passes", "block_sample_ms_per_step",
                 "block_head_ms_per_step", "paged_decode_roofline.block",
                 "flash_prefill_roofline.block",
                 "page_tokens_in_use_p50.block"):
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "tpot_p50_ms"
        assert man.layer_metric(name)["name"] == name
    for name in ("decode_step_device_ms", "paged_decode_ms_per_step",
                 "moe_experts_ms_per_step", "moe_experts_roofline",
                 "moe_pad_share", "moe_dispatch_ms_per_step",
                 "flash_prefill_ms_per_ktok",
                 "chunk_device_ms_per_ktok.steady",
                 "chunk_share_of_step.steady", "step_host_ms.steady",
                 "idle_in_device_wait_ms.steady",
                 "idle_outside_device_wait_ms.steady",
                 "compiles_in_window.steady", "peak_hbm_gb.steady",
                 "setup_compile_s",
                 "setup_trace_s"):
        assert CELL in listed[name]["workloads"], name
    # the accepted shares that count one query row a K/V head group, and
    # metrics of another end-to-end metric, stay off this cell
    for name in ("paged_decode_roofline", "paged_decode_roofline.hybrid",
                 "page_tokens_in_use_p50",
                 "flash_prefill_roofline.hybrid",
                 "prefill_device_ms_per_ktok"):
        assert name not in listed
    assert [m["name"] for m in man.end_to_end(CELL)] == ["tpot_p50_ms",
                                                        "setup_s"]
    assert man.cell(CELL)["chips"] == 1
