"""What the Laguna-S-2.1 cell added: a configuration whose grouped-query
layers take their QUERY head count, rotary table and rotated share from their
type; the operation and byte counts of the attention kernels with the head
counts by type (``typed_gqa_counts.py``: the accepted ``hybrid_attn_counts.py``
takes one query head count for both); a reader that reads nothing (and does
not raise) where the program has no such counter, the trace no such kernel or
the description no head counts by type, and roofline shares that cannot pass
100 %."""

import itertools
import json
import os
import subprocess
import sys

import jax
import pytest

from benchmark import manifest as manifest_mod
from benchmark import roofline, trace_reduce
from benchmark import typed_gqa_counts as counts

ROOT = os.path.dirname(manifest_mod.HERE)
CELL = "lagunas21-ep8-codeagent-saturated"
PEAK = roofline.peaks("TPU v5 lite")
#: the configuration's published widths and its stack here
DESC = {"heads_full": 48, "heads_window": 72, "kv_heads": 8, "head_dim": 128,
        "rot_full": 64, "rot_window": 128, "sliding_window": 512,
        "window_layers": [0, 1, 1, 1, 0, 1, 1, 1, 0], "hidden_size": 3072,
        "expert_width": 1024}
TINY = dict(DESC, heads_full=12, heads_window=18, kv_heads=2, head_dim=16,
            rot_full=8, rot_window=16, sliding_window=16)


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
    assert "ring_error" in said and "NEGATIVE CONTROL" not in said
    assert line["counts"]["preempted"] == 0


@pytest.mark.parametrize("control", [
    {"reference": {"weights_dtype": "float8_e4m3fn"}},
    {"reference": {"gate": "none"}},
    {"reference": {"yarn": "plain"}},
    {"reference": {"yarn": "scale_all"}},
    {"reference": {"rotary": "whole_head"}},
    {"reference": {"window": "full"}},
    {"program": {"cache_dtype": "float8_e4m3fn"}}],
    ids=["float8_weights", "no_gate", "yarn_plain", "yarn_scale_all",
         "whole_head", "full_window", "program_cache_float8"])
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


def test_the_cells_files_are_found_by_name():
    man = manifest_mod.Manifest()
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1-ep8-serve", "codeagent-saturated", 1)
    cfg = man.config(cell["config"])
    assert cfg["_file"].endswith("configs/laguna-s-2.1-ep8-serve.json")
    assert man.find("traffic", "codeagent-saturated.json")
    assert man.find("families", cfg["family"] + ".py")
    assert man.find("reference", cfg["reference"] + ".py")
    assert man.traffic(cell["traffic"])["kind"] == "serve_requests_mixed"
    assert man.find("generators", "serve_requests_mixed.py")
    for name in ("paged_decode_roofline.typed", "window_decode_roofline.typed",
                 "flash_prefill_roofline.typed"):
        spec = man.layer_metric(name)
        assert spec["reader"] == "typed_gqa_roofline"
        assert man.find("readers", spec["reader"] + ".py")


def test_the_configuration_is_the_catalogs_cut_as_the_issue_cuts_it():
    man = manifest_mod.Manifest()
    entry = next(c for c in man.data["configs"]
                 if c["name"] == man.cell(CELL)["config"])
    cfg = man.config(entry["name"])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    # the four per-layer lists stand whole (the family runs their first 9)
    cut = {"num_hidden_layers": 9, "num_experts": 32, "vocab_size": 12544}
    assert entry["reduced"] == cfg["reduced"] == list(cut)
    # no width is cut: nothing reduced is a size of a head, a layer's width,
    # an expert's or the experts a token takes
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "shared_expert_intermediate_size", "head_dim", "sliding_window",
              "num_attention_heads", "num_key_value_heads",
              "num_experts_per_tok", "rope_parameters")
    for key in cfg["reduced"]:
        assert key not in widths and not key.endswith(("_dim", "_rank")), key
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == cut.get(key, value), key
        if key in cut:
            assert cfg["published"][key] == value
    assert cfg["layer_types"][:9] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention"] * 2 + ["full_attention"]
    assert cfg["num_attention_heads_per_layer"][:9] == [48, 72, 72,
                                                        72] * 2 + [48]
    assert cfg["deployment_share"] == {
        "chips_sharing_a_layer": 8, "num_experts_published": 256,
        "first_expert": 0, "vocab_first_row": 0,
        "published_layers_kept": list(range(9))}
    assert cfg["reference"] == "gated_swa_moe_lm"
    assert cfg["cache"] == {"accessor": "read_kv"}
    for item in ("qk_norm", "gate", "attention_factor", "router",
                 "shared_expert", "router_weight"):
        assert item in cfg["assumed"], item
    desc = man.module("families", cfg["family"]).describe(cfg)
    for key, value in DESC.items():
        assert desc[key] == value, key  # what the readers read
    assert desc["yarn"]["attention_factor"] == 1.4852030263919618
    e = cfg["engine"]
    assert (e["max_seqs"], e["page_size"], e["max_pages_per_seq"]) == (
        64, 16, 2177)
    assert e["prefill_chunk"] in (512, 1024, 2048)
    assert not e["enable_prefix_cache"] and e["decode_horizon"] == 1
    # the traffic the issue names, to the digit
    tr = man.traffic(man.cell(CELL)["traffic"])
    arr = tr["arrivals"]
    assert (arr["process"], arr["preroll_s"] % 10, tr["ttft_share"]) == (
        "trace", 0, 0)
    assert arr["preroll_s"] >= 40
    assert arr["rate_per_s"] * 2 == int(arr["rate_per_s"] * 2)
    assert tr["prompt_tokens"]["dist"] == "mixture"
    assert tr["prompt_tokens"]["parts"] == [
        {"share": 0.8, "dist": "lognormal", "median": 2048, "sigma": 0.7,
         "min": 256, "max": 8192},
        {"share": 0.2, "dist": "lognormal", "median": 12288, "sigma": 0.5,
         "min": 6144, "max": 32768}]
    assert tr["output_tokens"] == {"dist": "lognormal", "median": 384,
                                   "sigma": 0.6, "min": 64, "max": 2048}
    assert tr["check_prompt_tokens"] == [600, 2600, 12288]
    assert (tr["check_decode_steps"], tr["tpot_min_gaps"], tr["schedule_seed"],
            tr["balance_group"], tr["trace_seconds"]) == (16, 16, 0, 20, 30)
    assert 32768 + 2048 < e["page_size"] * e["max_pages_per_seq"]
    assert "negative_control" not in tr


def test_the_engines_model_has_the_parameters_the_issue_counts():
    """3,199.4 M parameters but for the norms: the dense layer 0, six window
    and two full expert layers of 32 held experts, an eighth of the
    vocabulary twice — counted from the shapes the family builds."""
    import jax.numpy as jnp

    man = manifest_mod.Manifest()
    cfg = man.config(man.cell(CELL)["config"])
    model = man.module("families", cfg["family"]).build(
        cfg, cfg["num_hidden_layers"], 16 * 2177, jnp.bfloat16)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = sum(a.size for _, a in leaves)
    norms = sum(a.size for p, a in leaves if "norm" in jax.tree_util.keystr(p))
    attn_full = 3072 * 128 * (48 + 8 + 8 + 48) + 3072 * 48
    attn_win = 3072 * 128 * (72 + 8 + 8 + 72) + 3072 * 72
    expert = 3 * 3072 * 1024
    ffn = 32 * expert + expert + 3072 * 256
    want = (attn_full + 3 * 3072 * 12288 + 6 * (attn_win + ffn)
            + 2 * (attn_full + ffn) + 2 * 12544 * 3072)
    assert norms == (2 * 9 + 1) * 3072
    assert total - norms == want
    assert want == pytest.approx(3199.4e6, rel=1e-4)
    assert 2 * total == pytest.approx(6.40e9, rel=0.01)  # bfloat16 bytes


def test_counts_are_a_hand_count_of_the_least_that_moves():
    # one decoded row that sees one cached position: a layer keeps 8 x (128 +
    # 128) bfloat16 values = 4,096 B of it, three full layers; the row's 48
    # queries of 128 in and 48 outputs of 128 out, a layer
    ops, nbytes = counts.decode_ops_bytes(DESC, False, 1, 1)
    assert nbytes == 3 * (4096 + 2 * 2 * 48 * 128)
    assert ops == 3 * 48 * 4 * 128
    # the same position in a ring: six window layers of 72 query heads
    ops, nbytes = counts.decode_ops_bytes(DESC, True, 1, 1)
    assert nbytes == 6 * (4096 + 2 * 2 * 72 * 128)
    assert ops == 6 * 72 * 4 * 128
    # 6 and 9 operations a cached byte: far under the chip's 240
    assert 48 * 4 * 128 / 4096 == 6 and 72 * 4 * 128 / 4096 == 9
    assert PEAK["bf16_flops_per_s"] / PEAK["hbm_bytes_per_s"] > 200
    _, one = counts.decode_ops_bytes(DESC, False, 1000, 4)
    _, two = counts.decode_ops_bytes(DESC, False, 1001, 4)
    assert two - one == 12288         # a cached token over the 3 full layers
    _, one = counts.decode_ops_bytes(DESC, True, 1000, 4)
    _, two = counts.decode_ops_bytes(DESC, True, 1001, 4)
    assert two - one == 6 * 4096      # and a ring row over the 6 window ones
    # tiny sizes: 12 / 18 heads of 16 over 2 K/V heads, 3 rows, 50 tokens
    ops, nbytes = counts.decode_ops_bytes(TINY, False, 50, 3)
    assert ops == 50 * 3 * 12 * 4 * 16
    assert nbytes == 3 * 2 * (50 * 2 * 2 * 16 + 3 * 2 * 12 * 16)
    ops, nbytes = counts.decode_ops_bytes(TINY, True, 40, 3)
    assert ops == 40 * 6 * 18 * 4 * 16
    assert nbytes == 6 * 2 * (40 * 2 * 2 * 16 + 3 * 2 * 18 * 16)
    # a chunk's pairs: query i sees ctx + i + 1 keys, or the window's
    assert counts.chunk_pairs(4, 10) == 11 + 12 + 13 + 14
    assert counts.chunk_pairs(4, 10, 12) == 11 + 12 + 12 + 12
    assert counts.chunk_pairs(3, 0, 2) == 1 + 2 + 2
    # tiny: a window of 16 behind 14 cached positions binds from the third
    # query on: 15 + 16 + 17 + 18 pairs on a full layer at 12 heads, 15 + 16
    # + 16 + 16 on a window layer at 18
    ops, nbytes = counts.flash_ops_bytes(TINY, [(4, 14)])
    assert ops == 4 * 16 * (3 * 12 * 66 + 6 * 18 * 63)
    assert nbytes == 2 * (4 * 2 * 16 * (3 * 12 + 6 * 18)
                          + 2 * 2 * 16 * (3 * 18 + 6 * 18))
    # 2,048 tokens behind 12 k of context: the full layers' pairs bound it
    ops, nbytes = counts.flash_ops_bytes(DESC, [(2048, 12288)])
    assert roofline.roofline_seconds(ops, nbytes, PEAK)[1] == "compute"


def _step(contexts, chunks=(), in_use=0):
    """A step record as the generator leaves it, from the engine's own rule:
    ``contexts`` the decoded rows' visible tokens, ``chunks`` the step's
    ``(tokens, cached positions before them)``."""
    return {"decode_rows": len(contexts), "full_kv_tokens": sum(contexts),
            "window_kv_tokens": sum(min(c, 512) for c in contexts),
            "long_rows": sum(c > 8192 for c in contexts),
            "page_tokens_in_use": in_use, "state_slots_in_use": len(contexts),
            "chunk_tokens": sum(t for t, _ in chunks), "recompute_tokens": 0,
            "chunks": len(chunks), "chunk_spans": [list(c) for c in chunks],
            "ctx_tokens": sum(c for _, c in chunks), "moe_local_picks": 64,
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


def test_no_roofline_share_can_pass_100_percent():
    """Over the counter values the program can produce, the counted bytes and
    operations are at most what the kernels' calls must move and compute:
    every visible position's keys and values once a layer, a ring's live rows
    alone, the rows' queries and outputs at the type's head count, the
    visible pairs — a kernel that takes exactly that long reads 100 %."""
    reader = manifest_mod.Manifest().module("readers", "typed_gqa_roofline")
    hbm, flops = PEAK["hbm_bytes_per_s"], PEAK["bf16_flops_per_s"]
    for rows, ctx_len in itertools.product((1, 5, 64),
                                           (1, 17, 511, 512, 513, 34815)):
        steps = [_step([ctx_len] * rows)]
        for what, layers, heads, seen in (
                ("paged", 3, 48, ctx_len),
                ("window", 6, 72, min(ctx_len, 512))):
            least, bound = reader.bound(what, steps, DESC, PEAK)
            moved = layers * 2 * (rows * seen * 8 * 256
                                  + rows * heads * 256)
            assert bound == "memory" and least == pytest.approx(moved / hbm)
            ctx = {"trace": _Trace(moved / hbm),
                   "device": {"kind": "TPU v5 lite"},
                   "result": {"steps": steps, "desc": DESC}}
            kernel = "dstpu_" + what + "_decode"
            assert reader.read(ctx, what, kernel, "bench.step") == \
                pytest.approx(100.0)
            ctx["trace"] = _Trace(3 * moved / hbm)
            assert reader.read(ctx, what, kernel, "bench.step") == \
                pytest.approx(100.0 / 3)
    for tokens, ctx_len in itertools.product((1, 200, 2048),
                                             (0, 100, 2048, 30720)):
        steps = [_step([], [(tokens, ctx_len)])]
        least, bound = reader.bound("flash", steps, DESC, PEAK)
        ops, nbytes = counts.flash_ops_bytes(DESC, [(tokens, ctx_len)])
        assert least == pytest.approx(max(ops / flops, nbytes / hbm))
        # the pairs a masked kernel computes are never fewer than counted
        assert counts.chunk_pairs(tokens, ctx_len) <= tokens * (ctx_len
                                                                 + tokens)
        assert counts.chunk_pairs(tokens, ctx_len, 512) <= 512 * tokens
        ctx = {"trace": _Trace(least), "device": {"kind": "TPU v5 lite"},
               "result": {"steps": steps, "desc": DESC}}
        assert reader.read(ctx, "flash", "dstpu_flash_fwd", "bench.step") \
            == pytest.approx(100.0)


def test_the_reader_reads_a_number_or_nothing_and_never_raises():
    """A parent's step records lack the counters, a trace without the kernels
    has no time to divide by, and another family's description has no head
    counts by type: no reading, no raise.  The recorded serving fixture (a
    one-layer dense engine, PR 25) ran the flash and the paged kernel: over
    it the shares read a number."""
    man = manifest_mod.Manifest()
    reader = man.module("readers", "typed_gqa_roofline")
    old = [{"decode_rows": 4, "chunks": 1, "chunk_tokens": 9,
            "recompute_tokens": 0, "decode_pages": 7}] * 3
    ctx = {"trace": _Trace(1.0, spans=3), "device": {"kind": "TPU v5 lite"},
           "result": {"steps": old, "desc": DESC}}
    for what in ("paged", "window", "flash"):
        assert reader.read(ctx, what, "dstpu_x", "bench.step") is None
    # MiMo-V2-Flash's description: one query head count, K/V heads by type
    ctx["result"] = {"steps": [_step([60, 40])] * 3,
                     "desc": {"num_attention_heads": 64, "kv_heads_full": 4,
                              "window_layers": [0, 1, 1, 1, 1, 1, 0]}}
    assert reader.read(ctx, "paged", "dstpu_x", "bench.step") is None
    ctx["result"]["desc"] = DESC
    ctx["trace"] = _Trace(0.0, spans=3)  # no such kernel in the trace
    assert reader.read(ctx, "window", "dstpu_x", "bench.step") is None
    recorded = trace_reduce.reduce_file(os.path.join(
        manifest_mod.HERE, "fixtures", "small_serve_v5e.xplane.pb"))
    steps = [_step([60, 9000], [(25, 50)], in_use=9104)] * 4
    ctx = {"trace": recorded, "device": {"kind": "TPU v5 lite"},
           "result": {"steps": steps, "desc": DESC}}
    spec = man.layer_metric("window_decode_roofline.typed")
    assert reader.read(ctx, **spec["args"]) is None
    for name in ("paged_decode_roofline.typed",
                 "flash_prefill_roofline.typed"):
        got = reader.read(ctx, **man.layer_metric(name)["args"])
        assert got is None or got > 0, name


def test_every_new_metric_names_the_cell_and_moves_tpot():
    man = manifest_mod.Manifest()
    listed = {m["name"]: m for m in man.per_layer(CELL)}
    for name in ("paged_decode_roofline.typed",
                 "window_decode_roofline.typed",
                 "flash_prefill_roofline.typed",
                 "page_tokens_in_use_p50.typed", "long_rows_p50.typed"):
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "tpot_p50_ms"
        assert listed[name]["layer"] == (
            "Serve kernels" if "roofline" in name else "Serve entry")
        assert man.layer_metric(name)["name"] == name
    # MiMo's two counters' readers under names of this cell's own: the
    # accepted entries list that cell alone (its test holds them to it)
    for name in ("page_tokens_in_use_p50", "long_rows_p50"):
        mine, theirs = (man.layer_metric(n) for n in (name + ".typed", name))
        assert {k: v for k, v in mine.items() if k != "name"} == {
            k: v for k, v in theirs.items() if k != "name"}
        assert name not in listed
    for name in ("compiles_in_window.steady", "decode_step_device_ms",
                 "paged_decode_ms_per_step", "window_decode_ms_per_step",
                 "moe_experts_ms_per_step", "moe_experts_roofline",
                 "moe_pad_share", "state_slots_in_use_p50",
                 "chunk_device_ms_per_ktok.steady",
                 "chunk_share_of_step.steady", "flash_prefill_ms_per_ktok",
                 "prefill_ctx_tokens_per_token", "peak_hbm_gb.steady",
                 "step_host_ms.steady", "idle_in_device_wait_ms.steady",
                 "idle_outside_device_wait_ms.steady", "setup_import_s",
                 "setup_engine_init_s", "setup_trace_s", "setup_lower_s",
                 "setup_compile_s", "setup_cache_load_s",
                 "setup_cache_misses", "setup_unnamed_s"):
        assert CELL in listed[name]["workloads"], name
    # the accepted shares that take one query head count for both types stay
    # off this cell, and the row kernels' time: a bfloat16 row of 3072 is 12
    # word-sublanes, no multiple of 8, so ``ops/pallas/moe_dispatch.py`` hands
    # the picks to XLA's scatter and gathers and the trace has no such kernel
    for name in ("moe_dispatch_ms_per_step", "paged_decode_roofline.hybrid",
                 "window_decode_roofline.hybrid",
                 "flash_prefill_roofline.hybrid", "paged_decode_roofline",
                 "window_decode_roofline", "mla_decode_roofline"):
        assert name not in listed
    assert [m["name"] for m in man.end_to_end(CELL)] == ["tpot_p50_ms",
                                                        "setup_s"]
    assert man.data["workloads"][-1]["name"] == CELL
