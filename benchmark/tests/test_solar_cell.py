"""What the Solar-Open2 cell added: the generator kind that takes the
reference from the configuration, the kernels' operation and byte counts, and
readers that read nothing (and do not raise) where the program has no such
counter."""

import json
import os
import subprocess
import sys

from benchmark import kernel_counts
from benchmark import manifest as manifest_mod

ROOT = os.path.dirname(manifest_mod.HERE)
CELL = "solaropen2-ep8-reason-steady"


def test_the_cell_rehearses_against_its_own_reference():
    p = subprocess.run(
        [sys.executable, os.path.join(manifest_mod.HERE, "rehearse.py"),
         "--workload", CELL, "--seed", "3000000001", "--seconds", "2",
         "--trace", "0"], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert "largest regret against the float32 reference 0.000e+00" in p.stdout


def test_the_configuration_keeps_the_published_widths():
    man = manifest_mod.Manifest()
    cfg = man.config(man.cell(CELL)["config"])
    assert cfg["reference"] == "kda_moe_lm"
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (4096, 64, 8, 128)
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert (cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["n_shared_experts"]) == (1280, 8, 1)
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 320,
                                "vocab_size": 196608}
    share = cfg["deployment_share"]
    assert share["n_routed_experts_published"] == 320
    assert cfg["n_routed_experts"] * share["chips_sharing_a_layer"] == 320
    assert cfg["vocab_size"] * share["chips_sharing_a_layer"] == 196608


def test_kernel_counts_are_the_least_that_moves():
    # 40 touched experts of 4096 x 1280 in bf16: three matrices each
    ops, nbytes = kernel_counts.expert_ffn_ops_bytes(128, 40, 4096, 1280)
    assert nbytes == (3 * 40 * 4096 * 1280 + 2 * 128 * 4096) * 2
    assert ops == 6 * 128 * 4096 * 1280
    # a row's state is 64 x 128 x 128 float32, read once and written once
    _, nbytes = kernel_counts.kda_step_ops_bytes(1, 64, 128, 128)
    assert 2 * 4 * 64 * 128 * 128 <= nbytes < 2.1 * 4 * 64 * 128 * 128
    _, one = kernel_counts.kda_chunk_ops_bytes(512, 1, 64, 128, 128)
    _, two = kernel_counts.kda_chunk_ops_bytes(512, 2, 64, 128, 128)
    assert two - one == 2 * 4 * 64 * 128 * 128


def test_paged_bytes_are_the_visible_pages_of_the_layers_that_keep_pages():
    # 100 pages of 16 tokens, 8 K/V heads of 128 in bf16: K and V once
    ops, nbytes = kernel_counts.paged_decode_ops_bytes(100, 16, 64, 8, 128)
    assert nbytes == 2 * 100 * 16 * 8 * 128 * 2
    assert ops == 4 * 100 * 16 * 64 * 128
    man = manifest_mod.Manifest()
    steps = [{"decode_rows": 2, "decode_pages": 50}] * 2
    desc = {"period": ["gqa", "kda", "kda", "kda"], "num_attention_heads": 64,
            "num_key_value_heads": 8, "head_dim": 128}
    ctx = {"trace": _NoTrace(), "device": {"kind": "TPU v5 lite"},
           "result": {"steps": steps, "desc": desc, "n_layers": 4,
                      "engine_config": {"page_size": 16}}}
    # one of the four layers keeps pages: 100 pages' bytes over 1 s
    assert man.module("readers", "kernel_roofline").read(
        ctx, "paged", "dstpu_paged_decode", "bench.step") == \
        100.0 * (nbytes / 819e9) / 1.0


def test_state_readings_tell_a_bfloat16_state_from_a_float32_one():
    import ml_dtypes
    import numpy as np

    gen = manifest_mod.Manifest().module("generators", "serve_requests_ref")
    rng = np.random.default_rng(0)
    ref = [rng.normal(size=(4, 16, 8)).astype(np.float32) for _ in range(3)]
    kept = np.stack([r.transpose(0, 2, 1) for r in ref])   # S^T, as kept
    exact = gen.state_readings(kept, ref)
    assert exact["state_error"] == 0.0 and exact["state_bf16_share"] < 0.01
    rounded = kept.astype(ml_dtypes.bfloat16).astype(np.float32)
    low = gen.state_readings(rounded, ref)
    assert low["state_bf16_share"] == 1.0
    assert 1e-4 < low["state_error"] < 1e-2
    kept[1] = 0.0                                           # a lost layer
    assert gen.state_readings(kept, ref)["state_error"] == 1.0


class _NoTrace:
    def span_list(self, _name):
        return [object()] * 3

    def op_seconds(self, _match):
        return 1.0

    def devices(self):
        return ["d0"]


def test_readers_read_nothing_where_the_program_counts_nothing():
    """A parent's step records lack the new keys: no reading, no raise."""
    man = manifest_mod.Manifest()
    steps = [{"decode_rows": 4, "chunks": 1, "chunk_tokens": 9,
              "recompute_tokens": 0}] * 3
    ctx = {"trace": _NoTrace(), "result": {"steps": steps, "desc": {}},
           "device": {"kind": "TPU v5 lite"}}
    assert man.module("readers", "kernel_roofline").read(
        ctx, "experts", "dstpu_grouped_matmul", "bench.step") is None
    assert man.module("readers", "step_ratio").read(
        ctx, "bench.step", "moe_padded_rows", "moe_local_picks") is None
    assert man.module("readers", "step_percentile").read(
        ctx, "bench.step", "state_slots_in_use", 50) is None
    with_keys = [dict(s, moe_padded_rows=256, moe_local_picks=16,
                      state_slots_in_use=i) for i, s in enumerate(steps)]
    ctx["result"]["steps"] = with_keys
    assert man.module("readers", "step_ratio").read(
        ctx, "bench.step", "moe_padded_rows", "moe_local_picks") == 16.0
    assert man.module("readers", "step_percentile").read(
        ctx, "bench.step", "state_slots_in_use", 50) == 1.0
    assert man.module("readers", "op_ms_per_unit").read(
        ctx, ["dstpu_kda_chunk"], "bench.step",
        ["chunk_tokens", "recompute_tokens"], 0.001) == 1e3 / 0.027
