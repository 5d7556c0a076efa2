"""What the LFM2-MoE training cell added: the generator kind that takes the
reference, the check of gradients and picks and the operations per token from
the configuration's own modules; the counts of a trained expert share; readers
that read nothing (and do not raise) where the program counts nothing, and a
roofline share that cannot pass 100 %."""

import itertools
import json
import os
import subprocess
import sys

import numpy as np

from benchmark import manifest as manifest_mod
from benchmark import moe_train_counts, roofline

ROOT = os.path.dirname(manifest_mod.HERE)
CELL = "lfm2-ep4-pretrain-8k"
PEAK = roofline.peaks("TPU v5 lite")


def _rehearse(*more):
    p = subprocess.run(
        [sys.executable, os.path.join(manifest_mod.HERE, "rehearse.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "0", *more], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_cell_rehearses_against_its_own_reference():
    line, said = _rehearse()
    assert line["correct"] and line["failed"] == 0
    assert line["counts"]["moe_held_picks"] > 0
    for words in ("first-step loss", "gradient norm", "turned sign",
                  "picks on held experts", "window's held picks",
                  "buffers moved []", "param_change", "held_picks_drift"):
        assert words in said
    assert "NEGATIVE CONTROL" not in said


def test_the_negative_control_comes_out_not_correct(tmp_path):
    """The reference at a float8 mantissa in the program's place, under the
    limits the program has just passed: ``correct`` is false, by the
    gradient's leaf-by-leaf error and not by the loss or the norm."""
    man = manifest_mod.Manifest()
    traffic = man.traffic(man.cell(CELL)["traffic"])
    assert "negative_control" not in traffic  # no committed file has it
    traffic["negative_control"] = {"mantissa_bits": 3}
    os.makedirs(tmp_path / "benchmark" / "traffic")
    with open(tmp_path / "benchmark" / "traffic"
              / (man.cell(CELL)["traffic"] + ".json"), "w") as f:
        json.dump(traffic, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(man.data, f)
    line, said = _rehearse("--manifest", str(tmp_path / "BENCHMARK.json"))
    assert line["correct"] is False and line["failed"] == 0
    refused = said.split("NEGATIVE CONTROL")[1].split("refused by ")[1]
    refused = refused.splitlines()[0].split(", ")
    assert "grad_tree" in refused
    assert "loss" not in refused and "grad_norm" not in refused


def _generator():
    return manifest_mod.Manifest().module("generators", "train_steps_ref")


def test_plain_adamw_is_optaxs_first_step():
    import jax.numpy as jnp
    import optax

    gen = _generator()
    rng = np.random.default_rng(0)
    theta = rng.standard_normal((64, 8)).astype(np.float32) * 0.02
    g = rng.standard_normal((64, 8)).astype(np.float32) * 0.1
    norm = float(np.linalg.norm(g))
    cfg = {"gradient_clipping": 1.0,
           "optimizer": {"type": "AdamW",
                         "params": {"lr": 3e-4, "weight_decay": 0.01}},
           "scheduler": {"type": "WarmupLR", "params": {
               "warmup_min_lr": 2e-5, "warmup_max_lr": 3e-4,
               "warmup_num_steps": 100, "warmup_type": "linear"}}}
    assert gen.first_step_rate(cfg) == 2e-5
    tx = optax.adamw(2e-5, weight_decay=0.01)
    want, _ = tx.update(jnp.asarray(g / (norm + 1e-6)),
                        tx.init(jnp.asarray(theta)), jnp.asarray(theta))
    got = gen.adamw_first_step(g, theta, norm, cfg)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=1e-12)


def test_an_unchanged_state_reads_one_and_a_moved_buffer_is_named():
    gen = _generator()
    rng = np.random.default_rng(1)
    g = rng.standard_normal(1000).astype(np.float32)
    turned = g.copy()
    turned[:10] *= -1
    plain = -1e-3 * np.sign(g).astype(np.float32)
    zero = np.zeros_like(g)
    # nothing moved: the change reads 1 whatever the gradient
    r = gen.step_readings(2, [("w", False, g, g, zero, plain),
                              ("bias", True, zero, zero, zero, plain)
                              ].__getitem__)
    assert r["param_change"] == 1.0 and r["grad_tree"] == 0.0
    assert r["buffers_moved"] == [] and r["signs_turned"] == 0.0
    # ten signs of a thousand turned: the change reads 2 x root(1 %)
    r = gen.step_readings(2, [
        ("w", False, turned, g, -1e-3 * np.sign(turned).astype(np.float32),
         plain),
        ("bias", True, zero, zero, plain, plain)].__getitem__)
    assert abs(r["param_change"] - 0.2) < 1e-6
    assert r["signs_turned"] == 0.01 and r["buffers_moved"] == ["bias"]
    assert r["grad_leaf"] == r["grad_tree"] > 0
    assert r["worst_leaves"][0][1] == "w" and r["param_change_own"] is None
    # given the parameter: the change against plain AdamW with the program's
    # own gradient, which turned signs do not move
    cfg = {"optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}}
    own = gen.adamw_first_step(turned, zero, 1.0, cfg)
    r = gen.step_readings(1, [("w", False, turned, g, own, plain, zero)
                              ].__getitem__, cfg, 1.0)
    assert r["param_change_own"] == 0.0 and r["param_change"] > 0.19


def test_the_configuration_keeps_the_published_widths():
    man = manifest_mod.Manifest()
    cfg = man.config(man.cell(CELL)["config"])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = [json.loads(x) for x in f if '"LFM2-8B-A1B"' in x][0]
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
        "layer_types"}
    assert cfg["published"] == {k: row["config"][k] for k in changed}
    share = cfg["deployment_share"]
    assert cfg["num_experts"] * share["chips_sharing_a_layer"] == 32
    assert cfg["vocab_size"] * share["chips_sharing_a_layer"] == 65536
    # one leading dense layer and the four that follow the dense ones
    assert cfg["layer_types"] == row["config"]["layer_types"][1:6]
    assert cfg["reference"] == "lfm2_moe_lm"


def _desc(tiny=False):
    man = manifest_mod.Manifest()
    cfg = man.config(man.cell(CELL)["config"])
    if tiny:
        cfg.update(cfg["tiny"])
    return man.module("families", cfg["family"]).describe(cfg)


def test_flops_per_token_equal_a_hand_count():
    # tiny sizes: hidden 64, heads 4 x 16 and 2 K/V heads, dense 128,
    # experts of 32 (8 routed), vocabulary 256, one attention layer of five
    d = _desc(tiny=True)
    conv, attn = 4 * 64 * 64, 64 * 16 * (4 + 2 * 2) + 4 * 16 * 64
    matmul = (4 * conv + attn + 3 * 64 * 128 + 4 * 64 * 8 + 64 * 256
              + 1.5 * 3 * 64 * 32)
    scores = 2 * 2 * (32 / 2) * 4 * 16
    assert moe_train_counts.train_flops_per_token(d, 32, 1.5) == \
        3.0 * (2.0 * matmul + scores)
    # the published widths, one pick a token in each of the four expert
    # layers: ISSUE 32's count, 433 MFLOP forward
    full = moe_train_counts.train_flops_per_token(_desc(), 8192, 4.0)
    assert abs(full / 3.0 - 432.5e6) < 1e6
    assert abs(moe_train_counts.param_count(_desc()) - 507.8e6) < 0.1e6


def test_the_expert_roofline_cannot_pass_100_percent():
    """For any counter values the program can produce the least time is no
    more than the time of the operations at the chip's peak, which no kernel
    that does them can beat; and the least bytes are no more than what the
    kernels must move for one call a layer."""
    h, w = 2048, 1792
    for layers, held, picks in itertools.product(
            (1, 4), (1, 8), (0, 1, 7, 1024, 32768)):
        table = [[picks] + [0] * (held - 1)] * layers
        ops, nbytes = moe_train_counts.expert_train_ops_bytes(table, h, w)
        total = layers * picks
        assert ops == 18.0 * total * h * w
        touched = layers * (picks > 0)
        assert nbytes <= (9 * touched * h * w + 6 * total * (h + w)) * 2
        least, _ = roofline.roofline_seconds(ops, nbytes, PEAK)
        # what the kernels really do for these picks, each expert's rows
        # padded to a block of 128, at the peaks
        rows = layers * -(-picks // 128) * 128
        real = max(18.0 * rows * h * w / PEAK["bf16_flops_per_s"],
                   (9 * touched * h * w + 6 * rows * (h + w)) * 2
                   / PEAK["hbm_bytes_per_s"])
        assert least <= real * (1 + 1e-12)


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def span_list(self, _name):
        return [object()] * 2

    def op_seconds(self, _match):
        return self.seconds

    def devices(self):
        return ["d0"]


def test_new_readers_read_a_number_or_nothing_and_never_raise():
    man = manifest_mod.Manifest()
    d = _desc()
    picks = [[1024] * 8] * 4
    result = {"desc": d, "rows": 1, "gas": 4, "seq": 8192, "n_layers": 5,
              "moe": {"picks": picks}, "moe_rows_run": 9000.0,
              "moe_held_picks": 8192.0, "moe_load_max_over_mean": 1.25}
    ctx = {"trace": _Trace(1.0), "result": result, "chips": 1,
           "device": {"kind": "TPU v5 lite"}}
    specs = {n: man.layer_metric(n) for n in (
        "moe_train_experts_ms_per_step", "moe_train_experts_roofline",
        "moe_train_pad_share", "moe_load_max_over_mean",
        "flash_train_roofline.period")}

    def read(name, ctx):
        spec = specs[name]
        return man.module("readers", spec["reader"]).read(
            ctx, **spec.get("args", {}))

    assert read("moe_train_experts_ms_per_step", ctx) == 500.0
    ops, nbytes = moe_train_counts.expert_train_ops_bytes(picks, 2048, 1792)
    assert abs(read("moe_train_experts_roofline", ctx)
               - 100.0 * ops / PEAK["bf16_flops_per_s"]) < 1e-9
    assert read("moe_train_pad_share", ctx) == 9000.0 / 8192.0
    assert read("moe_load_max_over_mean", ctx) == 1.25
    # one attention layer of five: 2 steps x 4 micro-batches x 1 layer
    least = sum(roofline.roofline_seconds(
        *roofline.flash_ops_bytes(k, 1, 32, 8, 8192, 64), PEAK)[0]
        for k in ("fwd", "bwd_dq", "bwd_dkv"))
    assert abs(read("flash_train_roofline.period", ctx)
               - 100.0 * 8 * least / 3.0) < 1e-9
    # a program without the counters or the kernels (a parent commit, a
    # dense cell): nothing, and no raise
    bare = {"trace": _Trace(0.0), "chips": 1,
            "device": {"kind": "TPU v5 lite"},
            "result": {"desc": {"hidden_size": 64}, "rows": 1, "gas": 1,
                       "seq": 32, "n_layers": 2}}
    for name in specs:
        assert read(name, bare) is None, name
