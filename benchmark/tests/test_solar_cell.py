"""What the Solar-Open2 cell added: the generator kind that takes the
reference from the configuration, the kernels' operation and byte counts, and
readers that read nothing (and do not raise) where the program has no such
counter.  Since PR 50 the cell is ``solaropen2-ep8-reason-saturated``: the
same configuration, lengths, check and limits under a trace at twice what
the engine completes; the control and a planted fault run here through
``run.py``'s own path at the tiny sizes and read ``correct: false``."""

import json
import os
import subprocess
import sys

import numpy as np

from benchmark import kernel_counts
from benchmark import manifest as manifest_mod

ROOT = os.path.dirname(manifest_mod.HERE)
CELL = "solaropen2-ep8-reason-saturated"
ARGS = ["--workload", CELL, "--seed", "3000000001", "--seconds", "2",
        "--trace", "0"]


def test_the_cell_rehearses_against_its_own_reference():
    p = subprocess.run(
        [sys.executable, os.path.join(manifest_mod.HERE, "rehearse.py"),
         *ARGS], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert line["counts"]["preempted"] == 0
    assert "largest regret against the float32 reference 0.000e+00" in p.stdout


def _run_here(capsys, monkeypatch, on_generator=None):
    """``run.main`` at the tiny sizes in this process — everything of a run
    but the look for a chip — and its last line.  ``on_generator(module)``
    sees the cell's generator module as the run loads it."""
    from benchmark import run

    load = manifest_mod.Manifest.module

    def module(self, sub, name):
        mod = load(self, sub, name)
        if on_generator and (sub, name) == ("generators",
                                            "serve_requests_ref"):
            on_generator(mod)
        return mod

    monkeypatch.setattr(manifest_mod.Manifest, "module", module)
    assert run.main(ARGS, rehearse=True) == 0
    said = capsys.readouterr().out
    return json.loads(said.strip().splitlines()[-1]), said


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    """The timed path broken underneath: the first token ``step()`` returns
    for a request — what its prompt's last chunk samples — comes back with
    its lowest bit flipped; every other token is the program's."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    step = InferenceEngineV2.step
    seen = set()

    def altered(self):
        out = step(self)
        for uid, o in out.items():
            if o["tokens"] and uid not in seen:
                seen.add(uid)
                o["tokens"] = [o["tokens"][0] ^ 1] + list(o["tokens"][1:])
        return out

    monkeypatch.setattr(InferenceEngineV2, "step", altered)
    line, said = _run_here(capsys, monkeypatch)
    assert line["correct"] is False
    assert "largest regret against the float32 reference 0.000e+00" not in said


def _float8_reference_in_the_programs_place(gen):
    """The control: the reference with its weights rounded to float8_e4m3's
    mantissa decodes the check's prompts greedily — its own tokens, as the
    program's are its own — and its tokens and states are read against the
    float32 reference under the limits the program has just passed."""
    import jax.numpy as jnp

    check = gen.check_against_reference

    def control(reference, ctx, engine, desc, vocab):
        chk = check(reference, ctx, engine, desc, vocab)
        rng = np.random.default_rng(ctx.seed + 1)
        want = int(ctx.traffic["check_decode_steps"]) + 1
        low = dict(weights_dtype=jnp.float8_e4m3fn)
        regrets, state = [], {"state_error": 0.0, "state_bf16_share": 0.0}
        for n in ctx.traffic["check_prompt_tokens"]:
            ids = rng.integers(0, vocab, int(n), dtype=np.int64).tolist()
            toks = []
            for _ in range(want):
                logits, states = reference.forward(desc, engine.params,
                                                   ids + toks, **low)
                toks.append(int(np.argmax(np.asarray(logits)[-1])))
            ref, ref_states = reference.forward(desc, engine.params,
                                                ids + toks[:-1])
            for row, tok in zip(np.asarray(ref)[len(ids) - 1:], toks):
                regrets.append(float(row.max() - row[tok])
                               / float(np.abs(row).max()))
            kept = np.stack([np.asarray(s).transpose(0, 2, 1)
                             for s in states])
            for k, v in gen.state_readings(kept, ref_states).items():
                state[k] = max(state[k], v)
        chk.update(regrets=regrets, max_regret=max(regrets), **state)
        return chk

    gen.check_against_reference = control


def test_the_float8_reference_in_the_programs_place_is_not_correct(
        capsys, monkeypatch):
    line, said = _run_here(capsys, monkeypatch,
                           _float8_reference_in_the_programs_place)
    assert line["correct"] is False and line["failed"] == 0
    state = said.split("recurrent state of the check requests")[1]
    error = float(state.split("state_error ")[1].split()[0])
    assert error > 1e-2        # the tiny limit is 1e-4: the program 1e-6


def test_the_new_traffic_keeps_the_lengths_the_check_and_the_limits():
    """PR 50 changed the arrival process; lengths, check and limits are what
    the retired ``reason-steady.json`` had, key by key, but the limit on the
    largest regret, set anew from its two readings (0.075 there)."""
    man = manifest_mod.Manifest()
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b-ep8-serve", "reason-saturated", 1)
    tr = man.traffic(cell["traffic"])
    assert tr["kind"] == "serve_requests_ref"
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 768,
                                   "sigma": 0.7, "min": 64, "max": 4096}
    assert tr["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                   "sigma": 0.5, "min": 256, "max": 3072}
    assert (tr["balance_group"], tr["schedule_seed"], tr["tpot_min_gaps"],
            tr["trace_seconds"]) == (8, 0, 16, 20)
    assert (tr["check_prompt_tokens"], tr["check_decode_steps"]) == (
        [320, 384, 640], 16)
    assert (tr["regret_tolerance"], tr["state_error_tolerance"],
            tr["state_bf16_share_tolerance"]) == (0.1, 0.15, 0.01)
    assert tr["reports"] == {"tpot_p50_ms": "tpot_p50_ms",
                             "setup_s": "setup_s"}
    # over capacity: a trace, no TTFT sample, a pre-roll of 40 s at most
    arr = tr["arrivals"]
    assert arr["process"] == "trace" and tr["ttft_share"] == 0
    assert 25 <= arr["preroll_s"] <= 40
    # the worst case fits the pool: no preemption by construction
    cfg = man.config(cell["config"])["engine"]
    longest = tr["prompt_tokens"]["max"] + tr["output_tokens"]["max"]
    assert cfg["max_seqs"] * longest <= cfg["num_pages"] * cfg["page_size"]
    assert not man.find("traffic", "reason-steady.json")
    assert not any(w["name"] == "solaropen2-ep8-reason-steady"
                   for w in man.data["workloads"])


def test_the_cell_is_on_every_list_the_retired_cell_was_on():
    man = manifest_mod.Manifest()
    listed = {m["name"] for m in man.per_layer(CELL)}
    for name in ("compiles_in_window.steady", "decode_step_device_ms",
                 "paged_decode_ms_per_step", "peak_hbm_gb.steady",
                 "step_host_ms.steady", "idle_in_device_wait_ms.steady",
                 "idle_outside_device_wait_ms.steady",
                 "moe_experts_ms_per_step", "moe_experts_roofline",
                 "kda_step_ms_per_step", "kda_step_roofline",
                 "kda_chunk_ms_per_ktok", "kda_chunk_roofline",
                 "moe_pad_share", "state_slots_in_use_p50",
                 "paged_decode_roofline.period", "moe_dispatch_ms_per_step",
                 "setup_import_s", "setup_engine_init_s", "setup_trace_s",
                 "setup_lower_s", "setup_compile_s", "setup_cache_load_s",
                 "setup_cache_misses", "setup_unnamed_s",
                 # gained: chunk calls now ride the steps of this cell too
                 "chunk_share_of_step.steady",
                 "chunk_device_ms_per_ktok.steady"):
        assert name in listed, name
    # metrics of an end-to-end metric this cell does not report stay off it
    for name in ("queue_wait_p50_ms", "ttft_p50_ms.steady",
                 "ttft_p90_ms.steady"):
        assert name not in listed
    assert [m["name"] for m in man.end_to_end(CELL)] == ["tpot_p50_ms",
                                                        "setup_s"]


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
