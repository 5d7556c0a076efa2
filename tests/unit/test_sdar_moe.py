"""SDAR-MoE on the serving path: generation by diffusion over blocks — a
decode pass that takes a block of 4 positions a row, reveals 0 ... 4 of them
and commits a block only when none is masked — over a Qwen3-MoE block under a
mask causal between blocks and bidirectional inside one.

Oracle: ``benchmark/reference/block_diffusion_moe_lm.py`` (plain float32, one
whole sequence at every position, no cache, no pass that reuses another's K/V,
no code shared with the program): its generation loop for the tokens, its
forward replayed from the engine's own block states for the logits, its keys
and values for what the chunk program and the commit passes left in the
pages.  The tiny prompts have every remainder modulo the block length, the
tiny chunk (16) is two pages (8) and four blocks, and one prompt is longer
than it.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.families import sdar_moe as family  # noqa: E402
from benchmark.reference import block_diffusion_moe_lm as ref  # noqa: E402
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.inference.v2 import block_diffusion, model_runner  # noqa: E402
from deepspeed_tpu.inference.v2.speculative import SpeculativeConfig  # noqa: E402
from deepspeed_tpu.models import (mimo_v2_model, mistral_model,  # noqa: E402
                                  sdar_moe_config, sdar_moe_model)
from deepspeed_tpu.ops.pallas import flash_attention as flash_mod  # noqa: E402
from deepspeed_tpu.ops.pallas import paged_attention  # noqa: E402
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402
from deepspeed_tpu.telemetry.spans import get_span_recorder  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "sdar-30b-a3b-pp8-serve.json")) as _f:
    CONFIG = json.load(_f)
TINY = dict(CONFIG, **CONFIG["tiny"])
DESC = family.describe(TINY)
ENGINE = CONFIG["tiny_engine"]
B, MASK = DESC["block_length"], DESC["mask_token_id"]
LAYERS = TINY["num_hidden_layers"]


def _model():
    return family.build(TINY, LAYERS, ENGINE["page_size"]
                        * ENGINE["max_pages_per_seq"], jnp.float32)


def _params(model, seed=3):
    """Seeded weights with the attention's and the experts' outputs scaled up
    to the embedding's size, so that what a position sees decides its logits
    (at the initialiser's 0.02 the token's own embedding does)."""
    p = model.init_params(jax.random.PRNGKey(seed))
    p["layers"]["attn"]["wo"] = p["layers"]["attn"]["wo"] * 40
    p["layers"]["mlp"]["w_down"] = p["layers"]["mlp"]["w_down"] * 40
    return p


def _engine(**overrides):
    model = _model()
    return InferenceEngineV2(
        model, RaggedInferenceConfig(**dict(ENGINE, **overrides)),
        params=_params(model), seed=0)


def _prompt(n, seed=0):
    return np.random.default_rng(seed + n).integers(0, MASK, n).tolist()


def _serve(eng, requests):
    """-> {uid: (tokens, the sizes of its deliveries)} through put / step."""
    uids = [eng.put(r) for r in requests]
    got = {u: ([], []) for u in uids}
    for _ in range(10_000):
        if not eng.has_work():
            break
        for uid, rec in eng.step().items():
            got[uid][0].extend(rec["tokens"])
            if rec["tokens"]:
                got[uid][1].append(len(rec["tokens"]))
    assert not eng.has_work()
    return [got[u] for u in uids]


# ------------------------------------------- the served path and the reference
@pytest.mark.parametrize("length,new,steps", [
    (8, 8, 4),     # remainder 0: the first block is all masks
    (9, 9, 2),     # remainder 1
    (22, 7, 4),    # remainder 2, a chunk and a part
    (43, 12, 2),   # remainder 3, longer than two chunks
    (3, 5, 1),     # shorter than a block: no prefill at all
    (45, 10, 4),   # remainder 1, three chunks, a last block cut by the length
])
def test_tokens_passes_and_cache_agree_with_the_reference(length, new, steps):
    eng = _engine()
    eng.blocks.passes = {}
    prompt = _prompt(length)
    # one block more than is read back, so the pages are still the request's
    extra = RaggedRequest(prompt_ids=prompt, max_new_tokens=new + 2 * B,
                          denoising_steps=steps)
    uid = eng.put(extra)
    toks, kept = [], None
    whole = (length + new) // B * B  # the blocks committed by then
    while eng.has_work() and kept is None:
        for u, rec in eng.step().items():
            toks += rec["tokens"]
            if length + len(toks) >= whole + B:
                kept = eng.read_kv(uid)
                final = (prompt + toks)[:len(kept[0]["k"])]
                eng.release_sequence(uid, "checked")
    want, ref_passes = ref.generate(DESC, eng.params, prompt, new, steps)
    assert toks[:new] == want
    # every denoising pass, replayed from the engine's own block state: the
    # logits at the masked positions give the engine's token no regret, and
    # the engine revealed the reference's own choice of positions
    mine = [p for p in eng.blocks.passes[uid] if p["masked"].any()]
    assert len(mine) >= len(ref_passes)
    for p in mine[:len(ref_passes)]:
        start = int(p["start"])
        fed = final[:start] + [int(t) for t in p["ids"]]
        if start + B > len(final):
            continue
        logits, _ = ref.forward(DESC, eng.params, fed, range(start, start + B))
        ids, masked = ref.reveal(logits, p["ids"], p["masked"], B // steps)
        assert masked.tolist() == p["masked_after"].tolist()
        assert ids.tolist() == p["ids_after"].tolist()
    # what the chunk program and the commit passes left in the pages
    _, kv = ref.forward(DESC, eng.params, final)
    assert len(kept) == LAYERS and len(final) % B == 0
    for got, (k, v) in zip(kept, kv):
        np.testing.assert_allclose(got["k"], np.asarray(k), atol=2e-5, rtol=0)
        np.testing.assert_allclose(got["v"], np.asarray(v), atol=2e-5, rtol=0)


@pytest.mark.parametrize("new", [1, 4, 6, 8, 13])
def test_deliveries_sum_to_the_length_asked_for(new):
    eng = _engine()
    (toks, sizes), = _serve(eng, [RaggedRequest(
        prompt_ids=_prompt(10), max_new_tokens=new, denoising_steps=2)])
    assert len(toks) == new == sum(sizes)
    # the first block holds 2 prompt tokens; every delivery is a block's
    # worth at most, and only the last may be cut by the length
    assert sizes[0] == min(2, new) and all(s <= B for s in sizes)
    assert all(0 <= t < DESC["vocab_size"] and t != MASK for t in toks)
    eng.assert_no_leaks()


def test_both_tiers_in_one_batch_and_a_request_finishing_mid_batch():
    eng = _engine()
    spec = [(9, 16, 4), (22, 4, 2), (7, 12, 2), (40, 8, 4)]
    reqs = [RaggedRequest(prompt_ids=_prompt(n), max_new_tokens=w,
                          denoising_steps=s) for n, w, s in spec]
    served = _serve(eng, reqs)
    for (n, w, s), (toks, sizes) in zip(spec, served):
        want, _ = ref.generate(DESC, eng.params, _prompt(n), w, s)
        assert toks == want and sum(sizes) == w
    st = eng.decode_stats()
    # rows commit out of step with each other: 5 and 3 passes a whole block
    assert st["tokens_committed"] == sum(w for _, w, _ in spec)
    assert st["row_passes"] > st["block_passes"] > st["commit_row_passes"] > 0
    assert st["tokens_revealed"] >= st["tokens_committed"]
    eng.assert_no_leaks()


def test_a_preempted_row_redoes_its_block_and_ends_with_the_same_tokens():
    spec = [(30, 24, 4), (27, 24, 2), (21, 24, 4)]
    reqs = lambda: [RaggedRequest(prompt_ids=_prompt(n), max_new_tokens=w,  # noqa: E731
                                  denoising_steps=s) for n, w, s in spec]
    roomy = _serve(_engine(), reqs())
    # 14 pages of 8 hold 112 positions; the three contexts grow to 147
    tight = _engine(num_pages=14, max_pages_per_seq=14)
    served = _serve(tight, reqs())
    st = tight.decode_stats()
    assert st["blocks_dropped"] >= 1
    assert [t for t, _ in served] == [t for t, _ in roomy]
    tight.assert_no_leaks()


def test_the_kernels_serve_the_block_program_and_the_chunk_program(monkeypatch):
    """Pallas in interpret mode: the paged decode kernel with the block folded
    into its head axis, the flash kernel under the block mask."""
    spec = [(22, 7, 4), (43, 6, 2)]
    reqs = lambda: [RaggedRequest(prompt_ids=_prompt(n), max_new_tokens=w,  # noqa: E731
                                  denoising_steps=s) for n, w, s in spec]
    plain = _serve(_engine(), reqs())
    monkeypatch.setenv("DSTPU_PAGED_KERNEL", "1")
    seen = []
    paged, flash = (paged_attention.paged_decode_attention,
                    flash_mod.flash_attention)
    monkeypatch.setattr(
        paged_attention, "paged_decode_attention",
        lambda q, *a, **kw: seen.append(("paged", q.shape)) or paged(
            q, *a, **kw))
    monkeypatch.setattr(
        flash_mod, "flash_attention",
        lambda *a, **kw: seen.append(("flash", kw.get("block"))) or flash(
            *a, **kw))
    assert [t for t, _ in _serve(_engine(), reqs())] == [t for t, _ in plain]
    # 4 rows of B x NH = 16 query rows, B x G = 8 of them a K/V head
    assert ("paged", (4, B * DESC["num_attention_heads"],
                      DESC["head_dim"])) in seen
    assert ("flash", B) in seen


# ------------------------------------------------------- the two planted faults
def test_a_commit_that_is_never_written_is_told_from_the_cache(monkeypatch):
    """The last denoising pass's K/V left standing in place of the commit
    pass's: the tokens of the first block are what they were, the rows of the
    positions revealed last are the mask token's."""
    block_pass = block_diffusion.paged_block_pass
    monkeypatch.setattr(
        block_diffusion, "paged_block_pass",
        lambda cfg, params, pools, ids, masked, start, table, active, n:
        block_pass(cfg, params, pools, ids, masked, start, table,
                   active & masked.any(axis=1), n))
    eng = _engine()
    prompt = _prompt(9)
    uid = eng.put(RaggedRequest(prompt_ids=prompt, max_new_tokens=3 + 2 * B,
                                denoising_steps=4))
    toks, kept = [], None
    while eng.has_work() and kept is None:
        for _, rec in eng.step().items():
            toks += rec["tokens"]
            if len(toks) >= 3 + B:
                kept = eng.read_kv(uid)
                eng.release_sequence(uid, "checked")
    final = (prompt + toks)[:len(kept[0]["k"])]
    _, kv = ref.forward(DESC, eng.params, final)
    err = max(np.linalg.norm(kept[l]["k"][8:] - np.asarray(kv[l][0])[8:])
              / np.linalg.norm(np.asarray(kv[l][0])[8:])
              for l in range(LAYERS))
    assert err > 0.05
    # the prompt's whole blocks, written by the chunk program, are sound
    np.testing.assert_allclose(kept[0]["k"][:8], np.asarray(kv[0][0])[:8],
                               atol=2e-5, rtol=0)


def test_a_causal_mask_inside_the_block_is_told_from_the_block_mask():
    eng = _engine()
    ids = _prompt(13) + [MASK] * 3
    block, _ = ref.forward(DESC, eng.params, ids, range(12, 16))
    causal, kv = ref.forward(DESC, eng.params, ids, range(12, 16),
                             mask="causal")
    # the block's first position sees the three after it under one mask alone
    assert float(jnp.abs(block[0] - causal[0]).max()) > 1e-3
    # ... and the first layer's keys, which no attention precedes, are the same
    np.testing.assert_allclose(
        kv[0][0], ref.forward(DESC, eng.params, ids)[1][0][0], atol=1e-6)
    with pytest.raises(ValueError, match="unknown mask control"):
        ref.forward(DESC, eng.params, ids, [0], mask="window")


# ------------------------------------------------------------ kernels and rules
@pytest.mark.parametrize("offset", [0, 8, 20])
def test_flash_under_the_block_mask_is_the_dense_softmax(offset):
    rng = np.random.default_rng(offset)
    C, S, NH, KVH, D = 16, 48, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(1, C, NH, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, S, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, S, KVH, D)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, q_offset=offset, block=B,
                          block_q=8, block_k=8)
    rows = offset + np.arange(C)[:, None]
    vis = (np.arange(S)[None] // B) <= (rows // B)
    kk, vv = (jnp.repeat(a, NH // KVH, axis=2) for a in (k, v))
    sc = jnp.einsum("btnd,bsnd->bnts", q, kk) / np.sqrt(D)
    pr = jax.nn.softmax(jnp.where(vis[None, None], sc, -1e30), axis=-1)
    np.testing.assert_allclose(got, jnp.einsum("bnts,bsnd->btnd", pr, vv),
                               atol=2e-5, rtol=0)
    with pytest.raises(ValueError, match="block mask"):
        flash_attention(q, k, v, causal=True, block=B)      # no q_offset
    with pytest.raises(ValueError, match="block mask"):
        flash_attention(q, k, v, causal=True, q_offset=0, block=3)


def test_the_reveal_rule_on_the_device_is_the_references():
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(6, B, 32)).astype(np.float32)
    logits[2, 1] = logits[2, 3]           # a tie: the lower position first
    ids = rng.integers(0, 31, (6, B)).astype(np.int32)
    masked = rng.random((6, B)) < 0.6
    masked[0] = False                     # a commit: nothing to reveal
    masked[2] = True
    n = np.asarray([1, 1, 2, 4, 2, 1], np.int32)
    got_ids, got_masked = model_runner.reveal_tokens(
        jnp.asarray(logits), jnp.asarray(ids), jnp.asarray(masked),
        jnp.asarray(n))
    for r in range(6):
        want_ids, want_masked = ref.reveal(logits[r], ids[r], masked[r], n[r])
        assert np.asarray(got_masked[r]).tolist() == want_masked.tolist()
        assert np.asarray(got_ids[r]).tolist() == want_ids.tolist()


# ----------------------------------------------------------- refused, by name
@pytest.mark.parametrize("override,match", [
    ({"speculative": SpeculativeConfig(mode="ngram", k=2)},
     "speculative decoding: this model generates by diffusion over blocks"),
    ({"decode_horizon": 4}, "decode_horizon 4: this model generates"),
    ({"kv_quant": True}, "kv_quant: this model generates"),
    ({"enable_prefix_cache": True}, "enable_prefix_cache: this model"),
    ({"prefill_chunk": 0}, "prefill_chunk 0: this model generates"),
    ({"page_size": 6, "prefill_chunk": 12}, "never straddles a page of 6"),
])
def test_what_cannot_work_with_blocks_is_refused_at_construction(override,
                                                                 match):
    with pytest.raises(ValueError, match=match):
        _engine(**override)


@pytest.mark.parametrize("request_kw,match", [
    ({"temperature": 0.7}, "temperature > 0: the reveal rule is greedy"),
    ({"eos_id": 2}, "eos_id: max_new_tokens is the fixed generation length"),
    ({"denoising_steps": 3}, "denoising_steps 3: .* it divides 4"),
    ({"max_new_tokens": 400}, "in whole blocks of 4, is .* > max_seq_len"),
])
def test_what_a_request_cannot_ask_of_blocks_is_refused_at_put(request_kw,
                                                               match):
    eng = _engine()
    with pytest.raises(ValueError, match=match):
        eng.put(RaggedRequest(**dict({"prompt_ids": _prompt(9),
                                      "max_new_tokens": 8}, **request_kw)))
    assert not eng.has_work()


def test_other_models_refuse_denoising_steps_and_training_is_refused_by_name():
    eng = InferenceEngineV2(mistral_model("tiny", max_seq_len=64),
                            RaggedInferenceConfig(dtype="fp32", num_pages=16,
                                                  max_pages_per_seq=4))
    assert eng.blocks is None
    with pytest.raises(ValueError, match="one token a step, not by blocks"):
        eng.put(RaggedRequest(prompt_ids=[1, 2, 3], denoising_steps=2))
    model = sdar_moe_model("tiny")
    assert sdar_moe_config("30b-a3b").n_layers == 48
    with pytest.raises(NotImplementedError, match="sdar_moe is served only"):
        model.loss_fn(None, None, None)
    with pytest.raises(NotImplementedError, match="KVPageBundle export"):
        e = _engine()
        uid = e.put(RaggedRequest(prompt_ids=_prompt(9), max_new_tokens=8))
        e.step()
        e.export_sequence(uid)


# ------------------------------------------------------- spans and counters
def test_a_pass_says_what_it_held_and_a_step_what_it_counted():
    rec = get_span_recorder()
    if not rec.enabled:
        pytest.skip("the span recorder is off")
    eng = _engine()
    eng.put(RaggedRequest(prompt_ids=_prompt(9), max_new_tokens=7,
                          denoising_steps=2))
    eng.put(RaggedRequest(prompt_ids=_prompt(8), max_new_tokens=4,
                          denoising_steps=4))
    rec.clear()
    while eng.has_work():
        eng.step()
    spans = rec.spans()
    passes = [s.attrs for s in spans if s.name == "block_pass"]
    steps = [s.attrs for s in spans if s.name == "serve_step"
             and s.attrs.get("block_passes")]
    opened = [s.attrs for s in spans if s.name == "block_open"]
    assert opened == [dict(opened[0], start=8, prompt_tokens=1)]
    assert len(passes) == len(steps) == eng.decode_stats()["block_passes"]
    for p, s in zip(passes, steps):
        assert p["rows"] == s["decode_rows"] == s["row_passes"]
        assert p["commit_rows"] == s["commit_row_passes"]
        assert s["block_kv_tokens"] >= B * s["row_passes"]
        assert s["page_tokens_in_use"] % ENGINE["page_size"] == 0
    assert sum(p["masked_positions"] > 0 for p in passes) > 0
    assert sum(s["tokens_committed"] for s in steps) == 11
    # 9 + 7 = 16 positions: blocks 8-11 (3 masked) and 12-15; 8 + 4: one block
    assert sum(s["tokens_revealed"] for s in steps) == 3 + 4 + 4
    names = {s.name for s in spans}
    assert {"decode", "dispatch", "device_wait", "step_emit"} <= names


# ------------------------------ programs of models without blocks, as they were
# sha256[:16] of ``lower(...).as_text()`` of the decode and the chunk program of
# two models that do not generate by blocks, with the XLA forms (k0) and with
# the Pallas kernels interpreted (k1: flash under ``q_offset``, the paged
# decode kernel), taken on the parent commit (6c97d6e, jax 0.9.0) before
# ``block_length`` existed: a model with ``block_length`` 0 must lower to what
# it did.  A PR that means to change these programs takes the hashes anew from
# its parent.
# (Since PR 53 a serving program takes its inputs packed: the text pinned
# here is ``program.apart()``'s, the function behind the slices, which is
# the parent's.)
# (PR 61 pinned every paged program's head projections — ``h @ wq``
# behind an optimization barrier, ``transformer.head_projection`` — a
# change these programs were meant to take: the hashes of the programs
# that hold one are its tree's, jax 0.9.0.)
_PARENT_HLO = {"mistral.k0.decode": "7137cdeb9e02644b",
               "mistral.k0.chunk": "525661f151f6ada7",
               "mimo_v2.k0.decode": "38c9bc3fbc3cc308",
               "mimo_v2.k0.chunk": "cfeb74c00d39ec69",
               "mistral.k1.decode": "68b275007f97c114",
               "mistral.k1.chunk": "e80ce5c8ec4bc083",
               "mimo_v2.k1.decode": "b9f5e0f6f4e0b3c1",
               "mimo_v2.k1.chunk": "c841514809af4bb3"}


@pytest.mark.parametrize("program", sorted(_PARENT_HLO))
def test_models_without_blocks_lower_as_before(program, monkeypatch):
    name, kernel, prog = program.split(".")
    monkeypatch.setenv("DSTPU_PAGED_KERNEL", kernel[1])
    model = (mistral_model if name == "mistral" else mimo_v2_model)(
        "tiny", max_seq_len=256)
    assert model.config.block_length == 0
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, max_pages_per_seq=16, prefill_chunk=16,
        max_seqs=4, num_pages=80), seed=0)
    i32, S = jnp.int32, jax.ShapeDtypeStruct
    R, MP_ = 4, 16
    if prog == "decode":
        low = eng._decode.apart().lower(
            eng.params, eng._pools, S((R,), i32), S((R,), i32),
            S((R, MP_), i32), S((R,), jnp.bool_), S((R,), jnp.float32),
            S((R,), i32), S((2,), jnp.uint32))
    else:
        slot = (S((), i32),) if eng._state else ()
        low = eng._prefill_chunk.apart().lower(
            eng.params, eng._pools, S((16,), i32), S((2,), i32),
            S((4,), i32), S((), i32), S((), i32), *slot)
    got = hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
    assert got == _PARENT_HLO[program]


def test_the_configuration_keeps_every_published_width():
    for key, want in (("hidden_size", 2048), ("num_attention_heads", 32),
                      ("num_key_value_heads", 4), ("head_dim", 128),
                      ("num_experts", 128), ("moe_intermediate_size", 768),
                      ("num_experts_per_tok", 8), ("vocab_size", 151936)):
        assert CONFIG[key] == want
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["published"] == {"num_hidden_layers": 48}
    cfg = family.build(CONFIG, CONFIG["num_hidden_layers"], 5136,
                       jnp.bfloat16).config
    assert (cfg.block_length, cfg.mask_token_id) == (4, 151669)
    assert cfg.qk_norm and cfg.moe_held_count == cfg.moe_experts == 128
    assert CONFIG["engine"]["page_size"] % cfg.block_length == 0
    assert CONFIG["engine"]["prefill_chunk"] % cfg.block_length == 0
