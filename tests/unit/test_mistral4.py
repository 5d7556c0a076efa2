"""Mistral-Small-4 on the serving path: latent attention (MLA) over a page
format that belongs to the layer type — one latent leaf a token, no K or V
pool — with an expanded chunk form and an absorbed decode form, YaRN rotary
tables over interleaved pairs, and an expert share with a sigmoid router, a
selection bias and an ungated shared expert.

Oracles: ``benchmark/reference/mla_moe_lm.py`` (plain float32, expanded form
at every position, no cache, no code shared with the program) for the
engine's programs — logits and cached rows; a dense softmax for the decode
kernel (interpreted on the CPU); the closed form and, where they import,
``transformers``' YaRN initialiser and DeepSeek-V3 modules for the reference
itself.  The tiny rotary width (8) is not the head's (24), the tiny original
context (16) is crossed by the tiny prompts, and the tiny page (4) is smaller
than the tiny chunk (8).
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.families import mistral4 as family  # noqa: E402
from benchmark.reference import mla_moe_lm  # noqa: E402
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.inference.v2 import model_runner  # noqa: E402
from deepspeed_tpu.inference.v2.speculative import SpeculativeConfig  # noqa: E402
from deepspeed_tpu.models import mistral4_config, mistral4_model  # noqa: E402
from deepspeed_tpu.models.layer_types import (latent_width,  # noqa: E402
                                              layers_of, page_layers,
                                              page_leaves, state_leaves)
from deepspeed_tpu.models.transformer import (mlp_block,  # noqa: E402
                                              yarn_inv_freq)
from deepspeed_tpu.ops.pallas.mla_attention import mla_decode_attention  # noqa: E402
from deepspeed_tpu.telemetry.spans import get_span_recorder  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "mistral-small4-119b-ep8-serve.json")) as _f:
    CONFIG = json.load(_f)
TINY = dict(CONFIG, **CONFIG["tiny"])
DESC = family.describe(TINY)
ENGINE = CONFIG["tiny_engine"]
CHUNK, PS, MP = (ENGINE["prefill_chunk"], ENGINE["page_size"],
                 ENGINE["max_pages_per_seq"])
R, DR = DESC["kv_lora_rank"], DESC["qk_rope_head_dim"]


def _engine(seed=0, **over):
    model = family.build(TINY, TINY["num_hidden_layers"], PS * MP,
                         jnp.float32)
    return InferenceEngineV2(model, RaggedInferenceConfig(**dict(ENGINE,
                                                                 **over)),
                             seed=seed)


def _chunk_logits(eng, prompt, pages):
    """The chunk program called as the engine calls it, chunk by chunk, on
    pages taken by hand -> the logits of the prompt's last token."""
    table = np.full((MP,), eng.block.trash_page, np.int32)
    table[:len(pages)] = pages
    logits = None
    for start in range(0, len(prompt), CHUNK):
        n = min(CHUNK, len(prompt) - start)
        ids = np.zeros((CHUNK,), np.int32)
        ids[:n] = prompt[start:start + n]
        rows = np.full((CHUNK // PS,), eng.block.trash_page, np.int32)
        npg = -(-n // PS)
        rows[:npg] = pages[start // PS:start // PS + npg]
        logits, eng._pools = eng._prefill_chunk(
            eng.params, eng._pools, jnp.asarray(ids), jnp.asarray(rows),
            jnp.asarray(table), jnp.int32(start), jnp.int32(n))
    return np.asarray(logits), table


def _decode_logits(eng, table, slot, token, position):
    """One step of ``paged_decode`` for one row -> its logits."""
    B = eng.block.max_seqs
    last = np.zeros((B,), np.int32)
    pos = np.zeros((B,), np.int32)
    act = np.zeros((B,), bool)
    tab = np.full((B, MP), eng.block.trash_page, np.int32)
    last[slot], pos[slot], act[slot], tab[slot] = token, position, True, table
    if not hasattr(eng, "_logits_program"):
        eng._logits_program = jax.jit(
            lambda p, pools, *a: model_runner.paged_decode(eng.cfg, p, pools,
                                                           *a))
    logits, eng._pools = eng._logits_program(
        eng.params, eng._pools, jnp.asarray(last), jnp.asarray(pos),
        jnp.asarray(tab), jnp.asarray(act))
    return np.asarray(logits[slot])


def _serve(eng, prompts, new=8):
    uids = [eng.put(RaggedRequest(prompt_ids=p, max_new_tokens=new))
            for p in prompts]
    got = {u: [] for u in uids}
    steps = []
    while eng.has_work():
        out = eng.step()
        steps.append(dict(eng._step_counts))
        for u, o in out.items():
            got[u] += o["tokens"]
    return [got[u] for u in uids], steps


def _regrets(eng, prompt, toks):
    ref, _ = mla_moe_lm.forward(DESC, eng.params, prompt + toks[:-1],
                                logits_from=len(prompt) - 1)
    return [float(row.max() - row[t]) / float(np.abs(row).max())
            for row, t in zip(np.asarray(ref), toks)]


# ------------------------------------------------------------ the description
def test_the_type_declares_its_page_format_and_the_pool_follows():
    cfg = mistral4_model("tiny").config
    assert layers_of(cfg, "mla") == page_layers(cfg) == 4
    assert state_leaves(cfg) == {}
    assert page_leaves(cfg) == {"latent": (4, 128)}  # 32 + 8 in a lane tile
    eng = _engine()
    assert set(eng._pools) == {"latent", "moe_stats"}  # no K pool, no V pool
    assert eng._pools["latent"].shape == (4, ENGINE["num_pages"] + 1, PS, 128)
    # the published widths: 256 + 64 values a token, 384 lanes as laid out
    full = mistral4_config("119b", n_layers=8)
    assert page_leaves(full) == {"latent": (8, 384)}
    assert latent_width(full) == 384 and full.kv_lora_rank + \
        full.qk_rope_head_dim == 320
    # a model of attention layers keeps K and V as it always has
    from deepspeed_tpu.models import mistral_config
    dense = mistral_config("tiny")
    assert page_leaves(dense) == {"k": (2, 32), "v": (2, 32)}


# --------------------------------------------------- the engine, end to end
@pytest.mark.parametrize("kernels", ["xla", "interpreted"])
def test_chunked_prefill_then_decode_matches_the_reference_logits(
        kernels, monkeypatch):
    """Prompts inside one chunk (5), across two chunk boundaries and the
    original context length (21) and across nine (75): the last chunk's
    logits and six decode steps' against the reference's full forward —
    logits, not tokens — and then the pool's rows against the reference's
    ``[c | k_rope]``."""
    if kernels == "interpreted":
        monkeypatch.setenv("DSTPU_PAGED_KERNEL", "1")
    rng = np.random.default_rng(0)
    eng = _engine()
    for slot, n in enumerate((5, 21, 75)):
        prompt = rng.integers(0, 256, n).tolist()
        pages = list(range(40 * slot + 7, 40 * slot + 32))
        got, table = _chunk_logits(eng, prompt, pages)
        rows = [got]
        toks = list(prompt)
        for _ in range(6):
            toks.append(int(np.argmax(rows[-1])))
            rows.append(_decode_logits(eng, table, slot, toks[-1],
                                       len(toks) - 1))
        ref, latents = mla_moe_lm.forward(DESC, eng.params, toks,
                                          logits_from=n - 1)
        np.testing.assert_allclose(np.stack(rows), ref, rtol=0, atol=3e-5)
        used = -(-len(toks) // PS)
        for l, want in enumerate(latents):
            kept = np.asarray(eng._pools["latent"][l, np.asarray(pages[:used])]
                              ).reshape(used * PS, -1)
            np.testing.assert_allclose(kept[:len(toks), :R + DR],
                                       np.asarray(want), rtol=0, atol=1e-5)
            assert not kept[:len(toks), R + DR:].any()  # the lane padding


def test_the_stack_is_one_scanned_run_with_its_expert_matrices_left_whole(
        monkeypatch):
    """``served_runs`` is one run of the ``mla`` period; the scan slices a
    layer's small leaves and leaves an expert share's matrices stacked (a
    layer's blocks name their own through ``expert_first``): the logits of a
    scan that slices them too."""
    from deepspeed_tpu.models.layer_types import served_runs

    eng = _engine()
    ((types, n),) = served_runs(eng.cfg)
    assert [t.mixer for t in types] == ["mla"] and n == 4
    (tree,) = eng.params["layers"]
    assert tree["mlp"]["w_up"].shape[:2] == (4, DESC["experts_held"])
    prompt = np.random.default_rng(1).integers(0, 256, 21).tolist()
    pages = list(range(3, 12))
    stacked, table = _chunk_logits(eng, prompt, pages)
    stacked = [stacked, _decode_logits(eng, table, 0, 7, len(prompt))]
    left = []
    keep = model_runner._experts_left_stacked
    monkeypatch.setattr(model_runner, "_experts_left_stacked",
                        lambda body, trees: left.append(1) or keep(body, trees))
    _decode_logits(_engine(), table, 0, 7, len(prompt))
    assert left  # the scan of this stack goes through it
    monkeypatch.setattr(model_runner, "_experts_left_stacked",
                        lambda body, trees: (body, trees))
    sliced_eng = _engine()
    sliced, table = _chunk_logits(sliced_eng, prompt, pages)
    sliced = [sliced, _decode_logits(sliced_eng, table, 0, 7, len(prompt))]
    np.testing.assert_allclose(np.stack(stacked), np.stack(sliced), rtol=0,
                               atol=1e-6)


def test_put_step_serves_it_and_sequences_do_not_see_each_others_pages():
    """Three sequences interleaved in different rows, prefilling and decoding
    in the same steps, each against the reference alone; and what the step
    and its prefill spans say of the latent cache."""
    eng = _engine()
    recorder = get_span_recorder()
    recorder.clear()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (70, 13, 100)]
    got, steps = _serve(eng, prompts)
    for p, toks in zip(prompts, got):
        assert len(toks) == 8 and max(_regrets(eng, p, toks)) < 1e-5
    eng.assert_no_leaks()
    spans = recorder.spans()
    chunks = [sp.attrs for sp in spans if sp.name == "prefill"]
    assert chunks and all(c["ctx_tokens"] == c["start"] for c in chunks)
    assert sum(c["tokens"] for c in chunks) == 70 + 13 + 100
    served = [sp.attrs for sp in spans if sp.name == "serve_step"]
    assert len(served) == len(steps)
    dec = [s for s in steps if s["decode_rows"]]
    # every decoding row sees its prompt and what it has generated
    assert all(s["latent_kv_tokens"] >= 13 * s["decode_rows"] for s in dec)
    assert max(s["latent_kv_tokens"] for s in dec) <= 77 + 20 + 107
    assert all(s["latent_tokens_in_use"] % PS == 0 for s in steps)
    assert max(s["latent_tokens_in_use"] for s in steps) >= 70 + 13 + 100
    assert all(s["moe_layer_calls"] > 0 for s in dec)


def test_a_preempted_sequence_is_prefilled_again_and_goes_on_the_same():
    eng = _engine()
    prompt = np.random.default_rng(2).integers(0, 256, 45).tolist()

    def serve(preempt_after):
        uid = eng.put(RaggedRequest(prompt_ids=prompt, max_new_tokens=10))
        toks, cut = [], False
        while eng.has_work():
            toks += eng.step().get(uid, {"tokens": []})["tokens"]
            if not cut and len(toks) >= preempt_after:
                eng._preempt(eng._find_slotted(uid))
                cut = True
        return toks

    again = serve(4)
    assert again == serve(10 ** 9)
    assert max(_regrets(eng, prompt, again)) < 1e-5
    eng.assert_no_leaks()


def test_a_prefix_cache_hit_over_latent_pages_gives_a_cold_runs_logits():
    """A cached page holds its positions' latents and rotated keys: a second
    request that shares 48 tokens maps 12 pages and prefills the rest, and
    its tokens are the reference's (and a cold engine's)."""
    rng = np.random.default_rng(3)
    shared = rng.integers(0, 256, 48).tolist()
    a = shared + rng.integers(0, 256, 9).tolist()
    b = shared + rng.integers(0, 256, 14).tolist()
    warm = _engine(enable_prefix_cache=True)
    (first,), _ = _serve(warm, [a])
    (second,), _ = _serve(warm, [b])
    stats = warm.cache_stats()
    assert stats["cache_hits"] >= 48 // PS
    (cold,), _ = _serve(_engine(), [b])
    assert second == cold
    assert max(_regrets(warm, b, second)) < 1e-5
    assert max(_regrets(warm, a, first)) < 1e-5
    # a prompt cached whole enters through the decode program, its last page
    # copied on write: the one latent leaf is copied, the counters are not
    (third,), _ = _serve(warm, [shared])
    (fourth,), _ = _serve(warm, [shared])
    assert third == fourth and max(_regrets(warm, shared, fourth)) < 1e-5
    assert warm.cache_stats()["cache_hits"] > stats["cache_hits"]
    warm.assert_no_leaks()


def test_a_sequences_latent_pages_are_exported_and_imported_bit_exactly():
    src, dst = _engine(), _engine()
    prompt = np.random.default_rng(4).integers(0, 256, 30).tolist()
    uid = src.put(RaggedRequest(prompt_ids=prompt, max_new_tokens=12))
    toks = []
    while len(toks) < 4:
        toks += src.step().get(uid, {"tokens": []})["tokens"]
    bundle = src.export_sequence(uid)
    assert set(bundle.arrays) == {"latent"}
    assert bundle.model_sig == (4, 1, 128)
    assert dst.import_sequence(bundle)
    src.release_sequence(uid, reason="migrated")
    rest = []
    while dst.has_work():
        for o in dst.step().values():
            rest += o["tokens"]
    assert max(_regrets(dst, prompt, toks + rest)) < 1e-5
    assert len(toks + rest) == 12


@pytest.mark.parametrize("what", ["training", "speculative", "whole_prompt",
                                  "kv_quant"])
def test_what_cannot_run_a_latent_cache_is_refused_by_name(what):
    if what == "training":
        with pytest.raises(NotImplementedError, match="served only.*no cut"):
            mistral4_model("tiny").loss_fn(None, None, None)
        return
    over, match = {
        "speculative": ({"speculative": SpeculativeConfig(mode="ngram")},
                        "paged_verify has no form of the 'mla' mixer"),
        "whole_prompt": ({"prefill_chunk": 0},
                         "prefilled through the chunk program"),
        "kv_quant": ({"kv_quant": True}, "a latent pool has no heads"),
    }[what]
    with pytest.raises(ValueError, match=match):
        _engine(**over)


# --------------------------------------------------------- the two forms
def test_absorbed_attention_is_expanded_attention_at_float32():
    """One query a row over cached rows: the up-projections absorbed into the
    query and the output against keys and values expanded from the rows."""
    cfg = family.build(TINY, 4, PS * MP, jnp.float32).config
    rng = np.random.default_rng(5)
    NH, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    layer = {"attn": {"w_ukv": jnp.asarray(
        rng.normal(size=(R, NH * (dn + dv))) * 0.2, jnp.float32)}}
    B, S = 3, 10 * PS
    pool = jnp.asarray(rng.normal(size=(2, B * 10 + 1, PS, 128)), jnp.float32)
    table = jnp.arange(B * 10, dtype=jnp.int32).reshape(B, 10)
    positions = jnp.asarray([S - 1, 6, 17], jnp.int32)
    q_nope = jnp.asarray(rng.normal(size=(B, NH, dn)) * 0.3, jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(B, NH, DR)) * 0.3, jnp.float32)
    got = model_runner._mla_absorbed(
        cfg, layer, q_nope, q_rope, {"latent": pool}, 1, table, positions,
        jnp.ones((B,), bool), use_kernel=False)
    for b in range(B):
        rows = pool[1, table[b]].reshape(S, -1)
        want = model_runner._mla_expanded(
            cfg, layer, q_nope[b][None, None], q_rope[b][None, None], rows,
            positions[b][None], use_flash=False)
        np.testing.assert_allclose(got[b], want[0], rtol=0, atol=1e-5)


@pytest.mark.parametrize("lengths,active", [
    ([13, 0, 40, 8], [True, False, True, False]),
    ([1, 33, 32, 5, 0, 0, 7, 64], [True] * 4 + [False] * 2 + [True] * 2)])
def test_the_decode_kernel_is_a_dense_softmax_over_the_rows_pages(lengths,
                                                                  active):
    """``dstpu_mla_decode`` interpreted: NaN planted in the pages past a
    row's length, in the pages of inactive rows, in the trash page and in the
    lane padding — none of it may reach an output."""
    rank, dr, ps, MPk, L = 32, 16, 8, 8, 2
    B, NH = len(lengths), 4
    P = B * MPk
    rng = np.random.default_rng(6)
    pool = rng.normal(size=(L, P + 1, ps, 128)).astype(np.float32)
    pool[..., rank + dr:] = np.nan
    pool[:, P] = np.nan
    q = rng.normal(size=(B, NH, rank + dr)).astype(np.float32)
    table = np.arange(P, dtype=np.int32).reshape(B, MPk)
    for b, n in enumerate(lengths):
        live = -(-n // ps) if active[b] else 0
        pool[:, table[b, live:]] = np.nan
    pos = np.maximum(np.asarray(lengths, np.int32) - 1, 0)
    out = np.asarray(mla_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table),
        jnp.asarray(pos), 1, jnp.asarray(active), rank=rank))
    for b, n in enumerate(lengths):
        if not active[b]:
            assert not out[b].any()
            continue
        rows = pool[1, table[b]].reshape(-1, 128)[:n]
        s = q[b] @ rows[:, :rank + dr].T
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:, :rank]
        np.testing.assert_allclose(out[b], want, rtol=0, atol=1e-5)


# ------------------------------------------------------------------- rotary
def _closed_form(dim, theta, factor, orig, fast=32, slow=1):
    lo = math.floor(dim * math.log(orig / (fast * 2 * math.pi))
                    / (2 * math.log(theta)))
    hi = math.ceil(dim * math.log(orig / (slow * 2 * math.pi))
                   / (2 * math.log(theta)))
    i = np.arange(dim // 2)
    th = theta ** (-2.0 * i / dim)
    r = np.clip((i - lo) / (hi - lo), 0, 1)
    return th * (1 - r) + th / factor * r, lo, hi


def test_the_yarn_table_is_its_closed_form():
    want, lo, hi = _closed_form(64, 10000.0, 128.0, 8192)
    assert (lo, hi) == (12, 25)
    got = np.asarray(yarn_inv_freq(64, 10000.0, 128.0, 8192, 32.0, 1.0))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == 1.0 and got[12] == pytest.approx(10000.0 ** (-24 / 64))
    np.testing.assert_allclose(got[25:], want[25:] , rtol=1e-6)
    assert got[31] == pytest.approx(10000.0 ** (-62 / 64) / 128, rel=1e-6)
    # the reference computes its own, and the plain table is the blend off
    ref = mla_moe_lm.rope_frequencies({
        "qk_rope_head_dim": 64, "rope_theta": 10000, "rope_factor": 128,
        "rope_original_max": 8192, "rope_beta_fast": 32, "rope_beta_slow": 1})
    np.testing.assert_allclose(np.asarray(ref), want, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(yarn_inv_freq(64, 10000.0, 128.0, 8192, blend=False)),
        10000.0 ** (-np.arange(0, 64, 2) / 64), rtol=1e-6)


def _hf_config(**over):
    from transformers import DeepseekV3Config

    rope = dict(TINY["rope_parameters"])
    return DeepseekV3Config(**dict(dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=1, num_attention_heads=4,
        num_key_value_heads=4, n_shared_experts=1, n_routed_experts=8,
        routed_scaling_factor=1.0, kv_lora_rank=32, q_lora_rank=32,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, n_group=1,
        topk_group=1, num_experts_per_tok=2, first_k_dense_replace=0,
        norm_topk_prob=True, max_position_embeddings=1024, rms_norm_eps=1e-6,
        rope_theta=10000.0, rope_interleave=True, attention_bias=False,
        rope_scaling={"rope_type": "yarn", "factor": rope["factor"],
                      "original_max_position_embeddings":
                      rope["original_max_position_embeddings"],
                      "beta_fast": rope["beta_fast"],
                      "beta_slow": rope["beta_slow"], "mscale": 1.0,
                      "mscale_all_dim": 1.0},
        attn_implementation="eager"), **over))


def test_the_yarn_table_is_transformers_yarn():
    pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    for dim, factor, orig in ((8, 8.0, 16), (64, 128.0, 8192)):
        cfg = _hf_config(qk_rope_head_dim=dim, max_position_embeddings=orig
                         * int(factor))
        cfg.rope_scaling.update(factor=factor,
                                original_max_position_embeddings=orig)
        inv_freq, attention_factor = ROPE_INIT_FUNCTIONS["yarn"](cfg, "cpu")
        np.testing.assert_allclose(
            np.asarray(yarn_inv_freq(dim, 10000.0, factor, orig, 32.0, 1.0)),
            inv_freq.numpy(), rtol=2e-6)
        assert attention_factor == pytest.approx(1.0)  # mscale / mscale_all


def test_the_references_attention_and_router_are_deepseek_v3s():
    """The reference's attention block and router against the published
    modules of the family whose keys the config uses, weights copied,
    float32.  Switched off for the comparison: ``a_t`` (the query scaling by
    ``llama_4_scaling_beta``, which that code does not have)."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as hf

    cfg = _hf_config()
    rng = np.random.default_rng(7)
    S, H = 40, 64
    w = {"norm1": {"scale": jnp.ones((H,), jnp.float32)}, "attn": {
        name: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
        for name, shape in (("w_dq", (H, 32)), ("w_uq", (32, 4 * 24)),
                            ("w_dkv", (H, 40)), ("w_ukv", (32, 4 * 32)),
                            ("wo", (4 * 16, H)))}}
    w["attn"]["q_norm"] = jnp.asarray(rng.normal(size=32) + 1, jnp.float32)
    w["attn"]["kv_norm"] = jnp.asarray(rng.normal(size=32) + 1, jnp.float32)
    x = jnp.asarray(rng.normal(size=(S, H)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = mla_moe_lm.attention(DESC, x, w, query_scaling=False)
        h = mla_moe_lm._rms(x, 1.0, 1e-6)
    attn = hf.DeepseekV3Attention(cfg, layer_idx=0).eval()
    t = lambda a: torch.tensor(np.asarray(a).T.copy())  # noqa: E731
    with torch.no_grad():
        for mod, name in ((attn.q_a_proj, "w_dq"), (attn.q_b_proj, "w_uq"),
                          (attn.kv_a_proj_with_mqa, "w_dkv"),
                          (attn.kv_b_proj, "w_ukv"), (attn.o_proj, "wo")):
            mod.weight.copy_(t(w["attn"][name]))
        attn.q_a_layernorm.weight.copy_(torch.tensor(
            np.asarray(w["attn"]["q_norm"])))
        attn.kv_a_layernorm.weight.copy_(torch.tensor(
            np.asarray(w["attn"]["kv_norm"])))
        hs = torch.tensor(np.asarray(h))[None]
        cos_sin = hf.DeepseekV3RotaryEmbedding(config=cfg)(
            hs, torch.arange(S)[None])
        mask = torch.full((S, S), float("-inf")).triu(1)[None, None]
        out = attn(hs, cos_sin, mask)[0][0].numpy()
    np.testing.assert_allclose(np.asarray(got - x), out, rtol=0, atol=2e-5)

    router = hf.DeepseekV3TopkRouter(cfg).eval()
    wr = rng.normal(size=(H, 8)).astype(np.float32)
    bias = (rng.normal(size=8) * 0.1).astype(np.float32)
    with torch.no_grad():
        router.weight.copy_(torch.tensor(wr.T.copy()))
        router.e_score_correction_bias.copy_(torch.tensor(bias))
        idx, weights = router(torch.tensor(np.asarray(h))[None])
    want = np.zeros((S, 8), np.float32)
    np.put_along_axis(want, idx.numpy(), weights.numpy(), axis=1)
    whole = dict(DESC, experts_first=0, experts_held=8)
    with jax.default_matmul_precision("highest"):
        mine = mla_moe_lm.route(whole, h, jnp.asarray(wr), jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(mine), want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("control", [
    {"weights_dtype": jnp.float8_e4m3fn}, {"rope": "plain"},
    {"softmax_scale": "plain"}, {"router": "softmax"}],
    ids=["float8_weights", "rope_plain", "softmax_scale_plain",
         "router_softmax"])
def test_the_references_controls_are_not_the_reference(control):
    eng = _engine()
    ids = np.random.default_rng(8).integers(0, 256, 60).tolist()
    ref, rows = mla_moe_lm.forward(DESC, eng.params, ids)
    off, off_rows = mla_moe_lm.forward(DESC, eng.params, ids, **control)
    assert np.abs(np.asarray(off) - np.asarray(ref)).max() \
        > 1e-3 * np.abs(np.asarray(ref)).max()
    far = max(float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                    / np.linalg.norm(np.asarray(b)))
              for a, b in zip(off_rows, rows))
    assert far > 1e-3


# ------------------------------------------------------------ the share
def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each of 4 expert ranks holds 2 of 8 experts, routes over all 8 and
    computes its own experts' part; the parts, with the shared expert (which
    every rank computes alike) counted once, add up to what the uncut
    reference gives for the whole layer."""
    whole = dict(TINY, n_routed_experts=8, deployment_share=dict(
        TINY["deployment_share"], first_expert=0))
    cfg8 = family.build(whole, 1, 64, jnp.float32).config
    desc8 = family.describe(whole)
    from deepspeed_tpu.models.layer_types import layer_type

    stack = layer_type("mla").init(cfg8, jax.random.PRNGKey(9), 1)
    layer = jax.tree_util.tree_map(lambda a: a[0], stack)
    mlp = layer["mlp"]
    x = jnp.asarray(np.random.default_rng(9).normal(size=(1, 50, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = mla_moe_lm._rms(x[0], layer["norm2"]["scale"], 1e-6)
        gates = mla_moe_lm.route(desc8, h, mlp["router"], mlp["router_bias"])
        shared = mla_moe_lm._swiglu(h, mlp["shared_w_gate"],
                                    mlp["shared_w_up"], mlp["shared_w_down"])
        want = shared + sum(
            gates[:, e:e + 1] * mla_moe_lm._swiglu(
                h, mlp["w_gate"][e], mlp["w_up"][e], mlp["w_down"][e])
            for e in range(8))
    assert (np.asarray(gates) > 0).sum(1).tolist() == [2] * 50
    parts = []
    for first in (0, 2, 4, 6):
        share = dict(whole, n_routed_experts=2, deployment_share=dict(
            whole["deployment_share"], first_expert=first))
        cfg = family.build(share, 1, 64, jnp.float32).config
        cfg.moe_drop_tokens = False
        held = dict(layer, mlp=dict(mlp, **{
            n: mlp[n][first:first + 2] for n in ("w_gate", "w_up", "w_down")}))
        y, _ = mlp_block(cfg, held, x, training=False)
        parts.append(np.asarray(y - x)[0])
    total = sum(parts) - 3 * np.asarray(shared)
    np.testing.assert_allclose(total, np.asarray(want), rtol=0, atol=2e-6)
