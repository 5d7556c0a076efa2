"""Inference v2 (ragged/paged continuous batching) tests.

Oracle: the paged engine must produce token-for-token the same greedy
generations as the dense KV-cache path (inference v1), for sequences of
different lengths running concurrently.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (BlockAllocator, InferenceEngineV2,
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.models.llama import llama_model
from deepspeed_tpu.models.transformer import forward_with_cache, init_kv_cache

pytestmark = pytest.mark.slow  # multi-minute integration tier


def test_block_allocator():
    a = BlockAllocator(8)
    p = a.alloc(5)
    assert len(set(p)) == 5 and a.free_pages == 3
    a.free(p[:2])
    assert a.free_pages == 5
    with pytest.raises(MemoryError):
        a.alloc(6)
    with pytest.raises(ValueError):
        a.free([99])


def _dense_greedy(model, params, prompt, n_new):
    """Reference generation through the dense cache path."""
    cfg = model.config
    cache = init_kv_cache(cfg, 1, cfg.max_seq_len, jnp.float32)
    ids = jnp.asarray(np.array(prompt)[None], jnp.int32)
    logits, cache = forward_with_cache(cfg, params, ids,
                                       cache, jnp.zeros((1,), jnp.int32))
    toks = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
    for i in range(n_new - 1):
        pos = jnp.asarray([len(prompt) + i], jnp.int32)
        logits, cache = forward_with_cache(
            cfg, params, jnp.asarray([[toks[-1]]], jnp.int32), cache, pos)
        toks.append(int(jnp.argmax(logits[0, 0])))
    return toks


def test_paged_matches_dense_single():
    model = llama_model("tiny", max_seq_len=256)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = list(np.random.RandomState(1).randint(0, model.config.vocab_size, 13))
    want = _dense_greedy(model, params, prompt, 8)

    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=32, max_seqs=2,
        max_pages_per_seq=8), params=params)
    got = eng.generate_all([RaggedRequest(prompt_ids=prompt, max_new_tokens=8)])
    assert got[0] == want, (got, want)


@pytest.mark.parametrize("horizon", [1, 4])
def test_paged_kernel_path_matches_dense(horizon, monkeypatch):
    """Same oracle with the Pallas paged-decode kernel forced on
    (interpret mode on CPU) — the TPU hot path, token-for-token, and the
    XLA gather path's stream beside it: a mixed batch in which half the
    decode slots stay empty, the rows span one, two and three blocks of
    the kernel's walk (128 tokens at this page geometry), one row crosses
    a block while decoding and the rows finish at different steps — under
    the fused horizon, mid-scan."""
    model = llama_model("tiny", max_seq_len=384)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, model.config.vocab_size, n))
               for n in (13, 125, 262)]
    n_new = (8, 6, 7)
    wants = [_dense_greedy(model, params, p, n)
             for p, n in zip(prompts, n_new)]

    def serve(kernel):
        monkeypatch.setenv("DSTPU_PAGED_KERNEL", kernel)
        eng = InferenceEngineV2(model, RaggedInferenceConfig(
            dtype="fp32", page_size=8, num_pages=96, max_seqs=6,
            max_pages_per_seq=40, prefill_chunk=64, decode_horizon=horizon),
            params=params)
        got = eng.generate_all(
            [RaggedRequest(prompt_ids=p, max_new_tokens=n)
             for p, n in zip(prompts, n_new)])
        assert eng.decode_stats()["decode_kv_blocks"] == \
            7 * 1 + (3 * 1 + 2 * 2) + 6 * 3
        return [got[u] for u in range(3)]

    got = serve("1")
    assert got == serve("0")
    assert got == wants, (got, wants)


def test_continuous_batching_mixed_lengths():
    """Three prompts of different lengths, admitted together; results must
    match per-sequence dense generation exactly."""
    model = llama_model("tiny", max_seq_len=256)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    prompts = [list(rng.randint(0, model.config.vocab_size, n))
               for n in (5, 17, 30)]
    wants = [_dense_greedy(model, params, p, 6) for p in prompts]

    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=64, max_seqs=4,
        max_pages_per_seq=8), params=params)
    got = eng.generate_all(
        [RaggedRequest(prompt_ids=p, max_new_tokens=6) for p in prompts])
    for uid, want in enumerate(wants):
        assert got[uid] == want, (uid, got[uid], want)


def test_queueing_beyond_slots():
    """More requests than decode slots: later ones wait, all finish."""
    model = llama_model("tiny", max_seq_len=256)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(0, model.config.vocab_size, 9)) for _ in range(5)]

    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=16, max_seqs=2,
        max_pages_per_seq=4), params=params)
    got = eng.generate_all(
        [RaggedRequest(prompt_ids=p, max_new_tokens=4) for p in prompts])
    assert len(got) == 5
    assert all(len(v) == 4 for v in got.values())
    # all pages returned to the pool
    assert eng.allocator.free_pages == 16


def test_eos_stops_generation():
    model = llama_model("tiny", max_seq_len=256)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = list(np.random.RandomState(4).randint(0, model.config.vocab_size, 6))
    want = _dense_greedy(model, params, prompt, 8)
    eos = want[2]  # third generated token acts as EOS

    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=32, max_seqs=2,
        max_pages_per_seq=8), params=params)
    got = eng.generate_all([RaggedRequest(prompt_ids=prompt, max_new_tokens=8,
                                          eos_id=eos)])
    assert got[0] == want[:3]


def test_rejects_oversized_prompt():
    model = llama_model("tiny", max_seq_len=256)
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=16, max_seqs=2,
        max_pages_per_seq=2))
    with pytest.raises(ValueError):
        eng.put(RaggedRequest(prompt_ids=list(range(16)), max_new_tokens=1))


def test_kv_pressure_preempts_instead_of_crashing():
    """Decode-time page growth under a full pool must preempt + recompute,
    never raise (reference: v2 scheduler holds requests under KV pressure)."""
    model = llama_model("tiny", max_seq_len=256)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    # pool of 8 pages, two prompts of 28 tokens -> 4 pages each: pool full at
    # admission; the first boundary-crossing generated token forces preemption
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=8, max_seqs=2,
        max_pages_per_seq=8), params=params)
    prompts = [list(rng.randint(0, model.config.vocab_size, 28)) for _ in range(2)]
    got = eng.generate_all([RaggedRequest(prompt_ids=p, max_new_tokens=10)
                            for p in prompts])
    for uid, p in enumerate(prompts):
        assert len(got[uid]) == 10
        # preempted sequences recompute their prefix; result must equal the
        # uninterrupted dense generation
        want = _dense_greedy(model, params, p, 10)
        assert got[uid] == want


def test_pool_smaller_than_one_seq_rejected():
    model = llama_model("tiny", max_seq_len=256)
    with pytest.raises(ValueError):
        InferenceEngineV2(model, RaggedInferenceConfig(
            page_size=8, num_pages=4, max_seqs=2, max_pages_per_seq=8))


def test_learned_pos_window_capped_to_model_context():
    from deepspeed_tpu.models.gpt2 import gpt2_model
    model = gpt2_model("tiny", max_seq_len=32)
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=16, num_pages=32, max_seqs=2,
        max_pages_per_seq=16))  # paged window 256 >> model context 32
    assert eng.max_seq_len == 32
    with pytest.raises(ValueError):
        eng.put(RaggedRequest(prompt_ids=list(range(40))))


def test_prefill_bucket_capped_to_model_context():
    """The prefill bucket caps at the page-rounded MODEL window, not the
    (possibly much larger) paged window (ADVICE r1 engine_v2.py:135): a
    learned-position model must not prefill past its position table."""
    from deepspeed_tpu.models.gpt2 import gpt2_model
    model = gpt2_model("tiny", max_seq_len=40)  # not a page multiple
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=16, num_pages=32, max_seqs=2,
        max_pages_per_seq=16))  # paged window 256 >> model context 40
    assert eng._bucket(33) == 48  # page-rounded model window, not 64/256
    # end-to-end: a prompt near the context edge still prefills + decodes
    out = eng.generate_all(
        [RaggedRequest(prompt_ids=list(range(1, 34)), max_new_tokens=4)])
    (toks,) = out.values()
    assert len(toks) >= 1


# ----------------- weight-only quantized inference (ZeRO++-adjacent) -------
def test_wq_matmul_matches_dequant():
    """Pallas/XLA weight-quantized matmul == explicit dequant matmul, int8
    and packed int4 (reference inference/quantization weight-only path)."""
    from deepspeed_tpu.ops.pallas.wq_matmul import (dequantize_weight,
                                                    quantize_weight,
                                                    wq_matmul)
    rng = np.random.RandomState(0)
    for bits in (8, 4):
        for K, N in [(128, 64), (200, 96)]:  # 200: padded packing
            w = jnp.asarray(rng.randn(K, N).astype(np.float32))
            x = jnp.asarray(rng.randn(5, K).astype(np.float32))
            codes, scale = quantize_weight(w, bits, group=64)
            wd = dequantize_weight(codes, scale, bits=bits, group=64, k=K,
                                   dtype=jnp.float32)
            # quantization error bounded by half a step per group
            assert float(jnp.abs(wd - w).max()) <= \
                float(jnp.abs(w).max()) / (254 if bits == 8 else 14) + 1e-6
            for impl in ("xla", "pallas"):  # pallas: interpret mode on CPU
                y = wq_matmul(x, codes, scale, bits=bits, group=64, impl=impl)
                np.testing.assert_allclose(np.asarray(y), np.asarray(x @ wd),
                                           rtol=2e-5, atol=2e-5,
                                           err_msg=f"{bits}b {impl}")


@pytest.mark.parametrize("bits", [8, 4])
def test_v2_engine_generates_with_quantized_weights(bits):
    """The paged engine generates with int8/int4 weights: logits close to
    bf16, weight bytes measurably lower."""
    from deepspeed_tpu.models.llama import llama_model

    model = llama_model("tiny", max_seq_len=64, attn_impl="xla")
    params = model.init_params(jax.random.PRNGKey(0))
    cfg = RaggedInferenceConfig(dtype="fp32", page_size=8, num_pages=32,
                                max_seqs=2, max_pages_per_seq=8)
    qcfg = RaggedInferenceConfig(dtype="fp32", page_size=8, num_pages=32,
                                 max_seqs=2, max_pages_per_seq=8,
                                 quant_bits=bits, quant_group=64,
                                 quant_min_size=1024)  # tiny test matrices
    e_fp = InferenceEngineV2(model, cfg, params=params)
    e_q = InferenceEngineV2(model, qcfg, params=params)
    # flags stay on the engine's own config copy
    assert model.config.wq_bits == 0
    # HBM at rest: int8 ~2x lower, int4 ~4x lower on the quantized leaves
    assert e_q.param_bytes < e_fp.param_bytes * (0.72 if bits == 8 else 0.6)

    prompt = list(range(1, 20))
    from deepspeed_tpu.inference.v2.model_runner import paged_prefill
    ids = np.zeros((32,), np.int32)
    ids[:len(prompt)] = prompt
    rows = np.arange(4, dtype=np.int32)
    lf, _ = paged_prefill(e_fp.cfg, e_fp.params, e_fp._pools,
                          jnp.asarray(ids), jnp.asarray(rows),
                          jnp.int32(len(prompt)))
    lq, _ = paged_prefill(e_q.cfg, e_q.params, e_q._pools,
                          jnp.asarray(ids), jnp.asarray(rows),
                          jnp.int32(len(prompt)))
    lf, lq = np.asarray(lf, np.float64), np.asarray(lq, np.float64)
    cos = float((lf * lq).sum() / (np.linalg.norm(lf) * np.linalg.norm(lq)))
    assert cos > (0.999 if bits == 8 else 0.98), cos

    out = e_q.generate_all([RaggedRequest(prompt_ids=prompt, max_new_tokens=8)])
    toks = list(out.values())[0]
    assert len(toks) == 8 and all(0 <= t < 256 for t in toks)


def test_kv_quant_int8_pool(monkeypatch):
    """int8 KV pages: pool bytes < half of fp32, prefill logits exact
    (storage-only quantization), decode logits close to the fp pool."""
    from deepspeed_tpu.inference.v2.model_runner import (paged_decode,
                                                         paged_prefill)

    model = llama_model("tiny", max_seq_len=256)
    params = model.init_params(jax.random.PRNGKey(0))
    mk = lambda **kw: InferenceEngineV2(model, RaggedInferenceConfig(  # noqa: E731
        dtype="fp32", page_size=8, num_pages=32, max_seqs=2,
        max_pages_per_seq=8, **kw), params=params)
    e_fp, e_q = mk(), mk(kv_quant=True)
    nbytes = lambda pools: sum(x.size * x.dtype.itemsize  # noqa: E731
                               for x in jax.tree_util.tree_leaves(pools))
    assert nbytes(e_q._pools) < nbytes(e_fp._pools) * 0.5

    prompt = list(np.random.RandomState(6).randint(0, 256, 13))
    ids = np.zeros((16,), np.int32)
    ids[:13] = prompt
    rows = np.arange(2, dtype=np.int32)
    args = (jnp.asarray(ids), jnp.asarray(rows), jnp.int32(13))
    lf, pools_fp = paged_prefill(e_fp.cfg, e_fp.params, e_fp._pools, *args)
    lq, pools_q = paged_prefill(e_q.cfg, e_q.params, e_q._pools, *args)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lq), rtol=1e-5,
                               atol=1e-5)  # prefill attends pre-quant k/v

    table = np.full((2, 8), e_fp.block.trash_page, np.int32)
    table[0, :2] = rows
    tok = jnp.asarray([int(np.argmax(np.asarray(lf))), 0], jnp.int32)
    dargs = (tok, jnp.asarray([13, 0], jnp.int32), jnp.asarray(table),
             jnp.asarray([True, False]))
    df, _ = paged_decode(e_fp.cfg, e_fp.params, pools_fp, *dargs)
    dq, _ = paged_decode(e_q.cfg, e_q.params, pools_q, *dargs)
    a, b = np.asarray(df[0], np.float64), np.asarray(dq[0], np.float64)
    cos = float((a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b)))
    assert cos > 0.999, cos

    # end-to-end generation with quantized KV completes
    out = e_q.generate_all([RaggedRequest(prompt_ids=prompt, max_new_tokens=6)])
    assert len(list(out.values())[0]) == 6


def test_on_device_temperature_sampling_reproducible():
    """Decode samples on device (Gumbel-max in the jitted program): same
    seed => same generation; valid token ids; greedy unaffected."""
    model = llama_model("tiny", max_seq_len=128)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = list(range(1, 17))

    def gen(seed, temp):
        eng = InferenceEngineV2(model, RaggedInferenceConfig(
            page_size=16, num_pages=32, max_seqs=2, max_pages_per_seq=4),
            params=params, seed=seed)
        got = eng.generate_all([RaggedRequest(prompt_ids=prompt,
                                              max_new_tokens=12,
                                              temperature=temp)])
        return list(got.values())[0]

    a = gen(7, 0.8)
    b = gen(7, 0.8)
    c = gen(8, 0.8)
    assert a == b, "same seed must reproduce"
    assert all(0 <= t < model.config.vocab_size for t in a)
    assert len(a) == 12
    # different seed: overwhelmingly likely to diverge somewhere at T=0.8
    assert a != c or len(set(a)) == 1


@pytest.mark.parametrize("kernel", ["0", "1"])
def test_chunked_prefill_matches_whole_prompt(kernel, monkeypatch):
    monkeypatch.setenv("DSTPU_PAGED_KERNEL", kernel)
    """Dynamic-SplitFuse-style chunked prefill (prefill_chunk > 0): long
    prompts processed in page-aligned chunks, decode interleaving between
    chunks — generations must equal the whole-prompt path exactly, and
    the number of engine steps a long prompt can monopolize must drop to
    ceil(len/chunk) chunk-steps with other sequences decoding between."""
    model = llama_model("tiny", max_seq_len=256)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, model.config.vocab_size, n))
               for n in (37, 9, 52)]
    wants = [_dense_greedy(model, params, p, 6) for p in prompts]

    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=64, max_seqs=4,
        max_pages_per_seq=8, prefill_chunk=16), params=params)
    got = eng.generate_all(
        [RaggedRequest(prompt_ids=p, max_new_tokens=6) for p in prompts])
    for uid, want in enumerate(wants):
        assert got[uid] == want, (uid, got[uid], want)


def test_chunked_prefill_interleaves_decode():
    """While a long prompt chunk-prefills, an already-running sequence
    keeps generating: the long prompt must NOT stall running streams for
    its whole prefill (the FastGen latency property, host-observable)."""
    model = llama_model("tiny", max_seq_len=256)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(6)
    short = list(rng.randint(0, model.config.vocab_size, 4))
    long = list(rng.randint(0, model.config.vocab_size, 60))

    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=64, max_seqs=4,
        max_pages_per_seq=8, prefill_chunk=16), params=params)
    u_short = eng.put(RaggedRequest(prompt_ids=short, max_new_tokens=20))
    got = {u_short: []}
    for uid, rec in eng.step().items():  # short admitted+prefilled: token 1
        got[uid].extend(rec["tokens"])
    u_long = eng.put(RaggedRequest(prompt_ids=long, max_new_tokens=2))
    got[u_long] = []
    # 60-token prompt at chunk 16 = 4 chunk-steps; the short stream must
    # receive a token on EVERY one of those steps (no prefill stall)
    for i in range(4):
        res = eng.step()
        assert u_short in res and res[u_short]["tokens"], (i, res)
        for uid, rec in res.items():
            got[uid].extend(rec["tokens"])
    assert got[u_long], "long prompt should have sampled by chunk 4"
    while eng.has_work():
        for uid, rec in eng.step().items():
            got[uid].extend(rec["tokens"])
    assert got[u_short] == _dense_greedy(model, params, short, 20)
    assert got[u_long] == _dense_greedy(model, params, long, 2)
