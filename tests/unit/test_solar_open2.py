"""Solar-Open2 on the serving path: a period of layer types, recurrent state
in slots beside the paged pool, and one rank's share of the experts.

Oracles: ``benchmark/reference/kda_moe_lm.py`` (plain float32, token by token,
no code shared with the program) for the engine; the token-by-token
recurrence for the two kernels (interpreted on the CPU); the uncut expert
layer for the sum of the ranks' shares; the parent commit's lowered programs
for the dense models the period change must not touch.
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.reference import kda_moe_lm  # noqa: E402
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.inference.v2.speculative import SpeculativeConfig  # noqa: E402
from deepspeed_tpu.models import (mistral_model, opt_model,  # noqa: E402
                                  solar_open2_model)
from deepspeed_tpu.ops.pallas import kda  # noqa: E402

FIRST, HELD = 4, 4   # this rank holds experts 4..7 of the tiny preset's 16
DESC = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, vocab_size=256, norm_eps=1e-5,
            period=["gqa", "kda", "kda", "kda"], kda_heads=4, kda_head_dim=16,
            kda_conv=4, experts_held=HELD, experts_first=FIRST,
            num_experts_per_tok=4, norm_topk_prob=True)


def _engine(seed=0, max_seq_len=128, **over):
    model = solar_open2_model("tiny", moe_held_first=FIRST,
                              moe_held_count=HELD, max_seq_len=max_seq_len)
    cfg = dict(dtype="fp32", page_size=8, max_pages_per_seq=16,
               prefill_chunk=16, max_seqs=4, num_pages=80)
    cfg.update(over)
    return InferenceEngineV2(model, RaggedInferenceConfig(**cfg), seed=seed)


def _serve(eng, prompts, n_new):
    uids = [eng.put(RaggedRequest(prompt_ids=p, max_new_tokens=n_new))
            for p in prompts]
    got = {u: [] for u in uids}
    _step_while(eng, got, eng.has_work)
    return [got[u] for u in uids]


def _step_while(eng, got, more):
    while more():
        for u, o in eng.step().items():
            got[u] += o["tokens"]


@pytest.fixture(params=["xla", "interpreted"])
def kernels(request, monkeypatch):
    """The serving programs in their XLA form and with the Pallas kernels
    interpreted."""
    if request.param == "interpreted":
        monkeypatch.setenv("DSTPU_PAGED_KERNEL", "1")
    return request.param


def _regrets(eng, prompt, toks):
    ref = np.asarray(kda_moe_lm.logits(DESC, eng.params, prompt + toks[:-1]))
    return [float(row.max() - row[t]) / float(np.abs(row).max())
            for row, t in zip(ref[len(prompt) - 1:], toks)]


@pytest.mark.parametrize("horizon", [1, 4])
def test_engine_prefill_then_decode_matches_the_reference(kernels, horizon):
    """put / step against the float32 reference: a prompt over three chunks
    (state crosses two chunk boundaries), a shorter one and one of two
    blocks of the paged kernel's walk (256 tokens here) in the same batch,
    one decode slot left empty; nine, six and seven greedy tokens, so the
    rows retire at different steps (mid-scan under the fused horizon);
    every token the reference's own argmax."""
    eng = _engine(max_seq_len=320, max_pages_per_seq=40, num_pages=96,
                  decode_horizon=horizon)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).tolist() for n in (40, 13, 261)]
    n_new = (9, 6, 7)
    uids = [eng.put(RaggedRequest(prompt_ids=p, max_new_tokens=n))
            for p, n in zip(prompts, n_new)]
    got = {u: [] for u in uids}
    _step_while(eng, got, eng.has_work)
    for u, prompt, n in zip(uids, prompts, n_new):
        assert len(got[u]) == n
        assert max(_regrets(eng, prompt, got[u])) == 0.0
    eng.assert_no_leaks()
    stats = eng.decode_stats()
    assert stats["moe_layer_calls"] > 0
    assert 0 < stats["moe_local_picks"] <= stats["moe_padded_rows"]
    assert stats["moe_padded_rows"] < stats["moe_grid_rows"]
    assert stats["decode_kv_blocks"] == 8 * 1 + 5 * 1 + 6 * 2
    assert eng.state_slots.in_use == 0


@pytest.mark.parametrize("n_prompt", [13, 40])
def test_read_state_is_the_reference_state_after_the_same_tokens(kernels,
                                                                 n_prompt):
    """``read_state`` of an admitted sequence after m returned tokens is the
    reference's state after the prompt and the first m - 1 of them (one
    chunk, and three chunks and on into the decode program), transposed;
    the benchmark's check holds the chip's programs to this.  Three of the
    four decode rows are free: the step kernel walks one."""
    eng = _engine()
    prompt = np.random.default_rng(3).integers(0, 256, n_prompt).tolist()
    uid = eng.put(RaggedRequest(prompt_ids=prompt, max_new_tokens=8))
    toks = []
    while len(toks) < 5:
        toks += eng.step().get(uid, {"tokens": []})["tokens"]
    kept = eng.read_state(uid)
    assert sorted(kept) == ["kda_conv", "kda_s"]
    assert kept["kda_s"].shape == (3, 4, 16, 16)
    assert kept["kda_s"].dtype == np.float32
    _, ref = kda_moe_lm.forward(DESC, eng.params, prompt + toks[:-1])
    for got, want in zip(kept["kda_s"], ref):
        np.testing.assert_allclose(got, np.asarray(want).transpose(0, 2, 1),
                                   rtol=0, atol=2e-5)
    eng.release_sequence(uid, reason="checked")
    assert not eng.has_work() and eng.state_slots.in_use == 0
    eng.assert_no_leaks()
    with pytest.raises(KeyError):
        eng.read_state(uid)


@pytest.mark.parametrize("horizon", [1, 3])
def test_states_of_the_rows_that_stay_when_one_finishes_mid_run(kernels,
                                                                horizon):
    """Three sequences in four decode rows (one free throughout), the
    middle one done after two tokens — inside the scan at horizon 3 —: its
    row then decodes no more while its neighbours go on, and after six
    tokens each neighbour's state is the reference's."""
    eng = _engine(decode_horizon=horizon)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n).tolist() for n in (21, 9, 14)]
    uids = [eng.put(RaggedRequest(prompt_ids=p, max_new_tokens=n))
            for p, n in zip(prompts, (12, 2, 12))]
    got = {u: [] for u in uids}
    _step_while(eng, got,
                lambda: min(len(got[uids[0]]), len(got[uids[2]])) < 6)
    assert len(got[uids[1]]) == 2 and eng.state_slots.in_use == 2
    for u, prompt in ((uids[0], prompts[0]), (uids[2], prompts[2])):
        assert max(_regrets(eng, prompt, got[u])) == 0.0
        _, ref = kda_moe_lm.forward(DESC, eng.params, prompt + got[u][:-1])
        for have, want in zip(eng.read_state(u)["kda_s"], ref):
            np.testing.assert_allclose(
                have, np.asarray(want).transpose(0, 2, 1), rtol=0, atol=2e-5)
    eng.abort_all()
    eng.assert_no_leaks()


def test_a_row_that_stops_inside_the_scan_keeps_its_last_active_state(
        monkeypatch):
    """``paged_multi_decode`` over three iterations, row 0 with a budget of
    three tokens, row 1 of one, rows 2 and 3 free: the kernel form leaves
    row 1's state as one ``paged_decode`` leaves it, row 0's as three do,
    the free rows' slots bit for bit as they went in — and the XLA form
    agrees on all of them to rounding."""
    from deepspeed_tpu.inference.v2 import model_runner as mr

    eng = _engine()
    rng = np.random.default_rng(13)
    uids = [eng.put(RaggedRequest(prompt_ids=rng.integers(0, 256, n).tolist(),
                                  max_new_tokens=8)) for n in (17, 10)]
    got = {u: [] for u in uids}
    _step_while(eng, got, lambda: min(map(len, got.values())) < 2)
    seqs = [q for q in eng._slots if q is not None]
    assert sorted(q.slot for q in seqs) == [0, 1]
    last, pos, act, temps, sids = map(jnp.asarray, eng._decode_inputs(seqs))
    table = jnp.asarray(eng._page_table)
    budgets = jnp.zeros(4, jnp.int32).at[seqs[0].slot].set(3).at[
        seqs[1].slot].set(1)
    eos = jnp.full(4, -1, jnp.int32)
    pools = eng._pools

    def multi(pools):
        return mr.paged_multi_decode(eng.cfg, eng.params, pools, last, pos,
                                     table, act, temps, eos, budgets, sids,
                                     eng._sample_key, 3)

    def singles(pools, steps):
        l, p = last, pos
        for _ in range(steps):
            logits, pools = mr.paged_decode(eng.cfg, eng.params, pools, l, p,
                                            table, act)
            l = mr.sample_tokens(logits, temps, eng._sample_key, sids, p + 1)
            p = p + 1
        return pools

    monkeypatch.setenv("DSTPU_PAGED_KERNEL", "1")
    toks, produced, fused = jax.jit(multi)(pools)
    one = jax.jit(lambda x: singles(x, 1))(pools)["kda_s"]
    three = jax.jit(lambda x: singles(x, 3))(pools)["kda_s"]
    monkeypatch.delenv("DSTPU_PAGED_KERNEL")
    _, produced_x, fused_x = jax.jit(multi)(pools)
    s0, s1 = seqs[0].slot, seqs[1].slot
    assert produced.tolist() == produced_x.tolist()
    assert [int(produced[s0]), int(produced[s1])] == [3, 1]
    kept = np.asarray(fused["kda_s"])
    # (two programs, so to the last bits and not bit for bit; one more
    # update moves a state by a thousand times the tolerance)
    np.testing.assert_allclose(kept[:, s1], np.asarray(one[:, s1]), rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(kept[:, s0], np.asarray(three[:, s0]), rtol=0,
                               atol=1e-6)
    assert np.abs(kept[:, s0] - np.asarray(one[:, s0])).max() > 1e-3
    assert np.abs(kept[:, s1] - np.asarray(three[:, s1])).max() > 1e-3
    np.testing.assert_array_equal(kept[:, 2:4],
                                  np.asarray(pools["kda_s"][:, 2:4]))
    np.testing.assert_allclose(kept[:, :4],
                               np.asarray(fused_x["kda_s"][:, :4]),
                               rtol=0, atol=2e-5)
    eng.abort_all()
    eng.assert_no_leaks()


def test_a_model_without_state_reads_no_state():
    eng = InferenceEngineV2(mistral_model("tiny"), RaggedInferenceConfig(
        dtype="fp32", page_size=8, max_pages_per_seq=8, prefill_chunk=16,
        max_seqs=4, num_pages=32), seed=0)
    uid = eng.put(RaggedRequest(prompt_ids=list(range(9)), max_new_tokens=4))
    eng.step()
    assert eng.read_state(uid) == {}


def test_reference_with_a_bf16_state_is_not_the_reference():
    """The negative control the benchmark's tolerance rests on: rounding the
    recurrent state to bfloat16 moves the logits."""
    eng = _engine()
    ids = np.random.default_rng(1).integers(0, 256, 48).tolist()
    a = np.asarray(kda_moe_lm.logits(DESC, eng.params, ids))
    b = np.asarray(kda_moe_lm.logits(DESC, eng.params, ids,
                                     state_dtype=jnp.bfloat16))
    assert 1e-4 < np.abs(a - b).max() / np.abs(a).max() < 0.5


def test_reference_with_float8_weights_is_not_the_reference():
    """The other negative control: the reference with its weights rounded
    to float8_e4m3's mantissa (by ``reduce_precision``; a cast there and back
    is dropped on the TPU as excess precision) moves logits and state far
    more than a bfloat16 program does."""
    eng = _engine()
    ids = np.random.default_rng(1).integers(0, 256, 48).tolist()
    a, sa = kda_moe_lm.forward(DESC, eng.params, ids)
    b, sb = kda_moe_lm.forward(DESC, eng.params, ids,
                               weights_dtype=jnp.float8_e4m3fn)
    a, b = np.asarray(a), np.asarray(b)
    assert 1e-2 < np.abs(a - b).max() / np.abs(a).max() < 1.0
    errs = [np.linalg.norm(np.asarray(x) - np.asarray(y))
            / np.linalg.norm(np.asarray(x)) for x, y in zip(sa, sb)]
    assert len(errs) == 3 and min(errs) > 5e-2


def _kda_inputs(C, H, K, V, seed, beta_max):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (C, H, K))) / np.sqrt(K)
    k = unit(jax.random.normal(ks[1], (C, H, K)))
    v = jax.random.normal(ks[2], (C, H, V))
    g = -jax.random.uniform(ks[3], (C, H, K), minval=0.0, maxval=6.0)
    beta = jax.random.uniform(ks[4], (C, H), minval=0.0, maxval=beta_max)
    st = jax.random.normal(ks[5], (H, V, K))
    return q, k, v, g, beta, st


def _recurrence(q, k, v, g, beta, st):
    """S_t = (I - b k k^T) diag(a) S_{t-1} + b k v^T, o_t = S_t^T q_t, in
    numpy float64, S as [H, K, V]; ``st`` is the kernels' S^T."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    S = np.swapaxes(np.asarray(st, np.float64), -1, -2)
    out = []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[:, :, None] * S
        S = S - beta[t][:, None, None] * k[t][:, :, None] * np.einsum(
            "hk,hkv->hv", k[t], S)[:, None, :]
        S = S + beta[t][:, None, None] * k[t][:, :, None] * v[t][:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), np.swapaxes(S, -1, -2)


@pytest.mark.parametrize("beta_max,zero_state", [(2.0, False), (1.0, False),
                                                 (2.0, True)])
def test_kda_chunk_kernel_matches_the_recurrence(beta_max, zero_state):
    """Interpreted ``dstpu_kda_chunk`` over four sub-chunks, beta up to 2
    (negative eigenvalues), from a non-zero state and from none; a padded
    tail (g = 0, beta = 0) leaves the state as it was."""
    q, k, v, g, beta, st = _kda_inputs(64, 3, 32, 32, 0, beta_max)
    if zero_state:
        st = jnp.zeros_like(st)
    g, beta = g.at[56:].set(0.0), beta.at[56:].set(0.0)
    o, s1 = kda.kda_chunk(q, k, v, g, beta, st)
    o_ref, s_ref = _recurrence(q[:56], k[:56], v[:56], g[:56], beta[:56], st)
    np.testing.assert_allclose(np.asarray(o[:56]), o_ref, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), s_ref, atol=2e-5)
    o_x, s_x = kda.kda_chunk_xla(q, k, v, g, beta, st)
    np.testing.assert_allclose(np.asarray(o_x[:56]), o_ref, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_x), s_ref, atol=2e-5)


_MASKS = {"all": range(8), "none": (), "scattered": (1, 4, 5),
          "last": (7,), "first": (0,)}


def _step_case(mask):
    """Eight rows of 64 heads (two head blocks of the kernel) on a two-layer
    pool of nine slots, the rows of ``mask`` active."""
    B, H, K, V = 8, 2 * kda._HEADS, 32, 32
    q, k, v, g, beta, _ = _kda_inputs(B, H, K, V, 1, 2.0)
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, B + 1, H, V, K))
    active = np.zeros(B, bool)
    active[list(_MASKS[mask])] = True
    return (q, k, v, g, beta, pool), active


@pytest.mark.parametrize("mask", sorted(_MASKS))
def test_kda_step_kernel_matches_the_recurrence_in_place(mask):
    """Interpreted ``dstpu_kda_step``: every active row one token from its
    own slot's non-zero state, beta up to 2, over more than one head block;
    a row that is not active returns exactly zero, and its slot, like every
    slot of the other layer, is bit for bit what went in."""
    (q, k, v, g, beta, pool), active = _step_case(mask)
    o, new = kda.kda_step(q, k, v, g, beta, pool, 1, jnp.asarray(active))
    assert o.shape == v.shape and new.shape == pool.shape
    for b in range(len(active)):
        if active[b]:
            o_ref, s_ref = _recurrence(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                       g[b:b + 1], beta[b:b + 1], pool[1, b])
            np.testing.assert_allclose(np.asarray(o[b]), o_ref[0], atol=2e-5)
            np.testing.assert_allclose(np.asarray(new[1, b]), s_ref,
                                       atol=2e-5)
        else:
            assert not np.asarray(o[b]).any()
            np.testing.assert_array_equal(np.asarray(new[1, b]),
                                          np.asarray(pool[1, b]))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(pool[0]))


@pytest.mark.parametrize("mask", ["scattered", "last", "first"])
def test_kda_step_kernel_reads_nothing_of_a_row_that_does_not_decode(mask):
    """NaN in every inactive row's slot and in its q, k, v, g and beta: the
    active rows' outputs and states are finite and bit for bit what they
    are over clean inputs, the inactive rows return zero and keep their NaN."""
    (q, k, v, g, beta, pool), active = _step_case(mask)
    act = jnp.asarray(active)
    o, new = kda.kda_step(q, k, v, g, beta, pool, 1, act)
    dead = jnp.asarray(~active)
    nan = lambda a: jnp.where(  # noqa: E731
        dead.reshape((-1,) + (1,) * (a.ndim - 1)), jnp.nan, a)
    bad = pool.at[1, :len(active)].set(nan(pool[1, :len(active)]))
    o_n, new_n = kda.kda_step(nan(q), nan(k), nan(v), nan(g), nan(beta), bad,
                              1, act)
    assert np.isfinite(np.asarray(o_n)).all()
    np.testing.assert_array_equal(np.asarray(o_n), np.asarray(o))
    rows = np.flatnonzero(active)
    assert np.isfinite(np.asarray(new_n[1, rows])).all()
    np.testing.assert_array_equal(np.asarray(new_n[1, rows]),
                                  np.asarray(new[1, rows]))
    assert np.isnan(np.asarray(new_n[1, np.flatnonzero(~active)])).all()
    np.testing.assert_array_equal(np.asarray(new_n[0]), np.asarray(pool[0]))


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """Four ranks of four experts each: their routed parts, with the shared
    expert counted once, sum to the reference's uncut expert layer."""
    from deepspeed_tpu.models.transformer import _ffn

    full = solar_open2_model("tiny", max_seq_len=128)
    params = full.init_params(jax.random.PRNGKey(3))
    mlp = jax.tree_util.tree_map(lambda a: a[0], params["layers"][1]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 64))
    desc = dict(DESC, experts_first=0, experts_held=16)
    with jax.default_matmul_precision("highest"):
        gates = kda_moe_lm._route(desc, h[0], mlp["router"])
        shared = kda_moe_lm._swiglu(h[0], mlp["shared_w_gate"],
                                    mlp["shared_w_up"], mlp["shared_w_down"])
        want = shared + sum(
            gates[:, e:e + 1] * kda_moe_lm._swiglu(
                h[0], mlp["w_gate"][e], mlp["w_up"][e], mlp["w_down"][e])
            for e in range(16))
        assert float(jnp.abs(gates.sum(-1) - 1.0).max()) < 1e-5
        got = -3.0 * shared  # every rank adds the shared expert: count it once
        for rank in range(4):
            cfg = solar_open2_model(
                "tiny", moe_held_first=4 * rank, moe_held_count=4,
                moe_drop_tokens=False).config
            layer = {"mlp": dict(mlp, **{n: mlp[n][4 * rank:4 * rank + 4]
                                         for n in ("w_gate", "w_up",
                                                   "w_down")})}
            got = got + _ffn(cfg, layer, h, training=False)[0][0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_a_preempted_request_resumes_to_the_same_tokens():
    """A pool too small for both sequences' growth preempts one; its state
    is dropped with its slot and recomputed by the re-prefill, and every
    token still is the reference's argmax.  Slots are leak-checked."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, 28).tolist() for _ in range(2)]
    calm = _serve(_engine(), prompts, 10)
    eng = _engine(num_pages=9, max_seqs=2, max_pages_per_seq=8)
    got = _serve(eng, prompts, 10)
    assert eng.decode_stats()["state_slot_preemptions"] > 0
    assert got == calm
    for prompt, toks in zip(prompts, got):
        assert max(_regrets(eng, prompt, toks)) == 0.0
    eng.assert_no_leaks()
    assert eng.state_slots.in_use == 0


def test_state_slots_are_leak_checked():
    eng = _engine()
    eng.put(RaggedRequest(prompt_ids=list(range(20)), max_new_tokens=4))
    eng.step()
    assert eng.state_slots.in_use == 1
    eng.assert_no_leaks()
    eng.state_slots.claim(3, 99)  # a slot nobody scheduled holds
    with pytest.raises(AssertionError, match="state slots"):
        eng.assert_no_leaks()
    eng.state_slots.release(3)
    eng.abort_all()
    assert eng.state_slots.in_use == 0
    eng.assert_no_leaks()


@pytest.mark.parametrize("what", ["prefix_cache", "whole_prompt_prefill",
                                  "speculative", "proposer", "export",
                                  "training"])
def test_what_a_model_with_state_refuses(what):
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="prefix"):
            _engine(enable_prefix_cache=True)
    elif what == "whole_prompt_prefill":
        with pytest.raises(ValueError, match="prefill_chunk"):
            _engine(prefill_chunk=0)
    elif what == "speculative":
        with pytest.raises(ValueError, match="paged_verify"):
            _engine(speculative=SpeculativeConfig(mode="ngram", k=2))
    elif what == "proposer":
        model = solar_open2_model("tiny", moe_held_count=HELD)
        with pytest.raises(ValueError, match="paged_verify"):
            InferenceEngineV2(model, RaggedInferenceConfig(
                dtype="fp32", page_size=8, prefill_chunk=16, num_pages=32),
                proposer=object())
    elif what == "export":
        eng = _engine()
        uid = eng.put(RaggedRequest(prompt_ids=list(range(12)),
                                    max_new_tokens=4))
        eng.step()
        with pytest.raises(NotImplementedError, match="KVPageBundle"):
            eng.export_sequence(uid)
    else:
        model = solar_open2_model("tiny")
        # the delta-rule scan alone: grouped_matmul has its backward (PR 32)
        with pytest.raises(NotImplementedError,
                           match="dstpu_kda_chunk[^;]*does not exist"):
            model.loss_fn(None, None, None)


# sha256[:16] of ``lower(...).as_text()`` of the engine's programs at the
# sizes below, taken on the parent commit (8a61f03, jax 0.9.0): the period
# change must leave a homogeneous stack's programs as they were.  A PR that
# means to change these programs takes the hashes anew from its own parent.
# (Since PR 53 a serving program takes its inputs packed: the text pinned
# here is ``program.apart()``'s, the function behind the slices, which is
# the parent's.)
# (PR 61 pinned every paged program's head projections — ``h @ wq``
# behind an optimization barrier, ``transformer.head_projection`` — a
# change these programs were meant to take: the hashes of the programs
# that hold one are its tree's, jax 0.9.0.)
_PARENT_HLO = {
    ("mistral", "decode"): "d0b0017a588b453f",
    ("mistral", "chunk"): "dad9799eed8e03a7",
    ("mistral", "prefill"): "7450ab949f1579f2",
    ("opt", "decode"): "ac1ede5ce6b7906f",
    ("opt", "chunk"): "954e2fdb407d33b2",
    ("opt", "prefill"): "dbbe2de358dba036",
}


@pytest.mark.parametrize("family,program", sorted(_PARENT_HLO))
def test_dense_programs_lower_as_before_the_period_change(family, program):
    model = {"mistral": mistral_model, "opt": opt_model}[family]("tiny")
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, max_pages_per_seq=8, prefill_chunk=16,
        max_seqs=4, num_pages=32), seed=0)
    i32, S = jnp.int32, jax.ShapeDtypeStruct
    B, MP = 4, 8
    if program == "decode":
        low = eng._decode.apart().lower(
            eng.params, eng._pools, S((B,), i32), S((B,), i32),
            S((B, MP), i32), S((B,), jnp.bool_), S((B,), jnp.float32),
            S((B,), i32), S((2,), jnp.uint32))
    elif program == "chunk":
        low = eng._prefill_chunk.apart().lower(
            eng.params, eng._pools, S((16,), i32), S((2,), i32),
            S((4,), i32), S((), i32), S((), i32))
    else:
        low = eng._prefill.apart().lower(
            eng.params, eng._pools, S((16,), i32), S((2,), i32), S((), i32))
    got = hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
    assert got == _PARENT_HLO[(family, program)]


def test_decode_horizon_with_state_gives_the_same_streams():
    """The fused multi-step decode carries the state slots in its scan as it
    carries the pages: horizon 4 emits what horizon 1 emits."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, n).tolist() for n in (35, 9)]
    eng = _engine(decode_horizon=4)
    assert _serve(eng, prompts, 10) == _serve(_engine(), prompts, 10)
    assert eng.decode_stats()["moe_layer_calls"] > 0
    eng.assert_no_leaks()
