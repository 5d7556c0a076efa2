"""Phi-4-mini-flash (SambaY) on the serving path: a stack of runs of periods,
Mamba state and a window's ring in slots beside one layer of pages, gated
memory units and cross-attention over that layer's pages, and a prefill that
runs the cross-decoder for a prompt's last token only.

Oracles: ``benchmark/reference/sambay_lm.py`` (plain float32, every layer at
every position, token by token, no code shared with the program) for the
engine's programs — logits, state and convolution tail; the token-by-token
recurrence for the two scan kernels (interpreted on the CPU); the parent
commit's lowered programs for Solar-Open2, which the runs must not touch.
The tiny window (24) is smaller than the tiny chunk (32) and no divisor of it.
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

from benchmark.reference import sambay_lm  # noqa: E402
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.inference.v2 import model_runner  # noqa: E402
from deepspeed_tpu.inference.v2.speculative import SpeculativeConfig  # noqa: E402
from deepspeed_tpu.models import (phi4_flash_model,  # noqa: E402
                                  solar_open2_model)
from deepspeed_tpu.models.layer_types import (layers_of, page_layers,  # noqa: E402
                                              served_runs, state_leaves)
from deepspeed_tpu.models.phi4_flash import phi4_flash_runs  # noqa: E402
from deepspeed_tpu.ops.pallas import ssm  # noqa: E402

WINDOW, CHUNK, PS, MP = 24, 32, 8, 32
DESC = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, vocab_size=256, norm_eps=1e-5, sliding_window=WINDOW,
            ssm_state=8, ssm_conv=4, ssm_dt_rank=4, runs=phi4_flash_runs(12))


def _engine(seed=0, **over):
    model = phi4_flash_model("tiny", max_seq_len=PS * MP)
    cfg = dict(dtype="fp32", page_size=PS, max_pages_per_seq=MP,
               prefill_chunk=CHUNK, max_seqs=4, num_pages=160)
    cfg.update(over)
    return InferenceEngineV2(model, RaggedInferenceConfig(**cfg), seed=seed)


def _chunk_logits(eng, prompt, slot=0, pages=None):
    """The chunk program called as the engine calls it, chunk by chunk, on
    pages and a slot taken by hand -> the logits of the prompt's last token."""
    pages = list(range(-(-len(prompt) // PS))) if pages is None else pages
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
        final = start + n >= len(prompt)
        program = eng._prefill_chunk if final else eng._prefill_chunk_part
        logits, eng._pools = program(
            eng.params, eng._pools, jnp.asarray(ids), jnp.asarray(rows),
            jnp.asarray(table), jnp.int32(start), jnp.int32(n),
            jnp.int32(slot))
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


# ------------------------------------------------------------ the description
def test_the_stack_is_three_runs_of_periods():
    cfg = phi4_flash_model("tiny").config
    runs = served_runs(cfg)
    assert [([t.name for t in types], n) for types, n in runs] == [
        (["mamba", "swa"], 3), (["mamba", "dattn"], 1), (["gmu", "xattn"], 2)]
    assert [layers_of(cfg, m) for m in
            ("mamba", "swa", "dattn", "gmu", "xattn")] == [4, 3, 1, 2, 2]
    # one layer writes pages; the window layers' rings and the state-space
    # layers' state and tail live in the slots
    assert page_layers(cfg) == 1
    leaves = state_leaves(cfg)
    assert leaves["ssm_s"] == (4, (8, 128), jnp.float32)
    assert leaves["ssm_conv"] == (4, (3, 128), None)
    assert leaves["win_k"] == leaves["win_v"] == (3, (WINDOW, 32), None)
    eng = _engine()
    assert eng._pools["k"].shape == (1, 161, PS, 32)
    assert eng._pools["win_k"].shape == (3, 5, WINDOW, 32)
    # the published stack: 9 Mamba, 8 window, 1 full, 7 + 7 in the decoder
    full = phi4_flash_model("mini").config
    assert [layers_of(full, m) for m in
            ("mamba", "swa", "dattn", "gmu", "xattn")] == [9, 8, 1, 7, 7]
    assert page_layers(full) == 1


# --------------------------------------------------- the engine, end to end
@pytest.mark.parametrize("kernels", ["xla", "interpreted"])
def test_chunked_prefill_then_decode_matches_the_reference_logits(
        kernels, monkeypatch):
    """Prompts under the window (10), over two windows and across two chunk
    boundaries (70) and across three (100): the last chunk's logits and six
    decode steps' against the reference's full forward — logits, not
    tokens — and then the Mamba state and the convolution tail against the
    reference's after the same tokens."""
    if kernels == "interpreted":
        monkeypatch.setenv("DSTPU_PAGED_KERNEL", "1")
    rng = np.random.default_rng(0)
    eng = _engine()
    for slot, n in enumerate((10, 70, 100)):
        prompt = rng.integers(0, 256, n).tolist()
        pages = list(range(40 * slot, 40 * slot + 20))
        got, table = _chunk_logits(eng, prompt, slot, pages)
        rows = [got]
        toks = list(prompt)
        for _ in range(6):
            toks.append(int(np.argmax(rows[-1])))
            rows.append(_decode_logits(eng, table, slot, toks[-1],
                                       len(toks) - 1))
        ref, states, tails = sambay_lm.forward(DESC, eng.params, toks,
                                               with_tails=True)
        np.testing.assert_allclose(np.stack(rows), ref[n - 1:], rtol=0,
                                   atol=2e-5)
        for l, (s, t) in enumerate(zip(states, tails)):
            np.testing.assert_allclose(eng._pools["ssm_s"][l, slot],
                                       np.asarray(s).T, rtol=0, atol=2e-5)
            np.testing.assert_allclose(eng._pools["ssm_conv"][l, slot],
                                       np.asarray(t), rtol=0, atol=2e-5)


def test_put_step_serves_it_and_the_slots_do_not_leak_into_each_other():
    """Three sequences interleaved in different slots, prefilling and
    decoding in the same steps, each against the reference alone: a
    neighbour's window or state would move its logits."""
    eng = _engine()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (70, 13, 100)]
    uids = [eng.put(RaggedRequest(prompt_ids=p, max_new_tokens=8))
            for p in prompts]
    got = {u: [] for u in uids}
    steps = []
    while eng.has_work():
        out = eng.step()
        steps.append(dict(eng._step_counts))
        for u, o in out.items():
            got[u] += o["tokens"]
    for u, p in zip(uids, prompts):
        ref = sambay_lm.logits(DESC, eng.params, p + got[u][:-1])
        for row, t in zip(ref[len(p) - 1:], got[u]):
            assert row.max() - row[t] < 1e-5 * np.abs(row).max()
    eng.assert_no_leaks()
    assert eng.state_slots.in_use == 0
    # the counters of a step, from the host's own book
    dec = [s for s in steps if s["decode_rows"]]
    assert all(s["ssm_rows"] == s["decode_rows"] for s in dec)
    assert all(s["xdec_rows"] >= s["decode_rows"] for s in dec)
    assert all(s["window_tokens"] <= WINDOW * s["decode_rows"] for s in dec)
    # one cross-decoder row a prompt in the chunk program, whatever its
    # length: 3 prompts of 3 + 1 + 4 chunks
    assert sum(s["chunks"] for s in steps) == 8
    assert (sum(s.get("xdec_rows", 0) for s in steps)
            - sum(s["decode_rows"] for s in steps)) == 3


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

    assert serve(4) == serve(10 ** 9)
    assert eng.decode_stats()["state_slot_preemptions"] == 1
    eng.assert_no_leaks()


def test_the_cross_decoder_runs_for_the_last_token_and_the_logits_agree():
    """A chunk that is not a prompt's last returns no logits and stops at the
    full-attention layer's K/V write; the last chunk's logits are the
    reference's at the last position, which ran every layer at every
    position."""
    eng = _engine()
    prompt = np.random.default_rng(3).integers(0, 256, 50).tolist()
    ids = np.zeros((CHUNK,), np.int32)
    ids[:] = prompt[:CHUNK]
    table = np.full((MP,), eng.block.trash_page, np.int32)
    table[:7] = range(7)
    args = (jnp.asarray(ids), jnp.arange(4, dtype=jnp.int32),
            jnp.asarray(table), jnp.int32(0), jnp.int32(CHUNK), jnp.int32(0))
    text = eng._prefill_chunk_part.lower(eng.params, eng._pools,
                                         *args).as_text()
    whole = eng._prefill_chunk.lower(eng.params, eng._pools, *args).as_text()
    # the matmuls of two runs' bodies, less the full layer's attention and
    # feed-forward part, against those of all three and the head
    assert 0 < text.count("dot_general") < whole.count("dot_general") - 8
    part, eng._pools = eng._prefill_chunk_part(eng.params, eng._pools, *args)
    assert not np.asarray(part).any()
    got, _ = _chunk_logits(eng, prompt)
    ref = sambay_lm.logits(DESC, eng.params, prompt)
    np.testing.assert_allclose(got, ref[-1], rtol=0, atol=2e-5)


# ------------------------------------------------------- each layer type alone
@pytest.mark.parametrize("control,kwargs", [
    ("window", {"window": 2 * WINDOW}), ("lambda", {"lambda_scale": 0.0}),
    ("bf16_state", {"state_dtype": jnp.bfloat16}),
    ("float8_weights", {"weights_dtype": jnp.float8_e4m3fn})])
def test_the_references_controls_are_not_the_reference(control, kwargs):
    """Each planted departure moves the logits of a prompt longer than the
    window — a state rounded to bfloat16 moves the state (at these widths
    the recurrence is a hundredth of the scan's output, and the logits do
    not see its rounding): the benchmark's negative controls are visible."""
    eng = _engine()
    prompt = np.random.default_rng(4).integers(0, 256, 60).tolist()
    ref, states = sambay_lm.forward(DESC, eng.params, prompt)
    off, off_states = sambay_lm.forward(DESC, eng.params, prompt, **kwargs)
    if control == "bf16_state":
        for s, o in zip(states, off_states):
            s, o = np.asarray(s), np.asarray(o)
            assert 1e-3 < np.linalg.norm(o - s) / np.linalg.norm(s) < 2e-2
            assert np.mean(o.view(np.uint32) & 0xFFFF == 0) == 1.0
            assert np.mean(s.view(np.uint32) & 0xFFFF == 0) < 0.01
        return
    assert np.abs(off[-1] - ref[-1]).max() > 1e-4 * np.abs(ref[-1]).max()
    if control == "window":  # a prompt inside both windows cannot tell
        np.testing.assert_allclose(off[:WINDOW], ref[:WINDOW], atol=1e-6)


def _attn_by_hand(w, h, i, window, mem_kv=None):
    """The differential form of one layer over ``h = LN1(x)`` by its
    equations, pair by pair in numpy: -> what the mixer adds."""
    a = {k: np.asarray(v, np.float64) for k, v in w["attn"].items()}
    S, D = h.shape[0], 16
    q = (h @ a["wq"] + a["bq"]).reshape(S, 4, D)
    if mem_kv is None:
        k = (h @ a["wk"] + a["bk"]).reshape(S, 2, D)
        v = (h @ a["wv"] + a["bv"]).reshape(S, 2, D)
    else:
        k, v = mem_kv
    vv = np.concatenate([v[:, 0], v[:, 1]], -1)          # the one value pair
    t = np.arange(S)
    mask = t[:, None] >= t[None]
    if window:
        mask &= t[:, None] - t[None] < window
    lam0 = 0.8 - 0.6 * np.exp(-0.3 * i)
    lam = (np.exp(a["lam_q1"] @ a["lam_k1"]) - np.exp(a["lam_q2"] @ a["lam_k2"])
           + lam0)
    out = []
    for p in range(2):                                   # query pairs
        A = []
        for j in range(2):                               # the two softmaxes
            sc = q[:, 2 * p + j] @ k[:, j].T / 4.0
            sc = np.where(mask, sc, -np.inf)
            sc = np.exp(sc - sc.max(-1, keepdims=True))
            A.append((sc / sc.sum(-1, keepdims=True)) @ vv)
        d = A[0] - lam * A[1]
        d = d / np.sqrt((d * d).mean(-1, keepdims=True) + 1e-5) * a["sub_norm"]
        out.append((1 - lam0) * d)
    return np.concatenate(out, -1) @ a["wo"] + a["bo"], k, v


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    return ((x - mu) / np.sqrt(x.var(-1, keepdims=True) + 1e-5)
            * np.asarray(p["scale"], np.float64)
            + np.asarray(p["bias"], np.float64))


def _ffn_by_hand(w, x):
    m = {k: np.asarray(v, np.float64) for k, v in w["mlp"].items()}
    h = _ln(x, w["norm2"])
    g = h @ m["w_gate"]
    return x + (g / (1 + np.exp(-g)) * (h @ m["w_up"])) @ m["w_down"]


@pytest.mark.parametrize("kind", ["mamba", "swa", "dattn", "gmu", "xattn"])
def test_each_layer_type_alone_is_its_equations(kind):
    """One layer of the reference against numpy float64 written from the
    equations, for 40 tokens (over the window)."""
    eng = _engine()
    rng = np.random.default_rng(5)
    S = 40
    x = rng.normal(size=(S, 64))
    run, pos = {"mamba": (0, 0), "swa": (0, 1), "dattn": (1, 1),
                "gmu": (2, 0), "xattn": (2, 1)}[kind]
    w = jax.tree_util.tree_map(lambda a: np.asarray(a[0], np.float64),
                               eng.params["layers"][run][pos])
    wj = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), w)
    xj, i = jnp.asarray(x, jnp.float32), 7
    prog = sambay_lm._programs(sambay_lm._hashable(DESC), jnp.float32, WINDOW,
                               1.0)
    h = _ln(x, w["norm1"])
    mem = rng.normal(size=(S, 128))
    kv = (rng.normal(size=(S, 2, 16)), rng.normal(size=(S, 2, 16)))
    if kind == "mamba":
        m = w["mamba"]
        u, z = np.split(h @ m["w_in"], 2, -1)
        pad = np.concatenate([np.zeros((3, 128)), u])
        c = sum(pad[j:j + S] * m["conv"][j] for j in range(4)) + m["conv_b"]
        ub = c / (1 + np.exp(-c))
        dbc = ub @ m["w_x"]
        dt = np.log1p(np.exp(dbc[:, :4] @ m["w_dt"] + m["b_dt"]))
        A = -np.exp(m["a_log"]).T
        s, ys = np.zeros((128, 8)), []
        for t in range(S):
            s = (np.exp(dt[t][:, None] * A) * s
                 + (dt[t] * ub[t])[:, None] * dbc[t, 4:12][None])
            ys.append(s @ dbc[t, 12:] + m["d"] * ub[t])
        y = (np.stack(ys) * (z / (1 + np.exp(-z)))) @ m["w_out"]
        got, got_mem, got_s, got_tail = prog["mamba"](xj, wj)
        np.testing.assert_allclose(got_mem, np.stack(ys), atol=2e-5)
        np.testing.assert_allclose(got_s, s, atol=2e-5)
        np.testing.assert_allclose(got_tail, u[-3:], atol=2e-5)
    elif kind == "gmu":
        g = h @ w["gmu"]["w_in"]
        y = (mem * (g / (1 + np.exp(-g)))) @ w["gmu"]["w_out"]
        got = prog["gmu"](xj, wj, jnp.asarray(mem, jnp.float32))
    elif kind == "xattn":
        y, _, _ = _attn_by_hand(w, h, i, 0, kv)
        got = prog["xattn"](xj, wj, *(jnp.asarray(a, jnp.float32)
                                      for a in kv), jnp.int32(i))
    else:
        y, k, v = _attn_by_hand(w, h, i, WINDOW if kind == "swa" else 0)
        got, got_k, got_v = prog[kind](xj, wj, jnp.int32(i))
        np.testing.assert_allclose(got_k, k, atol=2e-5)
    np.testing.assert_allclose(got, _ffn_by_hand(w, x + y), atol=5e-5)


# ------------------------------------------------------------------ the kernels
def _scan_inputs(rng, C, DI, N):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return (jnp.abs(f(C, DI)) * 0.1, f(C, DI), f(C, N), f(C, N),
            -(jnp.abs(f(N, DI)) + 0.5), f(DI), f(N, DI))


@pytest.mark.parametrize("C,n,DI", [(32, 21, 256), (16, 16, 128),
                                    (24, 1, 2048)])
def test_ssm_chunk_is_the_token_by_token_scan_with_nan_in_the_padding(C, n,
                                                                      DI):
    dt, u, b, c, a, d, s = _scan_inputs(np.random.default_rng(6), C, DI, 8)
    y0, s0 = ssm.ssm_chunk_xla(dt[:n], u[:n], b[:n], c[:n], a, d, s)
    nan = lambda x: x.at[n:].set(jnp.nan)  # noqa: E731
    y1, s1 = ssm.ssm_chunk(nan(dt), nan(u), nan(b), nan(c), a, d, s,
                           jnp.int32(n))
    np.testing.assert_allclose(y1[:n], y0, atol=1e-5)
    np.testing.assert_allclose(s1, s0, atol=1e-5)
    assert not np.asarray(y1[n:]).any()


@pytest.mark.parametrize("active", [[True, False, True, True, False, False],
                                    [False] * 6, [True] * 6])
def test_ssm_step_moves_the_rows_that_decode_and_no_other(active):
    """NaN in every slot that does not decode, in the trash slot and in the
    inputs of the rows that do not decode: the active rows' outputs and
    states are the token-by-token scan's, everything else is as it was."""
    rng = np.random.default_rng(7)
    B, L, S1, N, DI = 6, 3, 8, 8, 256
    dt, u, b, c, a, d, _ = _scan_inputs(rng, B, DI, N)
    act = np.asarray(active)
    pool = jnp.asarray(rng.normal(size=(L, S1, N, DI)), jnp.float32)
    dead = np.concatenate([np.flatnonzero(~act), [6, 7]])
    pool = pool.at[:, dead].set(jnp.nan)
    off = jnp.asarray(~act)[:, None]
    y, new = jax.jit(ssm.ssm_step)(
        jnp.where(off, jnp.nan, dt), jnp.where(off, jnp.nan, u),
        jnp.where(off, jnp.nan, b), jnp.where(off, jnp.nan, c), a, d, pool,
        jnp.int32(1), jnp.asarray(act))
    y0, s0 = ssm.ssm_step_xla(dt, u, b, c, a, d, pool[1, :B])
    np.testing.assert_allclose(y[act], y0[act], atol=1e-5)
    np.testing.assert_allclose(new[1, :B][act], s0[act], atol=1e-5)
    assert not np.asarray(y[~act]).any()
    for layer in (0, 2):
        np.testing.assert_array_equal(new[layer], pool[layer])
    np.testing.assert_array_equal(new[1, np.flatnonzero(~act)],
                                  pool[1, np.flatnonzero(~act)])


def test_flash_with_a_window_is_the_masked_softmax():
    """``[ring in position order | chunk]`` with the window's mask, a ring
    that holds fewer than a window masked from the front; without a window
    the kernel is called as it was."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(8)
    W, C = 24, 32
    q = jnp.asarray(rng.normal(size=(1, C, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, W + C, 1, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, W + C, 1, 32)), jnp.float32)
    for k_first in (0, 10, W):
        got = flash_attention(q, k, v, causal=True, q_offset=W, window=W,
                              k_first=k_first, block_q=16, block_k=16)
        rows, cols = W + np.arange(C)[:, None], np.arange(W + C)[None]
        vis = (cols <= rows) & (rows - cols < W) & (cols >= k_first)
        sc = np.einsum("btnd,bsd->bnts", q, k[:, :, 0]) / np.sqrt(32)
        sc = np.where(vis[None, None], sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("bnts,bsd->btnd", pr / pr.sum(-1, keepdims=True),
                         v[:, :, 0])
        np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=W)


# ------------------------------------------------------- refusals, by name
@pytest.mark.parametrize("what", ["prefix_cache", "whole_prompt",
                                  "speculation", "training", "page_size",
                                  "kv_quant"])
def test_what_cannot_serve_a_window_or_state_is_refused_by_name(what):
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="window's ring"):
            _engine(enable_prefix_cache=True)
    elif what == "whole_prompt":
        with pytest.raises(ValueError, match="prefill_chunk"):
            _engine(prefill_chunk=0)
    elif what == "speculation":
        with pytest.raises(ValueError, match="window's ring"):
            _engine(speculative=SpeculativeConfig(mode="ngram", k=2))
    elif what == "kv_quant":
        with pytest.raises(ValueError, match="kv_quant"):
            _engine(kv_quant=True)
    elif what == "page_size":
        with pytest.raises(ValueError, match="whole number of pages"):
            _engine(page_size=16, prefill_chunk=32)
    else:
        with pytest.raises(NotImplementedError,
                           match="dstpu_ssm_chunk[^;]*none of which exists"):
            phi4_flash_model("tiny").loss_fn(None, None, None)


# --------------------------------------------- Solar's programs, as they were
# sha256[:16] of ``lower(...).as_text()`` of Solar-Open2's three serving
# programs at the sizes below, taken on the parent commit (c51cc14, jax 0.9.0)
# before ``_scan_layers`` learnt runs: a stack of one period must lower to
# what it did.  A PR that means to change these programs takes the hashes
# anew from its own parent.
# (Since PR 53 a serving program takes its inputs packed: the text pinned
# here is ``program.apart()``'s, the function behind the slices, which is
# the parent's.)
# (PR 61 pinned every paged program's head projections — ``h @ wq``
# behind an optimization barrier, ``transformer.head_projection`` — a
# change these programs were meant to take: the hashes of the programs
# that hold one are its tree's, jax 0.9.0.)
_PARENT_HLO = {"decode": "14ff6393090aaff1", "chunk": "11a259bb5124261e",
               "multi_decode": "224337b3e053444c"}


@pytest.mark.parametrize("program", sorted(_PARENT_HLO))
def test_solar_programs_lower_as_before_the_runs(program):
    model = solar_open2_model("tiny", moe_held_first=4, moe_held_count=4,
                              max_seq_len=128)
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, max_pages_per_seq=16, prefill_chunk=16,
        max_seqs=4, num_pages=80,
        decode_horizon=4 if program == "multi_decode" else 1), seed=0)
    i32, S = jnp.int32, jax.ShapeDtypeStruct
    B, MP_ = 4, 16
    rows = (S((B,), i32), S((B,), i32), S((B, MP_), i32), S((B,), jnp.bool_),
            S((B,), jnp.float32), S((B,), i32))
    key = S((2,), jnp.uint32)
    if program == "decode":
        low = eng._decode.apart().lower(eng.params, eng._pools, *rows, key)
    elif program == "chunk":
        low = eng._prefill_chunk.apart().lower(
            eng.params, eng._pools, S((16,), i32), S((2,), i32),
            S((4,), i32), S((), i32), S((), i32), S((), i32))
    else:
        low = eng._multi.apart((11,)).lower(
            eng.params, eng._pools, *rows, S((B,), i32), S((B,), i32), key, 4)
    got = hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
    assert got == _PARENT_HLO[program]
