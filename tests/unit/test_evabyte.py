"""EvaByte on the serving path: EVA attention (an exact window beside one
pooled summary a chunk of every closed window, under one softmax), a cache
whose rows advance once a chunk, a window that closes inside the decode
program, and eight prediction heads of 320 bytes.

Oracles: ``benchmark/reference/eva_lm.py`` (plain float32, the equations over
a whole sequence, no cache, no code shared with the program) for the engine
through ``put`` / ``step`` — all eight heads' logits and the rows the engine
holds; ``benchmark/reference/dense_lm.py`` (plain causal attention) for the
reference itself and for the engine, through the two identities of the layer
(``window_size >= n``; ``chunk_size`` 1 with ``mu`` 0); ``ragged.EvaRows``'s
closed forms for the page accounting; the parent commit's lowered programs
(their hashes, locations stripped) for the models with one kind of cache,
which must compile to what they compiled to.
"""

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

from benchmark.families import evabyte as family  # noqa: E402
from benchmark.reference import dense_lm, eva_lm  # noqa: E402
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.inference.v2 import model_runner  # noqa: E402
from deepspeed_tpu.inference.v2.packed_inputs import \
    unpack_inputs  # noqa: E402
from deepspeed_tpu.inference.v2.ragged import EvaRows  # noqa: E402
from deepspeed_tpu.models import (evabyte_config, evabyte_model,  # noqa: E402
                                  mimo_v2_model, mistral4_model,
                                  mistral_model, phi4_flash_model)
from deepspeed_tpu.models.layer_types import (eva_mix, layer_type,  # noqa: E402
                                              page_leaves, state_leaves)
from deepspeed_tpu.telemetry.spans import get_span_recorder  # noqa: E402
from tests.unit.test_xing4 import _lowered_hash  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "evabyte-6.5b-pp4-serve.json")) as _f:
    CONFIG = json.load(_f)
TINY = dict(CONFIG, **CONFIG["tiny"])
DESC = family.describe(TINY)
ENGINE = CONFIG["tiny_engine"]
PS, MP = ENGINE["page_size"], ENGINE["max_pages_per_seq"]
W, C, V, P = (DESC["window_size"], DESC["chunk_size"], DESC["vocab_size"],
              DESC["num_pred_heads"])
#: float32 on both sides, the same weights: what separates them is the order
#: of float32 sums (the program attends a chunk or a page block at a time and
#: pools from stored rows) through 4 layers — 2e-6 of the largest logit over
#: the seeds tried, where the mildest control (``mu=False``) moves them 1e-2
TOL = 1e-4


def _engine(seed=0, sizes=TINY, **over):
    model = family.build(sizes, sizes["num_hidden_layers"], PS * MP,
                         jnp.float32)
    return InferenceEngineV2(model, RaggedInferenceConfig(**dict(ENGINE,
                                                                 **over)),
                             seed=seed)


@pytest.fixture(scope="module")
def eng():
    return _engine()


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, V, n).tolist()


class _DecodeSpy:
    """In the place of the engine's decode program: the same inputs first go
    through ``paged_decode`` alone, over a copy of the pools, and its logits
    are kept by uid; then the engine's own program runs."""

    def __init__(self, eng):
        self.eng, self.inner, self.logits = eng, eng._decode, {}
        self.program = jax.jit(lambda p, pools, *a: model_runner.paged_decode(
            eng.cfg, p, pools, *a)[0])

    def run(self, params, pools, layout, packed, *rest):
        last, pos, table, act, _temps, _sids = unpack_inputs(
            jnp.asarray(packed), layout)
        logits = np.asarray(self.program(params, pools, last, pos, table,
                                         act))
        self.logits = {s.uid: logits[s.slot].reshape(P, V)
                       for s in self.eng._slots
                       if s is not None and bool(act[s.slot])}
        return self.inner.run(params, pools, layout, packed, *rest)


def run_logged(eng, requests, hold=(), between=None):
    """``requests`` through ``put`` / ``step`` -> per uid ``{"tokens", "heads",
    "logits": one ``[P, V]`` a returned token, "rows": what the engine held
    when the request stood at ``hold[uid index]`` returned tokens}``.  The
    first token's logits are what the chunk program handed the host; a decode
    token's are the decode program's over the inputs the engine gave it."""
    emit, first, spy = eng._emit_sampled, {}, _DecodeSpy(eng)

    def keeping(seq, logits, out):
        first[seq.uid] = np.asarray(logits, np.float32).reshape(P, V)
        return emit(seq, logits, out)

    eng._emit_sampled, eng._decode = keeping, spy
    uids = [eng.put(r) for r in requests]
    got = {u: {"tokens": [], "heads": [], "logits": [], "rows": None}
           for u in uids}
    hold = dict(zip(uids, hold))
    try:
        while eng.has_work():
            if between is not None:
                between(eng)
            for u, o in eng.step().items():
                if u not in got:
                    continue
                g = got[u]
                head = [first.pop(u)] if u in first else []
                g["logits"] += head + [spy.logits[u]] * (
                    len(o["tokens"]) - len(head))
                g["tokens"] += o["tokens"]
                g["heads"] += o["heads"]
                if hold.get(u) == len(g["tokens"]) and not o["done"]:
                    g["rows"] = eng.read_eva(u)
            eng.allocator.check_invariants(
                [s.pages for s in eng._slots if s is not None])
    finally:
        eng._emit_sampled, eng._decode = emit, spy.inner
    eng.assert_no_leaks()
    return [got[u] for u in uids]


def _against_reference(eng, prompt, g, desc=DESC, **controls):
    """Every returned token's eight heads of logits, the picks, and the held
    rows against the reference's full pass."""
    toks = g["tokens"]
    ref, _ = eva_lm.forward(desc, eng.params, prompt + toks[:-1],
                            logits_from=len(prompt) - 1, **controls)
    ref, mine = np.asarray(ref), np.stack(g["logits"])
    assert mine.shape == ref.shape
    assert np.abs(mine - ref).max() <= TOL * np.abs(ref).max()
    # greedy: the sampled byte is head 0's arg-max, the picks the others'
    np.testing.assert_array_equal(np.argmax(ref[:, 0], -1), toks)
    np.testing.assert_array_equal(np.argmax(ref[:, 1:], -1), g["heads"])
    return ref


# ------------------------------------------------------------ the description
def test_the_type_keeps_two_kinds_of_rows_in_k_and_v_pages():
    cfg = evabyte_config("tiny")
    t = layer_type("eva")
    assert t.mixer == "eva" and t.mix is eva_mix and not t.crosses
    assert page_leaves(cfg) == {"k": (4, 64), "v": (4, 64)}
    assert state_leaves(cfg) == {}
    params = evabyte_model("tiny").init_params(jax.random.PRNGKey(0))
    (stack,) = params["layers"]
    assert stack["attn"]["adaptive_phi"].shape == (4, 4, 16)
    assert stack["attn"]["adaptive_mu_k"].shape == (4, 4, 16)
    assert params["embed"]["tok"].shape == (320, 64)
    assert params["lm_head"]["w"].shape == (64, 8 * 320)  # untied, 8 heads


def test_the_published_widths():
    cfg = evabyte_config("6.5b")
    assert (cfg.hidden_size, cfg.n_heads, cfg.head_dim, cfg.kv_heads) == (
        4096, 32, 128, 32)
    assert (cfg.ffn_size, cfg.vocab_size, cfg.pred_heads) == (11008, 320, 8)
    assert (cfg.eva_window, cfg.eva_chunk, cfg.rope_theta) == (2048, 16, 1e5)
    full = family.describe(CONFIG)
    assert (full["window_size"], full["chunk_size"], full["head_dim"],
            full["num_pred_heads"]) == (2048, 16, 128, 8)
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["published"] == {"num_hidden_layers": 32}


def test_training_is_refused_by_name():
    model = evabyte_model("tiny")
    with pytest.raises(NotImplementedError, match="window-plus-summaries"):
        model.loss_fn(None, None, None)
    with pytest.raises(ValueError, match="whole number of chunks"):
        evabyte_config("tiny", eva_chunk=5)


# --------------------------------------------- (i) the reference's identities
def _dense_desc(desc):
    return {"hidden_size": desc["hidden_size"],
            "intermediate_size": desc["intermediate_size"],
            "num_attention_heads": desc["num_attention_heads"],
            "num_key_value_heads": desc["num_attention_heads"],
            "head_dim": desc["head_dim"], "vocab_size": V * P,
            "max_position_embeddings": desc["max_position_embeddings"],
            "mlp": "swiglu", "norm": "rmsnorm", "position": "rope",
            "bias": False, "tie_word_embeddings": False,
            "norm_eps": desc["norm_eps"], "rope_theta": desc["rope_theta"]}


def _dense_logits(params, ids):
    """Plain causal attention over the same weights (the tree of one period
    read as a homogeneous stack; the pooling's two vectors unread)."""
    return np.asarray(dense_lm.logits(
        _dense_desc(DESC), dict(params, layers=params["layers"][0]), ids)
    ).reshape(len(ids), P, V)


@pytest.mark.parametrize("identity", ["window_holds_all", "chunk_of_one",
                                      "exact_control"])
def test_the_reference_is_causal_attention_under_its_identities(eng, identity):
    """With ``window_size >= n`` no window ever closes; with ``chunk_size`` 1
    and ``mu`` 0 a summary IS its key and value: plain causal attention both
    times, as ``dense_lm`` computes it."""
    ids = _prompt(7, 75)
    desc, controls = {
        "window_holds_all": (dict(DESC, window_size=128), {}),
        "chunk_of_one": (dict(DESC, chunk_size=1), {"mu": False}),
        "exact_control": (DESC, {"exact": True})}[identity]
    got, _ = eva_lm.forward(desc, eng.params, ids, **controls)
    want = _dense_logits(eng.params, ids)
    assert np.abs(np.asarray(got) - want).max() <= 1e-5 * np.abs(want).max()
    # ... and EVA itself is not: the summaries decide what comes out
    eva, _ = eva_lm.forward(DESC, eng.params, ids)
    assert np.abs(np.asarray(eva) - want)[W:].max() > 1e-2 * np.abs(want).max()


@pytest.mark.parametrize("control", [{"summaries": False}, {"pool": "mean"},
                                     {"mu": False}, {"exact": True}])
def test_each_control_of_the_reference_moves_the_logits(eng, control):
    ids = _prompt(8, 90)
    ref, rows = eva_lm.forward(DESC, eng.params, ids, logits_from=W)
    off, off_rows = eva_lm.forward(DESC, eng.params, ids, logits_from=W,
                                   **control)
    ref = np.asarray(ref)
    assert np.abs(np.asarray(off) - ref).max() > 100 * TOL * np.abs(ref).max()
    # inside the first window nothing is pooled: every control but the full
    # attention's is EVA there
    head, _ = eva_lm.forward(DESC, eng.params, ids[:W])
    same, _ = eva_lm.forward(DESC, eng.params, ids[:W], **control)
    np.testing.assert_allclose(np.asarray(same), np.asarray(head), atol=1e-5)


@pytest.mark.parametrize("n", [7, 32, 70])
def test_the_whole_sequence_form_of_the_type_is_the_reference(eng, n):
    """``layer_types.eva_mix`` (the type's ``mix``) over one layer."""
    cfg, ids = eng.cfg, _prompt(n, n)
    (stack,) = eng.params["layers"]
    layer = jax.tree_util.tree_map(lambda a: a[0], stack)
    x = eng.params["embed"]["tok"][jnp.asarray(ids)]
    got = x + eva_mix(cfg, layer, x[None], jnp.arange(n)[None], None, None)[0]
    with jax.default_matmul_precision("highest"):
        want, _ = eva_lm.attention(DESC, x, layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------ (ii) engine vs reference
@pytest.mark.parametrize("n_prompt", [6, 8, 32, 96])
def test_put_and_step_agree_with_the_references_full_pass(eng, n_prompt):
    """A prompt that ends inside a chunk, on a chunk boundary, on a window
    boundary and two windows on; 40 decoded bytes cross ten chunk boundaries
    and a window boundary: all eight heads' logits, and the held rows."""
    prompt = _prompt(n_prompt, n_prompt)
    (g,) = run_logged(eng, [RaggedRequest(prompt_ids=prompt,
                                          max_new_tokens=41)], hold=[40])
    assert len(g["tokens"]) == 41
    _against_reference(eng, prompt, g)
    n = n_prompt + 39  # positions cached when 40 tokens had come
    _, rows = eva_lm.forward(DESC, eng.params, prompt + g["tokens"][:39])
    assert g["rows"].shape == (4, (W // C) * (n // W) + n % W, 2 * 64)
    for got, want in zip(g["rows"], rows):
        np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


def test_rows_of_one_batch_close_their_windows_at_different_steps(eng):
    """Four rows whose windows close at decode steps 3, 9, 14 and (twice) 2
    and 34 of one decode program, the others going on."""
    lens = [29, 23, 50, 62]
    prompts = [_prompt(100 + n, n) for n in lens]
    rec = get_span_recorder()
    rec.clear()
    out = run_logged(eng, [RaggedRequest(prompt_ids=p, max_new_tokens=36)
                           for p in prompts])
    for prompt, g in zip(prompts, out):
        _against_reference(eng, prompt, g)
    closed = [sp.attrs for sp in rec.spans()
              if sp.name == "eva_window_closed"]
    in_decode = sorted(a["windows_closed"] for a in closed
                       if a["where"] == "decode")
    # 29 -> 32, 64; 23 -> 32; 50 -> 64; 62 -> 64, 96
    assert in_decode == [1, 1, 2, 2, 2, 3]
    assert {a["pages_freed"] for a in closed if a["where"] == "decode"} == {
        W // PS}


def test_a_preempted_sequence_resumes_to_the_same_bytes(eng):
    prompts = [_prompt(200 + n, n) for n in (40, 70)]
    reqs = lambda: [RaggedRequest(prompt_ids=p, max_new_tokens=30)  # noqa: E731
                    for p in prompts]
    calm = run_logged(eng, reqs())
    done = []

    def preempt_once(e):
        seq = next((s for s in e._slots if s is not None and s.generated == 9
                    and not done), None)
        if seq is not None:
            done.append(seq.uid)
            e._preempt(seq)

    shaken = run_logged(eng, reqs(), between=preempt_once)
    assert done
    for prompt, a, b in zip(prompts, calm, shaken):
        assert a["tokens"] == b["tokens"] and a["heads"] == b["heads"]
        assert len(b["tokens"]) == 30
    assert eng.allocator.free_pages == ENGINE["num_pages"]


@pytest.mark.parametrize("chunk", [16, 32])
def test_a_prefill_chunk_shorter_than_the_window(chunk):
    """``prefill_chunk`` 16 of a window of 32: a chunk attends the open
    window's earlier rows from the pages, which the prefill holds whole and
    trims at its end."""
    eng = _engine(prefill_chunk=chunk)
    prompt = _prompt(chunk, 77)
    (g,) = run_logged(eng, [RaggedRequest(prompt_ids=prompt,
                                          max_new_tokens=8)], hold=[7])
    _against_reference(eng, prompt, g)
    n = 77 + 6
    assert g["rows"].shape[1] == (W // C) * (n // W) + n % W


def test_the_kernels_interpreted_agree(monkeypatch):
    """The flash kernel (the front of the table masked by ``k_first``) and
    the paged decode kernel over the composed table, in interpret mode."""
    monkeypatch.setenv("DSTPU_PAGED_KERNEL", "1")
    eng = _engine()
    prompt = _prompt(5, 70)
    (g,) = run_logged(eng, [RaggedRequest(prompt_ids=prompt,
                                          max_new_tokens=30)])
    _against_reference(eng, prompt, g)


@pytest.mark.parametrize("identity", ["window_holds_all", "chunk_of_one"])
def test_the_engine_is_causal_attention_under_the_identities(identity):
    """The program at ``window_size`` 128 >= n, and at ``chunk_size`` 1 with
    ``mu`` 0 (pages of one row, 32 summaries a window): ``dense_lm``."""
    if identity == "window_holds_all":
        eng = _engine(sizes=dict(TINY, window_size=128), prefill_chunk=128)
    else:
        eng = _engine(sizes=dict(TINY, chunk_size=1), page_size=1,
                      max_pages_per_seq=256, num_pages=600)
        (stack,) = eng.params["layers"]
        stack["attn"]["adaptive_mu_k"] = jnp.zeros_like(
            stack["attn"]["adaptive_mu_k"])
    prompt = _prompt(11, 50)
    (g,) = run_logged(eng, [RaggedRequest(prompt_ids=prompt,
                                          max_new_tokens=30)])
    want = _dense_logits(eng.params, prompt + g["tokens"][:-1])[49:]
    mine = np.stack(g["logits"])
    assert np.abs(mine - want).max() <= TOL * np.abs(want).max()


# -------------------------------------------------- (iii) the page accounting
@pytest.mark.parametrize("n", [0, 3, 4, 31, 32, 33, 64, 100, 255])
def test_the_rows_a_sequence_holds_follow_the_closed_forms(n):
    ev = EvaRows(W, C, PS, PS * MP)
    assert ev.visible(n) == (W // C) * (n // W)
    assert ev.rows_held(n) == (n // C, n % W)
    assert ev.summary_pages(n) == -(-(n // C) // PS)
    assert ev.open_pages(n) == -(-(n % W) // PS)
    assert ev.rows_attended(n) == ev.visible(n) + n % W + 1
    assert (ev.open_cap, ev.sum_cap, ev.table_pages) == (8, 16, 24)
    assert ev.max_pages == 16 + 8


def test_the_published_geometry():
    ev = EvaRows(2048, 16, 16, 32768)
    assert (ev.open_cap, ev.sum_cap, ev.table_pages) == (128, 128, 256)
    assert ev.visible(9000) == 512 and ev.rows_attended(9000) == 512 + 809
    assert ev.summary_pages(9000) == 36 and ev.open_pages(9000) == 51
    with pytest.raises(ValueError, match="is not eva_chunk"):
        EvaRows(2048, 16, 8, 32768)


def test_pages_follow_the_rows_and_go_back_when_a_window_closes(eng):
    """After every step: pages held = the summaries' + the open window's; a
    closed window leaves ``W / C`` summary rows a layer and no exact row; the
    pages a close gives back are taken again."""
    ev, seen, taken = eng.rows, [], set()

    def audit(e):
        for s in e._slots:
            if s is None or not e._ready_to_decode(s):
                continue
            n = s.prefilled
            assert s.n_sum == ev.summary_pages(n)
            assert len(s.pages) - s.n_sum == ev.open_pages(n)
            row = e._page_table[s.slot]
            assert list(row[:s.n_sum]) == s.pages[:s.n_sum]
            opened = s.pages[s.n_sum:]
            assert list(row[ev.sum_cap:ev.sum_cap + len(opened)]) == opened
            assert (row == e.block.trash_page).sum() == ev.table_pages - len(
                s.pages)
            seen.append((n, len(s.pages)))
            taken.update(opened)

    prompt = _prompt(3, 45)
    (g,) = run_logged(eng, [RaggedRequest(prompt_ids=prompt,
                                          max_new_tokens=90)], between=audit)
    assert len(g["tokens"]) == 90
    # at a close (n = 64, 96, 128) the sequence holds summary pages alone
    assert {(64, 4), (96, 6), (128, 8)} <= set(seen)
    # 3 windows of 8 open pages each from a pool of 160: reused, not leaked
    assert len(taken) <= 24 and eng.allocator.free_pages == ENGINE["num_pages"]


def test_the_steps_counters_and_the_close_event(eng):
    rec = get_span_recorder()
    rec.clear()
    prompt = _prompt(9, 30)
    run_logged(eng, [RaggedRequest(prompt_ids=prompt, max_new_tokens=6)])
    steps = [sp.attrs for sp in rec.spans() if sp.name == "serve_step"]
    decode = [a for a in steps if a["decode_rows"]]
    # positions 30 .. 34: the window closes after 31, then 8 summaries
    assert [a["eva_rows_attended"] for a in decode] == [31, 32, 9, 10, 11]
    assert [a.get("eva_windows_closed", 0) for a in decode] == [0, 1, 0, 0, 0]
    assert [(a["eva_summary_rows_in_use"], a["eva_window_rows_in_use"])
            for a in decode[:3]] == [(7, 31), (8, 0), (8, 1)]
    assert all(a["eva_rows_in_use"] == a["eva_summary_rows_in_use"]
               + a["eva_window_rows_in_use"] for a in steps)
    chunk = next(sp.attrs for sp in rec.spans() if sp.name == "prefill")
    assert chunk["ctx_tokens"] == 0 and chunk["tokens"] == 30


# ------------------------------------------------------------ (iv) refusals
@pytest.mark.parametrize("over,match", [
    ({"enable_prefix_cache": True}, "enable_prefix_cache: an 'eva' layer"),
    ({"speculative": {"mode": "ngram", "k": 2}}, "speculative decoding"),
    ({"kv_quant": True}, "kv_quant: an 'eva' layer pools"),
    ({"decode_horizon": 4}, "decode_horizon 4"),
    ({"prefill_chunk": 24}, "prefill_chunk 24"),
    ({"prefill_chunk": 64}, "prefill_chunk 64"),
    ({"prefill_chunk": 0}, "prefill_chunk 0"),
    ({"page_size": 8, "prefill_chunk": 32}, "page_size 8 is not eva_chunk 4"),
])
def test_what_cannot_be_right_yet_is_refused_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        _engine(**over)


def test_bundles_and_a_short_pool_are_refused_by_name(eng):
    uid = eng.put(RaggedRequest(prompt_ids=_prompt(1, 9), max_new_tokens=3))
    eng.step()
    with pytest.raises(NotImplementedError, match="KVPageBundle export"):
        eng.export_sequence(uid)
    while eng.has_work():
        eng.step()
    with pytest.raises(ValueError, match="summaries and an open window"):
        _engine(num_pages=20)


# ------------------------------------ (v) models with one kind of cache
def _programs(model, chunk=8):
    """(decode, chunk) of a tiny model, lowered over the engine's own weights
    and pools (``tests/unit/test_xing4.py::_one_stream_programs``, with the
    sequence's slot for a model that keeps state)."""
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=4, num_pages=32, max_seqs=2,
        max_pages_per_seq=8, prefill_chunk=chunk))
    cfg, B, mp = eng.cfg, 2, 8
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    slot = (jnp.int32(0),) if eng._state else ()
    decode = _lowered_hash(
        lambda p, pools, *a: model_runner.paged_decode(cfg, p, pools, *a),
        eng.params, eng._pools, i32(B), i32(B), i32(B, mp),
        jnp.zeros((B,), bool))
    chunked = _lowered_hash(
        lambda p, pools, *a: model_runner.paged_prefill_chunk(
            cfg, p, pools, *a),
        eng.params, eng._pools, i32(chunk), i32(chunk // 4), i32(mp),
        jnp.int32(0), jnp.int32(3), *slot)
    return decode, chunked


#: the lowered text of the parent commit's programs (0f78d52, before the 'eva'
#: forms), locations stripped: this file's ``_programs`` run in a checkout of
#: that commit
#: (PR 61 pinned every paged program's head projections — ``h @ wq``
#: behind an optimization barrier, ``transformer.head_projection`` — a
#: change these programs were meant to take: the hashes of the programs
#: that hold one are its tree's, jax 0.9.0.)
PARENT_PROGRAMS = {
    "mistral": ("cc62c9d28da856c0", "b0a8be65dccd46cd"),
    "mistral4": ("99a7e4c4266c9a8b", "855b7928d9e3cd10"),
    "mimo_v2": ("649bb00efa1c0803", "f747549fbcb0bee7"),
    "phi4_flash": ("eb955ed0a861ab9c", "c1e3ef0585c3d77d"),
}
_MODELS = {"mistral": mistral_model, "mistral4": mistral4_model,
           "mimo_v2": mimo_v2_model, "phi4_flash": phi4_flash_model}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_models_with_one_kind_of_cache_lower_to_the_parents_programs(name):
    model = _MODELS[name]("tiny", max_seq_len=32)
    assert _programs(model) == PARENT_PROGRAMS[name]
