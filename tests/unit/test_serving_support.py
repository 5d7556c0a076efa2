"""The seam between the serving engine and the layer types (tiny widths, CPU).

(i) what a cache supports: for every served family with a cache of its own
and every engine feature, the engine refuses exactly what
``layer_types.unsupported`` declares, in its words, and serves the rest; (ii)
how a sequence's pages grow: ``ragged.PageRows`` and ``ragged.EvaRows`` under
one contract, driven with a ``BlockAllocator`` to a full pool and to the
longest sequence; (iii) the engine's source names no mixer and no cache leaf.
"""

import ast
import dataclasses
import functools
import importlib
import json
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig)
from deepspeed_tpu.inference.v2 import engine_v2  # noqa: E402
from deepspeed_tpu.inference.v2.ragged import (BlockAllocator,  # noqa: E402
                                               EvaRows, KVPageBundle,
                                               PageRows, SequenceState)
from deepspeed_tpu.models import layer_types  # noqa: E402
from deepspeed_tpu.models.layer_types import FEATURES, unsupported  # noqa: E402

#: the eight served families with a cache of their own, and dense Mistral
FAMILIES = {
    "mistral": "mistral-7b-serve",
    "solar": "solar-open2-250b-ep8-serve",
    "phi4": "phi4-mini-flash-serve",
    "mistral_small4": "mistral-small4-119b-ep8-serve",
    "mimo": "mimo-v2-flash-ep16-serve",
    "sdar": "sdar-30b-a3b-pp8-serve",
    "laguna": "laguna-s-2.1-ep8-serve",
    "xing4": "xing4-29b-a4b-pp7-serve",
    "evabyte": "evabyte-6.5b-pp4-serve",
}
#: how a configuration asks for each construction-time feature
ASKS = {
    "prefix_cache": {"enable_prefix_cache": True},
    "whole_prompt_prefill": {"prefill_chunk": 0},
    "speculation": {"speculative": {"mode": "ngram", "k": 2}},
    "kv_quant": {"kv_quant": True},
    "kv_tier": {"enable_prefix_cache": True, "kv_tier": {"enabled": True}},
    "decode_horizon": {"decode_horizon": 4},
}
#: the features a configuration asks for beside the one it is named for
ALSO = {"kv_tier": ("prefix_cache",)}


@functools.lru_cache(maxsize=None)
def _family(name):
    """(the family's tiny model, its engine options, its seeded weights)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           FAMILIES[name] + ".json")) as f:
        conf = json.load(f)
    tiny, ecfg = dict(conf, **conf["tiny"]), conf["tiny_engine"]
    family = importlib.import_module("benchmark.families." + conf["family"])
    model = family.build(tiny, tiny["num_hidden_layers"],
                         ecfg["page_size"] * ecfg["max_pages_per_seq"],
                         jnp.float32)
    return model, ecfg, model.init_params(jax.random.PRNGKey(0))


def _engine(name, model=None, **over):
    base, ecfg, params = _family(name)
    return InferenceEngineV2(model or base, RaggedInferenceConfig(
        **dict(ecfg, **over)), params=params)


# --------------------------------------------- (i) what a cache supports
@pytest.mark.parametrize("feature", FEATURES)
@pytest.mark.parametrize("name", FAMILIES)
def test_the_engine_refuses_what_the_layer_types_declare_and_no_more(
        name, feature):
    model = _family(name)[0]
    table = unsupported(model.config)
    assert set(table) <= set(FEATURES)
    if feature in ASKS:
        asked = [f for f in FEATURES if f == feature
                 or f in ALSO.get(feature, ())]
        why = next((table[f] for f in asked if f in table), None)
        if why is None and model.config.block_length:
            # generation by blocks is the model's, not a cache's: what its
            # policy cannot work with it refuses itself (block_diffusion.py)
            why = "this model generates by diffusion over blocks"
        if why is None:
            _engine(name, **ASKS[feature]).close()
        else:
            with pytest.raises(ValueError, match=re.escape(why)):
                _engine(name, **ASKS[feature])
    elif feature == "bundle_export":
        eng = _engine(name)
        if feature in table:
            with pytest.raises(NotImplementedError,
                               match=re.escape(table[feature])):
                eng.export_sequence(0)
        else:  # past the refusal: there is no such sequence
            with pytest.raises(KeyError, match="not in a decode slot"):
                eng.export_sequence(0)
    elif feature == "bundle_import":
        eng = _engine(name)
        bundle = KVPageBundle(
            uid=0, tokens=[1, 2], prompt_len=1, max_new_tokens=4,
            temperature=0.0, eos_id=None, prefilled=1, decode_entry=False,
            page_size=eng.block.page_size, page_keys=[], src_pages=[],
            arrays={}, model_sig=(0, 0, 0), kv_quant=False, dtype="fp32")
        # past the refusal: a bundle of another model is refused as that
        why = table.get(feature, "bundle model_sig (0, 0, 0) != engine")
        with pytest.raises(ValueError, match=re.escape(why)):
            eng.import_sequence(bundle)
    else:
        assert feature == "block_generation"
        cfg = model.config
        by_blocks = types.SimpleNamespace(config=dataclasses.replace(
            cfg, block_length=cfg.block_length or 4,
            mask_token_id=cfg.vocab_size - 1))
        if feature in table:
            with pytest.raises(NotImplementedError,
                               match=re.escape(table[feature])):
                _engine(name, model=by_blocks)
        else:
            assert _engine(name, model=by_blocks).blocks is not None


def test_every_declared_feature_is_one_the_engine_knows():
    for kind in layer_types._TYPES:
        assert set(layer_types.layer_type(kind).refuses) <= set(FEATURES)


# ------------------------------------- (ii) how a sequence's pages grow
PS, W, C, MAXPOS = 4, 32, 4, 160


def _rows(kind, chunk, num_pages=0):
    if kind == "eva":
        return EvaRows(W, C, PS, MAXPOS, num_pages=num_pages,
                       prefill_chunk=chunk)
    return PageRows(PS, MAXPOS // PS, num_pages=num_pages)


def _want(rows, n):
    """(pages in front, the others) a sequence with ``n`` positions cached
    and a pending token at position ``n`` holds: the closed form."""
    if isinstance(rows, EvaRows):
        return -(-((n + 1) // C) // PS), (n % W) // PS + 1
    return 0, n // PS + 1


def _drive(rows, length, total, chunk, num_pages):
    """A sequence of ``length`` positions admitted, prefilled in chunks of
    ``chunk`` and decoded to ``total`` positions over an allocator of
    ``num_pages``, as the engine drives the rows object.  Every table handed
    to a program is checked; returns the sequence, the allocator and the
    (position, pages given back) of every close."""
    alloc, trash = BlockAllocator(num_pages), num_pages
    row = np.full((rows.table_pages,), trash, np.int32)
    seq = SequenceState(uid=0, tokens=[1] * length, prompt_len=length,
                        max_new_tokens=total - length, temperature=0.0,
                        eos_id=None, slot=0)

    def handed(*tables):
        for t in tables:
            assert t.dtype == np.int32 and ((0 <= t) & (t <= num_pages)).all()

    def give_back(after_chunk):
        drop, closed = rows.give_back(seq, after_chunk)
        alloc.free(drop)
        if drop:
            rows.write_table(seq, row, trash)
        if closed:
            closes.append((seq.prefilled, len(drop)))

    closes = []
    seq.n_sum, rest = rows.admit_pages(length)
    seq.pages = alloc.alloc(seq.n_sum + rest)
    rows.write_table(seq, row, trash)
    for start in range(0, length, chunk):
        c_n = min(chunk, length - start)
        handed(*rows.chunk_tables(seq, row, start, c_n, chunk, trash))
        seq.prefilled = start + c_n
        give_back(True)
    seq.tokens.append(1)  # the token the last chunk sampled
    while seq.length < total:
        pos = seq.length - 1
        need = rows.needs(seq, pos)
        if need > 0:
            rows.take(seq, pos, alloc.alloc(need), row, trash)
        assert (seq.n_sum, len(seq.pages) - seq.n_sum) == _want(rows, pos)
        handed(row)
        held = [p for p in row.tolist() if p != trash]
        assert sorted(held) == sorted(seq.pages)
        seq.tokens.append(1)
        seq.prefilled = seq.length - 1
        give_back(False)
    return seq, alloc, closes


@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("length", [3, 4, 5, 15, 16, 17, 31, 32, 33, 63, 64,
                                    65])
@pytest.mark.parametrize("kind", ["plain", "eva"])
def test_pages_admitted_and_grown_are_the_closed_form(kind, length, chunk):
    """Prompts that end before, on and after a page, a chunk and a window."""
    rows = _rows(kind, chunk)
    total = length + 2 * W + 3
    seq, alloc, closes = _drive(rows, length, total, chunk, rows.max_pages)
    n = seq.prefilled
    assert n == total - 1
    if kind == "eva":
        # every window that closed while decoding gave its open pages back,
        # all W / PS of them, and left W / C summaries
        assert [c for c in closes if c[0] > length] == [
            (w, W // PS) for w in range(-(-(length + 1) // W) * W, n + 1, W)]
        assert seq.n_sum == rows.summary_pages(n)
        assert len(seq.pages) - seq.n_sum == rows.open_pages(n)
        assert rows.rows_attended(n) == (n // W) * (W // C) + n % W + 1
        assert rows.context(n) == rows.rows_attended(n) - 1
    else:
        assert closes == [] and len(seq.pages) == -(-n // PS)
        assert rows.rows_attended(n) == n + 1 and rows.context(n) == n
    alloc.free(seq.pages)
    assert alloc.free_pages == rows.max_pages


@pytest.mark.parametrize("kind", ["plain", "eva"])
def test_a_full_pool_serves_the_longest_sequence_and_no_entry_leaves_it(kind):
    """ROADMAP D17, the test half: a pool of exactly ``max_pages`` carries one
    sequence to ``max_positions``; every table entry handed to a program lies
    in ``[0, num_pages]`` (``_drive`` checks each), the trash page included."""
    rows = _rows(kind, 16, num_pages=0)
    num_pages = rows.max_pages
    rows = _rows(kind, 16, num_pages=num_pages)
    seq, alloc, _ = _drive(rows, 37, MAXPOS, 16, num_pages)
    assert seq.length == MAXPOS and len(seq.pages) <= num_pages
    assert alloc.free_pages == num_pages - len(seq.pages)
    with pytest.raises(ValueError, match="one sequence could never run"):
        _rows(kind, 16, num_pages=num_pages - 1)


def test_the_rows_objects_refuse_a_geometry_they_cannot_keep():
    with pytest.raises(ValueError, match="whole number of pages"):
        PageRows(16, 8, ring=24)
    with pytest.raises(ValueError, match="prefill_chunk 24"):
        EvaRows(W, C, PS, MAXPOS, prefill_chunk=24)
    assert PageRows(8, 8, ring=24, whole_row=True).chunk_tables(
        SequenceState(0, [1] * 9, 9, 1, 0.0, None, pages=[3, 5]),
        np.arange(8, dtype=np.int32), 0, 9, 16, 99)[1].shape == (8,)


# --------------------------------------------- (iii) the engine names no mixer
def test_the_engine_names_no_mixer_and_no_cache_leaf():
    """``engine_v2.py`` asks ``layer_types`` and ``self.rows``: no string
    constant of its own equals a mixer's or a cache leaf's name (docstrings
    apart; ``k`` and ``v`` are also the keys of ``read_kv``'s records), and it
    counts no layers by mixer."""
    names = set(layer_types._TYPES) | {
        layer_types.layer_type(k).mixer for k in layer_types._TYPES}
    names |= {"latent", "win_k", "win_v", "kda_s", "kda_conv", "ssm_s",
              "ssm_conv", "conv_tail"}
    with open(engine_v2.__file__) as f:
        tree = ast.parse(f.read())
    docs = {id(n.body[0].value) for n in ast.walk(tree)
            if isinstance(n, (ast.Module, ast.ClassDef, ast.FunctionDef))
            and n.body and isinstance(n.body[0], ast.Expr)
            and isinstance(n.body[0].value, ast.Constant)}
    found = [(n.lineno, n.value) for n in ast.walk(tree)
             if isinstance(n, ast.Constant) and isinstance(n.value, str)
             and id(n) not in docs and n.value in names]
    calls = [n.lineno for n in ast.walk(tree) if isinstance(n, ast.Call)
             and getattr(n.func, "id", getattr(n.func, "attr", ""))
             == "layers_of"]
    assert not found and not calls, (found, calls)
