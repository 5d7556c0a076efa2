"""The latent decode kernel (``dstpu_mla_decode``) interpreted, over rows
that span SEVERAL items of its walk at the block its own rule gives the two
latent configurations' pools.

Oracle: a dense float32 softmax over the row's visible cached rows.  Two
dtypes: float32 holds the walk to 5e-5 (the statistics carried from block to
block, ``i_next``, ``last``, the partial item after full ones), bfloat16 runs
the very blocks the configurations' pools get (64 pages at 384 lanes, 48 at
640) to the rounding of its probabilities.  NaN is planted in every page past
a row's length, in inactive rows' pages, in the trash page and in the lane
padding: none of it may reach an output.
"""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from deepspeed_tpu.ops.pallas import mla_attention  # noqa: E402
from deepspeed_tpu.ops.pallas.mla_attention import (  # noqa: E402
    latent_pages_per_block, mla_decode_attention)
from deepspeed_tpu.ops.pallas.paged_attention import n_blocks  # noqa: E402

PS, NH = 16, 8
#: (rank, rotary, lanes as laid out): Mistral-Small-4's pool, Xing4.0's
GEOMETRIES = [(256, 64, 384), (512, 64, 640)]
#: rows by name -> (lengths in tokens given a block of T, active); a length
#: of None is a row that is not active (its pages hold NaN)
ROWS = {
    # 8 rows a grid step: every edge of the walk in one item sequence
    "edges8": lambda T: [1, PS, T - 1, T, T + 1, 3 * T + PS, None, 2 * T],
    # 5 rows a grid step, inactive rows between active ones
    "between5": lambda T: [T + 1, None, 3 * T + PS, None, 1],
    # two grid steps of 8: the first has no item at all, the second starts
    # and ends on inactive rows
    "steps16": lambda T: [None] * 8 + [None, 2 * T + 3, None, None, T,
                                       PS + 1, 2 * T, None],
}


def _dense(q, rows, rank):
    s = q @ rows[:, :q.shape[-1]].T
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ rows[:, :rank]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("rank,dr,lanes", GEOMETRIES)
def test_rows_of_several_items_are_a_dense_softmax(rank, dr, lanes, rows,
                                                   dtype):
    dt = jnp.dtype(dtype)
    nb = latent_pages_per_block(PS, lanes, dt.itemsize)
    T = nb * PS
    lengths = ROWS[rows](T)
    B = len(lengths)
    pages = [-(-(n or 0) // PS) for n in lengths]
    P = sum(pages) + 3 * B          # what the rows hold, and spare pages
    MP = max(pages) + 1             # a table no multiple of the block wide
    assert MP % nb and max(pages) > 2 * nb
    rng = np.random.default_rng(59)
    pool = rng.normal(size=(2, P + 1, PS, lanes)).astype(np.float32)
    pool = np.array(jnp.asarray(pool, dt).astype(jnp.float32))
    pool[..., rank + dr:] = np.nan                      # the lane padding
    pool[:, P] = np.nan                                 # the trash page
    table = np.full((B, MP), P, np.int32)
    ids = rng.permutation(P)                            # shuffled page ids
    live = np.zeros((P + 1,), bool)
    for b, n in enumerate(pages):
        table[b, :n], ids = ids[:n], ids[n:]
        live[table[b, :n]] = True
    pool[:, ~live] = np.nan           # every page past a row's length
    # an inactive row points at pages full of NaN and at a stale position
    for b, n in enumerate(lengths):
        if n is None:
            table[b, :5] = ids[:5]
    q = (rng.normal(size=(B, NH, rank + dr)) * 0.3).astype(np.float32)
    q = np.asarray(jnp.asarray(q, dt).astype(jnp.float32))
    active = np.asarray([n is not None for n in lengths])
    pos = np.asarray([40 if n is None else n - 1 for n in lengths], np.int32)
    out = np.asarray(mla_decode_attention(
        jnp.asarray(q, dt), jnp.asarray(pool, dt), jnp.asarray(table),
        jnp.asarray(pos), 1, jnp.asarray(active), rank=rank
    ).astype(jnp.float32))
    assert out.shape == (B, NH, rank)
    tol = 5e-5 if dtype == "float32" else 2e-2
    for b, n in enumerate(lengths):
        if n is None:
            assert not out[b].any()
            continue
        seen = pool[1, table[b, :pages[b]]].reshape(-1, lanes)[:n]
        np.testing.assert_allclose(out[b], _dense(q[b], seen, rank), rtol=0,
                                   atol=tol)


def test_a_row_at_position_minus_one_attends_nothing():
    """Length zero by position, not by ``active``: no item, zeros."""
    rank, dr, lanes = GEOMETRIES[0]
    pool = np.full((1, 3, PS, lanes), np.nan, np.float32)
    out = mla_decode_attention(
        jnp.ones((2, NH, rank + dr), jnp.float32), jnp.asarray(pool),
        jnp.zeros((2, 4), jnp.int32), jnp.asarray([-1, -1], jnp.int32), 0,
        jnp.asarray([True, True]), rank=rank)
    assert not np.asarray(out).any()


def test_a_table_entry_outside_the_pool_is_held_inside_it():
    """The compiler's bounds checks are off in this kernel, and it clamps
    the entry itself: one past the pool's last page (or under its first)
    reads that page, not memory that is no page.  (The interpreter clamps an
    index whatever the kernel does; this pins what the output must be.)"""
    rank, dr, lanes = GEOMETRIES[0]
    rng = np.random.default_rng(7)
    pool = rng.normal(size=(1, 6, PS, lanes)).astype(np.float32)
    q = (rng.normal(size=(2, NH, rank + dr)) * 0.3).astype(np.float32)
    pos = jnp.asarray([3 * PS - 1, 2 * PS - 1], jnp.int32)
    out, want = (np.asarray(mla_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table, jnp.int32),
        pos, 0, jnp.asarray([True, True]), rank=rank))
        for table in ([[2, 6 + 100, 4], [-7, 1, 0]], [[2, 5, 4], [0, 1, 0]]))
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("ps,lanes,itemsize,want", [
    (16, 384, 2, 64),    # Mistral-Small-4: 786 KB a slot, 1,024 tokens
    (16, 640, 2, 48),    # Xing4.0: 983 KB a slot, 768 tokens
    (16, 384, 4, 40), (16, 640, 4, 24),
    (256, 384, 2, 4),    # the cap on tokens binds
    (16, 8192, 2, 4),    # a page of 256 KB: the slot binds, under a lane tile
    (4, 128, 4, 256)])   # the tiny engines' pages
def test_the_block_fills_a_slot_under_the_cap_on_tokens(ps, lanes, itemsize,
                                                        want):
    nb = latent_pages_per_block(ps, lanes, itemsize)
    assert nb == want
    assert nb * ps * lanes * itemsize <= mla_attention._SLOT_BYTES
    assert nb * ps <= mla_attention._BLOCK_TOKENS
    assert nb * ps % 128 == 0 or nb * ps < 128
    # one page more would not fit, or would pass the cap, or split a tile
    more = nb + max(1, 128 // ps)
    assert (more * ps * lanes * itemsize > mla_attention._SLOT_BYTES
            or more * ps > mla_attention._BLOCK_TOKENS)


def test_the_engine_counts_the_blocks_this_kernel_walks(monkeypatch):
    """A latent engine's ``decode_kv_blocks`` and ``latent_block_slots`` are
    this module's own arithmetic over the rows' lengths — at a cap on tokens
    small enough that the tiny rows span several blocks."""
    from benchmark.families import mistral4 as family
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig,
                                            RaggedRequest)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-small4-119b-ep8-serve.json")) as f:
        config = json.load(f)
    tiny, ecfg = dict(config, **config["tiny"]), config["tiny_engine"]
    ps = ecfg["page_size"]
    monkeypatch.setattr(mla_attention, "_BLOCK_TOKENS", 4 * ps)
    model = family.build(tiny, tiny["num_hidden_layers"],
                         ps * ecfg["max_pages_per_seq"], jnp.float32)
    eng = InferenceEngineV2(model, RaggedInferenceConfig(**ecfg), seed=0)
    leaf = eng._pools["latent"]
    nb = latent_pages_per_block(ps, leaf.shape[-1], leaf.dtype.itemsize)
    assert eng._kv_block_pages == nb == 4
    rng = np.random.default_rng(3)
    prompts = {eng.put(RaggedRequest(prompt_ids=rng.integers(0, 256, n)
                                     .tolist(), max_new_tokens=6)): n
               for n in (70, 13, 37)}
    have = dict.fromkeys(prompts, 0)
    blocks = steps = 0
    while eng.has_work():
        out = eng.step()
        for uid, o in out.items():
            have[uid] += len(o["tokens"])
        counts = eng._step_counts
        # a row that decoded saw its prompt and what it had generated, the
        # token of this step excepted (a prompt's last chunk decodes at once)
        lengths = np.asarray([prompts[u] + have[u] - 1 for u in out], int)
        assert counts["decode_rows"] == len(lengths)
        want = int(n_blocks(lengths, ps, nb).sum()) if len(lengths) else 0
        assert counts["decode_kv_blocks"] == want
        assert counts.get("latent_block_slots", 0) == want * nb * ps
        assert counts.get("latent_kv_tokens", 0) == int(lengths.sum())
        blocks, steps = blocks + want, steps + 1
    assert blocks > 3 * steps       # rows of several blocks
    assert eng.decode_stats()["decode_kv_blocks"] == blocks


def test_the_benchmark_reads_the_fill_from_the_engines_counters():
    """``mla_block_fill``: listed for both latent cells and no other, read
    by its own reader off ``decode_stats``; a program that counts no blocks
    (the parent commit) gives no reading and does not raise."""
    from benchmark.manifest import Manifest

    man = Manifest()
    cells = [w["name"] for w in man.data["workloads"]
             if any(m["name"] == "mla_block_fill"
                    for m in man.per_layer(w["name"]))]
    assert cells == ["mistralsmall4-ep8-docqa-saturated",
                     "xing4-pp7-longrag-saturated"]
    spec = man.layer_metric("mla_block_fill")
    read = man.module("readers", spec["reader"]).read
    stats = {"latent_kv_tokens": 900, "latent_block_slots": 1024}
    assert read({"result": {"decode_stats": stats}}, **spec["args"]) == \
        900 / 1024
    for result in ({}, {"decode_stats": {"decode_kv_blocks": 7}},
                   {"decode_stats": dict(stats, latent_block_slots=0)}):
        assert read({"result": result}, **spec["args"]) is None
