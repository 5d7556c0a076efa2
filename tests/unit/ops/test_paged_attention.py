"""Paged decode attention kernel vs the XLA gather reference
(reference tests: inference/v2 ragged_ops numeric parity).

Each case runs twice: over one layer's ``[P, ps, KVH, D]`` pool against the
gather formulation written out here, and ``layered`` — over the engine's
``[L, P, ps, KVH*D]`` pools of three different layers, read at the middle
one through the kernel's ``layer`` operand, against the decode program's
own ``_gather_window_attend`` at that layer.

The kernel walks each row's live pages in blocks of ``pages_per_block``
pages: the cases at the end hold lengths that straddle a block, an inactive
row, and poison in every page no live length covers."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.model_runner import _gather_window_attend
from deepspeed_tpu.models.transformer import (TransformerConfig,
                                              alibi_slopes)
from deepspeed_tpu.ops.pallas.paged_attention import (n_blocks,
                                                      pages_per_block,
                                                      paged_decode_attention)

N_LAYERS, LAYER = 3, 1


def _reference(q, k_pool, v_pool, page_table, positions):
    """The gather formulation paged_decode used before the kernel."""
    B, NH, D = q.shape
    P, ps, KVH, _ = k_pool.shape
    S = page_table.shape[1] * ps
    kk = k_pool[page_table].reshape(B, S, KVH, D)
    vv = v_pool[page_table].reshape(B, S, KVH, D)
    kk = jnp.repeat(kk, NH // KVH, axis=2)
    vv = jnp.repeat(vv, NH // KVH, axis=2)
    s = jnp.einsum("bnd,bsnd->bns", q, kk).astype(jnp.float32) / math.sqrt(D)
    vis = jnp.arange(S)[None, None, :] <= positions[:, None, None]
    s = jnp.where(vis, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bns,bsnd->bnd", p, vv)


def _layered(make, **scales):
    """Engine-layout pools of N_LAYERS layers, each filled by ``make()``
    (``[P, ps, KVH, D]``, KVH and D merged here as the engine stores
    them), plus per-layer scales ``name=make_scale``."""
    pools = {n: jnp.stack([x.reshape(*x.shape[:2], -1) for x in
                           (make() for _ in range(N_LAYERS))])
             for n in ("k", "v")}
    pools.update({n: jnp.stack([mk() for _ in range(N_LAYERS)])
                  for n, mk in scales.items()})
    return pools


def _attend_layer(q, pools, page_table, positions, kvh):
    """(kernel, decode program's gather path) at layer LAYER of pools."""
    B, NH, D = q.shape
    S = page_table.shape[1] * pools["k"].shape[2]
    cfg = TransformerConfig(hidden_size=NH * D, n_heads=NH, n_kv_heads=kvh,
                            position="rope")
    out = paged_decode_attention(
        q, pools["k"], pools["v"], page_table, positions,
        k_scale=pools.get("k_scale"), v_scale=pools.get("v_scale"),
        layer=jnp.int32(LAYER))
    vis = jnp.arange(S)[None, None, :] <= positions[:, None, None]
    ref = _gather_window_attend(cfg, q[:, None], pools, LAYER, page_table,
                                positions[:, None], vis)
    return out, ref[:, 0].reshape(B, NH, D)


@pytest.mark.parametrize("layered", [False, True], ids=["one", "layered"])
@pytest.mark.parametrize("kvh", [4, 1, 2])
def test_paged_decode_matches_gather(kvh, layered):
    rng = np.random.RandomState(0)
    B, NH, D, ps, MP = 3, 4, 16, 8, 4
    P = B * MP + 1  # +1 trash
    trash = P - 1
    q = jnp.asarray(rng.randn(B, NH, D), jnp.float32)
    k_pool = jnp.asarray(rng.randn(P, ps, kvh, D), jnp.float32)
    v_pool = jnp.asarray(rng.randn(P, ps, kvh, D), jnp.float32)
    # each sequence: random distinct pages, trash beyond its length
    positions = jnp.asarray([5, 17, 30], jnp.int32)  # 1, 3, 4 pages used
    table = np.full((B, MP), trash, np.int64)
    perm = rng.permutation(P - 1)
    n = 0
    for b, pos in enumerate([5, 17, 30]):
        used = pos // ps + 1
        table[b, :used] = perm[n:n + used]
        n += used
    page_table = jnp.asarray(table, jnp.int32)

    if layered:
        pools = _layered(lambda: jnp.asarray(
            rng.randn(P, ps, kvh, D), jnp.float32))
        out, ref = _attend_layer(q, pools, page_table, positions, kvh)
    else:
        out = paged_decode_attention(q, k_pool, v_pool, page_table, positions)
        ref = _reference(q, k_pool, v_pool, page_table, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_paged_decode_trash_pages_ignored():
    """Garbage in the trash page must not leak: only slots <= position
    contribute, and pages past the length are trash by construction."""
    rng = np.random.RandomState(1)
    B, NH, D, ps, MP = 1, 2, 8, 4, 3
    P = 4
    q = jnp.asarray(rng.randn(B, NH, D), jnp.float32)
    k_pool = jnp.asarray(rng.randn(P, ps, NH, D), jnp.float32)
    v_pool = jnp.asarray(rng.randn(P, ps, NH, D), jnp.float32)
    k_huge = k_pool.at[-1].set(1e4)  # poison the trash page
    v_huge = v_pool.at[-1].set(1e4)
    positions = jnp.asarray([3], jnp.int32)  # one page used
    page_table = jnp.asarray([[0, P - 1, P - 1]], jnp.int32)
    out = paged_decode_attention(q, k_huge, v_huge, page_table, positions)
    clean = paged_decode_attention(
        q, k_pool.at[-1].set(0), v_pool.at[-1].set(0), page_table, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(clean),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("layered", [False, True], ids=["one", "layered"])
def test_paged_decode_quantized_matches_dequant(layered):
    """Kernel dequant-in-VMEM path vs dequantize-then-gather reference."""
    rng = np.random.RandomState(2)
    B, NH, D, ps, MP, KVH = 2, 4, 16, 8, 3, 2
    P = 8
    q = jnp.asarray(rng.randn(B, NH, D), jnp.float32)
    codes_k = jnp.asarray(rng.randint(-127, 128, (P, ps, KVH, D)), jnp.int8)
    codes_v = jnp.asarray(rng.randint(-127, 128, (P, ps, KVH, D)), jnp.int8)
    ks = jnp.asarray(rng.rand(P, ps, KVH) * 0.05 + 0.01, jnp.float32)
    vs = jnp.asarray(rng.rand(P, ps, KVH) * 0.05 + 0.01, jnp.float32)
    positions = jnp.asarray([10, 20], jnp.int32)
    table = jnp.asarray([[0, 1, 7], [2, 3, 4]], jnp.int32)

    if layered:
        scale = lambda: jnp.asarray(  # noqa: E731
            rng.rand(P, ps, KVH) * 0.05 + 0.01, jnp.float32)
        pools = _layered(lambda: jnp.asarray(
            rng.randint(-127, 128, (P, ps, KVH, D)), jnp.int8),
            k_scale=scale, v_scale=scale)
        out, ref = _attend_layer(q, pools, table, positions, KVH)
    else:
        out = paged_decode_attention(q, codes_k, codes_v, table, positions,
                                     k_scale=ks, v_scale=vs)
        ref = _reference(q, codes_k.astype(jnp.float32) * ks[..., None],
                         codes_v.astype(jnp.float32) * vs[..., None],
                         table, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------- the walk over a row's live pages
WALK_PS, WALK_MP, WALK_KVH, WALK_D = 16, 40, 2, 16


def _walk_case(rng, g, variant, kvh=WALK_KVH, d=WALK_D, dtype=jnp.float32,
               inactive_at=5, **kernel_kw):
    """Rows whose lengths straddle a block of the kernel's walk — one of a
    single token, one that ends exactly on a block's edge, one a token
    past it, a full table — and one row inactive (at ``inactive_at`` of the
    one grid step the six rows make), over scattered non-monotone page
    tables.  Table entries past a row's live pages — all of the inactive
    row's — name POISON pages (± 3e38 and NaN), as do the pool's unused
    pages.  Returns the kernel's output over the poisoned pools (stored as
    ``dtype``; ``kernel_kw``: its ``scale`` and ``name``) and
    `_gather_window_attend` in float32 over the same pools with the poison
    zeroed."""
    ps, MP, KVH, D = WALK_PS, WALK_MP, kvh, d
    quant, alibi = variant == "int8", variant == "alibi"
    layered = variant != "one"
    nb = pages_per_block(ps, KVH * D, 1 if quant else jnp.dtype(dtype).itemsize)
    T = nb * ps
    assert 2 * T + 3 < MP * ps  # the table holds more than two blocks
    order = list(range(5))
    order.insert(inactive_at, 5)
    positions = np.asarray([0, T - 1, T, MP * ps - 1, 2 * T + 3, 37])[order]
    active = np.asarray([True, True, True, True, True, False])[order]
    B, NH = len(positions), KVH * g
    n_live = np.where(active, positions // ps + 1, 0)
    P = int(n_live.sum()) + 9  # live pages, 8 poison pages, the trash page
    perm = rng.permutation(P - 1)
    live, poison = perm[:n_live.sum()], perm[n_live.sum():]
    table = rng.choice(poison, (B, MP))
    n = 0
    for b in range(B):
        table[b, :n_live[b]] = live[n:n + n_live[b]]
        n += n_live[b]
    bad = np.asarray([3e38, -3e38, np.nan, 3e38, np.nan, -3e38, np.nan, 3e38],
                     np.float32)

    def pool(make, fill, store):
        """(poisoned as stored, clean float32) [L, P, ps, ...] pools of
        N_LAYERS layers; the clean one holds what the stored one does."""
        made = np.stack([make() for _ in range(N_LAYERS if layered else 1)])
        dirty = made.copy()
        shape = (1, len(poison)) + (1,) * (made.ndim - 2)
        made[:, poison] = 0
        dirty[:, poison] = fill.reshape(shape).astype(made.dtype)
        clean = jnp.asarray(made, store)
        return jnp.asarray(dirty, store), (
            clean if store == jnp.int8 else clean.astype(jnp.float32))

    if quant:
        codes = lambda: rng.randint(-127, 128, (P, ps, KVH * D)).astype(  # noqa: E731
            np.int8)
        # values of about a unit normal's size at any head dimension
        scale = lambda: ((rng.rand(P, ps, KVH) * 0.05 + 0.01)  # noqa: E731
                         * 4 / math.sqrt(D)).astype(np.float32)
        names = {"k": (codes, np.full(8, 127), jnp.int8),
                 "v": (codes, np.full(8, -127), jnp.int8),
                 "k_scale": (scale, bad, jnp.float32),
                 "v_scale": (scale, bad, jnp.float32)}
    else:
        vals = lambda: rng.randn(P, ps, KVH * D).astype(np.float32)  # noqa: E731
        names = {"k": (vals, bad, dtype), "v": (vals, bad[::-1], dtype)}
    dirty, clean = {}, {}
    for name, (make, fill, store) in names.items():
        dirty[name], clean[name] = pool(make, fill, store)

    q = jnp.asarray(rng.randn(B, NH, D), dtype)
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    cfg = TransformerConfig(hidden_size=NH * D, n_heads=NH, n_kv_heads=KVH,
                            position="alibi" if alibi else "rope")
    slopes = alibi_slopes(NH) if alibi else None
    lyr = LAYER if layered else 0
    if layered:
        out = paged_decode_attention(
            q, dirty["k"], dirty["v"], table, pos,
            k_scale=dirty.get("k_scale"), v_scale=dirty.get("v_scale"),
            alibi_slopes=slopes, layer=jnp.int32(lyr),
            active=jnp.asarray(active), **kernel_kw)
    else:  # one layer's [P, ps, KVH, D] pool
        out = paged_decode_attention(
            q, dirty["k"][0].reshape(P, ps, KVH, D),
            dirty["v"][0].reshape(P, ps, KVH, D), table, pos,
            active=jnp.asarray(active), **kernel_kw)
    vis = jnp.arange(MP * ps)[None, None, :] <= pos[:, None, None]
    ref = _gather_window_attend(cfg, q.astype(jnp.float32)[:, None], clean,
                                lyr, table, pos[:, None], vis,
                                scale=kernel_kw.get("scale"))
    return (np.asarray(out.astype(jnp.float32)),
            np.asarray(ref[:, 0].reshape(B, NH, D)), active)


@pytest.mark.parametrize("variant", ["layered", "one", "int8", "alibi"])
@pytest.mark.parametrize("g", [1, 4, 8])
def test_paged_decode_walks_the_live_pages_only(g, variant):
    """Positions 0, nb*ps - 1, nb*ps, MP*ps - 1 and mid-block against the
    decode program's gather path; whatever the table names past a row's
    length is never read, and an inactive row is finite zeros."""
    out, ref, active = _walk_case(np.random.RandomState(7 + g), g, variant)
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out[active], ref[active], rtol=1e-4,
                               atol=1e-5)
    assert not out[~active].any()


#: the geometries a block is attended at in the serving cells: Phi-4-mini-
#: flash's pairs (F = 1280) under the window layers' name and scale, and
#: Solar-Open2's 8 query heads a K/V head (64 stacked rows of scores)
GEOMETRIES = {
    "phi4-pairs": dict(kvh=10, g=4, scale=0.125, name="dstpu_window_decode"),
    "solar-g8": dict(kvh=8, g=8),
}


@pytest.mark.parametrize("variant", ["layered", "one", "int8", "alibi"])
@pytest.mark.parametrize("geo", sorted(GEOMETRIES))
def test_paged_decode_attends_a_block_for_all_its_heads(geo, variant):
    """bfloat16 at head 128 in blocks of `pages_per_block` pages: a row of
    one token, one that ends on a block's edge, an inactive row between
    two live ones in the one grid step, poison in every unfetched page;
    against the gather path in float32."""
    out, ref, active = _walk_case(
        np.random.RandomState(len(geo)), variant=variant, d=128,
        dtype=jnp.bfloat16, inactive_at=2, **GEOMETRIES[geo])
    assert active.tolist() == [True, True, False, True, True, True]
    assert np.all(np.isfinite(out))
    # bfloat16 keys, values, probabilities and result against float32
    np.testing.assert_allclose(out[active], ref[active], rtol=2e-2,
                               atol=2e-2 * np.abs(ref).max())
    assert not out[~active].any()


def test_n_blocks_is_the_kernels_loop_bound():
    """The host's `decode_kv_blocks` and the kernel's loop share one
    function; block size follows the page geometry alone."""
    assert pages_per_block(16, 8 * 128, 2) == 16     # bf16 Mistral / Solar
    assert pages_per_block(16, 8 * 128, 1) == 16     # int8: 256 tokens bind
    assert pages_per_block(16, 10 * 128, 2) == 16    # Phi-4's pairs
    assert pages_per_block(16, 32 * 128, 2) == 8     # MHA: the slot binds
    assert pages_per_block(256, 8 * 128, 2) == 1
    lengths = np.asarray([0, 1, 255, 256, 257, 4096])
    assert n_blocks(lengths, 16, 16).tolist() == [0, 1, 1, 1, 2, 16]
