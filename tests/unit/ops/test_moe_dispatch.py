"""The dispatch and combine row kernels (``ops/pallas/moe_dispatch.py``,
interpreted) against the XLA form of ``_sorted_expert_ffn``: the rows of
the picks held here are moved, nothing of the blocks past ``n_real`` is read
or reaches a gradient, and the padding rows of the real blocks are zeros in
``xs`` and in ``d ys`` (ISSUE 33).

The interpreter fills what a kernel never writes with NaN."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import (MoEConfig, _sorted_expert_ffn,
                                           moe_ffn_dropless, pick_row_maps,
                                           sort_pad_by_expert)
from deepspeed_tpu.ops.pallas import grouped_matmul as gmm_mod
from deepspeed_tpu.ops.pallas import moe_dispatch as rows_mod
from deepspeed_tpu.ops.pallas.moe_dispatch import (combine_rows,
                                                   dispatch_rows,
                                                   moe_combine, moe_dispatch,
                                                   rows_kernel_serves)


@pytest.fixture
def kernels(monkeypatch):
    """``impl="auto"`` as on the chip: the grouped matmul's kernels too
    (the einsum's backward reads every row of the buffer, undefined ones
    included)."""
    monkeypatch.setattr(gmm_mod, "on_tpu", lambda: True)


def _experts(rng, E, H, F, dtype):
    return {n: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[1]), dtype)
            for n, s in (("w_gate", (E, H, F)), ("w_up", (E, H, F)),
                         ("w_down", (E, F, H)))}


def _keys(rng, how, T, K, E):
    """Each pick's key; ``E`` is the invalid one."""
    if how == "none":
        return np.full(T * K, E)
    if how == "all":
        return rng.integers(0, E, T * K)
    # tokens with 0, 1, ... and top_k held picks, some experts untouched
    key = rng.choice([0, 0, 2, E - 1], (T, K))
    held = np.arange(T) % (K + 1)
    key[np.arange(K)[None, :] >= held[:, None]] = E
    return key.reshape(-1)


#: (picks held, dtype, block rows, top_k, hidden).  The last three are a row
#: of 12 word-sublanes (ISSUE 57: Laguna's 3072 in bfloat16, 1536 in float32)
#: and one of 4; the kernels have ONE layout, on the chip and under the
#: interpreter — the ring holds a row in ``sw`` sublanes whatever ``sw`` is —
#: so nothing has to be forced here for the chip's form to be the one run
CASES = [("mixed", "float32", 8, 2, 256), ("mixed", "bfloat16", 16, 4, 256),
         ("none", "float32", 8, 2, 256), ("all", "float32", 8, 3, 256),
         ("all", "bfloat16", 16, 2, 256), ("mixed", "float32", 32, 4, 256),
         ("mixed", "bfloat16", 16, 3, 3072), ("mixed", "float32", 8, 2, 1536),
         ("all", "bfloat16", 16, 2, 1024),
         # 14 word-sublanes (ISSUE 58: Xing4.0's 3584 in bfloat16): right
         # under the interpreter, which has no tiles; on the chip Mosaic
         # refuses to cut such a row out of the source's uint32 view, and
         # ``rows_kernel_serves`` leaves the call to XLA
         ("mixed", "bfloat16", 16, 4, 3584)]


@pytest.mark.parametrize("how, dtype, block_rows, K, H", CASES)
def test_tail_through_the_kernels_equals_the_xla_form(how, dtype, block_rows,
                                                      K, H):
    """Forward of dispatch -> three grouped matmuls -> combine, and d xt,
    d gate and every expert matrix's gradient (the combine's ``dot`` form
    and both kernels' ``custom_vjp`` backward), against ``jax.grad`` of the
    XLA form; tokens with 0, 1 and ``top_k`` held picks, no pick held at all
    (``n_real`` 0) and every pick held."""
    dtype = jnp.dtype(dtype)
    T, E, F = 21, 4, 128
    rng = np.random.default_rng(block_rows + K)
    key = jnp.asarray(_keys(rng, how, T, K, E), jnp.int32)
    xt = jnp.asarray(rng.standard_normal((T, H)), dtype)
    gate = jnp.asarray(rng.random(T * K), jnp.float32)
    experts = _experts(rng, E, H, F, dtype)

    def f(experts, xt, gate, impl):
        out, counts, ran, grid = _sorted_expert_ffn(
            xt, key, gate, K, E, experts, "swiglu", block_rows, impl=impl)
        return jnp.sum(jnp.cos(out.astype(jnp.float32))), (out, counts, ran)

    (_, got), g = jax.value_and_grad(f, (0, 1, 2), has_aux=True)(
        experts, xt, gate, "pallas")
    (_, want), r = jax.value_and_grad(f, (0, 1, 2), has_aux=True)(
        experts, xt, gate, "xla")
    # (a gradient entry at 3072 sums twelve times the products of one at
    # 256, each rounded to bfloat16 by the XLA form: an ulp more)
    tol = 2e-5 if dtype == jnp.float32 else 2.0 ** (-6 if H == 256 else -5)
    for a, b in zip(jax.tree_util.tree_leaves((got, g)),
                    jax.tree_util.tree_leaves((want, r))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, atol=tol * max(1.0, np.abs(b).max()))
    if how == "none":
        assert int(got[2]) == 0 and not np.asarray(got[0]).any()
        assert not any(np.asarray(x).any()
                       for x in jax.tree_util.tree_leaves(g))
    # a token without a held pick gets exact zeros and no gradient
    none_held = np.all(np.asarray(key).reshape(T, K) == E, axis=1)
    assert not np.asarray(got[0], np.float32)[none_held].any()
    assert not np.asarray(g[1], np.float32)[none_held].any()


@pytest.mark.parametrize("dtype, block_rows, H", [
    ("float32", 8, 256), ("bfloat16", 16, 256), ("bfloat16", 16, 3072),
    ("float32", 8, 1536)])
def test_no_row_past_n_real_is_read_and_padding_rows_are_zeros(dtype,
                                                               block_rows, H):
    """NaN in every row of the blocks past ``n_real`` — by the interpreter in
    ``xs`` and ``d ys``, planted in ``ys`` and ``d xs`` — reaches no output
    and no gradient; the rows of the real blocks past their expert's picks
    read zero in ``xs`` and in ``d ys``."""
    dtype = jnp.dtype(dtype)
    T, K, E = 19, 3, 5
    rng = np.random.default_rng(block_rows)
    key = jnp.asarray(_keys(rng, "mixed", T, K, E), jnp.int32)
    row_pick, n_valid, dest, counts, n_rows, be, n_real = pick_row_maps(
        key, K, E, block_rows)
    maps = (row_pick, n_valid, n_real, dest, block_rows)
    real = int(n_real) * block_rows
    assert 0 < real < n_rows and int(np.sum(n_valid)) < real
    held = np.zeros(n_rows, bool)
    held[np.asarray(dest)[np.asarray(dest) >= 0]] = True
    xt = jnp.asarray(rng.standard_normal((T, H)), dtype)
    gate = jnp.asarray(rng.random((T, K)), jnp.float32)
    past = (jnp.arange(n_rows) >= real)[:, None]

    xs, pull = jax.vjp(lambda x: moe_dispatch(x, *maps), xt)
    xs32 = np.asarray(xs, np.float32)
    assert np.isnan(xs32[real:]).all()          # never written
    assert not xs32[:real][~held[:real]].any()  # padding rows
    np.testing.assert_array_equal(
        xs32[:real][held[:real]],
        np.asarray(xt, np.float32)[np.asarray(row_pick)[:real][held[:real]]
                                   // K])
    dxs = jnp.where(past, jnp.nan, jnp.asarray(
        rng.standard_normal((n_rows, H)), dtype))
    (dxt,) = pull(dxs)
    assert np.isfinite(np.asarray(dxt, np.float32)).all()

    ys = jnp.where(past, jnp.nan, jnp.asarray(
        rng.standard_normal((n_rows, H)), dtype))
    out, pull = jax.vjp(lambda y, g: moe_combine(y, g, *maps), ys, gate)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    dys, dgate = pull(jnp.asarray(rng.standard_normal((T, H)), dtype))
    dys32 = np.asarray(dys, np.float32)
    assert np.isnan(dys32[real:]).all()
    assert not dys32[:real][~held[:real]].any()
    assert np.abs(dys32[:real][held[:real]]).min(axis=1).max() > 0
    assert np.isfinite(np.asarray(dgate)).all()
    assert not np.asarray(dgate)[np.asarray(dest) < 0].any()


@pytest.mark.parametrize("block_rows", [8, 16, 128])
@pytest.mark.parametrize("seed", [0, 1])
def test_row_maps_agree_with_the_sort(block_rows, seed):
    """``pick_row_maps`` (arithmetic, a cumulative sum, block slices of the
    sort) names the rows ``sort_pad_by_expert``'s scatter would fill."""
    rng = np.random.default_rng(seed)
    E, K, T = 7, 3, 50
    key = rng.choice(np.arange(E + 1), T * K,
                     p=[.4, .2, 0, .1, .05, 0, .05, .2])
    keyj = jnp.asarray(key, jnp.int32)
    order, sdest, n_rows, be, n_real = sort_pad_by_expert(keyj, E, block_rows)
    row_pick, n_valid, dest, counts, n_rows2, be2, n_real2 = pick_row_maps(
        keyj, K, E, block_rows)
    assert n_rows2 == n_rows and int(n_real2) == int(n_real)
    np.testing.assert_array_equal(np.asarray(be2), np.asarray(be))
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(key, minlength=E + 1)[:E])
    want = np.full(T * K, -1)
    ok = np.asarray(sdest) < n_rows
    want[np.asarray(order)[ok]] = np.asarray(sdest)[ok]
    np.testing.assert_array_equal(np.asarray(dest).reshape(-1), want)
    row_pick, n_valid = np.asarray(row_pick), np.asarray(n_valid)
    for b in range(n_rows // block_rows):
        for j in range(n_valid[b]):
            assert want[row_pick[b * block_rows + j]] == b * block_rows + j
    assert n_valid.sum() == (key < E).sum()
    assert not n_valid[int(n_real):].any()


def test_under_checkpoint_inside_a_scan():
    """As the training cell runs it: the layer under ``jax.checkpoint`` in a
    ``lax.scan`` over stacked experts."""
    T, K, E, H, F, L = 16, 2, 3, 256, 128, 2
    rng = np.random.default_rng(5)
    key = jnp.asarray(_keys(rng, "mixed", T, K, E), jnp.int32)
    gate = jnp.asarray(rng.random(T * K), jnp.float32)
    xt = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    stacked = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a),
        *[_experts(rng, E, H, F, jnp.float32) for _ in range(L)])

    def loss(stacked, xt, impl):
        @jax.checkpoint
        def layer(x, experts):
            out, _, _, _ = _sorted_expert_ffn(x, key, gate, K, E, experts,
                                              "swiglu", 8, impl=impl)
            return x + out, None
        return jnp.sum(jnp.sin(jax.lax.scan(layer, xt, stacked)[0]))

    got = jax.grad(loss, (0, 1))(stacked, xt, "pallas")
    want = jax.grad(loss, (0, 1))(stacked, xt, "xla")
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_the_dropless_layer_moves_every_pick(kernels, monkeypatch):
    """``moe_ffn_dropless`` (every expert held) through the kernels: all
    ``T * top_k`` rows are moved, which is the proof that nothing is
    dropped."""
    monkeypatch.setattr(rows_mod, "on_tpu", lambda: True)
    monkeypatch.setattr(rows_mod, "rows_kernel_serves", lambda h, d, n: True)
    rng = np.random.default_rng(6)
    B, S, H, F, E, K = 2, 9, 256, 128, 4, 2
    cfg = MoEConfig(num_experts=E, top_k=K, drop_tokens=False)
    x = jnp.asarray(rng.standard_normal((B, S, H)), jnp.float32)
    gate_w = jnp.asarray(rng.standard_normal((H, E)), jnp.float32)
    experts = _experts(rng, E, H, F, jnp.float32)
    seen = []
    real = rows_mod.dispatch_rows

    def spy(src, row_src, n_valid, n_real, block_rows):
        seen.append(int(np.sum(n_valid)))
        return real(src, row_src, n_valid, n_real, block_rows)

    monkeypatch.setattr(rows_mod, "dispatch_rows", spy)
    got, _ = moe_ffn_dropless(x, gate_w, experts, cfg, block_rows=8)
    assert seen == [B * S * K]
    monkeypatch.setattr(rows_mod, "on_tpu", lambda: False)
    want, _ = moe_ffn_dropless(x, gate_w, experts, cfg, block_rows=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_combine_sums_in_float32_and_rounds_once():
    """Many bfloat16 rows onto one token: the kernel's sum is the float32
    sum rounded once."""
    H, K = 256, 8
    rng = np.random.default_rng(7)
    ys = jnp.asarray(rng.standard_normal((16, H)) * 37, jnp.bfloat16)
    dest = jnp.asarray([np.arange(K), [-1] * K], jnp.int32)
    w = jnp.asarray(rng.random((2, K)), jnp.float32)
    got = np.asarray(combine_rows(ys, dest, weights=w), np.float32)
    exact = (np.asarray(ys[:K], np.float64)
             * np.asarray(w[0], np.float64)[:, None]).sum(0)
    np.testing.assert_array_equal(
        got[0], np.asarray(jnp.asarray(exact, jnp.float32)
                           .astype(jnp.bfloat16), np.float32))
    assert not got[1].any()


@pytest.mark.parametrize("h, dtype, on_chip, picks, want", [
    (2048, "bfloat16", True, 32768, True), (4096, "bfloat16", True, 1024, True),
    (1024, "float32", True, 8, True),
    # 12 word-sublanes: Laguna's hidden size in bfloat16, and in float32
    (3072, "bfloat16", True, 10240, True), (1536, "float32", True, 8, True),
    # 4, 2 and 1 word-sublanes: served since PR 57 — one form, the kernel a
    # row of 8 has (the ring takes a row at any sublane), and on the chip
    # both kernels read equal to XLA at each of these widths as at 12
    # (PERF.md section 6, PR 57), so no width is held back on the TPU
    (1024, "bfloat16", True, 8, True), (512, "bfloat16", True, 8, True),
    (256, "bfloat16", True, 8, True), (128, "float32", True, 8, True),
    (64, "float32", True, 8, False),
    (2048, "float16", True, 8, False), (2000, "float32", False, 8, False),
    (32, "float32", False, 8, True), (128, "bfloat16", False, 8, False),
    (2048, "bfloat16", True, 2 ** 17, True),
    (2048, "bfloat16", True, 2 ** 17 + 4, False),
    # a bfloat16 source is read through its uint32 view, tiled by 4
    # word-sublanes: compiled for a described v5e, a row of 3, 5, 6, 10 or 14
    # of them (Xing4.0's 3584) is REFUSED by Mosaic ("Slice shape along
    # dimension 1 must be aligned to tiling (4)"), so XLA serves those on the
    # chip; the interpreter, and a float32 row of any count, take the kernels
    (3584, "bfloat16", True, 8192, False), (3584, "bfloat16", False, 8192, True),
    (768, "bfloat16", True, 8, False), (1280, "bfloat16", True, 8, False),
    (1792, "float32", True, 8, True), (640, "float32", True, 8, True)])
def test_which_calls_the_kernels_serve(h, dtype, on_chip, picks, want,
                                       monkeypatch):
    monkeypatch.setattr(rows_mod, "on_tpu", lambda: on_chip)
    assert rows_kernel_serves(h, jnp.dtype(dtype), picks) is want


def test_dispatch_zero_rows_when_a_block_is_ragged():
    """``dispatch_rows`` alone: a block's rows past ``n_valid`` are zeros
    whatever the ring held before (the second block reuses the first's
    slot two steps later)."""
    rng = np.random.default_rng(8)
    src = jnp.asarray(rng.standard_normal((10, 128)), jnp.float32)
    row_src = jnp.asarray(rng.integers(0, 10, 4 * 8), jnp.int32)
    n_valid = jnp.asarray([8, 3, 5, 0], jnp.int32)
    out = np.asarray(dispatch_rows(src, row_src, n_valid, 3, 8))
    for b, n in enumerate([8, 3, 5]):
        np.testing.assert_array_equal(
            out[b * 8:b * 8 + n], np.asarray(src)[np.asarray(row_src)[
                b * 8:b * 8 + n]])
        assert not out[b * 8 + n:(b + 1) * 8].any()
    assert np.isnan(out[24:]).all()
