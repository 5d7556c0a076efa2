"""The grouped expert matmul itself (``impl="pallas"`` under the interpreter)
and the dropless tail above it: the kernel does the work of the row blocks
that hold picks and leaves the rest of the worst-case buffer alone, and the
block height follows the picks an expert gets (ISSUE 30).

The interpreter fills what a kernel never writes with NaN, so a read of a
row past the real blocks shows here as a NaN in a gathered result."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import (MOE_COUNTERS, MoEConfig,
                                           _gate_and_aux, _sorted_expert_ffn,
                                           moe_ffn_share, sort_pad_by_expert)
from deepspeed_tpu.ops.pallas import grouped_matmul as gmm_mod
from deepspeed_tpu.ops.pallas.grouped_matmul import (expert_block_rows,
                                                     grouped_matmul)


def _layout(key, n_experts, block_rows):
    """numpy's own count of the blocks that hold rows."""
    counts = np.bincount(np.minimum(key, n_experts),
                         minlength=n_experts + 1)[:n_experts]
    return counts, int(np.sum(-(-counts // block_rows)))


def _sorted_inputs(rng, key, n_experts, block_rows, H, dtype):
    """Rows scattered into the padded buffer as ``_sorted_expert_ffn`` does."""
    order, dest, n_rows, be, n_real = sort_pad_by_expert(
        jnp.asarray(key, jnp.int32), n_experts, block_rows)
    rows = jnp.asarray(rng.standard_normal((len(key), H)), dtype)
    xs = jnp.zeros((n_rows, H), dtype).at[dest].set(rows[order], mode="drop")
    return xs, dest, be, n_real


@pytest.mark.parametrize("shape", ["gate", "down"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("block_rows", [8, 16, 32, 128])
def test_kernel_matches_the_einsum_on_the_rows_that_hold_picks(
        block_rows, dtype, shape, monkeypatch):
    """Parity with ``impl="xla"`` on every gathered row, over block heights,
    dtypes and both matrix shapes (H > F as gate and up, F > H as down),
    with more than one output tile (the tile budget is cut for the test)."""
    monkeypatch.setattr(gmm_mod, "_W_TILE_BYTES", 128 * 256 * 4)
    H, F = (256, 384) if shape == "down" else (384, 256)
    E, dtype = 6, jnp.dtype(dtype)
    rng = np.random.default_rng(block_rows)
    # experts 1 and 4 untouched, expert 2 over several blocks of 8 and 16
    key = rng.choice([0, 2, 2, 2, 3, 5, E], 40)
    xs, dest, be, n_real = _sorted_inputs(rng, key, E, block_rows, H, dtype)
    w = jnp.asarray(rng.standard_normal((E, H, F)) / np.sqrt(H), dtype)
    assert gmm_mod._out_tile(H, F, dtype.itemsize) < F
    got = grouped_matmul(xs, w, be, block_rows, impl="pallas", n_real=n_real)
    want = grouped_matmul(xs, w, be, block_rows, impl="xla")
    assert got.shape == want.shape == (xs.shape[0], F) and got.dtype == dtype
    valid = np.asarray(dest) < xs.shape[0]
    assert valid.sum() == np.sum(key < E)
    rows = np.asarray(dest)[valid]
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32)[rows],
                               np.asarray(want, np.float32)[rows],
                               rtol=tol, atol=tol)
    # the real blocks are whole: their padding rows are defined too
    real = int(n_real) * block_rows
    assert np.isfinite(np.asarray(got, np.float32)[:real]).all()


def test_without_a_count_every_block_is_run():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5 * 8, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 32, 48)), jnp.float32)
    be = jnp.asarray([0, 2, 1, 1, 0], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(grouped_matmul(x, w, be, 8, impl="pallas")),
        np.asarray(grouped_matmul(x, w, be, 8, impl="xla")),
        rtol=2e-5, atol=2e-5)


def _experts(rng, E, H, F, dtype=jnp.float32, scale=0.3):
    return {k: jnp.asarray(rng.standard_normal(s) * scale, dtype)
            for k, s in (("w_gate", (E, H, F)), ("w_up", (E, H, F)),
                         ("w_down", (E, F, H)))}


def _tail(monkeypatch, xt, key, gate, top_k, E, experts, block_rows):
    """``_sorted_expert_ffn`` with the kernel interpreted."""
    monkeypatch.setattr(gmm_mod, "on_tpu", lambda: True)
    out, _, ran, grid = _sorted_expert_ffn(
        xt, jnp.asarray(key, jnp.int32), jnp.asarray(gate, jnp.float32),
        top_k, E, experts, "swiglu", block_rows)
    return out, ran, grid


def _tail_reference(xt, key, gate, top_k, E, experts):
    out = np.zeros(xt.shape, np.float64)
    x64 = np.asarray(xt, np.float64)
    g, u, d = (np.asarray(experts[k], np.float64)
               for k in ("w_gate", "w_up", "w_down"))
    for a, (e, wt) in enumerate(zip(key, gate)):
        if e < E:
            t = a // top_k
            z = x64[t] @ g[e]
            out[t] += wt * ((z / (1 + np.exp(-z)) * (x64[t] @ u[e])) @ d[e])
    return out


def test_untouched_experts_are_never_read(monkeypatch):
    """Experts with no pick hold NaN weights; the tokens' sums stay finite
    and right: no block of theirs is run, and no row of the empty blocks is
    gathered."""
    rng = np.random.default_rng(1)
    T, K, E, H, F = 12, 2, 5, 32, 48
    experts = _experts(rng, E, H, F)
    for k in experts:
        experts[k] = experts[k].at[jnp.asarray([1, 3])].set(jnp.nan)
    key = rng.choice([0, 2, 4, E], T * K)
    gate = rng.random(T * K)
    xt = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    out, ran, grid = _tail(monkeypatch, xt, key, gate, K, E, experts, 8)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               _tail_reference(xt, key, gate, K, E, experts),
                               rtol=1e-4, atol=1e-4)
    assert int(ran) == _layout(key, E, 8)[1] * 8 < grid


def test_every_pick_on_one_expert_takes_several_blocks(monkeypatch):
    rng = np.random.default_rng(2)
    T, K, E, H, F = 20, 2, 4, 32, 48
    experts = _experts(rng, E, H, F)
    key = np.full(T * K, 2)
    gate = rng.random(T * K)
    xt = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    out, ran, grid = _tail(monkeypatch, xt, key, gate, K, E, experts, 8)
    assert int(ran) == 40 and grid == _worst_case_rows(40, E, 8) == 64
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               _tail_reference(xt, key, gate, K, E, experts),
                               rtol=1e-4, atol=1e-4)


def test_no_pick_on_a_held_expert_runs_no_block(monkeypatch):
    """Zero real blocks: the kernel writes nothing (the interpreter leaves
    NaN in its whole output) and the tokens' sums are exact zeros."""
    rng = np.random.default_rng(3)
    T, K, E, H, F = 6, 2, 3, 32, 48
    experts = _experts(rng, E, H, F)
    xt = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    out, ran, grid = _tail(monkeypatch, xt, np.full(T * K, E),
                           np.ones(T * K), K, E, experts, 8)
    assert int(ran) == 0 and grid == _worst_case_rows(12, E, 8) == 32
    assert not np.asarray(out).any()
    ys = grouped_matmul(jnp.ones((grid, H)), experts["w_up"],
                        jnp.zeros((grid // 8,), jnp.int32), 8, impl="pallas",
                        n_real=jnp.int32(0))
    assert np.isnan(np.asarray(ys)).all()


@pytest.mark.parametrize("block_rows", [8, 16, 128])
@pytest.mark.parametrize("seed", [0, 1])
def test_real_block_count_against_numpy(block_rows, seed):
    rng = np.random.default_rng(seed)
    E, N = 7, 150
    key = rng.choice(np.arange(E + 1), N, p=[.4, .2, 0, .1, .05, 0, .05, .2])
    order, dest, n_rows, be, n_real = sort_pad_by_expert(
        jnp.asarray(key, jnp.int32), E, block_rows)
    counts, want = _layout(key, E, block_rows)
    assert int(n_real) == want
    assert n_rows == _worst_case_rows(N, E, block_rows)
    dest, be = np.asarray(dest), np.asarray(be)
    valid = dest < n_rows
    # dest points into the real blocks only, one row a pick, and a row's
    # block is its expert's
    assert valid.sum() == counts.sum() == len(set(dest[valid]))
    assert dest[valid].max(initial=-1) < want * block_rows
    np.testing.assert_array_equal(be[dest[valid] // block_rows],
                                  key[np.asarray(order)][valid])


def _share_reference(x, gate_w, experts, cfg):
    """The per-token loop of ``test_moe_depth.py``, over the held experts."""
    B, S, H = x.shape
    xt = np.asarray(x.reshape(-1, H), np.float64)
    logits = jnp.dot(jnp.asarray(xt, jnp.float32), gate_w,
                     precision=jax.lax.Precision.HIGHEST)
    _, idx, gk, _ = _gate_and_aux(logits, cfg)
    key = np.asarray(idx).reshape(-1) - cfg.held_first
    key = np.where((key >= 0) & (key < cfg.held_count), key, cfg.held_count)
    out = _tail_reference(xt, key, np.asarray(gk, np.float64).reshape(-1),
                          cfg.top_k, cfg.held_count, experts)
    return out.reshape(B, S, H), key


@pytest.mark.parametrize("T", [128, 512])
def test_share_with_the_derived_height_matches_the_per_token_loop(
        T, monkeypatch):
    """``moe_ffn_share`` at the decode call's and the chunk call's token
    counts, 8 of 64 experts held, the block height left to
    ``expert_block_rows``; the counters say what the kernel ran."""
    monkeypatch.setattr(gmm_mod, "on_tpu", lambda: True)
    rng = np.random.default_rng(T)
    H, F = 32, 48
    cfg = MoEConfig(num_experts=64, top_k=8, drop_tokens=False,
                    held_first=16, held_count=8)
    x = jnp.asarray(rng.standard_normal((1, T, H)), jnp.float32)
    gate_w = jnp.asarray(rng.standard_normal((H, 64)), jnp.float32)
    experts = _experts(rng, 8, H, F)
    out, stats = moe_ffn_share(x, gate_w, experts, cfg)
    ref, key = _share_reference(x, gate_w, experts, cfg)
    np.testing.assert_allclose(np.asarray(out, np.float64), ref,
                               rtol=1e-4, atol=1e-4)
    bs = expert_block_rows(T * 8 / 64, jnp.float32)
    assert bs == {128: 32, 512: 128}[T]
    counts, blocks = _layout(key, 8, bs)
    got = dict(zip(MOE_COUNTERS, np.asarray(stats).tolist()))
    assert got == {"moe_local_picks": counts.sum(),
                   "moe_experts_touched": (counts > 0).sum(),
                   "moe_padded_rows": blocks * bs, "moe_layer_calls": 1,
                   "moe_grid_rows": _worst_case_rows(T * 8, 8, bs)}
    assert got["moe_grid_rows"] > got["moe_padded_rows"]


def _worst_case_rows(n, n_experts, bs):
    m = min(n, n_experts)
    return (m + (n - m) // bs) * bs


def test_grid_rows_equal_padded_rows_when_every_block_is_full(monkeypatch):
    """The buffer is the most blocks the picks can take: every expert one
    row into a block of its own and the rest filling blocks.  Such a layout
    leaves no block of the grid empty; one row fewer on an expert does."""
    bs, E, K, H = 8, 3, 1, 32
    rng = np.random.default_rng(4)
    experts = _experts(rng, E, H, 48)
    for counts, spare in (([bs + 1, 2 * bs + 1, 1], 0), ([bs, 2 * bs + 2, 1], 1)):
        key = np.repeat(np.arange(E), counts)
        xt = jnp.asarray(rng.standard_normal((len(key), H)), jnp.float32)
        out, ran, grid = _tail(monkeypatch, xt, key, np.ones(len(key)), K, E,
                               experts, bs)
        assert grid == _worst_case_rows(len(key), E, bs) == 6 * bs
        assert int(ran) == grid - spare * bs
        np.testing.assert_allclose(
            np.asarray(out, np.float64),
            _tail_reference(xt, key, np.ones(len(key)), K, E, experts),
            rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("picks, dtype, want", [
    (128 * 8 / 320, "bfloat16", 16), (512 * 8 / 320, "bfloat16", 32),
    (4096 * 2 / 8, "bfloat16", 128), (128 * 8 / 320, "float32", 8),
    (0.01, "bfloat16", 16), (40, "float32", 128), (5, "float32", 16)])
def test_block_height_follows_the_expected_picks(picks, dtype, want):
    assert expert_block_rows(picks, jnp.dtype(dtype)) == want


# ------------------------------------------------------------- the backward
@pytest.mark.parametrize("shape", ["gate", "down"])
@pytest.mark.parametrize("block_rows", [8, 32])
def test_vjp_matches_per_expert_autodiff_and_reads_no_row_past_n_real(
        block_rows, shape, monkeypatch):
    """dX and dW of the kernel against ``jnp`` autodiff of a plain
    per-expert product, with experts 1 and 4 without a pick (zero gradient),
    a ragged last block in every touched expert, more than one output tile,
    and NaN planted in every row of the blocks past ``n_real`` — of x, and,
    by the interpreter, of what the forward and dX kernels never write: none
    of it reaches dX's gathered rows or dW (ISSUE 32)."""
    monkeypatch.setattr(gmm_mod, "_W_TILE_BYTES", 128 * 256 * 4)
    H, F = (256, 384) if shape == "down" else (384, 256)
    E = 6
    rng = np.random.default_rng(block_rows + H)
    key = jnp.asarray(rng.choice([0, 2, 2, 2, 3, 5, E], 45), jnp.int32)
    order, dest, n_rows, be, n_real = sort_pad_by_expert(key, E, block_rows)
    counts, real = _layout(np.asarray(key), E, block_rows)
    assert counts[1] == counts[4] == 0 and real < n_rows // block_rows
    assert any(c % block_rows for c in counts)
    rows = jnp.asarray(rng.standard_normal((45, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, H, F)) / np.sqrt(H), jnp.float32)
    past = (jnp.arange(n_rows) // block_rows >= n_real)[:, None]

    def through_kernel(rows, w):
        xs = jnp.zeros((n_rows, H), rows.dtype).at[dest].set(
            rows[order], mode="drop")
        xs = jnp.where(past, jnp.nan, xs)
        ys = grouped_matmul(xs, w, be, block_rows, impl="pallas",
                            n_real=n_real)
        got = ys.at[dest].get(mode="fill", fill_value=0)
        return jnp.sum(jnp.sin(got)), got

    def per_expert(rows, w):
        srt = rows[order]
        ks = key[order]
        out = jnp.zeros((45, F), rows.dtype)
        for e in range(E):
            out = out + jnp.where((ks == e)[:, None], srt @ w[e], 0.0)
        return jnp.sum(jnp.sin(out)), out

    (_, got), (gx, gw) = jax.value_and_grad(through_kernel, (0, 1),
                                            has_aux=True)(rows, w)
    (_, want), (rx, rw) = jax.value_and_grad(per_expert, (0, 1),
                                             has_aux=True)(rows, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert np.isfinite(np.asarray(gx)).all()
    assert np.isfinite(np.asarray(gw)).all()
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), atol=1e-5)
    assert not np.asarray(gw)[[1, 4]].any()
    # a pick on an absent expert (key E) gets no gradient
    assert not np.asarray(gx)[np.asarray(key) == E].any()


def test_vjp_in_bfloat16_sums_an_experts_blocks_in_float32():
    """dW of an expert spread over many blocks: the kernel adds the blocks'
    products in a float32 scratch and rounds once."""
    H, F, E, block_rows = 128, 128, 2, 16
    rng = np.random.default_rng(3)
    key = jnp.asarray([0] * 200 + [1] * 8, jnp.int32)
    order, dest, n_rows, be, n_real = sort_pad_by_expert(key, E, block_rows)
    rows = jnp.asarray(rng.standard_normal((208, H)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((E, H, F)) / np.sqrt(H), jnp.bfloat16)
    up = jnp.asarray(rng.standard_normal((208, F)), jnp.bfloat16)

    def f(w, impl):
        xs = jnp.zeros((n_rows, H), rows.dtype).at[dest].set(
            rows[order], mode="drop")
        ys = grouped_matmul(xs, w, be, block_rows, impl=impl,
                            n_real=n_real if impl == "pallas" else None)
        return jnp.sum(ys.at[dest].get(mode="fill", fill_value=0)
                       .astype(jnp.float32) * up.astype(jnp.float32))

    got = jax.grad(f)(w, "pallas")
    assert got.dtype == jnp.bfloat16
    exact = np.einsum("th,tf->hf", np.asarray(rows[order][:200], np.float64),
                      np.asarray(up[:200], np.float64))
    err = np.abs(np.asarray(got[0], np.float64) - exact).max()
    assert err <= 2.0 ** -8 * np.abs(exact).max()


def test_the_dropless_tail_differentiates_through_the_kernels():
    """``_sorted_expert_ffn`` (three grouped matmuls and SwiGLU between)
    under ``jax.grad`` with the kernels interpreted, against the einsum
    path: weights, tokens and gates."""
    H, F, E, K, T = 128, 256, 4, 2, 24
    rng = np.random.default_rng(9)
    xt = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    key = jnp.asarray(rng.choice([0, 1, 3, E], T * K), jnp.int32)
    gate = jnp.asarray(rng.random(T * K), jnp.float32)
    experts = {n: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[1]),
                              jnp.float32)
               for n, s in (("w_gate", (E, H, F)), ("w_up", (E, H, F)),
                            ("w_down", (E, F, H)))}

    def f(experts, xt, gate, impl):
        out, _, _, _ = _sorted_expert_ffn(xt, key, gate, K, E, experts,
                                          "swiglu", 8, impl=impl)
        return jnp.sum(jnp.cos(out))

    got = jax.grad(f, (0, 1, 2))(experts, xt, gate, "pallas")
    want = jax.grad(f, (0, 1, 2))(experts, xt, gate, "xla")
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5)
    assert not np.asarray(got[0]["w_up"])[2].any()
