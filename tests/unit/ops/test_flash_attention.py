"""Numeric parity of the Pallas flash attention vs the XLA reference
(reference test style: tests/unit/ops numeric parity vs torch)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import xla_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(b=2, s=128, nh=4, d=64, dtype=jnp.float32, seed=0, kvh=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype)
                 for k, h in zip(ks, (nh, kvh or nh, kvh or nh)))


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_xla(causal):
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_forward_uneven_blocks():
    # seq not a multiple of block size exercises edge blocks
    q, k, v = _qkv(s=96)
    ref = xla_attention(q, k, v, True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_xla(causal):
    q, k, v = _qkv(b=1, s=64, nh=2, d=32)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=32, block_k=32) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


def test_gqa_via_repeat():
    # models repeat kv heads before calling attention; just check shape flow
    q, k, v = _qkv(s=64)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert out.shape == q.shape


@pytest.mark.parametrize("causal", [True, False])
def test_gqa_native_matches_repeated(causal):
    """GQA-native path (KVH < NH through kernel index maps) vs explicitly
    repeated kv: forward and all three gradients."""
    from deepspeed_tpu.models.transformer import _repeat_kv

    b, s, nh, kvh, d = 2, 64, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, s, nh, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)

    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = flash_attention(q, _repeat_kv(k, nh // kvh), _repeat_kv(v, nh // kvh),
                          causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)

    def loss_gqa(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=32, block_k=32) ** 2)

    def loss_rep(q, k, v):
        return jnp.sum(flash_attention(
            q, _repeat_kv(k, nh // kvh), _repeat_kv(v, nh // kvh),
            causal=causal, block_q=32, block_k=32) ** 2)

    g_gqa = jax.grad(loss_gqa, argnums=(0, 1, 2))(q, k, v)
    # the repeat's VJP sums each group back to [b, s, kvh, d] for us
    g_rep = jax.grad(loss_rep, argnums=(0, 1, 2))(q, k, v)
    for a, r, name in zip(g_gqa, g_rep, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=5e-4,
                                   rtol=1e-3, err_msg=name)


@pytest.mark.slow
def test_random_shape_sweep_forward():
    """Randomized shapes: uneven seqs, GQA ratios, odd head dims, cross
    attention (Sq != Sk), tiny blocks — forward parity vs XLA."""
    rng = np.random.RandomState(11)
    from deepspeed_tpu.models.transformer import _repeat_kv

    for trial in range(8):
        b = int(rng.randint(1, 3))
        nh = int(rng.choice([1, 2, 4, 8]))
        kvh = int(rng.choice([h for h in (1, 2, 4, 8) if nh % h == 0]))
        d = int(rng.choice([8, 16, 32]))
        sq = int(rng.randint(3, 97))
        causal = bool(rng.randint(2))
        sk = sq if causal else int(rng.randint(3, 97))
        bq = int(rng.choice([16, 32, 64]))
        bk = int(rng.choice([16, 32, 64]))
        ks = jax.random.split(jax.random.PRNGKey(trial), 3)
        q = jax.random.normal(ks[0], (b, sq, nh, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, sk, kvh, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, sk, kvh, d), jnp.float32)
        ref = xla_attention(q, _repeat_kv(k, nh // kvh),
                            _repeat_kv(v, nh // kvh), causal)
        out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-5, rtol=5e-4,
            err_msg=f"trial {trial}: b={b} sq={sq} sk={sk} nh={nh} "
                    f"kvh={kvh} d={d} causal={causal} bq={bq} bk={bk}")


@pytest.mark.slow
def test_random_shape_sweep_gradients():
    """Two randomized gradient-parity draws (full pipeline incl. padding)."""
    from deepspeed_tpu.models.transformer import _repeat_kv

    for trial, (sq, nh, kvh, d, bq) in enumerate(
            [(45, 4, 2, 16, 16), (70, 2, 1, 8, 32)]):
        ks = jax.random.split(jax.random.PRNGKey(100 + trial), 3)
        q = jax.random.normal(ks[0], (1, sq, nh, d), jnp.float32)
        k = jax.random.normal(ks[1], (1, sq, kvh, d), jnp.float32)
        v = jax.random.normal(ks[2], (1, sq, kvh, d), jnp.float32)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(xla_attention(
            q, _repeat_kv(k, nh // kvh), _repeat_kv(v, nh // kvh), True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bq) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, r, nm in zip(g_fl, g_ref, ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       atol=1e-3, rtol=2e-3,
                                       err_msg=f"trial {trial} {nm}")


@pytest.mark.parametrize("bwd_bq,bwd_bk", [(16, 16), (64, 32), (32, 64)])
def test_gradients_with_independent_bwd_blocks(bwd_bq, bwd_bk):
    """bwd tiling decoupled from fwd tiling (incl. non-divisible mixes
    that force lcm padding) must not change any gradient."""
    q, k, v = _qkv(b=1, s=48, nh=2, d=32)  # 48: not a multiple of 32

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=32, block_k=32,
                                       bwd_block_q=bwd_bq,
                                       bwd_block_k=bwd_bk) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


def test_flash_alibi_matches_xla_bias_fwd_bwd():
    """In-kernel ALiBi (bias from block indices, never materializing
    [S, S]) must match the XLA additive-bias formulation in outputs AND
    q/k/v gradients, across GQA and multi-block shapes."""
    from deepspeed_tpu.models.transformer import (_repeat_kv, alibi_slopes,
                                                  xla_attention)

    rng = np.random.RandomState(7)
    B, S, NH, KVH, D = 2, 96, 4, 2, 16  # multi-block at block 32, GQA 2x
    q = jnp.asarray(rng.randn(B, S, NH, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, S, KVH, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, S, KVH, D).astype(np.float32)) * 0.3
    slopes = alibi_slopes(NH)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            alibi_slopes=slopes)
        return jnp.sum(o * o)

    def loss_xla(q, k, v):
        rel = (jnp.arange(S)[:, None] - jnp.arange(S)[None, :]).astype(
            jnp.float32)
        bias = -slopes[None, :, None, None] * rel
        o = xla_attention(q, _repeat_kv(k, NH // KVH),
                          _repeat_kv(v, NH // KVH), True, bias=bias)
        return jnp.sum(o * o)

    lf, gf = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    lx, gx = jax.value_and_grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lf), float(lx), rtol=1e-5)
    for a, b, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-4, err_msg=name)
    # without slopes the default path is untouched (regression guard)
    o_plain = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    o_xla = xla_attention(q, _repeat_kv(k, NH // KVH),
                          _repeat_kv(v, NH // KVH), True)
    np.testing.assert_allclose(np.asarray(o_plain), np.asarray(o_xla),
                               atol=2e-5, rtol=2e-4)


def test_flash_on_mesh_matches_xla(devices8):
    """The mesh wrap (Mosaic kernels cannot be GSPMD-partitioned, so the
    kernel runs under shard_map: batch over the batch axes, heads over the
    model axis): same values as plain attention, from a jit over 4 devices
    and from inside an enclosing shard_map that already made the data axis
    manual (the ZeRO overlap wrap / pipe stage case)."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.models.transformer import _repeat_kv, flash_on_mesh
    from deepspeed_tpu.parallel.mesh import MeshConfig, initialize_topology
    from deepspeed_tpu.utils.jax_compat import shard_map

    topo = initialize_topology(MeshConfig(data=2, model=2), devices8[:4])
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (4, 64, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (4, 64, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (4, 64, 2, 16), jnp.float32)
    ref = xla_attention(q, _repeat_kv(k, 2), _repeat_kv(v, 2), True)
    with topo.mesh:
        out = jax.jit(lambda q, k, v: flash_on_mesh(q, k, v, True))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)
        spec = P("data", None, None, None)
        nested = jax.jit(shard_map(
            lambda q, k, v: flash_on_mesh(q, k, v, True), topo.mesh,
            in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
            axis_names={"data"}))(q, k, v)
        np.testing.assert_allclose(np.asarray(nested), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# tile classes (backward kernels): an interior tile's bare body is the edge
# body where the mask is all true, so the classes change no bit of a gradient
# ---------------------------------------------------------------------------
# (id, S, NH, KVH, D, keywords of the call), forward tiles of 32
_CLASS_CASES = [
    ("causal-mha-d64", 128, 2, 2, 64, {}),
    ("full-mha-d64", 128, 2, 2, 64, {"causal": False}),
    ("causal-gqa4-d128-padded", 80, 4, 1, 128, {}),
    ("full-gqa4-d64-padded", 80, 4, 1, 64, {"causal": False}),
    ("causal-gqa16-d64", 96, 16, 1, 64, {}),
    ("causal-gqa4-d192", 96, 4, 1, 192, {}),
    ("causal-bf16-gqa4", 128, 4, 1, 64, {"dtype": jnp.bfloat16}),
    ("causal-bwd-tiles-wider", 128, 2, 1, 64,
     {"bwd_block_q": 64, "bwd_block_k": 64}),
    ("causal-bwd-tiles-narrower-padded", 112, 2, 1, 64,
     {"bwd_block_q": 16, "bwd_block_k": 64}),
    ("causal-alibi", 96, 2, 1, 64, {"alibi": True}),
]


@pytest.mark.parametrize("s,nh,kvh,d,kw", [c[1:] for c in _CLASS_CASES],
                         ids=[c[0] for c in _CLASS_CASES])
def test_tile_classes_change_no_bit(monkeypatch, s, nh, kvh, d, kw):
    """The shipped classes against kernels that run the edge body on every
    computed tile: out, dq, dk and dv bit for bit.

    The scale and the slopes are powers of two.  XLA:CPU contracts the bare
    body's ``dot * scale - lse`` into one fused multiply-add, which rounds
    once (in the masked body the select stands between the two); a power of
    two rounds the same either way, so what is compared is the kernel and not
    the interpreter's compiler.  Mosaic contracts nothing: the chip's
    comparison (PERF.md §6, PR 55) ran the models' own scales."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    kw = dict(kw)
    q, k, v = _qkv(1, s, nh, d, kw.pop("dtype", jnp.float32), seed=5,
                   kvh=kvh)
    if kw.pop("alibi", False):
        kw["alibi_slopes"] = 2.0 ** -jnp.arange(2, 2 + nh, dtype=jnp.float32)

    def call(q, k, v):
        return fa.flash_attention(q, k, v, block_q=32, block_k=32,
                                  sm_scale=0.125, **{"causal": True, **kw})

    seen = []
    scores = fa._scores
    monkeypatch.setattr(fa, "_scores", lambda *a, masked=True, **k: (
        seen.append(masked), scores(*a, masked=masked, **k))[1])

    def run():
        del seen[:]
        out, vjp = jax.vjp(call, q, k, v)
        return (out,) + vjp(jnp.cos(out.astype(jnp.float32)).astype(
            out.dtype)), set(seen)

    shipped, bodies = run()
    assert bodies == {True, False}
    monkeypatch.setattr(fa, "_run_tile", lambda cls, body: fa.pl.when(cls[0])(
        lambda: body(True)))
    all_edge, bodies = run()
    assert bodies == {True}
    for a, b, name in zip(shipped, all_edge, ("out", "dq", "dk", "dv")):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a), np.asarray(b)), name


def _classes_by_mask(seq_q, seq_k, block_q, block_k, causal=True,
                     valid_q=None, valid_k=None):
    """The classes counted over every (row, column) of every tile's mask."""
    bq, bk = min(block_q, seq_q), min(block_k, seq_k)
    skipped = interior = edge = 0
    for q0 in range(0, seq_q, bq):
        rows = q0 + np.arange(bq)[:, None]
        for k0 in range(0, seq_k, bk):
            cols = k0 + np.arange(bk)[None, :]
            under = rows >= cols if causal else np.ones((bq, bk), bool)
            mask = (under & (rows < (seq_q if valid_q is None else valid_q))
                    & (cols < (seq_k if valid_k is None else valid_k)))
            if not under.any():
                skipped += 1
            elif mask.all():
                interior += 1
            else:
                edge += 1
    return skipped, interior, edge


@pytest.mark.parametrize("seq,block,want", [
    (8192, 512, (120, 120, 16)),   # lfm2-ep4-pretrain-8k, a head
    (4096, 512, (28, 28, 8)),      # mistral7b-zero3-4chip
    (2048, 512, (6, 6, 4)),        # opt6.7b-sft-1chip
])
def test_tile_classes_of_the_training_cells(seq, block, want):
    from deepspeed_tpu.ops.pallas.flash_attention import tile_classes

    assert tile_classes(seq, seq, block, block) == want
    assert _classes_by_mask(seq, seq, block, block) == want


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_k", [(32, 32), (64, 32), (32, 64),
                                             (48, 16)])
def test_tile_classes_match_the_mask(causal, block_q, block_k):
    """A tile is interior iff its mask is all true and skipped iff no row of
    it reaches a column under the causal rule — over lengths that do and do
    not divide the tile, square and not, with padded rows and keys."""
    from deepspeed_tpu.ops.pallas.flash_attention import tile_classes

    n = 0
    for seq_q, seq_k in ((32, 32), (64, 64), (96, 96), (192, 192), (240, 240),
                         (64, 192), (192, 96)):
        for pad_q, pad_k in ((0, 0), (5, 0), (0, 17), (24, 24), (40, 70)):
            args = dict(causal=causal,
                        valid_q=seq_q - pad_q if pad_q else None,
                        valid_k=seq_k - pad_k if pad_k else None)
            got = tile_classes(seq_q, seq_k, block_q, block_k, **args)
            assert got == _classes_by_mask(seq_q, seq_k, block_q, block_k,
                                           **args), (seq_q, seq_k, args)
            n += sum(got)
    assert n > 200
