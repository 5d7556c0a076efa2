"""The two attention kernels at the SDAR-30B-A3B cell's call shapes, compiled
for a described v5e with no chip: what interpret mode accepts Mosaic may
refuse (VMEM for 128 query rows a grid step's row, a table of 256 x 320
entries in scalar memory, the block mask's ``|`` on a tile of positions).

``dstpu_paged_decode`` at 256 rows x (4 positions x 32 heads = 128 query rows,
32 of them a K/V head) x 320 pages of 16 over a pool of 4 K/V heads of 128;
``dstpu_flash_fwd`` under the block mask at a chunk of 2,048 against a window
of 4,096 positions, 32 / 4 heads of 128.  It compiles; it does not run.

Since PR 59 also ``dstpu_mla_decode`` at both latent cells' tables and pools
(the tests that compile for a described chip live in ONE file: a second file
may go to another worker, whose libtpu is then taken): a block of 64 or 48
pages in a ring of two slots, the whole page table flat in scalar memory, the
compiler's bounds checks off.
"""

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu.utils.platform as plat
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.mla_attention import (latent_pages_per_block,
                                                    mla_decode_attention)
from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention

ROWS, BLOCK, NH, KVH, D, PAGES, PS = 256, 4, 32, 4, 128, 320, 16


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e:2x2 (never at import: a machine whose
    libtpu cannot describe one skips the file)."""
    try:
        from jax.experimental import topologies

        dev = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip(f"no v5e topology description: {e}")
    return jax.sharding.SingleDeviceSharding(dev)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels compiled, not interpreted; nothing compiled here is cached
    where a chip run would look for it."""
    monkeypatch.setattr(plat, "platform", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", cache)


def _arr(v5e, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)


def test_paged_decode_with_a_block_folded_into_its_heads(v5e,
                                                         compiled_kernels):
    pool = _arr(v5e, (6, 1025, PS, KVH * D), jnp.bfloat16)
    f = jax.jit(lambda q, k, v, table, last, act: paged_decode_attention(
        q, k, v, table, last, layer=3, active=act))
    compiled = f.lower(
        _arr(v5e, (ROWS, BLOCK * NH, D), jnp.bfloat16), pool, pool,
        _arr(v5e, (ROWS, PAGES), jnp.int32), _arr(v5e, (ROWS,), jnp.int32),
        _arr(v5e, (ROWS,), jnp.bool_)).compile()
    assert "dstpu_paged_decode" in compiled.as_text()


def test_flash_under_the_block_mask_at_a_chunk(v5e, compiled_kernels):
    compiled = jax.jit(lambda q, k, v, off: flash_attention(
        q, k, v, causal=True, q_offset=off, block=BLOCK)).lower(
        _arr(v5e, (1, 2048, NH, D), jnp.bfloat16),
        _arr(v5e, (1, 4096, KVH, D), jnp.bfloat16),
        _arr(v5e, (1, 4096, KVH, D), jnp.bfloat16),
        _arr(v5e, (), jnp.int32)).compile()
    assert "dstpu_flash_fwd" in compiled.as_text()


@pytest.mark.parametrize("rows,pages,rank,lanes,nb", [
    (128, 1089, 256, 384, 64),    # mistralsmall4-ep8-docqa-saturated
    (48, 2113, 512, 640, 48)])    # xing4-pp7-longrag-saturated
def test_latent_decode_at_a_latent_cells_block(v5e, compiled_kernels, rows,
                                               pages, rank, lanes, nb):
    assert latent_pages_per_block(PS, lanes, 2) == nb
    compiled = jax.jit(lambda q, pool, table, last, act: mla_decode_attention(
        q, pool, table, last, 1, act, rank=rank)).lower(
        _arr(v5e, (rows, NH, rank + 64), jnp.bfloat16),
        _arr(v5e, (2, 8193, PS, lanes), jnp.bfloat16),
        _arr(v5e, (rows, pages), jnp.int32), _arr(v5e, (rows,), jnp.int32),
        _arr(v5e, (rows,), jnp.bool_)).compile()
    assert "dstpu_mla_decode" in compiled.as_text()


# Since PR 60 also the EvaByte cell's two kernels: the paged decode kernel at
# MHA of 32 K/V heads x 128 (a 4096-wide key row and value row: a block of 8
# pages) over a table of [summary pages | open-window pages] under its own
# name, and the chunk form's flash over [summaries | the open window | the
# chunk] with the table's unused front masked by ``k_first``.
def test_eva_decode_at_a_4096_wide_row(v5e, compiled_kernels):
    from deepspeed_tpu.ops.pallas.paged_attention import pages_per_block

    assert pages_per_block(PS, 32 * D, 2) == 8
    pool = _arr(v5e, (2, 1025, PS, 32 * D), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v, table, last, act:
                       paged_decode_attention(q, k, v, table, last, layer=1,
                                              active=act,
                                              name="dstpu_eva_decode")).lower(
        _arr(v5e, (32, 32, D), jnp.bfloat16), pool, pool,
        _arr(v5e, (32, 256), jnp.int32), _arr(v5e, (32,), jnp.int32),
        _arr(v5e, (32,), jnp.bool_)).compile()
    assert "dstpu_eva_decode" in compiled.as_text()


@pytest.mark.parametrize("before", [512, 2048])
def test_flash_over_summaries_an_open_window_and_a_chunk(v5e,
                                                         compiled_kernels,
                                                         before):
    compiled = jax.jit(lambda q, k, v, first: flash_attention(
        q, k, v, causal=True, q_offset=before, window=before + 2048,
        k_first=first)).lower(
        _arr(v5e, (1, 2048, 32, D), jnp.bfloat16),
        _arr(v5e, (1, before + 2048, 32, D), jnp.bfloat16),
        _arr(v5e, (1, before + 2048, 32, D), jnp.bfloat16),
        _arr(v5e, (), jnp.int32)).compile()
    assert "dstpu_flash_fwd" in compiled.as_text()


# Since PR 61 also two whole serving engines' programs, compiled through the
# engine's own jitted functions: a projection whose result is used by head is
# pinned as a plain product (``transformer.head_projection``), so the compiled
# program reads ``wq`` / ``wk`` / ``wv`` in place, as stored — no copy of a
# projection weight (whole, a layer's slice of the stack, or an async copy of
# either) and no product over the heads as a window.  A scanned homogeneous
# stack (the chat cell's case: the slice + ``{1,2,0}`` copy of ``copy.35``) and
# a typed stack whose period runs once, unrolled (MiMo's: a copy of the
# argument itself).
def _scanned_stack():
    from deepspeed_tpu.models import mistral_model

    return mistral_model("tiny", max_seq_len=256, hidden_size=1024, n_heads=8,
                         n_kv_heads=2, intermediate_size=2048, n_layers=2,
                         vocab_size=1024)


def _unrolled_stack():
    from deepspeed_tpu.models import mimo_v2_model

    return mimo_v2_model("tiny", max_seq_len=256, hidden_size=1024, n_heads=8,
                         head_dim_override=192, v_head_dim=128, n_kv_heads=2,
                         swa_kv_heads=4, sliding_window=128, vocab_size=1024,
                         dense_ffn_size=2048, intermediate_size=256)


@pytest.fixture(scope="module", params=["scanned", "unrolled"])
def serving_programs(request):
    """A small lane-aligned engine with real weights on the CPU; the tests
    lower its own programs for the described chip over abstract arguments."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)

    model = {"scanned": _scanned_stack,
             "unrolled": _unrolled_stack}[request.param]()
    return InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="bf16", page_size=16, max_pages_per_seq=16, prefill_chunk=128,
        max_seqs=16, num_pages=64), seed=0)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_a_serving_program_copies_no_projection_weight(
        v5e, compiled_kernels, serving_programs, program):
    from tools.aot_serve_step import weight_copies

    eng = serving_programs
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: _arr(v5e, a.shape, a.dtype), tree)
    params, pools = on_chip(eng.params), on_chip(eng._pools)
    i32, B, MP = jnp.int32, 16, 16
    if program == "decode":
        lowered = eng._decode.lower(
            params, pools, _arr(v5e, (B,), i32), _arr(v5e, (B,), i32),
            _arr(v5e, (B, MP), i32), _arr(v5e, (B,), jnp.bool_),
            _arr(v5e, (B,), jnp.float32), _arr(v5e, (B,), i32),
            _arr(v5e, (2,), jnp.uint32))
    else:
        slot = (_arr(v5e, (), i32),) if eng._state else ()
        lowered = eng._prefill_chunk.lower(
            params, pools, _arr(v5e, (128,), i32), _arr(v5e, (8,), i32),
            _arr(v5e, (MP,), i32), _arr(v5e, (), i32), _arr(v5e, (), i32),
            *slot)
    hlo = lowered.compile().as_text()
    products = [line for line in hlo.splitlines()
                if " convolution(" in line and "region.attn_qkv" in line]
    assert products, "the projections are products the compiler names"
    assert not [line for line in products if "window=" in line]
    assert not [c for c in weight_copies(hlo, floor=0) if "['attn']" in c[4]]
