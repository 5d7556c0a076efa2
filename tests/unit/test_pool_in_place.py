"""The KV pools are written and read in place by every serving program.

A compile-time guard (ISSUE 26): the pools ride in the carry of
``model_runner._scan_layers`` and are donated at the jit boundary, so XLA
updates the caller's buffer.  Handed through the layer scan as per-layer
operands and stacked outputs instead — the form every program had before —
each program keeps a second pool in its temporaries.  On the CPU backend's
memory analysis the two forms are far apart already (fp32 pools of 33.6 MB:
temporaries 50.5 MB before, under 1 MB now), so this runs in tier-1 with no
chip; ``tools/aot_serve_step.py`` asks the same of the real sizes compiled
for a described v5e.  The float case is fp32 here, not the chip's bf16: the
CPU backend widens a bf16 scatter to f32, and that copy of the pool is the
backend's own.
"""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig,
                                        SpeculativeConfig)
from deepspeed_tpu.models.llama import llama_model

PS, MP, B, CHUNK, HORIZON = 8, 8, 2, 16, 4


@pytest.fixture(scope="module")
def engine_for():
    """(program, kv_quant) -> an engine that has that program: pools of
    4096 pages (two layers of K and V: 33.6 MB in fp32, 10.5 MB as int8
    codes and scales) beside a 0.5 MB model.  A proposer and a fused
    horizon exclude each other, so ``multi_decode`` gets an engine of its
    own."""
    model = llama_model("tiny", max_seq_len=PS * MP)
    params = model.init_params(jax.random.PRNGKey(0))
    built = {}

    def get(program, kv_quant):
        fused = program == "multi_decode"
        if (fused, kv_quant) not in built:
            built[fused, kv_quant] = InferenceEngineV2(
                model, RaggedInferenceConfig(
                    dtype="fp32", page_size=PS, num_pages=4096, max_seqs=B,
                    max_pages_per_seq=MP, prefill_chunk=CHUNK,
                    kv_quant=kv_quant, decode_horizon=HORIZON if fused else 1,
                    speculative=SpeculativeConfig(
                        mode="off" if fused else "ngram", k=3)),
                params=params)
        return built[fused, kv_quant]

    return get


def _program(eng, name):
    """The engine's own jitted program and its arguments after (params,
    pools), shaped as ``engine_v2`` calls it."""
    i32 = jnp.int32

    def arr(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype)

    rows = (arr((B,)), arr((B,)), arr((B, MP)), arr((B,), jnp.bool_))
    key = arr((2,), jnp.uint32)
    return {
        "prefill": lambda: (eng._prefill, (
            arr((32,)), arr((32 // PS,)), arr(()))),
        "prefill_chunk": lambda: (eng._prefill_chunk, (
            arr((CHUNK,)), arr((CHUNK // PS,)), arr((MP,)), arr(()),
            arr(()))),
        "verify": lambda: (eng._verify, (
            arr((B, eng.spec.k + 1)), arr((B,)), arr((B, MP)),
            arr((B,), jnp.bool_), arr((B,)))),
        "decode": lambda: (eng._decode, rows + (
            arr((B,), jnp.float32), arr((B,)), key)),
        "multi_decode": lambda: (eng._multi, rows + (
            arr((B,), jnp.float32), arr((B,)), arr((B,)), arr((B,)), key,
            HORIZON)),
    }[name]()


@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp32", "kv_quant"])
@pytest.mark.parametrize("program", ["prefill", "prefill_chunk", "verify",
                                     "decode", "multi_decode"])
def test_serving_program_keeps_the_pools_in_place(engine_for, program,
                                                  kv_quant):
    eng = engine_for(program, kv_quant)
    fn, args = _program(eng, program)
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(eng._pools))
    assert pool_bytes > 20 * eng.param_bytes  # the pool dwarfs the rest
    mem = fn.lower(eng.params, eng._pools, *args).compile().memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes // 4, (
        f"{program}: temporaries {mem.temp_size_in_bytes} B beside pools of "
        f"{pool_bytes} B — a pool-sized copy is back in the program")
    assert mem.alias_size_in_bytes >= pool_bytes, (
        f"{program}: {mem.alias_size_in_bytes} B aliased input to output, "
        f"the pools are {pool_bytes} B — their donation no longer holds")


# ------------------------------------------------- state and rings in slots
S_B, S_MP, S_CHUNK = 256, 8, 32


@pytest.fixture(scope="module")
def slots_engine():
    """Phi-4-mini-flash at its tiny widths with 256 slots: the window layers'
    rings (2.4 MB each of K and V) and the state-space layers' state (4.2 MB)
    beside one layer of 1024 pages (1 MB each) and a 1.3 MB model — what is
    kept per sequence is most of the pools."""
    from deepspeed_tpu.models.phi4_flash import phi4_flash_model

    return InferenceEngineV2(
        phi4_flash_model("tiny", max_seq_len=PS * S_MP),
        RaggedInferenceConfig(dtype="fp32", page_size=PS, num_pages=1024,
                              max_seqs=S_B, max_pages_per_seq=S_MP,
                              prefill_chunk=S_CHUNK))


@pytest.mark.parametrize("program", ["decode", "prefill_chunk",
                                     "prefill_chunk_part"])
def test_state_slots_and_window_rings_are_kept_in_place(slots_engine,
                                                        program):
    eng = slots_engine
    i32 = jnp.int32

    def arr(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype)

    if program == "decode":
        fn, args = eng._decode, (
            arr((S_B,)), arr((S_B,)), arr((S_B, S_MP)),
            arr((S_B,), jnp.bool_), arr((S_B,), jnp.float32), arr((S_B,)),
            arr((2,), jnp.uint32))
    else:
        fn = (eng._prefill_chunk if program == "prefill_chunk"
              else eng._prefill_chunk_part)
        args = (arr((S_CHUNK,)), arr((S_CHUNK // PS,)), arr((S_MP,)),
                arr(()), arr(()), arr(()))
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(eng._pools))
    slots = sum(eng._pools[n].size * 4
                for n in ("win_k", "win_v", "ssm_s", "ssm_conv"))
    assert slots > 3 * eng.param_bytes and slots > 0.8 * pool_bytes
    mem = fn.lower(eng.params, eng._pools, *args).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, (
        f"{program}: {mem.alias_size_in_bytes} B aliased input to output, "
        f"the pools are {pool_bytes} B — their donation does not hold")
    if program == "decode":
        # the CPU tier's decode runs the gather path, whose gathered windows
        # and all-rows scan step are row-sized temporaries by nature: what
        # the chip's program keeps is tools/aot_serve_step.py's to say
        return
    # the CPU backend copies the two rings once where their page view (a
    # reshape of the leading dimensions) enters the layer loop — 4.7 MB here;
    # libtpu does not (aot_serve_step: temporaries 0.05 GiB beside 5.4 GiB).
    # A per-layer operand or stacked output would be every leaf again
    assert mem.temp_size_in_bytes < pool_bytes // 2, (
        f"{program}: temporaries {mem.temp_size_in_bytes} B beside pools of "
        f"{pool_bytes} B — a slot-pool-sized copy is in the program")


# ------------------------------------------------------- one latent leaf
L_B, L_MP, L_CHUNK, L_PS = 8, 16, 16, 4


@pytest.fixture(scope="module")
def latent_engine():
    """Mistral-Small-4 at its tiny widths with a pool of 4096 pages: one
    latent leaf of 4 layers x 4097 x 4 x 128 float32 = 33.6 MB beside a 0.7
    MB model — the pool is nearly all of the program's memory."""
    from deepspeed_tpu.models import mistral4_model

    return InferenceEngineV2(
        mistral4_model("tiny", max_seq_len=L_PS * L_MP, moe_held_first=2,
                       moe_held_count=2),
        RaggedInferenceConfig(dtype="fp32", page_size=L_PS, num_pages=4096,
                              max_seqs=L_B, max_pages_per_seq=L_MP,
                              prefill_chunk=L_CHUNK))


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_the_latent_pool_is_kept_in_place(latent_engine, program):
    """The chunk program gathers a window of latent rows and expands it; the
    decode program scatters one row a sequence: neither may hold a second
    pool, and the one leaf is aliased input to output."""
    eng = latent_engine
    i32 = jnp.int32

    def arr(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype)

    if program == "decode":
        fn, args = eng._decode, (
            arr((L_B,)), arr((L_B,)), arr((L_B, L_MP)),
            arr((L_B,), jnp.bool_), arr((L_B,), jnp.float32), arr((L_B,)),
            arr((2,), jnp.uint32))
    else:
        fn, args = eng._prefill_chunk, (
            arr((L_CHUNK,)), arr((L_CHUNK // L_PS,)), arr((L_MP,)), arr(()),
            arr(()))
    assert set(eng._pools) == {"latent", "moe_stats"}
    pool_bytes = eng._pools["latent"].size * 4
    assert pool_bytes > 40 * eng.param_bytes
    mem = fn.lower(eng.params, eng._pools, *args).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, (
        f"{program}: {mem.alias_size_in_bytes} B aliased input to output, "
        f"the latent pool is {pool_bytes} B — its donation does not hold")
    assert mem.temp_size_in_bytes < pool_bytes // 4, (
        f"{program}: temporaries {mem.temp_size_in_bytes} B beside a pool of "
        f"{pool_bytes} B — a pool-sized copy is in the program")
