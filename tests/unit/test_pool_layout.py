"""The pool is ``[L, P+1, ps, KVH*D]`` on the device; everything that leaves
it keeps ``KVPageBundle.arrays``' ``[L, n, ps, KVH, D]`` (ISSUE 26).

``fixtures/kv_bundle_5d_*.bin`` are wire bytes of a mid-decode sequence
exported at commit bf4d09d, when the pool itself was five-dimensional
(tiny llama, PRNGKey(0) weights, a 20-token prompt, four tokens generated):
the layout an older replica, the host tier or the NVMe tier may still hold.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2, KVBlockConfig,
                                        PagedKVCache, RaggedInferenceConfig,
                                        RaggedRequest)
from deepspeed_tpu.inference.v2.model_runner import (paged_gather_pages,
                                                     paged_scatter_pages)
from deepspeed_tpu.models.llama import llama_model
from deepspeed_tpu.serving.kv_transfer import (bundle_from_bytes,
                                               bundle_to_bytes, page_crcs)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
L, KVH, D, PS = 2, 4, 16, 8


def _random_pools(dtype, kv_quant, seed):
    block = KVBlockConfig(page_size=PS, num_pages=16, max_seqs=2,
                          max_pages_per_seq=4)
    pools = PagedKVCache.init(L, KVH, D, block, dtype, kv_quant=kv_quant)
    rng = np.random.RandomState(seed)

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.randint(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.randn(*a.shape), a.dtype)

    return {name: fill(a) for name, a in sorted(pools.items())}


@pytest.mark.parametrize("dtype,kv_quant", [
    (jnp.float32, False), (jnp.bfloat16, False), (jnp.float32, True)],
    ids=["fp32", "bf16", "kv_quant"])
def test_gather_scatter_round_trip_is_bit_identical(dtype, kv_quant):
    src = _random_pools(dtype, kv_quant, seed=0)
    pages = [3, 7, 1]
    arrays = paged_gather_pages(src, pages, KVH)
    assert arrays["k"].shape == arrays["v"].shape == (L, 3, PS, KVH, D)
    assert all(arrays[n].dtype == src[n].dtype for n in src)
    if kv_quant:
        assert arrays["k_scale"].shape == (L, 3, PS, KVH)
    # head h of a slot is lanes [h*D, (h+1)*D) of the pool's merged axis
    pool_k = np.asarray(src["k"])
    for j, page in enumerate(pages):
        for h in range(KVH):
            assert np.array_equal(arrays["k"][:, j, :, h],
                                  pool_k[:, page, :, h * D:(h + 1) * D])
    dst = paged_scatter_pages(_random_pools(dtype, kv_quant, seed=1),
                              [5, 0, 9], arrays)
    assert all(dst[n].shape == src[n].shape for n in src)
    back = paged_gather_pages(dst, [5, 0, 9], KVH)
    for name in arrays:
        assert back[name].dtype == arrays[name].dtype
        assert back[name].tobytes() == arrays[name].tobytes(), name
    leaves = sorted(arrays)
    assert page_crcs(back, leaves) == page_crcs(arrays, leaves)


@pytest.mark.parametrize("kind", ["fp32", "kv_quant"])
def test_bundle_of_the_five_dimensional_pool_imports_unchanged(kind):
    with open(os.path.join(FIXTURES, f"kv_bundle_5d_{kind}.bin"), "rb") as f:
        wire = f.read()
    bundle = bundle_from_bytes(wire)  # re-verifies every page's CRC
    assert bundle.arrays["k"].shape == (L, 3, PS, KVH, D)

    model = llama_model("tiny", max_seq_len=128)
    params = model.init_params(jax.random.PRNGKey(0))

    def engine():
        return InferenceEngineV2(model, RaggedInferenceConfig(
            dtype="fp32", page_size=PS, num_pages=64, max_seqs=4,
            max_pages_per_seq=12, enable_prefix_cache=False,
            kv_quant=kind == "kv_quant"), params=params)

    def drain(eng):
        toks = []
        while eng.has_work():
            for rec in eng.step().values():
                toks.extend(rec["tokens"])
        return toks

    dst = engine()
    assert dst.import_sequence(bundle)
    seq = dst._find_slotted(bundle.uid)
    got = paged_gather_pages(dst._pools, seq.pages, KVH)
    leaves = sorted(bundle.arrays)
    for name in leaves:
        assert got[name].dtype == bundle.arrays[name].dtype
        assert got[name].tobytes() == bundle.arrays[name].tobytes(), name
    assert page_crcs(got, leaves) == page_crcs(bundle.arrays, leaves)
    # what this tree exports of those pages is the same bytes on the wire
    again = bundle_from_bytes(bundle_to_bytes(dst.export_sequence(bundle.uid)))
    for name in leaves:
        assert again.arrays[name].tobytes() == bundle.arrays[name].tobytes()

    # and the pages mean what they meant: the imported stream goes on as
    # the same request does when this tree serves it from its prompt
    whole = engine()
    whole.put(RaggedRequest(prompt_ids=bundle.tokens[:bundle.prompt_len],
                            max_new_tokens=bundle.max_new_tokens))
    assert bundle.tokens[bundle.prompt_len:] + drain(dst) == drain(whole)
