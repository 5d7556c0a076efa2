"""Continuous-batching inference with the paged (ragged) engine.

Three prompts of different lengths run concurrently; pages are reclaimed
as sequences finish.  Add ``kv_quant=True`` for int8 KV pages or
``quant_bits=8`` for weight-only quantization.

  JAX_PLATFORMS=cpu python examples/serve_paged_inference.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.models.llama import llama_model


def main():
    model = llama_model("tiny", max_seq_len=256)
    engine = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=16, num_pages=64, max_seqs=4,
        max_pages_per_seq=8, kv_quant=False))

    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, model.config.vocab_size, n))
               for n in (7, 19, 33)]
    uids = [engine.put(RaggedRequest(prompt_ids=p, max_new_tokens=12))
            for p in prompts]

    # drive the scheduler step by step (a server loop would look like this)
    done = {}
    while engine.has_work():
        for uid, rec in engine.step().items():
            done.setdefault(uid, []).extend(rec["tokens"])
    for uid in uids:
        print(f"request {uid}: {done[uid]}")
    print(f"pages free again: {engine.allocator.free_pages}")


if __name__ == "__main__":
    main()
