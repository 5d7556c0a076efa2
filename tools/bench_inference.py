"""Serving throughput bench: continuous-batching decode on the local chip.

Measures the InferenceEngineV2 ragged path end to end — paged KV, Pallas
paged-decode kernel, flash prefill, preemption — the way the reference's
inference-v2 (DeepSpeed-FastGen) benchmarks measure theirs: N concurrent
requests, fixed prompt/generation lengths, report decode tokens/sec and
per-token latency.

Prints ONE JSON line.  Knobs (env):
    DSTPU_IBENCH_SIZE   model size (default 160m on TPU, tiny on CPU)
    DSTPU_IBENCH_PROMPT prompt length   (default 512 TPU / 32 CPU)
    DSTPU_IBENCH_GEN    new tokens/req  (default 128 TPU / 16 CPU)
    DSTPU_IBENCH_NREQ   total requests  (default 32 TPU / 4 CPU)
    DSTPU_IBENCH_SLOTS  concurrent decode slots (default 8)
    DSTPU_IBENCH_KVQ    1 = int8 KV pages
    DSTPU_IBENCH_WQ     weight-only bits (4/8; 0 = off)
    DSTPU_IBENCH_CHUNK  chunked-prefill tokens per step (0 = whole prompt)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from bench import _device_or_exit, _int_env as _int, _pin_cpu


def main() -> None:
    import jax

    from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                      RaggedInferenceConfig,
                                                      RaggedRequest)
    from deepspeed_tpu.models.llama import llama_model

    on_tpu = jax.default_backend() != "cpu"
    size = os.environ.get("DSTPU_IBENCH_SIZE", "160m" if on_tpu else "tiny")
    prompt = _int("DSTPU_IBENCH_PROMPT", 512 if on_tpu else 32)
    gen = _int("DSTPU_IBENCH_GEN", 128 if on_tpu else 16)
    nreq = _int("DSTPU_IBENCH_NREQ", 32 if on_tpu else 4)
    slots = _int("DSTPU_IBENCH_SLOTS", 8)

    page = 16
    pages_per_seq = -(-(prompt + gen) // page) + 1
    cfg = RaggedInferenceConfig(
        page_size=page, max_pages_per_seq=pages_per_seq,
        num_pages=pages_per_seq * slots + slots,  # full pool + slack
        max_seqs=slots,
        kv_quant=os.environ.get("DSTPU_IBENCH_KVQ") == "1",
        quant_bits=_int("DSTPU_IBENCH_WQ", 0),
        prefill_chunk=_int("DSTPU_IBENCH_CHUNK", 0))
    model = llama_model(size, max_seq_len=prompt + gen + page)
    engine = InferenceEngineV2(model, cfg)

    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size

    def requests(n):
        return [RaggedRequest(prompt_ids=rng.randint(1, vocab, prompt).tolist(),
                              max_new_tokens=gen) for _ in range(n)]

    # warmup: compile the prompt-length prefill bucket + the decode
    # program on a SHORT wave — full-length generations would double the
    # session for no extra compile coverage
    warm = requests(min(2, nreq))
    for r in warm:
        r.max_new_tokens = min(8, gen)
    engine.generate_all(warm)

    t0 = time.perf_counter()
    got = engine.generate_all(requests(nreq))
    dt = time.perf_counter() - t0
    out_tokens = sum(len(v) for v in got.values())
    assert out_tokens == nreq * gen, (out_tokens, nreq * gen)

    dev = jax.devices()[0]
    result = {
        "metric": f"llama-{size} serving decode tok/s "
                  f"(prompt={prompt}, gen={gen}, nreq={nreq}, slots={slots}, "
                  f"kvq={int(cfg.kv_quant)}, wq={cfg.quant_bits}, "
                  f"chunk={cfg.prefill_chunk})",
        "value": round(out_tokens / dt, 1),
        "unit": "tokens/s",
        "ms_per_token": round(1000.0 * dt * slots / out_tokens, 2),
        "backend": jax.default_backend(),
        "device_kind": str(getattr(dev, "device_kind", "unknown")),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    # in-process on the platform JAX selects; a CPU nobody asked for is
    # a non-zero exit, not a fallback (bench._device_or_exit)
    if "--cpu" in sys.argv:
        _pin_cpu()
    _device_or_exit(allow_cpu="--cpu" in sys.argv)
    main()
