"""MFU tuning harness: A/B-times train_batch variants on the real chip.

Usage: python tools/tune_mfu.py [variant ...]   (no args = all)
Prints one line per variant: name, step_ms, tok/s/chip, mfu.

Findings go in PERF.md, with their origin.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def timed_variant(name, size, seq, micro_bs, steps=12, **model_overrides):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.llama import llama_model
    from deepspeed_tpu.models.transformer import flops_per_token

    fused_opt = bool(model_overrides.pop("fused_opt", False))
    mu_dtype = model_overrides.pop("mu_dtype", None)
    # zero-config override (the overlap before/after variants): merged
    # over the default stage-1 block
    zero_cfg = {"stage": 1, **model_overrides.pop("zero", {})}
    model = llama_model(size, max_seq_len=seq, **model_overrides)
    config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "FusedAdam" if fused_opt else "AdamW",
                      "params": {"lr": 1e-4, "weight_decay": 0.1,
                                 **({"fused_kernel": True} if fused_opt else {}),
                                 **({"mu_dtype": mu_dtype} if mu_dtype else {})}},
        "bf16": {"enabled": True},
        "zero_optimization": zero_cfg,
        "gradient_clipping": 1.0,
    }
    engine, *_ = deepspeed_tpu.initialize(model=model, config=config)
    if fused_opt:
        # on a multi-chip mesh the engine falls back to optax — that would
        # silently A/B the identical path; fail loudly instead
        assert getattr(engine.optimizer, "direct_update", None) is not None, \
            "fused_kernel fell back to optax (multi-device mesh?)"
    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size

    def batch():
        ids = rng.randint(0, vocab, (1, micro_bs, seq)).astype(np.int32)
        return {"input_ids": jnp.asarray(ids)}

    loss = engine.train_batch(batch())
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch())
    final = float(loss)  # host roundtrip: real completion
    dt = time.perf_counter() - t0
    assert np.isfinite(final), name

    tokens = steps * micro_bs * seq
    tok_s = tokens / dt
    flops = flops_per_token(model.config, seq) * tokens
    import bench
    peak = bench._peak_for(jax.devices()[0])  # per-chip bf16 peak by device kind
    mfu = flops / dt / peak
    rep = engine.overlap_report()
    ovl = f"  ovl={rep.overlapped_fraction:.2f}" if rep is not None else ""
    print(f"{name:36s} step={dt/steps*1e3:8.1f}ms  tok/s={tok_s:9.0f}  "
          f"mfu={mfu:.3f}{ovl}", flush=True)
    del engine
    return mfu


VARIANTS = {
    # name: (size, seq, bs, overrides)
    "base-160m-flash512": ("160m", 1024, 8, {}),
    "160m-xla-attn": ("160m", 1024, 8, {"attn_impl": "xla"}),
    "160m-flash-jaxstock": ("160m", 1024, 8, {"attn_impl": "flash_jax"}),
    "160m-flash-bq256": ("160m", 1024, 8, {"attn_impl": "flash_bq256"}),
    "160m-losschunk341": ("160m", 1024, 8, {"loss_chunk": 341}),
    "160m-bs32": ("160m", 1024, 32, {}),
    "160m-bs16": ("160m", 1024, 16, {}),
    # bwd-tile decoupling: fwd stays 512/512 (the measured optimum), bwd
    # kernels sweep their own tiles (ROADMAP S3)
    "160m-bwd256x256": ("160m", 1024, 16, {"attn_impl": "flash_bwd256x256"}),
    "160m-bwd256x512": ("160m", 1024, 16, {"attn_impl": "flash_bwd256x512"}),
    "160m-bwd512x256": ("160m", 1024, 16, {"attn_impl": "flash_bwd512x256"}),
    "160m-bwd1024x512": ("160m", 1024, 16, {"attn_impl": "flash_bwd1024x512"}),
    # single-pass Pallas Adam vs the XLA-fused optax chain (ROADMAP S4)
    "160m-fusedadam": ("160m", 1024, 16, {"fused_opt": True}),
    "1b-bs8-remat": ("1b", 1024, 8, {"remat": True}),
    "1b-bs4": ("1b", 1024, 4, {}),
    # memory-lean 1b: bf16 exp_avg + fused single-pass update — the
    # config the 1b-mu16 bench rung runs if plain 1b OOMs
    "1b-bs8-mu16-fused": ("1b", 1024, 8, {"remat": True, "fused_opt": True,
                                          "mu_dtype": "bf16"}),
    # remat policy tradeoff: keeping matmul outputs costs HBM but saves
    # recompute FLOPs — worth an A/B at the 1b shape
    "1b-bs8-remat-dots": ("1b", 1024, 8, {
        "remat": True, "mu_dtype": "bf16", "fused_opt": True,
        "remat_policy": "dots_with_no_batch_dims_saveable"}),
    # compute/collective overlap before/after (runtime/zero/overlap.py;
    # docs/COMM.md "Overlap & scheduling"): run the off/on pairs in ONE
    # session so the chip + flag state is identical — the wall delta IS
    # the exposed-comm recovery, and the printed ovl= column shows the
    # structural fraction backing it
    "160m-z1-overlap-off": ("160m", 1024, 16, {"zero": {"stage": 1}}),
    "160m-z1-overlap": ("160m", 1024, 16, {
        "zero": {"stage": 1, "overlap_grad_reduce": True}}),
    "160m-z3-overlap-off": ("160m", 1024, 16, {"zero": {"stage": 3}}),
    "160m-z3-overlap": ("160m", 1024, 16, {
        "zero": {"stage": 3, "overlap_grad_reduce": True,
                 "zero3_param_prefetch": True}}),
}


def main():
    names = sys.argv[1:] or list(VARIANTS)
    # patch the special attn impl variants in via TransformerConfig.attn_impl
    import deepspeed_tpu.models.transformer as T

    orig_pick = T._pick_attn

    def pick(cfg):
        if cfg.attn_impl == "flash_jax":
            from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
            return lambda q, k, v, causal, mask=None: flash_attention(
                q, k, v, causal=causal, segment_mask=mask, impl="jax")
        if cfg.attn_impl == "flash_bq256":
            from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
            return lambda q, k, v, causal, mask=None: flash_attention(
                q, k, v, causal=causal, segment_mask=mask,
                block_q=256, block_k=256)
        if cfg.attn_impl.startswith("flash_bwd"):
            from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
            bq, bk = map(int, cfg.attn_impl[len("flash_bwd"):].split("x"))
            fn = lambda q, k, v, causal, mask=None: flash_attention(  # noqa: E731
                q, k, v, causal=causal, segment_mask=mask,
                bwd_block_q=bq, bwd_block_k=bk)
            fn.handles_gqa = True  # GQA-native kernel, kv heads unrepeated
            return fn
        return orig_pick(cfg)

    T._pick_attn = pick
    for n in names:
        size, seq, bs, ov = VARIANTS[n]
        try:
            timed_variant(n, size, seq, bs, **ov)
        except Exception as e:  # OOM etc: report and continue
            print(f"{n:36s} FAILED: {type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
