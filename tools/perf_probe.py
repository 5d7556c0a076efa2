"""Perf probe: time each piece of the training step on the real chip.

Every timed jit returns ONE SCALAR that depends on every output we care
about (no DCE), so pulling it to the host ends the timed window with the
work.

Usage: python tools/perf_probe.py [--size 160m] [--seq 1024] [--bs 16]
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, steps=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    float(out)  # pull the scalar: the window ends with the work
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    float(out)
    return (time.perf_counter() - t0) / steps


def tree_sumsq(tree):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree_util.tree_leaves(tree))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="160m")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--bs", type=int, default=16)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    from deepspeed_tpu.models.llama import llama_config
    from deepspeed_tpu.models.transformer import (causal_lm_loss,
                                                  flops_per_token,
                                                  init_transformer_params,
                                                  logits_fn,
                                                  transformer_forward)

    cfg = llama_config(args.size, max_seq_len=args.seq)
    rng = jax.random.PRNGKey(0)
    params32 = init_transformer_params(cfg, rng)
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params32)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (args.bs, args.seq)),
        jnp.int32)
    batch = {"input_ids": ids}

    tokens = args.bs * args.seq
    fpt = flops_per_token(cfg, args.seq)
    peak = 197e12
    fwd_frac = 1.0 / 3.0

    def report(name, dt, frac=1.0):
        mfu = fpt * tokens * frac / dt / peak
        print(f"{name:44s} {dt*1e3:8.2f} ms   mfu={mfu:.3f}", flush=True)

    print(f"size={args.size} params={n_params/1e6:.1f}M seq={args.seq} "
          f"bs={args.bs} flops/tok={fpt/1e9:.2f}G ideal_fwdbwd="
          f"{fpt*tokens/peak*1e3:.1f}ms", flush=True)

    def make_loss(c):
        return lambda p, b: causal_lm_loss(c, p, b)

    c = llama_config(args.size, max_seq_len=args.seq, attn_impl="flash")
    report("fwd-only [flash512]",
           timeit(jax.jit(make_loss(c)), params, batch, steps=args.steps),
           fwd_frac)

    def grad_scalar(loss_fn):
        def f(p, b):
            g = jax.grad(loss_fn)(p, b)
            return tree_sumsq(g)
        return jax.jit(f)

    report("fwd+bwd  [flash512]",
           timeit(grad_scalar(make_loss(c)), params, batch, steps=args.steps))

    # flash block sweep
    for bq, bk in [(512, 1024), (1024, 512), (256, 1024), (1024, 256)]:
        def loss_blk(p, b, _bq=bq, _bk=bk):
            return _loss_custom(cfg, p, b, ce="plain", bq=_bq, bk=_bk)
        try:
            report(f"fwd+bwd flash bq={bq} bk={bk}",
                   timeit(grad_scalar(loss_blk), params, batch,
                          steps=args.steps))
        except Exception as e:
            print(f"flash bq={bq} bk={bk}: {type(e).__name__}: {str(e)[:100]}",
                  flush=True)

    # CE variants at flash 512/1024
    for ce in ["lse", "chunk"]:
        def loss_ce(p, b, _ce=ce):
            return _loss_custom(cfg, p, b, ce=_ce, bq=512, bk=1024)
        report(f"fwd+bwd CE={ce} flash512/1024",
               timeit(grad_scalar(loss_ce), params, batch, steps=args.steps))

    # forward without the lm_head/loss at all (isolate trunk vs head)
    def trunk_only(p, b):
        h, aux = transformer_forward(cfg, p, b["input_ids"])
        return jnp.sum(h.astype(jnp.float32)) + aux
    report("fwd+bwd trunk-only (no head/CE)",
           timeit(grad_scalar(trunk_only), params, batch, steps=args.steps))

    # head+CE only (frozen hidden)
    hidden = jax.jit(lambda p, b: transformer_forward(
        cfg, p, b["input_ids"])[0])(params, batch)

    def head_only(p, h):
        logits = logits_fn(cfg, p, h[:, :-1]).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ids[:, 1:][..., None], -1)[..., 0]
        return jnp.mean(lse - tgt)

    def head_grad(p, h):
        return tree_sumsq(jax.grad(head_only)(p, h))
    report("fwd+bwd head+CE only",
           timeit(jax.jit(head_grad), params, hidden, steps=args.steps))

    # optimizer apply
    import optax
    opt = optax.adamw(1e-4, weight_decay=0.1)
    opt_state = opt.init(params32)
    grads = jax.tree_util.tree_map(jnp.ones_like, params32)

    @jax.jit
    def apply(p, s, g):
        u, s2 = opt.update(g, s, p)
        p2 = optax.apply_updates(p, u)
        return tree_sumsq(p2) + tree_sumsq(jax.tree_util.tree_leaves(s2)[0])

    dt = timeit(apply, params32, opt_state, grads, steps=args.steps)
    print(f"{'adamw apply (fp32 master)':44s} {dt*1e3:8.2f} ms", flush=True)


def _loss_custom(cfg, params, batch, ce: str, bq: int, bk: int):
    """causal LM loss with pinned flash blocks and a chosen CE formulation."""
    import deepspeed_tpu.models.transformer as tf_mod
    from deepspeed_tpu.models.transformer import logits_fn, transformer_forward
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    orig = tf_mod._pick_attn
    tf_mod._pick_attn = lambda c: (
        lambda q, k, v, causal, mask=None: flash_attention(
            q, k, v, causal=causal, segment_mask=mask, block_q=bq, block_k=bk))
    try:
        ids = batch["input_ids"]
        hidden, aux = transformer_forward(cfg, params, ids)
        hidden = hidden[:, :-1]
        targets = ids[:, 1:]
        if ce == "plain":
            logits = logits_fn(cfg, params, hidden)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
            return jnp.mean(nll) + aux
        if ce == "lse":
            logits = logits_fn(cfg, params, hidden).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
            return jnp.mean(lse - tgt) + aux
        if ce == "chunk":
            B, S, H = hidden.shape
            n, chunk = 16, S // 16
            h_c = hidden.reshape(B, n, chunk, H).transpose(1, 0, 2, 3)
            t_c = targets.reshape(B, n, chunk).transpose(1, 0, 2)

            @jax.checkpoint
            def chunk_nll(h, t):
                logits = logits_fn(cfg, params, h).astype(jnp.float32)
                lse = jax.nn.logsumexp(logits, axis=-1)
                tgt = jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
                return jnp.sum(lse - tgt)

            def body(carry, xs):
                return carry + chunk_nll(*xs), None

            tot, _ = jax.lax.scan(body, jnp.asarray(0.0, jnp.float32),
                                  (h_c, t_c))
            return tot / (B * S) + aux
        raise ValueError(ce)
    finally:
        tf_mod._pick_attn = orig


if __name__ == "__main__":
    main()
