#!/usr/bin/env python3
"""Compile every Pallas kernel once at a realistic shape and say what happened.

    python tools/kernel_probe.py              # on the chip: compile, run, compare
    python tools/kernel_probe.py --aot        # no chip: compile only, for v5e

``--aot`` compiles against a ``v5e:2x2`` topology description through libtpu,
which works on a machine with no TPU: Mosaic refusals, illegal block shapes and
VMEM overflows surface exactly as on the chip, so kernel bring-up costs no chip
time.  It says nothing about numerics — only a run on the chip does.

One line per kernel: ``compiles`` (and, on the chip, the error against its XLA
reference) or ``refused`` with the compiler's message.  Exit code 1 if any
kernel was refused or disagreed.  On the chip it also times
the expert share's two row kernels (``--only moe_dispatch``), the paged
decode kernel at its five geometries (``--only paged_decode``: the time a
call, a block, and the share of the bytes' roofline) and the latent decode
kernel at its two (``--only mla_decode``: the same, and the kernel against
its XLA form over 12 seeds, each call twice, bit for bit).
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


#: the expert shares' layer calls: (name, experts held, of, picks a token,
#: tokens, hidden size)
ROW_SHAPES = [("LFM2 share", 8, 32, 4, 8192, 2048),
              ("Solar decode", 40, 320, 8, 128, 4096),
              ("Solar chunk", 40, 320, 8, 512, 4096),
              # a row of 12 word-sublanes (PR 57)
              ("Laguna chunk", 32, 256, 10, 1024, 3072),
              ("Laguna decode", 32, 256, 10, 64, 3072)]
# (not here: Xing4.0's row of 3584 = 14 word-sublanes, which Mosaic refuses to
# cut out of a bfloat16 source — ``rows_kernel_serves``; PERF.md §6, PR 58)


def cases():
    """(name, fn, arg specs [(shape, dtype)], reference fn or None, tol)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.fused_adam import fused_adam_update
    from deepspeed_tpu.moe.sharded_moe import sort_pad_by_expert
    from deepspeed_tpu.ops.pallas.moe_dispatch import (moe_combine,
                                                       moe_dispatch)
    from deepspeed_tpu.ops.pallas.grouped_matmul import (expert_block_rows,
                                                         grouped_matmul)
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_decode_attention
    from deepspeed_tpu.ops.pallas.quantization import (dequantize_int8,
                                                       quantize_int8)
    from deepspeed_tpu.ops.pallas.wq_matmul import (dequantize_weight,
                                                    wq_matmul)

    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    out = []

    # flash fwd+bwd at the smoke's shape, then at 8k (VMEM must not scale
    # with the sequence)
    def flash_grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(*a, causal=True)
                        .astype(f32).sum(), argnums=(0, 1, 2))(q, k, v)

    for s in (4096, 8192):
        out.append((f"flash_attention fwd+bwd seq={s} 32/8 heads D=128",
                    flash_grads, [((1, s, 32, 128), bf), ((1, s, 8, 128), bf),
                                  ((1, s, 8, 128), bf)], None, 0))

    # head_dim 64 (half a lane tile) at 8k, the LFM2 cell's attention layer
    out.append(("flash_attention fwd+bwd seq=8192 32/8 heads D=64",
                flash_grads, [((1, 8192, 32, 64), bf), ((1, 8192, 8, 64), bf),
                              ((1, 8192, 8, 64), bf)], None, 0))

    # paged decode at the two serving cells' geometries (rows x table, G)
    # and the int8 pool; compile only: chip_smoke.py holds its numerics
    def paged(q, k, v, table, pos, act, *scales):
        return paged_decode_attention(q, k, v, table, pos, *scales,
                                      layer=jnp.int32(1), active=act)

    for rows, mp, nh, dt in ((64, 256, 32, bf), (128, 512, 64, bf),
                             (64, 256, 32, jnp.int8)):
        pool = ((2, 1025, 16, 1024), dt)
        scales = [((2, 1025, 16, 8), f32)] * 2 if dt == jnp.int8 else []
        out.append((f"paged_decode {rows}x{mp} pages of 16, {nh}/8 heads "
                    f"D=128 {jnp.dtype(dt).name}", paged,
                    [((rows, nh, 128), bf), pool, pool, ((rows, mp), i32),
                     ((rows,), i32), ((rows,), jnp.bool_)] + scales, None, 0))

    # EvaByte's cell: MHA of 32 K/V heads x 128 (a 4096-wide key row and
    # value row: blocks of 8 pages) over a table composed of summary pages
    # and open-window pages, under its own name; and the chunk form's flash
    # over [summaries | the open window | the chunk], the front masked
    def eva_decode(q, k, v, table, pos, act):
        return paged_decode_attention(q, k, v, table, pos,
                                      layer=jnp.int32(1), active=act,
                                      name="dstpu_eva_decode")

    pool = ((2, 1025, 16, 4096), bf)
    out.append(("paged_decode 32x256 pages of 16, 32/32 heads D=128 as "
                "dstpu_eva_decode", eva_decode,
                [((32, 32, 128), bf), pool, pool, ((32, 256), i32),
                 ((32,), i32), ((32,), jnp.bool_)], None, 0))

    def eva_chunk(q, k, v, first):
        return flash_attention(q, k, v, causal=True, q_offset=2048,
                               window=4096, k_first=first)

    out.append(("flash_attention eva chunk 2048 over 2048 + 2048, 32/32 "
                "heads D=128, k_first", eva_chunk,
                [((1, 2048, 32, 128), bf), ((1, 4096, 32, 128), bf),
                 ((1, 4096, 32, 128), bf), ((), i32)], None, 0))

    # Phi-4-mini-flash's cell: the shared pool's decode (pairs: 40 query
    # heads of 128 over 10 K/V heads, F = 1280, blocks of 6 pages) and the
    # window decode over a ring of 32 pages a slot, under its own name
    def paired(name):
        def run(q, k, v, table, pos, act):
            return paged_decode_attention(
                q, k, v, table, pos, layer=jnp.int32(0), active=act,
                scale=0.125, name=name)
        return run

    for rows, mp, pages, name in ((128, 640, 32769, "dstpu_paged_decode"),
                                  (128, 32, 129 * 32, "dstpu_window_decode")):
        pool = ((1, pages, 16, 1280), bf)
        out.append((f"paged_decode pairs {rows}x{mp} pages of 16, 40/10 "
                    f"heads D=128 as {name}", paired(name),
                    [((rows, 40, 128), bf), pool, pool, ((rows, mp), i32),
                     ((rows,), i32), ((rows,), jnp.bool_)], None, 0))

    # the two latent cells: the latent decode over one leaf of 256 + 64 lanes
    # a token (384 as laid out: whole lane tiles; Mistral-Small-4) and of
    # 512 + 64 (640; Xing4.0), 32 absorbed heads, each at the block its
    # page's bytes give (``latent_pages_per_block``: 64 and 48 pages)
    def mla(rank):
        def run(q, pool, table, pos, act):
            from deepspeed_tpu.ops.pallas.mla_attention import \
                mla_decode_attention

            return mla_decode_attention(q, pool, table, pos, jnp.int32(1),
                                        act, rank=rank)
        return run

    for _, rows, mp, nh, rank, dr, lanes, _ in MLA_SHAPES:
        out.append((f"mla_decode {rows}x{mp} pages of 16, {nh} heads over a "
                    f"latent of {rank} + {dr}", mla(rank),
                    [((rows, nh, rank + dr), bf), ((2, 8193, 16, lanes), bf),
                     ((rows, mp), i32), ((rows,), i32), ((rows,), jnp.bool_)],
                    None, 0))

    # its window layers' chunk attention: [ring | chunk] keys with the mask
    def flash_window(q, k, v, k_first):
        return flash_attention(q, k, v, causal=True, q_offset=512,
                               sm_scale=0.125, window=512, k_first=k_first)

    out.append(("flash_attention fwd window=512 chunk=512 40/10 heads D=128",
                flash_window, [((1, 512, 40, 128), bf),
                               ((1, 1024, 10, 128), bf),
                               ((1, 1024, 10, 128), bf), ((), i32)], None, 0))

    # its selective scan: a 512-token chunk and 128 decode rows of a 129-slot
    # pool, state [16, 5120] float32, against the token-by-token scan
    from deepspeed_tpu.ops.pallas import ssm

    def positive(dt, a):
        return jnp.abs(dt) * 0.05, -jnp.abs(a) - 0.5

    def ssm_chunk(dt, u, b, c, a, d, s, kernel=True):
        dt, a = positive(dt, a)
        return ssm.ssm_chunk(dt, u, b, c, a, d, s, jnp.int32(400),
                             kernel=kernel)

    def ssm_step(dt, u, b, c, a, d, pool, kernel=True):
        dt, a = positive(dt, a)
        act = jnp.arange(dt.shape[0]) % 5 != 0
        y, pool = ssm.ssm_step(dt, u, b, c, a, d, pool, jnp.int32(1), act,
                               kernel=kernel)
        return y, pool[1, :dt.shape[0]] * act[:, None, None]

    rows_of = lambda n: [((n, 5120), f32), ((n, 5120), f32),  # noqa: E731
                         ((n, 16), f32), ((n, 16), f32), ((16, 5120), f32),
                         ((5120,), f32)]
    out.append(("ssm_chunk 512 tokens inner=5120 state=16", ssm_chunk,
                rows_of(512) + [((16, 5120), f32)],
                functools.partial(ssm_chunk, kernel=False), 1e-5))
    out.append(("ssm_step 128 rows of 129 slots x 2 layers inner=5120 "
                "state=16", ssm_step,
                rows_of(128) + [((2, 129, 16, 5120), f32)],
                functools.partial(ssm_step, kernel=False), 1e-5))

    # Solar-Open2's delta-rule step: 128 decode rows of a 129-slot pool, 64
    # heads of 128 x 128 float32 a row, three in five rows active
    from deepspeed_tpu.ops.pallas import kda

    def kda_step(q, k, v, g, beta, pool, kernel=True):
        unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        q, k, g, beta = unit(q), unit(k), -jnp.abs(g), jax.nn.sigmoid(beta)
        B = q.shape[0]
        act = jnp.arange(B) % 5 < 3
        if kernel:
            o, pool = kda.kda_step(q, k, v, g, beta, pool, jnp.int32(1), act)
        else:
            o, st = kda.kda_step_xla(q, k, v, g, beta, pool[1, :B])
            o, pool = o * act[:, None, None], pool.at[1, :B].set(st)
        return o, pool[1, :B] * act[:, None, None, None]

    out.append(("kda_step 128 rows of 129 slots x 2 layers, 64 heads of "
                "128 x 128", kda_step,
                [((128, 64, 128), f32), ((128, 64, 128), f32),
                 ((128, 64, 128), f32), ((128, 64, 128), f32),
                 ((128, 64), f32), ((2, 129, 64, 128, 128), f32)],
                functools.partial(kda_step, kernel=False), 1e-5))

    # Mixtral-8x7B expert matrices: 4096 x 14336, 8 experts, 4096 rows
    def gmm(x, w, be):
        return grouped_matmul(x, w, be % w.shape[0], impl="pallas")

    def gmm_ref(x, w, be):
        return grouped_matmul(x, w, be % w.shape[0], impl="xla")

    out.append(("grouped_matmul E=8 H=4096 F=14336 rows=4096", gmm,
                [((4096, 4096), bf), ((8, 4096, 14336), bf), ((32,), i32)],
                gmm_ref, 2.0 ** -7))

    # the Solar-Open2 share's expert matrices (40 of 320 experts held, gate
    # / up 4096 x 1280 and down 1280 x 4096) at its decode call's 1,024
    # picks and its chunk call's 4,096: sorted and padded at the derived
    # block height, the blocks that hold picks run, the picks' rows gathered
    # (a key of 40 or more is a pick on an absent expert)
    def share(impl):
        def run(rows, w, key):
            bs = expert_block_rows(key.shape[0] / 320, rows.dtype)
            order, dest, n_rows, be, n_real = sort_pad_by_expert(
                key, w.shape[0], bs)
            xs = jnp.zeros((n_rows, rows.shape[1]), rows.dtype).at[dest].set(
                rows[order], mode="drop")
            ys = grouped_matmul(xs, w, be, bs, impl=impl, n_real=n_real)
            return ys.at[dest].get(mode="fill", fill_value=0)
        return run

    for picks in (1024, 4096):
        for h, f in ((4096, 1280), (1280, 4096)):
            out.append((f"grouped_matmul share E=40 H={h} F={f} picks={picks}"
                        f" blocks of {expert_block_rows(picks / 320, bf)}",
                        share("pallas"), [((picks, h), bf), ((40, h, f), bf),
                                          ((picks,), i32)],
                        share("xla"), 2.0 ** -7))

    # the LFM2 training share (8 of 32 experts, 4 picks a token, 8192 tokens:
    # 32,768 picks of which a quarter land here): forward and both backward
    # kernels, gate / up 2048 x 1792 and down 1792 x 2048
    def share_grads(impl):
        def run(rows, w, key):
            return jax.grad(lambda r, m: share(impl)(r, m, key).astype(f32)
                            .sum(), argnums=(0, 1))(rows, w)
        return run

    for h, f in ((2048, 1792), (1792, 2048)):
        out.append((f"grouped_matmul fwd+dx+dw share E=8 H={h} F={f} "
                    "picks=32768 of 32 experts", share_grads("pallas"),
                    [((32768, h), bf), ((8, h, f), bf), ((32768,), i32)],
                    share_grads("xla"), 2.0 ** -6))

    # the rows into the sorted, padded buffer and back onto their tokens
    # (no expert between: out[t] = x[t] * the sum of t's held gates), forward
    # and backward, through the two row kernels against XLA's scatter and
    # gathers, at every expert share's layer call
    def moved(impl, held, of, top_k):
        def run(xt, gate, key):
            key = _held_keys(key, held, of)
            bs = expert_block_rows(key.shape[0] / of, xt.dtype)

            def through(xt, gate):
                if impl == "pallas":
                    maps = _row_maps(key, top_k, held, bs)
                    out = moe_combine(moe_dispatch(xt, *maps), gate, *maps)
                else:
                    order, dest, n_rows, _, _ = sort_pad_by_expert(key, held,
                                                                   bs)
                    xs = jnp.zeros((n_rows, xt.shape[1]), xt.dtype).at[
                        dest].set(xt[order // top_k], mode="drop")
                    rows = (xs.at[dest].get(mode="fill", fill_value=0)
                            * gate.reshape(-1)[order][:, None])
                    out = jnp.zeros(xt.shape, f32).at[order // top_k].add(rows)
                return (out.astype(f32) * jnp.cos(
                    jnp.arange(xt.shape[1], dtype=f32))).sum(), out
            (_, out), grads = jax.value_and_grad(through, (0, 1),
                                                 has_aux=True)(xt, gate)
            return out.astype(f32), grads
        return run

    for what, held, of, top_k, t, h in ROW_SHAPES:
        out.append((f"moe_dispatch + moe_combine fwd+bwd {what}: {t} tokens "
                    f"x {top_k} picks, {held} of {of} experts held, H={h}",
                    moved("pallas", held, of, top_k),
                    [((t, h), bf), ((t, top_k), f32), ((t * top_k,), i32)],
                    moved("xla", held, of, top_k), 2.0 ** -6))

    # Mistral-7B down projection, decode batch of 4
    for bits in (8, 4):
        rows = 14336 if bits == 8 else 14336 // 2

        def wq(x, c, s, bits=bits):
            return wq_matmul(x, c, s, bits=bits, group=128, impl="pallas")

        def wq_ref(x, c, s, bits=bits):
            w = dequantize_weight(c, s, bits=bits, group=128, k=14336,
                                  dtype=f32)
            return (x.astype(f32) @ w).astype(x.dtype)

        out.append((f"wq_matmul int{bits} M=4 K=14336 N=4096", wq,
                    [((4, 14336), bf),
                     ((rows, 4096), jnp.int8 if bits == 8 else jnp.uint8),
                     ((112, 4096), f32)], wq_ref, 2.0 ** -6))

    # one Mistral-7B layer's parameters as a flat fp32 buffer
    n = 218_112_000

    def adam(p, g, m, v):
        return fused_adam_update(p, g, m, v, jnp.asarray(3, i32), 1e-3,
                                 weight_decay=0.01)

    def adam_ref(p, g, m, v):
        m2 = 0.9 * m + 0.1 * g
        v2 = 0.999 * v + 0.001 * g * g
        up = (m2 / (1 - 0.9 ** 3)) / (jnp.sqrt(v2 / (1 - 0.999 ** 3)) + 1e-8)
        return p - 1e-3 * (up + 0.01 * p), m2, v2

    out.append((f"fused_adam n={n}", adam, [((n,), f32)] * 4, adam_ref, 1e-5))

    def quant_roundtrip(x):
        q, s, length = quantize_int8(x)
        return dequantize_int8(q, s, length, x.dtype)

    out.append(("quantization int8 round trip n=64Mi", quant_roundtrip,
                [((64 * 2 ** 20,), bf)], lambda x: x, 2.0 ** -6))
    return out


def _held_keys(key, held: int, of: int):
    """Random ints as expert picks over ``of`` experts, ``held`` of them
    here (the others get the invalid key)."""
    key = key % of
    return jnp.where(key < held, key, held)


def _row_maps(key, top_k: int, held: int, block_rows: int):
    from deepspeed_tpu.moe.sharded_moe import pick_row_maps

    row_pick, n_valid, dest, _, _, _, n_real = pick_row_maps(
        key, top_k, held, block_rows)
    return row_pick, n_valid, n_real, dest, block_rows


def row_rates() -> None:
    """On the chip: what a call of each row kernel, of the maps they walk
    and of XLA's scatter and gather of the same rows costs, each as 64
    calls chained in one program (a program's launch and return is about a
    millisecond) by the host's clock."""
    from deepspeed_tpu.moe.sharded_moe import sort_pad_by_expert
    from deepspeed_tpu.ops.pallas.grouped_matmul import expert_block_rows
    from deepspeed_tpu.ops.pallas.moe_dispatch import (combine_rows,
                                                       dispatch_rows)

    reps = 64

    def chained(body):
        """``body(bump) -> array``; ``bump`` is an int32 0 that XLA cannot
        fold, taken from the previous call's result."""
        def run(*args):
            def step(_, bump):
                out = body(bump, *args)
                return (out.reshape(-1)[0] > 3e38).astype(jnp.int32)
            return jax.lax.fori_loop(0, reps, step, jnp.int32(0))
        return run

    for what, held, of, top_k, t, h in ROW_SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        key = _held_keys(jax.random.randint(ks[0], (t * top_k,), 0, of * 64),
                         held, of)
        xt = jax.random.normal(ks[1], (t, h), jnp.float32).astype(jnp.bfloat16)
        gate = jax.random.uniform(ks[2], (t, top_k), jnp.float32)
        bs = expert_block_rows(t * top_k / of, xt.dtype)
        row_pick, n_valid, n_real, dest, _ = jax.jit(
            _row_maps, static_argnums=(1, 2, 3))(key, top_k, held, bs)
        order, sdest, n_rows, _, _ = jax.jit(
            sort_pad_by_expert, static_argnums=(1, 2))(key, held, bs)
        ys = jax.random.normal(ks[3], (n_rows, h), jnp.float32).astype(
            jnp.bfloat16)
        rows = int(jnp.sum(n_valid))
        forms = {
            "maps (argsort, cumulative sum, block slices)": (
                lambda b, key: sum(jnp.sum(m) for m in _row_maps(
                    key + b, top_k, held, bs)[:4]).astype(
                        jnp.float32).reshape(1), (key,)),
            "dstpu_moe_dispatch": (
                lambda b, xt, rp, nv, nr: dispatch_rows(
                    xt, rp // top_k, nv, nr + b, bs), (xt, row_pick, n_valid,
                                                      n_real)),
            "dstpu_moe_combine (rows already tiles)": (
                lambda b, ys, dest, gate: combine_rows(
                    ys, dest + b, weights=gate), (ys, dest, gate)),
            "dstpu_moe_combine, dot": (
                lambda b, ys, dest, xt: combine_rows(ys, dest + b, dot=xt),
                (ys, dest, xt)),
            "relayout of the buffer to tiles + combine": (
                lambda b, ys, dest, gate: combine_rows(
                    ys + b.astype(ys.dtype), dest, weights=gate),
                (ys, dest, gate)),
            "XLA scatter of the rows": (
                lambda b, xt, order, sdest: jnp.zeros(
                    (n_rows, h), xt.dtype).at[sdest + b].set(
                        xt[order // top_k], mode="drop"), (xt, order, sdest)),
            "XLA gather back and scatter-add": (
                lambda b, ys, order, sdest, gate: jnp.zeros(
                    (t, h), ys.dtype).at[order // top_k].add(
                        ys.at[sdest + b].get(mode="fill", fill_value=0)
                        * gate.reshape(-1)[order][:, None].astype(ys.dtype)),
                (ys, order, sdest, gate)),
        }
        print(f"row rates, {what}: {t * top_k} picks, {rows} held, "
              f"{int(n_real)} real blocks of {bs}, buffer {n_rows} rows x "
              f"{h}", flush=True)
        for name, (body, args) in forms.items():
            fn = jax.jit(chained(body))
            fn(*args).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(3):
                fn(*args).block_until_ready()
            ms = (time.perf_counter() - t0) / 3 / reps * 1e3
            print(f"  {name}: {ms:.4f} ms a call"
                  + (f" = {ms * 1e3 / rows:.4f} us a held row"
                     if name.startswith("dstpu") else ""), flush=True)


def _shuffled_table(pages, mp: int, perm):
    """A page table ``[rows, mp]`` whose row ``r`` holds ``pages[r]`` ids of
    a pool of ``sum(pages)`` pages in the order ``perm`` and the pool's one
    page more (the trash page) past them."""
    import numpy as np

    n_pages = int(pages.sum())
    table = np.full((len(pages), mp), n_pages, np.int32)
    ends = np.cumsum(pages)
    for r, n in enumerate(pages):
        table[r, :n] = perm[ends[r] - n:ends[r]]
    return table


def _median_ms(fn, args, reps: int) -> float:
    """ms a call of a program that chains ``reps`` calls: the median of
    five runs by the host's clock, after one that compiles."""
    fn(*args).block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return sorted(times)[2] / reps * 1e3


#: the paged kernel's five geometries: (name in a trace, rows, table pages,
#: query heads, K/V heads, scale, visible tokens of row i)
PAGED_SHAPES = [
    ("dstpu_paged_decode", "Mistral chat", 64, 256, 32, 8, None,
     lambda i: 4096),
    ("dstpu_paged_decode", "Solar", 128, 512, 64, 8, None, lambda i: 2048),
    ("dstpu_paged_decode", "Phi-4 pairs, the shared pool", 128, 640, 40, 10,
     0.125, lambda i: 160 + (i * 613) % 1900),
    ("dstpu_window_decode", "Phi-4 pairs, a ring of 512", 128, 32, 40, 10,
     0.125, lambda i: 512),
    ("dstpu_eva_decode", "EvaByte, summaries + an open window", 32, 256, 32,
     32, None, lambda i: 128 * (i % 6) + 16 + (i * 613) % 2032),
]


def paged_rates() -> None:
    """On the chip: what a call of the paged decode kernel costs at each of
    its five geometries (pages of 16 tokens, heads of 128, bfloat16), as 16
    calls chained in one program (a call's result is the next one's
    queries) by the host's clock: the time a call, a block of
    ``pages_per_block`` pages, and the visible pages' bytes against the
    HBM's peak."""
    import json
    import pathlib

    import numpy as np

    from deepspeed_tpu.ops.pallas.paged_attention import (
        n_blocks, paged_decode_attention, pages_per_block)

    reps, ps, d = 16, 16, 128
    peaks = json.loads((pathlib.Path(__file__).resolve().parents[1]
                        / "benchmark" / "peaks.json").read_text())
    hbm = peaks[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    for name, what, b, mp, nh, kvh, scale, tokens in PAGED_SHAPES:
        feat = kvh * d
        toks = np.asarray([min(tokens(i), mp * ps) for i in range(b)])
        pages = -(-toks // ps)
        n_pages = int(pages.sum())
        nb = pages_per_block(ps, feat, 2)
        blocks = int(n_blocks(toks, ps, nb).sum())
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        q = jax.random.normal(ks[0], (b, nh, d), jnp.bfloat16)
        k_pool, v_pool = (jax.random.normal(
            k, (1, n_pages + 1, ps, feat), jnp.bfloat16) for k in ks[1:3])
        table = _shuffled_table(pages, mp, np.asarray(
            jax.random.permutation(ks[3], n_pages)))

        @jax.jit
        def chained(q, k_pool, v_pool, table, pos):
            return jax.lax.fori_loop(
                0, reps, lambda _, x: paged_decode_attention(
                    x, k_pool, v_pool, table, pos, layer=jnp.int32(0),
                    scale=scale, name=name), q)

        args = (q, k_pool, v_pool, jnp.asarray(table),
                jnp.asarray(toks - 1, jnp.int32))
        ms = _median_ms(chained, args, reps)
        least_ms = 2 * n_pages * ps * feat * 2 / hbm * 1e3
        print(f"paged rates, {what} as {name}: {b} rows x {mp} pages, "
              f"{nh}/{kvh} heads, {n_pages} visible pages in {blocks} blocks "
              f"of {nb}: {ms:.4f} ms a call = {ms * 1e3 / blocks:.4f} us a "
              f"block, {least_ms / ms * 100:.1f} % of the bytes' roofline "
              f"({least_ms:.4f} ms)", flush=True)
        del args, k_pool, v_pool


#: the latent kernel's two geometries: (cell, rows, table pages, heads, rank,
#: rotary, lanes as laid out, mean visible tokens a row)
MLA_SHAPES = [
    ("Mistral-Small-4", 128, 1088, 32, 256, 64, 384, 5200),
    ("Xing4.0", 48, 2113, 32, 512, 64, 640, 7600),
]


def mla_rates() -> int:
    """On the chip: the latent decode kernel at both latent cells' sizes
    (pages of 16 tokens, bfloat16) over ragged rows — lengths drawn like the
    cells' prompts, lognormal about the cell's mean — and shuffled page ids.
    (i) What a call costs, as 16 calls chained in one program by the host's
    clock: the time a call, a page, a block of ``latent_pages_per_block``
    pages, and the visible rows' bytes against the HBM's peak.  (ii) Over 12
    seeds, ``model_runner._mla_absorbed`` through the kernel against its XLA
    form (over the same values in float32, and as the program runs it), the
    kernel's call made twice and the two outputs compared bit for bit: a
    race in the ring shows there and nowhere on the CPU.  Returns the number
    of seeds that differed."""
    import json
    import pathlib
    import types

    import numpy as np

    from deepspeed_tpu.inference.v2.model_runner import _mla_absorbed
    from deepspeed_tpu.ops.pallas.mla_attention import (
        latent_pages_per_block, mla_decode_attention)
    from deepspeed_tpu.ops.pallas.paged_attention import n_blocks

    reps, ps, seeds, tol = 16, 16, 12, 2.0 ** -6
    peaks = json.loads((pathlib.Path(__file__).resolve().parents[1]
                        / "benchmark" / "peaks.json").read_text())
    hbm = peaks[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    bad = 0
    for what, b, mp, nh, rank, dr, lanes, mean in MLA_SHAPES:
        nb = latent_pages_per_block(ps, lanes, 2)
        cfg = types.SimpleNamespace(n_heads=nh, kv_lora_rank=rank,
                                    qk_nope_head_dim=64, qk_rope_head_dim=dr)
        absorbed = jax.jit(functools.partial(_mla_absorbed, cfg),
                           static_argnames=("use_kernel",))

        @jax.jit
        def chained(q, pool, table, pos, act, rank=rank):
            def step(_, x):
                return x.at[:, :, :rank].set(mla_decode_attention(
                    x, pool, table, pos, jnp.int32(0), act, rank=rank))
            return jax.lax.fori_loop(0, reps, step, q)

        worst = [0.0, 0.0]
        for seed in range(seeds):
            rng = np.random.default_rng(seed)
            toks = np.clip(rng.lognormal(np.log(mean / 1.2776), 0.7, b), 512,
                           mp * ps).astype(np.int64)
            if seed % 4 == 3:  # empty slots among the rows
                toks[rng.random(b) < 0.1] = 0
            pages = -(-toks // ps)
            n_pages = int(pages.sum())
            table = _shuffled_table(pages, mp, rng.permutation(n_pages))
            ks = jax.random.split(jax.random.PRNGKey(seed), 4)
            pool = jax.random.normal(ks[0], (1, n_pages + 1, ps, lanes),
                                     jnp.bfloat16)
            q_nope, q_rope = (
                (jax.random.normal(k, (b, nh, w), jnp.float32) * 0.3).astype(
                    jnp.bfloat16) for k, w in ((ks[1], 64), (ks[2], dr)))
            layer = {"attn": {"w_ukv": (jax.random.normal(
                ks[3], (rank, nh * 192), jnp.float32) * rank ** -0.5).astype(
                    jnp.bfloat16)}}
            args = (layer, q_nope, q_rope, {"latent": pool}, jnp.int32(0),
                    jnp.asarray(table), jnp.asarray(toks - 1, jnp.int32),
                    jnp.asarray(toks > 0))
            if seed == 0:
                q = jnp.concatenate([jnp.einsum(
                    "bnd,rnd->bnr", q_nope, layer["attn"]["w_ukv"].reshape(
                        rank, nh, -1)[..., :64]), q_rope], axis=-1)
                ms = _median_ms(chained, (q, pool) + args[5:], reps)
                blocks = int(n_blocks(toks, ps, nb).sum())
                stated, laid = (int(toks.sum()) * w * 2 / hbm * 1e3
                                for w in (rank + dr, lanes))
                print(f"mla rates, {what}: {b} rows x {mp} pages, {nh} heads "
                      f"over {rank} + {dr} in {lanes} lanes, "
                      f"{int(toks.sum())} visible tokens in {n_pages} pages, "
                      f"{blocks} blocks of {nb} (fill "
                      f"{toks.sum() / (blocks * nb * ps):.3f}): {ms:.4f} ms a "
                      f"call = {ms * 1e6 / n_pages:.1f} ns a page, "
                      f"{ms * 1e3 / blocks:.3f} us a block, "
                      f"{stated / ms * 100:.1f} % of the stated bytes' "
                      f"roofline ({stated:.4f} ms; {laid / ms * 100:.1f} % as "
                      "laid out)", flush=True)
                del q
            one = absorbed(*args, use_kernel=True)
            two = absorbed(*args, use_kernel=True)
            same = bool(jnp.array_equal(one, two))
            # the XLA form as the program would run it (its scores rounded to
            # bfloat16) and over the same values in float32: the limit is on
            # the second; a row that is not active is the kernel's alone (the
            # XLA form's softmax over no key is uniform)
            errs = []
            for dt in (jnp.bfloat16, jnp.float32):
                want = absorbed(*jax.tree_util.tree_map(
                    lambda a, dt=dt: a.astype(dt) if jnp.issubdtype(
                        a.dtype, jnp.floating) else a, args),
                    use_kernel=False).astype(jnp.float32)
                diff = (one.astype(jnp.float32) - want) * args[-1][:, None,
                                                                   None]
                errs.append(float(jnp.max(jnp.abs(diff))
                                  / jnp.max(jnp.abs(want))))
                del want, diff
            zeros = not bool(jnp.any(jnp.where(args[-1][:, None, None], 0,
                                               one)))
            worst = [max(w, e) for w, e in zip(worst, errs)]
            if not (same and zeros and errs[1] < tol):
                bad += 1
                print(f"  seed {seed}: DIFFERS — two calls equal {same}, "
                      f"inactive rows zero {zeros}, rel err vs the XLA form "
                      f"in float32 {errs[1]:.2e} (limit {tol:.2e}), as the "
                      f"program runs it {errs[0]:.2e}", flush=True)
            del pool, args, one, two
        print(f"  {seeds} seeds x 2 calls, each pair bit for bit: worst rel "
              f"err vs _mla_absorbed(use_kernel=False) in float32 "
              f"{worst[1]:.2e} (limit {tol:.2e}), as the program runs it "
              f"{worst[0]:.2e}; {bad} differences so far", flush=True)
    return bad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--aot", action="store_true",
                    help="compile only, against a v5e:2x2 topology (no chip)")
    ap.add_argument("--only", default="",
                    help="probe only the kernels whose line holds this")
    args = ap.parse_args()

    sharding = None
    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        import deepspeed_tpu.utils.platform as plat

        plat.platform = lambda: "tpu"  # compile the kernels, not interpret
        dev = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
        sharding = SingleDeviceSharding(dev)
        print(f"kernel_probe: AOT for {dev.device_kind!r}, compile only")
    else:
        dev = jax.devices()[0]
        print(f"kernel_probe: platform={dev.platform} "
              f"device_kind={dev.device_kind!r}")
        if dev.platform != "tpu":
            print("kernel_probe: no chip (use --aot to compile without one)",
                  file=sys.stderr)
            return 2

    bad = 0
    for name, fn, specs, ref, tol in cases():
        if args.only not in name:
            continue
        t0 = time.time()
        abstract = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                    for s, d in specs]
        try:
            compiled = jax.jit(fn).lower(*abstract).compile()
        # the probe's whole job is to report a compiler refusal per kernel
        except Exception as e:  # noqa: BLE001
            bad += 1
            print(f"refused   {name}: {type(e).__name__}: "
                  f"{' '.join(str(e).split())[:600]}", flush=True)
            continue
        line = f"compiles  {name} ({time.time() - t0:.1f} s)"
        if not args.aot and ref is not None:
            keys = jax.random.split(jax.random.PRNGKey(0), len(specs))
            vals = [jax.random.normal(k, s, jnp.float32).astype(d)
                    if jnp.issubdtype(d, jnp.floating)
                    else jax.random.randint(k, s, 0, 127).astype(d)
                    for k, (s, d) in zip(keys, specs)]
            if "adam" in name:  # second moments are non-negative
                vals[3] = jnp.abs(vals[3])
            got = jax.tree_util.tree_leaves(compiled(*vals))
            want = jax.tree_util.tree_leaves(jax.jit(ref)(*vals))
            err = max(float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                            - w.astype(jnp.float32)))
                            / jnp.max(jnp.abs(w.astype(jnp.float32))))
                      for g, w in zip(got, want))
            ok = err < tol
            bad += not ok
            line += (f", rel err vs XLA reference {err:.2e} "
                     f"{'<' if ok else '>= (DISAGREES)'} {tol:.2e}")
            del vals, got, want
        print(line, flush=True)
    if not args.aot and args.only in "moe_dispatch + moe_combine":
        row_rates()
    if not args.aot and args.only in "paged_decode":
        paged_rates()
    if not args.aot and args.only in "mla_decode":
        bad += mla_rates()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
