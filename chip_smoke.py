#!/usr/bin/env python3
"""The chip check: train and serve paths end to end on the TPU at Mistral-7B widths.

    python chip_smoke.py                 # on the chip (what the driver runs)
    python chip_smoke.py --rehearse      # control flow only, tiny sizes, CPU

One process, no children, nothing caught: any failed check raises and the
exit code is non-zero.  Without ``--rehearse`` the script refuses any
platform but ``tpu`` before doing work, and only a run on the chip prints the
final result line.  Legs:

* kernels — flash forward / backward / ``q_offset`` forward and paged decode
  against their XLA references at the shapes the other legs use, and the
  grouped expert matmul at the Solar-Open2 share's (40 experts of
  4096 x 1280 and 1280 x 4096, 1,024 and 4,096 picks), and its delta-rule
  step kernel (128 rows of 64 heads of 128 x 128, 8, 40 and 128 of them
  decoding);
* train   — ``deepspeed_tpu.initialize`` -> ``engine.train_batch``: bf16,
  AdamW, ZeRO-1, clipping 1.0, seq 4096, one repeated seeded batch;
* serve   — ``InferenceEngineV2`` driven by the ``put`` / ``step`` loop of
  examples/serve_paged_inference.py, whole-prompt and chunked prefill;
* multichip — the train leg on four devices, ``{data: 4}`` ZeRO-3 and
  ``{model: 2, data: 2}`` ZeRO-1 (skipped, loudly, below four devices).

Widths are ``MISTRAL_SIZES["7b"]`` uncut (hidden 4096, 32/8 heads of 128,
feed-forward 14336, vocabulary 32000); depth is cut to what one 16 GB chip
holds and printed.  Weights are random from a seed.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import sys
import time

LEGS = ("kernels", "train", "serve", "multichip")

#: full size: the chip.  Train depth 1 is 0.48 B parameters: 16 B/param of
#: fp32 master + Adam moments + accumulation buffer is 7.7 GB, ~9 GB with the
#: step's temporaries; depth 2 needs ~16.8 GB (AOT memory analysis) and does
#: not fit one v5e.  bf16 serving holds 4 layers in 2.3 GB.
FULL = dict(size="7b", seq=4096, train_layers=1, serve_layers=4, steps=6,
            heads=32, kv_heads=8, head_dim=128,
            prompt_lens=(200, 1400, 650, 2000, 330, 1100, 1999, 480),
            new_tokens=32, max_seqs=4, pages_per_seq=128, chunk=512,
            offset_chunk=512, offset_window=2048, offset=1024,
            decode_positions=(5, 700, 1999, 2047), walk_table=(64, 256),
            moe=dict(held=40, experts=320, hidden=4096, ffn=1280,
                     picks=(1024, 4096)),
            kda=dict(rows=128, heads=64, dim=128, layers=3,
                     active=(8, 40, 128)))
#: rehearsal: same control flow at sizes the CPU interpreter finishes
TINY = dict(size="tiny", seq=128, train_layers=2, serve_layers=2, steps=4,
            heads=4, kv_heads=2, head_dim=16,
            prompt_lens=(20, 70, 33, 100, 17, 55, 99, 24),
            new_tokens=6, max_seqs=4, pages_per_seq=8, chunk=32,
            offset_chunk=32, offset_window=128, offset=64,
            decode_positions=(5, 40, 100, 127), walk_table=(4, 16),
            moe=dict(held=4, experts=32, hidden=256, ffn=128,
                     picks=(64, 256)),
            kda=dict(rows=8, heads=64, dim=32, layers=2, active=(1, 3, 8)))

#: Kernel-vs-reference tolerances: max |kernel - ref| over max |ref|, the
#: reference computed in float32 at "highest" matmul precision from the same
#: bf16-rounded inputs.  The kernels keep scores, softmax state and
#: accumulators in float32 and round to bf16 only the probabilities (before
#: the PV matmul), ds (before the dq/dk matmuls) and the outputs, so the
#: forward sits within two bf16 roundings at the tensor's scale (2 * 2^-8)
#: and the backward, which chains one more rounded operand, within four.
#: fp8 or int8 compute, or bf16 softmax state, lands near 2^-4 and fails.
TOL_FWD = 2 * 2.0 ** -8
TOL_BWD = 4 * 2.0 ** -8
#: first-step loss, four chips vs one at equal global batch: the same
#: forward in another reduction order (tensor-parallel partial sums, sharded
#: batch mean).  bf16 rounding perturbs each logit by ~2^-8 relative; the
#: mean over 16k tokens of a loss near ln(32000) = 10.4 averages that far
#: below 2e-2, while a wrong shard, mask or duplicated sample moves it more.
TOL_LOSS = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: FAILED {what}")
    print(f"  ok: {what}", flush=True)


def rel_err(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def peak_gb(dev) -> str:
    stats = dev.memory_stats()
    if not stats:
        return "n/a (backend reports no memory stats)"
    return f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB"


def compiles() -> int:
    from deepspeed_tpu.telemetry.compile_sentinel import compile_counts

    return compile_counts()[0]


# --------------------------------------------------------------- kernels
def leg_kernels(sz, on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_runner import _gather_window_attend
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  _repeat_kv, xla_attention)
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_decode_attention

    S, NH, KVH, D = sz["seq"], sz["heads"], sz["kv_heads"], sz["head_dim"]
    G = NH // KVH
    f32 = jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q = jax.random.normal(ks[0], (1, S, NH, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, S, KVH, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, S, KVH, D), jnp.bfloat16)
    do = jax.random.normal(ks[3], (1, S, NH, D), jnp.bfloat16)

    # reference one kv group at a time: the [G, S, S] fp32 scores of one
    # group are 256 MB at seq 4096, all 32 heads at once would be 2 GB a copy
    @jax.jit
    def ref_group(qg, kg, vg, dog):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(
                lambda q_, k_, v_: xla_attention(
                    q_, _repeat_kv(k_, G), _repeat_kv(v_, G), True),
                qg.astype(f32), kg.astype(f32), vg.astype(f32))
            return (out,) + vjp(dog.astype(f32))

    refs = [ref_group(q[:, :, h * G:(h + 1) * G], k[:, :, h:h + 1],
                      v[:, :, h:h + 1], do[:, :, h * G:(h + 1) * G])
            for h in range(KVH)]
    ref_o, ref_dq, ref_dk, ref_dv = (
        jnp.concatenate([r[i] for r in refs], axis=2) for i in range(4))

    out, vjp = jax.vjp(lambda q_, k_, v_: flash_attention(
        q_, k_, v_, causal=True), q, k, v)
    dq, dk, dv = vjp(do)
    e = rel_err(out, ref_o)
    check(e < TOL_FWD, f"flash forward vs xla_attention seq={S} heads={NH}/"
          f"{KVH} D={D}: rel err {e:.2e} < {TOL_FWD:.2e}")
    for name, got, ref in (("dq", dq, ref_dq), ("dk", dk, ref_dk),
                           ("dv", dv, ref_dv)):
        e = rel_err(got, ref)
        check(e < TOL_BWD, f"flash backward {name}: rel err {e:.2e} < "
              f"{TOL_BWD:.2e}")

    # q_offset forward (chunked prefill): C queries at positions off+i over
    # a position-ordered window; keys past each query are masked
    C, W, off = sz["offset_chunk"], sz["offset_window"], sz["offset"]
    qc, kw, vw = q[:, :C], k[:, :W], v[:, :W]
    vis = (off + jnp.arange(C))[:, None] >= jnp.arange(W)[None, :]
    with jax.default_matmul_precision("highest"):
        ref = xla_attention(qc.astype(f32), _repeat_kv(kw.astype(f32), G),
                            _repeat_kv(vw.astype(f32), G), False,
                            bias=jnp.where(vis, 0.0, -1e30)[None, None])
    got = jax.jit(lambda a, b, c, o: flash_attention(
        a, b, c, causal=True, q_offset=o))(qc, kw, vw, jnp.int32(off))
    e = rel_err(got, ref)
    check(e < TOL_FWD, f"flash q_offset forward chunk={C} window={W} "
          f"offset={off}: rel err {e:.2e} < {TOL_FWD:.2e}")

    # paged decode vs the XLA gather path of the decode program, on a
    # three-layer pool in the engine's layout, read at the middle layer
    ps, MP, L, lyr = 16, sz["pages_per_seq"], 3, 1
    pos = jnp.asarray(sz["decode_positions"], jnp.int32)
    B = pos.shape[0]
    P = B * MP + 1  # + the trash page
    k_pool = jax.random.normal(ks[4], (L, P, ps, KVH * D), jnp.bfloat16)
    v_pool = jax.random.normal(ks[5], (L, P, ps, KVH * D), jnp.bfloat16)
    table = jax.random.permutation(ks[6], P - 1)[:B * MP].reshape(B, MP)
    used = pos[:, None] // ps >= jnp.arange(MP)[None, :]
    table = jnp.where(used, table, P - 1).astype(jnp.int32)
    qd = jax.random.normal(ks[7], (B, NH, D), jnp.bfloat16)
    cfg = TransformerConfig(hidden_size=NH * D, n_heads=NH, n_kv_heads=KVH,
                            position="rope")
    vis = jnp.arange(MP * ps)[None, None, :] <= pos[:, None, None]
    with jax.default_matmul_precision("highest"):
        ref = _gather_window_attend(
            cfg, qd.astype(f32)[:, None],
            {"k": k_pool.astype(f32), "v": v_pool.astype(f32)}, lyr, table,
            pos[:, None], vis)
    layered = jax.jit(lambda *a: paged_decode_attention(*a, layer=lyr))
    got = layered(qd, k_pool, v_pool, table, pos)
    e = rel_err(got.reshape(B, -1), ref[:, 0])
    check(e < TOL_FWD, f"paged decode at layer {lyr} of {L} vs "
          f"_gather_window_attend page={ps} KVH={KVH} G={G} D={D} "
          f"positions={sz['decode_positions']}: rel err {e:.2e} < "
          f"{TOL_FWD:.2e}")
    one = jax.jit(paged_decode_attention)(
        qd, k_pool[lyr].reshape(P, ps, KVH, D),
        v_pool[lyr].reshape(P, ps, KVH, D), table, pos)
    check(bool(jnp.array_equal(one, got)),
          "paged decode over that layer alone as [P, ps, KVH, D] == the "
          "layer-addressed read")
    if on_chip:
        text = layered.lower(qd, k_pool, v_pool, table, pos).as_text()
        check("tpu_custom_call" in text and "dstpu_paged_decode" in text,
              "paged decode lowers to the Mosaic custom call")

    # the kernel's time follows the pages the rows have, not the table: at
    # the chat cell's table, 16 calls in one program (each call's output
    # the next one's query), every row full / an eighth full / inactive
    B, MP = sz["walk_table"]
    k_pool, v_pool = (jax.random.normal(kk, (1, B * MP + 1, ps, KVH * D),
                                        jnp.bfloat16) for kk in ks[4:6])
    table = jax.random.permutation(ks[6], B * MP).reshape(B, MP)
    table = table.astype(jnp.int32)
    qd = jax.random.normal(ks[7], (B, NH, D), jnp.bfloat16)

    @jax.jit
    def walk(q_, k_, v_, pos_, act_):
        return jax.lax.fori_loop(0, 16, lambda _, x: paged_decode_attention(
            x, k_, v_, table, pos_, layer=0, active=act_), q_)

    def walk_ms(tokens, active):
        args = (qd, k_pool, v_pool, jnp.full((B,), tokens - 1, jnp.int32),
                jnp.full((B,), active))
        out = walk(*args).block_until_ready()
        check(bool(jnp.all(jnp.isfinite(out.astype(f32)))) and
              (active or not bool(jnp.any(out))),
              f"paged decode over {tokens} tokens a row, rows "
              f"{'active' if active else 'inactive'}: finite"
              f"{'' if active else ' zeros'}")
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            walk(*args).block_until_ready()
            times.append(time.perf_counter() - t0)
        return sorted(times)[2] * 1e3 / 16

    full, eighth, none = (walk_ms(MP * ps, True), walk_ms(MP * ps // 8, True),
                          walk_ms(MP * ps, False))
    if on_chip:  # a time off the chip says nothing
        gbs = 2 * B * MP * ps * KVH * D * 2 / full / 1e6
        print(f"  paged decode {B} x {MP} pages, a call: full {full:.3f} ms "
              f"({gbs:.0f} GB/s of K and V), an eighth {eighth:.3f} ms, all "
              f"inactive {none:.3f} ms", flush=True)
        check(eighth < full / 3, f"rows an eighth full take {eighth / full:.3f}"
              " of rows full < 1/3")
        check(none < 0.05 * full, f"inactive rows take {none / full:.4f} of "
              "rows full < 0.05")
    del k_pool, v_pool
    expert_matmul_checks(sz["moe"], on_chip)
    kda_step_checks(sz["kda"], on_chip)


def expert_matmul_checks(moe, on_chip: bool) -> None:
    """The grouped expert matmul at an expert share's shapes: against the
    einsum on the rows that hold picks, its time following the experts
    touched, not the worst-case buffer, and the whole tail — the rows laid by
    ``dstpu_moe_dispatch`` and collected by ``dstpu_moe_combine`` around the
    matmuls — against the XLA form, the tokens' and the gates' gradients
    included."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe.sharded_moe import (_sorted_expert_ffn,
                                               sort_pad_by_expert)
    from deepspeed_tpu.ops.pallas.grouped_matmul import (expert_block_rows,
                                                         grouped_matmul)

    E, H, F = moe["held"], moe["hidden"], moe["ffn"]
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    w_up = jax.random.normal(ks[0], (E, H, F), bf) * H ** -0.5
    w_down = jax.random.normal(ks[1], (E, F, H), bf) * F ** -0.5

    def padded(rows, key, bs):
        """The rows sorted by expert and scattered into the block-padded
        buffer, as ``moe.sharded_moe._sorted_expert_ffn`` lays them."""
        order, dest, n_rows, be, n_real = sort_pad_by_expert(key, E, bs)
        xs = jnp.zeros((n_rows, rows.shape[1]), bf).at[dest].set(
            rows[order], mode="drop")
        return xs, dest, be, n_real

    def run(impl, bs):
        def f(rows, w, key):
            xs, dest, be, n_real = padded(rows, key, bs)
            ys = grouped_matmul(xs, w, be, bs, impl=impl, n_real=n_real)
            return ys.at[dest].get(mode="fill", fill_value=0), n_real
        return jax.jit(f)

    for picks in moe["picks"]:
        bs = expert_block_rows(picks / moe["experts"], bf)
        # the router's picks over all the experts: one in eight is held
        key = jax.random.randint(ks[2], (picks,), 0, moe["experts"])
        key = jnp.minimum(key, E).astype(jnp.int32)
        for name, w in (("gate / up", w_up), ("down", w_down)):
            rows = jax.random.normal(ks[3], (picks, w.shape[1]), bf)
            got, n_real = run("pallas", bs)(rows, w, key)
            ref, _ = run("xla", bs)(rows, w, key)
            e = rel_err(got, ref)
            check(e < TOL_FWD and bool(jnp.any(ref)),
                  f"grouped matmul {name} {w.shape[1]} x {w.shape[2]}, {E} "
                  f"experts, {picks} picks in blocks of {bs} "
                  f"({int(n_real)} hold picks) vs the einsum: rel err "
                  f"{e:.2e} < {TOL_FWD:.2e}")
            del got, ref

    # the layer's tail as the share runs it (8 picks a token, gates on them):
    # laid and collected by the two row kernels around the up and down
    # matmuls, against XLA's scatter and gathers around the einsums in
    # float32
    f32, top_k = jnp.float32, 8
    experts = {"w_up": w_up, "w_down": w_down}
    for picks in moe["picks"]:
        bs = expert_block_rows(picks / moe["experts"], bf)
        key = jax.random.randint(ks[2], (picks,), 0, moe["experts"])
        key = jnp.minimum(key, E).astype(jnp.int32)
        xt = jax.random.normal(ks[3], (picks // top_k, H), bf)
        gate = jax.random.uniform(ks[4], (picks,), f32)

        def tail(impl):
            def loss(xt, gate, experts):
                out, _, _, _ = _sorted_expert_ffn(xt, key, gate, top_k, E,
                                                  experts, "gelu", bs,
                                                  impl=impl)
                return (out.astype(f32) * jnp.cos(
                    jnp.arange(H, dtype=f32))).sum(), out
            # the tokens' and the gates' gradients are the two row kernels'
            # transposes; the expert matrices' is the grouped matmul's dW,
            # which runs at a trained share's shapes (tools/kernel_probe.py)
            # and at this share's asks for more VMEM than a kernel may have
            return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))

        (_, got), g_got = tail("pallas")(xt, gate, experts)
        (_, ref), g_ref = tail("xla")(xt.astype(f32), gate, {
            n: w.astype(f32) for n, w in experts.items()})
        e = rel_err(got, ref)
        check(e < TOL_FWD and bool(jnp.any(ref)),
              f"expert tail through dstpu_moe_dispatch / _combine, {picks} "
              f"picks of {top_k} a token vs the XLA form: rel err {e:.2e} < "
              f"{TOL_FWD:.2e}")
        for name, a, b in zip(("d tokens", "d gates"), g_got, g_ref):
            e = rel_err(a, b)
            check(e < TOL_BWD, f"expert tail backward {name}: rel err "
                  f"{e:.2e} < {TOL_BWD:.2e}")
        del got, ref, g_got, g_ref

    # a decode call's picks; all, a quarter and none of the experts touched,
    # about three picks a touched expert as the router gives; 256 calls in
    # one program (a program's launch and return, about a millisecond, is
    # then a fifth of the shortest reading), each call's first row fed by
    # the last one's
    picks, n_calls = moe["picks"][0], 256 if on_chip else 2
    bs = expert_block_rows(picks / moe["experts"], bf)
    rows = jax.random.normal(ks[3], (picks, H), bf)

    @jax.jit
    def calls(rows_, w, key):
        xs, _, be, n_real = padded(rows_, key, bs)

        def body(_, x):
            y = grouped_matmul(x, w, be, bs, impl="pallas", n_real=n_real)
            return x.at[0, :128].add(y[0, :128] * 0)

        return jax.lax.fori_loop(0, n_calls, body, xs)[0, 0]

    def call_ms(touched):
        key = jnp.where(jnp.arange(picks) < 3 * touched,
                        jnp.arange(picks) % max(touched, 1), E)
        args = (rows, w_up, key.astype(jnp.int32))
        calls(*args).block_until_ready()
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            calls(*args).block_until_ready()
            times.append(time.perf_counter() - t0)
        return sorted(times)[2] * 1e3 / n_calls

    every, quarter, none = call_ms(E), call_ms(E // 4), call_ms(0)
    if on_chip:  # a time off the chip says nothing
        gbs = E * H * F * 2 / every / 1e6
        print(f"  grouped matmul {picks} picks over {E} experts of {H} x {F},"
              f" a call: all touched {every:.3f} ms ({gbs:.0f} GB/s of "
              f"weights), a quarter {quarter:.3f} ms, none {none:.3f} ms",
              flush=True)
        check(quarter < 0.4 * every, "a quarter of the experts touched takes "
              f"{quarter / every:.3f} of all of them < 0.4")
        check(none < 0.05 * every, f"no expert touched takes "
              f"{none / every:.4f} of all of them < 0.05")


def kda_step_checks(kda, on_chip: bool) -> None:
    """The delta-rule step kernel at a serving share's widths: a scattered
    set of decode rows against ``kda_step_xla`` on the same inputs, every
    other slot left as it was, and its time following the rows that decode,
    not the slots."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.kda import kda_step, kda_step_xla

    B, H, D, L = kda["rows"], kda["heads"], kda["dim"], kda["layers"]
    few, some, every = kda["active"]
    f32, lyr = jnp.float32, 1
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(x * x, -1, keepdims=True))
    q = unit(jax.random.normal(ks[0], (B, H, D), f32)) / math.sqrt(D)
    k = unit(jax.random.normal(ks[1], (B, H, D), f32))
    v = jax.random.normal(ks[2], (B, H, D), f32)
    g = -jax.random.uniform(ks[3], (B, H, D), f32, 0.0, 6.0)
    beta = jax.random.uniform(ks[4], (B, H), f32, 0.0, 2.0)
    order = jax.random.permutation(ks[6], B)

    def mask(n):
        return jnp.zeros((B,), bool).at[order[:n]].set(True)

    pool = jax.random.normal(ks[5], (L, B + 1, H, D, D), f32)
    act = mask(some)
    want_o, want_s = jax.jit(kda_step_xla)(q, k, v, g, beta, pool[lyr, :B])
    got_o, got_s = kda_step(q, k, v, g, beta, pool, lyr, act)
    e_o = rel_err(got_o, want_o * act[:, None, None])
    e_s = rel_err(got_s[lyr, :B][act], want_s[act])
    check(e_o < 1e-5 and e_s < 1e-5 and bool(jnp.any(want_o)),
          f"dstpu_kda_step {some} scattered rows of {B}, {H} heads of {D} x "
          f"{D}, layer {lyr} of {L} vs kda_step_xla: o rel err {e_o:.2e}, "
          f"state {e_s:.2e} < 1e-05")
    check(not bool(jnp.any(got_o[~act])) and bool(jnp.array_equal(
        got_s[lyr, :B][~act], pool[lyr, :B][~act])) and all(
            bool(jnp.array_equal(got_s[i], pool[i]))
            for i in range(L) if i != lyr),
          "a row that does not decode returns zero and its slot, like the "
          "other layers', is bit for bit what went in")
    if on_chip:
        text = kda_step.lower(q, k, v, g, beta, pool, lyr, act).as_text()
        check("tpu_custom_call" in text and "dstpu_kda_step" in text,
              "the step kernel lowers to the Mosaic custom call")
    del want_o, want_s, got_o, got_s

    # 64 calls in one program, the pool updated in place from call to call
    # and each call's first output fed to the next one's queries
    n_calls = 64 if on_chip else 2

    @functools.partial(jax.jit, donate_argnums=(1,))
    def calls(q_, pool_, act_):
        def body(_, c):
            q_, pool_ = c
            o, pool_ = kda_step(q_, k, v, g, beta, pool_, lyr, act_)
            return q_.at[0, 0].add(o[0, 0] * 0), pool_
        return jax.lax.fori_loop(0, n_calls, body, (q_, pool_))[1]

    def call_ms(n):
        nonlocal pool
        pool = calls(q, pool, mask(n)).block_until_ready()
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            pool = calls(q, pool, mask(n)).block_until_ready()
            times.append(time.perf_counter() - t0)
        return sorted(times)[2] * 1e3 / n_calls

    t_few, t_some, t_every = call_ms(few), call_ms(some), call_ms(every)
    check(bool(jnp.all(jnp.isfinite(pool[lyr, :B]))),
          "the states stay finite over the timed calls")
    if on_chip:  # a time off the chip says nothing
        row_mb = 2 * H * D * D * 4 / 1e6
        print(f"  kda step {B} rows x {H} heads of {D} x {D}, a call: "
              f"{few} rows decode {t_few:.3f} ms, {some} rows {t_some:.3f} "
              f"ms ({some * row_mb / t_some:.0f} GB/s of state), {every} "
              f"rows {t_every:.3f} ms ({every * row_mb / t_every:.0f} GB/s)",
              flush=True)
        check(t_some < 0.4 * t_every, f"{some} rows of {every} take "
              f"{t_some / t_every:.3f} of all of them < 0.4")
    del pool


# ----------------------------------------------------------------- train
def train_run(sz, mesh, stage, micro, gas, devices, on_chip: bool):
    """One engine, ``steps`` steps on one repeated seeded batch.  Returns
    (engine, losses).  Global batch = micro * data-ranks * gas."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.families import mistral_model
    from deepspeed_tpu.parallel.mesh import initialize_topology
    from deepspeed_tpu.runtime.config import MeshConfig

    topo = initialize_topology(MeshConfig(**mesh), devices=devices)
    check(len({d.id for d in topo.mesh.devices.flat}) == len(devices),
          f"mesh {mesh} holds {len(devices)} distinct devices "
          f"{[d.id for d in topo.mesh.devices.flat]}")
    model = mistral_model(sz["size"], max_seq_len=sz["seq"],
                          n_layers=sz["train_layers"])
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "zero_optimization": {"stage": stage},
        "mesh": mesh,
        "steps_per_print": 1 << 30,
    }
    t0 = time.time()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config,
                                               topology=topo)
    dp = topo.dp_world_size
    ids = np.random.RandomState(0).randint(
        0, model.config.vocab_size, (4, sz["seq"])).astype(np.int32)
    check(micro * dp * gas == ids.shape[0],
          f"global batch {micro}x{dp}x{gas} = {ids.shape[0]} sequences")
    batch = ids.reshape(gas, micro * dp, sz["seq"])

    if on_chip:
        with topo.mesh:
            text = engine._train_batch.lower(
                engine.state, batch, jax.random.PRNGKey(0)).as_text()
        for kern in ("dstpu_flash_fwd", "dstpu_flash_bwd_dq",
                     "dstpu_flash_bwd_dkv"):
            check("tpu_custom_call" in text and kern in text,
                  f"lowered train step calls the Mosaic kernel {kern}")

    losses = []
    for step in range(sz["steps"]):
        if step == 2:  # steps 0 and 1 warm every program
            warm = compiles()
        loss = float(engine.train_batch(batch))
        if step == 0:
            print(f"  time to first step: {time.time() - t0:.1f} s "
                  "(init + compile + step)", flush=True)
        losses.append(loss)
    print(f"  losses: {[round(x, 4) for x in losses]}", flush=True)
    check(all(math.isfinite(x) for x in losses), "every loss is finite")
    check(losses[-1] < losses[0] - 0.1,
          f"loss falls on the repeated batch: {losses[0]:.3f} -> "
          f"{losses[-1]:.3f}")
    check(compiles() == warm, "zero compiles after warm-up "
          f"({compiles() - warm} in steps 2..{sz['steps'] - 1})")
    return engine, losses


def leg_train(sz, dev0, on_chip: bool) -> float:
    import jax

    from deepspeed_tpu.models.transformer import param_count

    engine, losses = train_run(sz, {"data": 1}, 1, 1, 4, [dev0], on_chip)
    n = param_count(engine.model.config)
    print(f"  train: N={sz['train_layers']} layers, {n / 1e9:.3f} B "
          f"parameters, peak HBM {peak_gb(dev0)}", flush=True)
    engine.close()
    del engine
    gc.collect()
    jax.clear_caches()
    return losses[0]


# ----------------------------------------------------------------- serve
def drive(engine, requests):
    """The put / step loop of examples/serve_paged_inference.py."""
    uids = [engine.put(r) for r in requests]
    done = {u: [] for u in uids}
    while engine.has_work():
        for uid, rec in engine.step().items():
            done[uid].extend(rec["tokens"])
    return [done[u] for u in uids]


def leg_serve(sz, dev0, on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig,
                                            RaggedRequest)
    from deepspeed_tpu.models.families import mistral_model

    model = mistral_model(sz["size"], max_seq_len=sz["seq"],
                          n_layers=sz["serve_layers"], dtype=jnp.bfloat16)
    new, ms, mp = sz["new_tokens"], sz["max_seqs"], sz["pages_per_seq"]
    base = dict(dtype="bf16", page_size=16, num_pages=ms * mp + mp,
                max_seqs=ms, max_pages_per_seq=mp)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, model.config.vocab_size, n).tolist()
               for n in sz["prompt_lens"]]

    def reqs(idx):
        return [RaggedRequest(prompt_ids=prompts[i], max_new_tokens=new)
                for i in idx]

    t0 = time.time()
    engine = InferenceEngineV2(model, RaggedInferenceConfig(**base))
    outs = drive(engine, reqs(range(len(prompts))))
    print(f"  serve: N={sz['serve_layers']} layers, "
          f"{len(prompts)} requests (prompts {sz['prompt_lens']}), "
          f"max_seqs {ms}: {time.time() - t0:.1f} s with compiles",
          flush=True)
    check(all(len(o) == new for o in outs),
          f"every request finished with {new} new tokens")
    vocab = model.config.vocab_size
    check(all(0 <= t < vocab for o in outs for t in o),
          "every token is a vocabulary id")
    engine.assert_no_leaks()

    # alone vs in the batch: the longest and the shortest prompt again,
    # one at a time, on the drained engine (every shape is warm)
    warm = compiles()
    order = np.argsort(sz["prompt_lens"])
    for i in (int(order[-1]), int(order[0])):
        alone = drive(engine, reqs([i]))[0]
        check(alone == outs[i],
              f"greedy tokens of request {i} (prompt "
              f"{sz['prompt_lens'][i]}) alone == in the batch")
    check(compiles() == warm, "zero compiles replaying warm shapes")

    if on_chip:
        B = ms
        i32, table = jnp.zeros((B,), jnp.int32), jnp.asarray(
            engine._page_table)
        text = engine._decode.lower(
            engine.params, engine._pools, i32, i32, table,
            jnp.zeros((B,), bool), jnp.zeros((B,), jnp.float32), i32,
            engine._sample_key).as_text()
        check("tpu_custom_call" in text and "dstpu_paged_decode" in text,
              "decode program calls the Mosaic kernel dstpu_paged_decode")

    # chunked prefill: the longest prompt in chunks, two short ones
    # decoding between its chunks
    params = engine.params
    engine.close()
    del engine
    chunked = InferenceEngineV2(
        model, RaggedInferenceConfig(prefill_chunk=sz["chunk"], **base),
        params=params)
    idx = [int(order[-1]), int(order[0]), int(order[1])]
    outs_c = drive(chunked, reqs(idx))
    check(all(len(o) == new for o in outs_c),
          f"prefill_chunk={sz['chunk']}: prompts "
          f"{[sz['prompt_lens'][i] for i in idx]} finished with {new} "
          "new tokens each")
    chunked.assert_no_leaks()
    chunked.close()
    print(f"  serve: peak HBM {peak_gb(dev0)} (process lifetime)", flush=True)
    del chunked, params
    gc.collect()
    jax.clear_caches()


# ------------------------------------------------------------- multichip
def shard_report(engine, devices):
    """Per-leaf shard fraction of engine.state on every device (leaves under
    1 MiB — norm scales — may replicate by design and are left out of the
    fraction table, not out of the per-device totals)."""
    import jax

    by_frac, per_dev = {}, {d.id: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves(engine.state):
        if not hasattr(leaf, "addressable_shards") or leaf.ndim == 0:
            continue
        shards = leaf.addressable_shards
        on = {s.device.id for s in shards}
        if on != set(per_dev):
            raise AssertionError(
                f"chip_smoke: FAILED a {leaf.shape} state leaf lives on "
                f"devices {sorted(on)}, not all of {sorted(per_dev)}")
        nbytes = leaf.size * leaf.dtype.itemsize
        for s in shards:
            per_dev[s.device.id] += s.data.size * leaf.dtype.itemsize
        if nbytes >= 1 << 20:
            frac = shards[0].data.size / leaf.size
            by_frac[frac] = by_frac.get(frac, 0) + nbytes
    return by_frac, per_dev


def leg_multichip(sz, devices, loss_one: float, on_chip: bool) -> None:
    import jax

    compositions = (
        ("zero3 {data: 4}", {"data": 4}, 3, 1, 1, 0.25),
        ("zero1 {model: 2, data: 2}", {"model": 2, "data": 2}, 1, 1, 2, 0.5),
    )
    for name, mesh, stage, micro, gas, frac in compositions:
        print(f"multichip: {name}", flush=True)
        engine, losses = train_run(sz, mesh, stage, micro, gas, devices,
                                   on_chip)
        by_frac, per_dev = shard_report(engine, devices)
        total = sum(by_frac.values())
        print("  state bytes by shard fraction: " + ", ".join(
            f"1/{round(1 / f)}: {b / 1e9:.2f} GB"
            for f, b in sorted(by_frac.items())), flush=True)
        check(all(f <= frac for f in by_frac),
              f"every state leaf of 1 MiB or more ({total / 1e9:.2f} GB) is "
              f"sharded to <= 1/{round(1 / frac)} on every device")
        vals = list(per_dev.values())
        check(max(vals) == min(vals), "state bytes per device equal: "
              f"{[round(x / 1e9, 2) for x in vals]} GB")
        stats = [d.memory_stats() for d in devices]
        if all(stats):
            used = [s["bytes_in_use"] for s in stats]
            print(f"  bytes_in_use per device: "
                  f"{[round(x / 1e9, 2) for x in used]} GB, peak "
                  f"{[round(s['peak_bytes_in_use'] / 1e9, 2) for s in stats]}"
                  " GB", flush=True)
            check(max(used) <= 1.1 * min(used),
                  "bytes_in_use balanced within 10%, not piled on device 0")
        elif on_chip:
            raise AssertionError("chip_smoke: FAILED no memory stats on tpu")
        d = abs(losses[0] - loss_one)
        check(d < TOL_LOSS, f"first-step loss {losses[0]:.4f} vs one chip "
              f"{loss_one:.4f}: |diff| {d:.1e} < {TOL_LOSS}")
        engine.close()
        del engine
        gc.collect()
        jax.clear_caches()


# ------------------------------------------------------------------ main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform JAX selects; "
                         "checks control flow only, prints no result line")
    ap.add_argument("--legs", default=",".join(LEGS),
                    help=f"comma-separated subset of {LEGS} (multichip needs "
                         "train: it compares against the one-chip loss)")
    args = ap.parse_args()
    legs = args.legs.split(",")
    if not set(legs) <= set(LEGS) or ("multichip" in legs
                                      and "train" not in legs):
        ap.error(f"bad --legs {args.legs!r}")
    t_start = time.time()

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}",
          flush=True)
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.rehearse:
        print(f"chip_smoke: platform is {device['platform']!r}, not 'tpu' — "
              "no chip, no result", file=sys.stderr)
        return 2
    sz = TINY if args.rehearse else FULL

    from deepspeed_tpu.telemetry.compile_sentinel import \
        install_compile_listener
    from deepspeed_tpu.telemetry.mfu import peak_flops_for_kind
    from deepspeed_tpu.utils.platform import ensure_compile_cache

    placed = ensure_compile_cache()
    where = placed or (f"{jax.config.jax_compilation_cache_dir} (from the "
                       "environment)" if on_chip else "off (not a tpu)")
    print(f"chip_smoke: compile cache at {where}; peak-FLOPs row for this "
          f"device kind: {peak_flops_for_kind(device['kind']):.3g}",
          flush=True)
    check(install_compile_listener(), "jax.monitoring compile events "
          "observable")

    loss_one = None
    for leg in LEGS:
        if leg not in legs:
            print(f"{leg}: not selected (--legs)", flush=True)
            continue
        t0 = time.time()
        if leg == "multichip" and len(devs) < 4:
            print(f"multichip: skipped ({len(devs)} devices)", flush=True)
            continue
        print(f"{leg}:", flush=True)
        if leg == "kernels":
            leg_kernels(sz, on_chip)
        elif leg == "train":
            loss_one = leg_train(sz, devs[0], on_chip)
        elif leg == "serve":
            leg_serve(sz, devs[0], on_chip)
        else:
            leg_multichip(sz, devs[:4], loss_one, on_chip)
        print(f"{leg}: passed in {time.time() - t0:.1f} s", flush=True)

    print(f"chip_smoke: wall {time.time() - t_start:.1f} s", flush=True)
    if not on_chip or set(legs) != set(LEGS):
        print("chip_smoke: partial or rehearsal run — no result line")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
