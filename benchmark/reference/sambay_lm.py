"""Plain float32 reference of Phi-4-mini-flash (SambaY): Mamba-1 and
sliding-window differential-attention layers, one full differential-attention
layer, then gated memory units and cross-attention layers that read that one
layer's keys and values, a dense SwiGLU after every mixer, a tied head.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
every layer at every position, the recurrence token by token (``lax.scan``),
attention as a dense masked softmax, no kernels, no cache, no chunks, and no
skipping of the cross-decoder (the program runs it for a prompt's last token
only; the logits must agree).  It reads the program's parameter tree — the
same weights — and shares no code with the program; one layer is cast to
float32 at a time and the head is taken in blocks of the vocabulary, so it
fits beside a serving engine that nearly fills the chip.

Equations (``h = LayerNorm(x)`` with scale and bias; every layer ``x <- x +
mixer(LN1(x))`` then ``x <- x + W_down(SiLU(W_gate h) * W_up h)``, ``h =
LN2(x)``; a final LayerNorm; logits by the embedding).  Layer ``i`` of ``L``:
even and ``<= L/2`` Mamba, odd and ``< L/2`` window attention, ``L/2 + 1``
full attention, even beyond a gated memory unit, odd beyond cross-attention.

  Mamba  [u, z] = split(W_in h); u~ = SiLU(conv4(u) + b_c) (depthwise,
         causal); [delta, B, C] = split(W_x u~); dt = softplus(W_dt delta +
         b_dt); A = -exp(A_log) [inner, state];
         s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * u~_t) (x) B_t  (float32);
         m_t = s_t C_t + D * u~_t;  y = W_out(m * SiLU(z)).
         The last Mamba layer's m is the memory of the gated memory units.
  GMU    y = W_out(m_t * SiLU(W_in h_t)).
  attention, differential: q (NH heads), k, v (KVH heads of D) = W_qkv h + b;
         q1, q2 the even and odd query heads, k1, k2 the even and odd key
         heads, the value pairs concatenated to KVH/2 heads of 2D;
         A^j = softmax(q^j k^jT / sqrt(D) + mask) [v1|v2] (query pair p reads
         key-value pair p // 2); lam = exp(lq1.lk1) - exp(lq2.lk2) + lam0,
         lam0 = 0.8 - 0.6 exp(-0.3 i);
         y = W_o[(1 - lam0) RMSNorm_2D(A^1 - lam A^2)] + b_o.
         mask: causal; in a window layer also t - s < window.
  cross-attention: q = W_q h + b_q only; keys and values are the full
         attention layer's; the same differential form.

Departures from the published model: none is known — no copy of the published
modeling code was found offline (PR 34), so everything without a key in the
model's ``config.json`` is as the configuration file lists it under
``assumed`` (the split of layers at the middle, the Mamba sizes, the
differential form in whole, no positions, the initialisation).

``forward``'s controls (the benchmark's negative controls, each of which the
cell's check must read as not correct): ``weights_dtype`` rounds every weight
to that type's mantissa first; ``window`` replaces the window layers' reach;
``lambda_scale=0`` drops the second softmax; ``state_dtype`` rounds the
recurrent state after every token.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_HEAD_BLOCK = 16384


def _ln(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _ffn(desc, x, w):
    h = _ln(x, w["norm2"], desc["norm_eps"])
    m = w["mlp"]
    return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def _mamba(desc, x, w, state_dtype):
    """-> (the layer's output, the scan's output m ``[S, inner]``, the state
    after the last token ``[inner, state]``, the last conv - 1 rows of u)."""
    n, k, r = desc["ssm_state"], desc["ssm_conv"], desc["ssm_dt_rank"]
    s = x.shape[0]
    m = w["mamba"]
    h = _ln(x, w["norm1"], desc["norm_eps"])
    u, z = jnp.split(h @ m["w_in"], 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), F32), u])
    ub = jax.nn.silu(sum(padded[j:j + s] * m["conv"][j] for j in range(k))
                     + m["conv_b"])
    dbc = ub @ m["w_x"]
    delta, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    dt = jax.nn.softplus(delta @ m["w_dt"] + m["b_dt"])
    a = -jnp.exp(m["a_log"]).T                            # [inner, state]
    bits = jnp.finfo(state_dtype).nmant

    def token(st, xs):
        dt_t, u_t, b_t, c_t = xs
        st = (jnp.exp(dt_t[:, None] * a) * st
              + (dt_t * u_t)[:, None] * b_t[None, :])
        if bits < 23:  # (not a cast there and back, which a compiler drops)
            st = jax.lax.reduce_precision(st, exponent_bits=8,
                                          mantissa_bits=bits)
        return st, st @ c_t + m["d"] * u_t

    last, mem = jax.lax.scan(token, jnp.zeros(a.shape, F32), (dt, ub, b, c))
    y = (mem * jax.nn.silu(z)) @ m["w_out"]
    return _ffn(desc, x + y, w), mem, last, padded[s:]


def _gmu(desc, x, w, mem):
    g = w["gmu"]
    h = _ln(x, w["norm1"], desc["norm_eps"])
    return _ffn(desc, x + (mem * jax.nn.silu(h @ g["w_in"])) @ g["w_out"], w)


def _differential(desc, x, w, q, k, v, i, window, lambda_scale):
    """q ``[S, NH, D]``, k, v ``[S, KVH, D]`` -> the layer's output."""
    d = desc["head_dim"]
    s = x.shape[0]
    a = w["attn"]
    t = jnp.arange(s)
    mask = t[:, None] >= t[None, :]
    if window:
        mask = mask & (t[:, None] - t[None, :] < window)
    # [KVH/2, S, 2 (query pairs of this kv pair), D] / [KVH/2, S, D] / 2D
    pairs = k.shape[1] // 2
    q1 = q[:, 0::2].reshape(s, pairs, -1, d).transpose(1, 0, 2, 3)
    q2 = q[:, 1::2].reshape(s, pairs, -1, d).transpose(1, 0, 2, 3)
    k1, k2 = k[:, 0::2].transpose(1, 0, 2), k[:, 1::2].transpose(1, 0, 2)
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], -1).transpose(1, 0, 2)

    def one_kv_pair(args):
        qa, qb, ka, kb, vp = args

        def soft(qj, kj):
            sc = jnp.einsum("sgd,td->gst", qj, kj) / math.sqrt(d)
            sc = jnp.where(mask[None], sc, -jnp.inf)
            return jnp.einsum("gst,te->sge", jax.nn.softmax(sc, axis=-1), vp)

        return soft(qa, ka), soft(qb, kb)

    a1, a2 = jax.lax.map(one_kv_pair, (q1, q2, k1, k2, vv))
    # [KVH/2, S, G, 2D] -> [S, NH/2 pairs in order, 2D]
    a1, a2 = (o.transpose(1, 0, 2, 3).reshape(s, -1, 2 * d)
              for o in (a1, a2))
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * i.astype(F32))
    lam = (jnp.exp(jnp.sum(a["lam_q1"] * a["lam_k1"]))
           - jnp.exp(jnp.sum(a["lam_q2"] * a["lam_k2"])) + lam0)
    diff = a1 - lambda_scale * lam * a2
    diff = diff * jax.lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True)
                                + desc["norm_eps"]) * a["sub_norm"]
    y = ((1.0 - lam0) * diff).reshape(s, -1) @ a["wo"] + a["bo"]
    return _ffn(desc, x + y, w)


def _self_attn(desc, x, w, i, window, lambda_scale):
    """-> (the layer's output, its keys, its values)."""
    nh, kvh, d = (desc["num_attention_heads"], desc["num_key_value_heads"],
                  desc["head_dim"])
    s = x.shape[0]
    a = w["attn"]
    h = _ln(x, w["norm1"], desc["norm_eps"])
    q = (h @ a["wq"] + a["bq"]).reshape(s, nh, d)
    k = (h @ a["wk"] + a["bk"]).reshape(s, kvh, d)
    v = (h @ a["wv"] + a["bv"]).reshape(s, kvh, d)
    return _differential(desc, x, w, q, k, v, i, window, lambda_scale), k, v


def _cross_attn(desc, x, w, k, v, i, lambda_scale):
    a = w["attn"]
    h = _ln(x, w["norm1"], desc["norm_eps"])
    q = (h @ a["wq"] + a["bq"]).reshape(x.shape[0], -1, desc["head_dim"])
    return _differential(desc, x, w, q, k, v, i, 0, lambda_scale)


def _hashable(desc: Dict[str, Any]):
    def h(v):
        return tuple(h(x) for x in v) if isinstance(v, (list, tuple)) else v
    return tuple(sorted((k, h(v)) for k, v in desc.items()))


@functools.lru_cache(maxsize=None)
def _programs(desc_items, state_dtype, window, lambda_scale):
    desc = dict(desc_items)

    def hi(f):
        def g(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return jax.jit(g)

    return {
        "mamba": hi(lambda x, w: _mamba(desc, x, w, state_dtype)),
        "gmu": hi(lambda x, w, mem: _gmu(desc, x, w, mem)),
        "swa": hi(lambda x, w, i: _self_attn(desc, x, w, i, window,
                                             lambda_scale)),
        "dattn": hi(lambda x, w, i: _self_attn(desc, x, w, i, 0,
                                               lambda_scale)),
        "xattn": hi(lambda x, w, k, v, i: _cross_attn(desc, x, w, k, v, i,
                                                      lambda_scale)),
        "norm": hi(lambda x, p: _ln(x, p, desc["norm_eps"])),
        "head": hi(lambda x, rows: x @ rows.T),
    }


@functools.lru_cache(maxsize=None)
def _as_f32(weights_dtype):
    """A weight as float32.  ``weights_dtype`` first rounds it to that type's
    mantissa, the exponent's range kept — what a tensor-scaled float8 holds.
    (By ``reduce_precision``: the compiler may take a cast there and back for
    excess precision and drop it.)"""
    def cast(a):
        a = a.astype(F32)
        if weights_dtype is None:
            return a
        return jax.lax.reduce_precision(
            a, exponent_bits=8, mantissa_bits=jnp.finfo(weights_dtype).nmant)
    return jax.jit(cast)


def logits(desc: Dict[str, Any], params, ids, **controls):
    """Full causal forward of ONE sequence.  ids: [S] ints -> [S, V] float32
    logits at every position."""
    return forward(desc, params, ids, **controls)[0]


def forward(desc: Dict[str, Any], params, ids, state_dtype=F32,
            weights_dtype=None, window=None, lambda_scale=1.0,
            logits_from: int = 0, with_tails: bool = False):
    """-> (logits of positions ``logits_from ..`` as a numpy array ``[S -
    logits_from, V]``, what every Mamba layer keeps after the last token: a
    list, in layer order, of states ``[inner, state]`` float32 — a program
    may keep the transpose) and, ``with_tails``, the last ``conv - 1`` rows of
    every Mamba layer's ``u``."""
    f32 = _as_f32(weights_dtype)
    prog = _programs(_hashable(desc), state_dtype,
                     desc["sliding_window"] if window is None else int(window),
                     float(lambda_scale))
    ids = jnp.asarray(ids, jnp.int32)
    tok = params["embed"]["tok"]
    x = f32(tok[ids])
    states, tails = [], []
    mem = kv = None
    i = 0
    for (period, reps), trees in zip(desc["runs"], params["layers"]):
        for p in range(reps):
            for kind, stack in zip(period, trees):
                w = jax.tree_util.tree_map(lambda a: f32(a[p]), stack)
                at = jnp.int32(i)
                if kind == "mamba":
                    x, mem, last, tail = prog["mamba"](x, w)
                    states.append(last)
                    tails.append(tail)
                elif kind == "gmu":
                    x = prog["gmu"](x, w, mem)
                elif kind == "xattn":
                    x = prog["xattn"](x, w, *kv, at)
                else:
                    x, *fresh = prog[kind](x, w, at)
                    if kind == "dattn":
                        kv = fresh
                i += 1
    fn = params["final_norm"]
    x = prog["norm"](x[logits_from:], {k: f32(v) for k, v in fn.items()})
    out = np.concatenate(
        [np.asarray(prog["head"](x, f32(tok[b:b + _HEAD_BLOCK])))
         for b in range(0, tok.shape[0], _HEAD_BLOCK)], axis=-1)
    return (out, states, tails) if with_tails else (out, states)
