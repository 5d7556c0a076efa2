"""Plain float32 reference of the EvaByte block: EVA attention in every layer
(an exact window beside one pooled summary a chunk of every closed window,
under one softmax) over a Llama block, and a head of ``num_pred_heads`` heads
over a vocabulary of bytes.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no kernels, no cache, no pages, no batching; one sequence, the attention a
window at a time and a block of queries at a time and the feed-forward part a
block of rows at a time, so that a prompt of 9,000 bytes fits beside a serving
engine that nearly fills the chip.  It reads the
program's parameter tree — the same weights — a layer at a time, and shares no
code with the program: it is written from the equations below.

Equations (per head ``h`` of ``NH``, ``d`` = head size, ``sigma = d^(-1/2)``,
``W`` = ``window_size``, ``C`` = ``chunk_size``; positions ``t = 0, 1, ...``;
chunk ``c`` = positions ``[C c, C c + C)``; window ``j`` = positions ``[W j, W
(j + 1))``):

  norm   N(x) = x / sqrt(mean(x^2) + eps) (1 + w)   (``norm_add_unit_offset``;
         the parameter tree keeps ``scale = 1 + w``)
  q k v  z = N1(x);  q_t = R_t (z_t W_q)_h,  k_t = R_t (z_t W_k)_h,  v_t = (z_t
         W_v)_h;  R_t rotary at the absolute position t over all d dimensions,
         theta^(-2i/d), the head's halves rotated (Llama's ``rotate_half``)
  pool   a_m = softmax over m in c of (sigma k_m . phi_h);  k~_c = sum_m a_m
         k_m + mu_h;  v~_c = sum_m a_m v_m   (``adaptive_phi``,
         ``adaptive_mu_k``: learned, per head and layer)
  attend a query at t, j = floor(t / W), sees E_t = {m : W j <= m <= t} exactly
         and S_t = {c : c < (W / C) j} — every chunk of every CLOSED window,
         none of its own — under ONE softmax:
         o_t = (sum_E e^(sigma q.k_m) v_m + sum_S e^(sigma q.k~_c) v~_c)
               / (sum_E e^(sigma q.k_m) + sum_S e^(sigma q.k~_c))
  block  x <- x + concat_h(o) W_o;  x <- x + W_down(silu(z' W_gate) * z' W_up),
         z' = N2(x)
  head   logits = N(x) W_head, ``W_head [H, P V]`` read as P heads of V: head i
         at position t predicts byte t + 1 + i.

``forward`` returns, beside the logits of all heads, what a sequence of ``S``
positions would hold in a cache, a layer: the closed windows' ``[k~ | v~]``
(``(W / C) floor(S / W)`` rows) then the open window's ``[k | v]`` (``S mod W``
rows), heads side by side.

Departures from the published model, each shared with the program and listed
in the configuration file under ``assumed``: no copy of EvaByte's modeling
code (``eva.py``, ``eva_prep_kv_kernel.py``, ``eva_agg_kernel.py``) is in the
sandbox, so the pooling weights' form and scale, ``mu`` on the pooled key only,
rotary before pooling and by absolute position, and the invisibility of the
query's own window's summaries follow the published description from memory.

Controls, each turning one mechanism off, for the benchmark's tolerance
readings and nothing else (each must read ``correct: false``):
``summaries=False`` (the window alone), ``pool="mean"`` (``a_m = 1 / C``),
``mu=False`` (``k~`` without ``mu``), ``exact=True`` (plain causal attention
over every position), ``weights_dtype`` (every weight rounded to that type's
mantissa).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: queries a block of the masked softmax holds
_Q_BLOCK = 256


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rotate(x, theta, t):
    """``x [S, NH, d]`` at positions ``t [S]``, the halves of a head paired."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = t.astype(F32)[:, None, None] * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _softmax_rows(q, keys, values, visible):
    """``q [Q, NH, d]`` over ``keys`` / ``values`` ``[K, NH, d]`` where
    ``visible [Q, K]``, a block of queries at a time -> ``[Q, NH, d]``."""
    n, sigma = q.shape[0], q.shape[-1] ** -0.5
    pad = -n % _Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, _Q_BLOCK, *q.shape[1:])
    vb = jnp.pad(visible, ((0, pad), (0, 0)), constant_values=True).reshape(
        -1, _Q_BLOCK, visible.shape[1])

    def block(args):
        qs, vis = args
        sc = jnp.einsum("qnd,knd->nqk", qs, keys) * sigma
        sc = jnp.where(vis[None], sc, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(sc, axis=-1), values)

    return jax.lax.map(block, (qb, vb)).reshape(-1, *q.shape[1:])[:n]


def attention(desc, x, w, summaries=True, pool="softmax", mu=True,
              exact=False):
    """One layer's ``x + Attn(N1(x))`` over ``x [S, H]`` -> (that, the rows a
    sequence of ``S`` positions would hold: ``[rows, 2 NH d]``)."""
    nh, d = desc["num_attention_heads"], desc["head_dim"]
    W, C = desc["window_size"], desc["chunk_size"]
    s = x.shape[0]
    a = w["attn"]
    t = jnp.arange(s)
    z = _rms(x, w["norm1"]["scale"], desc["norm_eps"])
    q = _rotate((z @ a["wq"]).reshape(s, nh, d), desc["rope_theta"], t)
    k = _rotate((z @ a["wk"]).reshape(s, nh, d), desc["rope_theta"], t)
    v = (z @ a["wv"]).reshape(s, nh, d)

    # one summary a whole chunk
    whole = s // C
    kc = k[:whole * C].reshape(whole, C, nh, d)
    vc = v[:whole * C].reshape(whole, C, nh, d)
    if pool == "softmax":
        am = jax.nn.softmax(jnp.einsum("cmnd,nd->cmn", kc, a["adaptive_phi"])
                            * d ** -0.5, axis=1)
    elif pool == "mean":
        am = jnp.full((whole, C, nh), 1.0 / C, F32)
    else:
        raise ValueError(f"unknown pool control {pool!r}")
    k_sum = jnp.einsum("cmn,cmnd->cnd", am, kc)
    if mu:
        k_sum = k_sum + a["adaptive_mu_k"]
    v_sum = jnp.einsum("cmn,cmnd->cnd", am, vc)

    outs = []
    if exact:  # plain causal attention over every position
        outs.append(_softmax_rows(q, k, v, t[None, :] <= t[:, None]))
    else:
        for j in range(-(-s // W)):
            lo, hi = W * j, min(W * (j + 1), s)
            seen = (W // C) * j if summaries else 0
            keys = jnp.concatenate([k_sum[:seen], k[lo:hi]])
            values = jnp.concatenate([v_sum[:seen], v[lo:hi]])
            tq = t[lo:hi]
            vis = jnp.concatenate(
                [jnp.ones((hi - lo, seen), bool),
                 tq[None, :] <= tq[:, None]], axis=1)
            outs.append(_softmax_rows(q[lo:hi], keys, values, vis))
    o = jnp.concatenate(outs).reshape(s, nh * d)
    closed = (W // C) * (s // W)
    rows = jnp.concatenate([
        jnp.concatenate([k_sum[:closed].reshape(closed, nh * d),
                         k[W * (s // W):].reshape(-1, nh * d)]),
        jnp.concatenate([v_sum[:closed].reshape(closed, nh * d),
                         v[W * (s // W):].reshape(-1, nh * d)])], axis=-1)
    return x + o @ a["wo"], rows


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _by_rows(f, x, rows: int = 1024):
    """``f`` over ``x [S, H]`` a block of rows at a time (the feed-forward
    part's ``[S, intermediate]`` products of a long prompt, a block's worth)."""
    n = x.shape[0]
    xb = jnp.pad(x, ((0, -n % rows), (0, 0))).reshape(-1, rows, x.shape[1])
    return jax.lax.map(f, xb).reshape(-1, x.shape[1])[:n]


def _hashable(desc: Dict[str, Any]):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in desc.items()))


@functools.lru_cache(maxsize=None)
def _programs(desc_items, controls):
    desc = dict(desc_items)

    def hi(f):
        def g(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return jax.jit(g)

    return {
        "attn": hi(lambda x, w: attention(desc, x, w, **dict(controls))),
        "ffn": hi(lambda x, s, a, b, c: x + _by_rows(
            lambda r: _swiglu(_rms(r, s, desc["norm_eps"]), a, b, c), x)),
        "head": hi(lambda x, s, w: _rms(x, s, desc["norm_eps"]) @ w),
    }


@functools.lru_cache(maxsize=None)
def _as_f32(weights_dtype):
    """A weight as float32; ``weights_dtype`` first rounds it to that type's
    mantissa, the exponent's range kept (by ``reduce_precision``: a cast there
    and back is the compiler's to drop)."""
    def cast(a):
        a = a.astype(F32)
        if weights_dtype is None:
            return a
        return jax.lax.reduce_precision(
            a, exponent_bits=8, mantissa_bits=jnp.finfo(weights_dtype).nmant)
    return jax.jit(cast)


def forward(desc: Dict[str, Any], params, ids, logits_from: int = 0,
            weights_dtype=None, summaries: bool = True, pool: str = "softmax",
            mu: bool = True, exact: bool = False):
    """Full forward of ONE sequence.  ids ``[S]`` ints -> (float32 logits ``[S
    - logits_from, P, V]`` of all ``P`` heads at positions ``logits_from ..``,
    each layer's held rows ``[(W / C) floor(S / W) + S mod W, 2 NH d]``)."""
    f32 = _as_f32(weights_dtype)
    prog = _programs(_hashable(desc), (
        ("summaries", summaries), ("pool", pool), ("mu", mu),
        ("exact", exact)))
    ids = jnp.asarray(ids, jnp.int32)
    x = f32(params["embed"]["tok"])[ids]
    held = []
    # the tree holds one period of one layer, its leaves stacked [layers]
    (stack,) = params["layers"]
    for i in range(stack["norm2"]["scale"].shape[0]):
        # a sublayer's weights in float32 at a time (a layer's are 0.8 GB at
        # the published widths), the held rows kept on the host
        def part(*names):
            return jax.tree_util.tree_map(lambda a: f32(a[i]),
                                          {k: stack[k] for k in names})

        x, rows = prog["attn"](x, part("attn", "norm1"))
        held.append(np.asarray(rows))
        w = part("mlp", "norm2")
        x = prog["ffn"](x, w["norm2"]["scale"], w["mlp"]["w_gate"],
                        w["mlp"]["w_up"], w["mlp"]["w_down"])
    logits = prog["head"](x[logits_from:], f32(params["final_norm"]["scale"]),
                          f32(params["lm_head"]["w"]))
    return logits.reshape(logits.shape[0], desc["num_pred_heads"], -1), held
