"""Plain float32 reference of the Mistral-Small-4 block: latent attention (MLA)
in every layer, every layer followed by an expert layer with a sigmoid router,
a selection bias and one ungated shared expert.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
**expanded form only** — keys and values of every head made from the latent at
every position — no kernels, no cache, no chunks, no absorption, no batching;
the attention is a masked softmax computed a block of queries at a time so
that a prompt of 8,448 fits beside a serving engine that nearly fills the
chip, the experts one at a time over every token.  It reads the program's
parameter tree — the same weights — a layer (and an expert) at a time, and
shares no code with the program.

Equations (RMSNorm eps 1e-6, pre-norm; ``x <- x + Attn(N1(x))``, then ``x <- x
+ MoE(N2(x))``; a final RMSNorm; logits by an untied head over the held slice
of the vocabulary):

  MLA    h = N1(x);  c_q = RMSNorm(h W_dq), q = c_q W_uq -> NH heads of
         [q_nope (dn) | q_rope (dr)];  [c_kv | k_r] = h W_dkv,
         c = RMSNorm(c_kv), k_rope = R_t(k_r) (one rotary key for all heads);
         [k_nope (dn) | v (dv)] = c W_ukv per head;  q_rope <- R_t(q_rope);
         s = a_t sigma (q_nope . k_nope + q_rope . k_rope), causal softmax
         over u <= t, o = sum p v, out = o W_o.  No bias anywhere.
         sigma = (dn + dr)^(-1/2) m^2, m = 0.1 mscale_all_dim ln(factor) + 1;
         a_t = 1 + beta ln(1 + floor(t / original_max)) on the query.
  R_t    rotates pairs (2i, 2i + 1) of dr dimensions by t f_i, i < dr / 2:
         theta_i = theta^(-2i / dr), f_i = theta_i (1 - r_i) + theta_i /
         factor r_i, r_i = clip((i - lo) / (hi - lo), 0, 1), lo = floor(dr
         ln(original_max / (beta_fast 2 pi)) / (2 ln theta)), hi = ceil(dr
         ln(original_max / (beta_slow 2 pi)) / (2 ln theta)) (YaRN).
  expert h = N2(x);  g = sigmoid(h W_r);  the top_k largest of g + b over all
         routed experts;  w = g[picked] / (sum g[picked] + 1e-20) x
         routed_scaling_factor;  y = SwiGLU_shared(h) + sum over picked and
         held of w_e SwiGLU_e(h).

``forward`` returns, beside the logits, what a token leaves in a cache: each
layer's ``[c | k_rope]`` rows ``[S, R + dr]``.  A rotated vector is written
with the pairs' even members first and the odd ones second, as the published
``apply_rotary_pos_emb_interleave`` leaves it (every query and key alike, so
no product changes).

Departures from the published model, each shared with the program and listed
in the configuration file under ``assumed`` or ``reduced``:
- no copy of Mistral's own modeling code is in the sandbox (``grep -rl -i
  "mistral4\\|llama_4_scaling"`` over site-packages finds none); the config's
  keys are DeepSeek-V3's, whose ``modeling_deepseek_v3.py`` (transformers
  4.57.6) this follows: the softmax scale times ``mscale^2`` when
  ``mscale_all_dim`` is set, sigmoid scores with a selection bias, ``n_group``
  1 (no group limit).  ``a_t`` (``llama_4_scaling_beta``) is applied to the
  query: that file has no code for the key;
- the share of an expert-parallel deployment: of the routed experts only those
  the parameter tree holds (``experts_first`` ..) are evaluated — a pick on an
  absent expert adds nothing, here as in the program — and the vocabulary is
  the slice the tree holds;
- the vision tower is absent: the traffic is text.

Controls, for the benchmark's negative runs (each must read ``correct:
false``): ``weights_dtype`` (every weight rounded to that type's mantissa),
``rope="plain"`` (YaRN's blend left out: ``f_i = theta_i``),
``softmax_scale="plain"`` (``m^2`` left out), ``router="softmax"`` (softmax
scores, no bias).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: queries a block of the masked softmax holds: [NH, 256, S] float32 scores
_Q_BLOCK = 256


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope_frequencies(desc, rope: str = "yarn"):
    """``[dr / 2]`` angles a position: the closed form above."""
    dr, theta = desc["qk_rope_head_dim"], desc["rope_theta"]
    i = jnp.arange(dr // 2, dtype=F32)
    th = theta ** (-2.0 * i / dr)
    if rope == "plain":
        return th
    if rope != "yarn":
        raise ValueError(f"unknown rope control {rope!r}")
    orig, factor = desc["rope_original_max"], desc["rope_factor"]

    def pair(rotations):
        return dr * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair(desc["rope_beta_fast"])), 0)
    hi = min(math.ceil(pair(desc["rope_beta_slow"])), dr - 1)
    r = jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return th * (1.0 - r) + th / factor * r


def _rotate(x, freqs, t):
    """``x [S, ..., dr]`` at positions ``t [S]``: pairs (2i, 2i + 1) turned by
    ``t f_i``; the even members come out first."""
    ang = t.reshape((-1,) + (1,) * (x.ndim - 1)).astype(F32) * freqs
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def softmax_scale(desc, softmax_scale: str = "yarn") -> float:
    dn, dr = desc["qk_nope_head_dim"], desc["qk_rope_head_dim"]
    m = 1.0
    if softmax_scale == "yarn":
        m = 0.1 * desc["rope_mscale_all_dim"] * math.log(desc["rope_factor"]) \
            + 1.0
    elif softmax_scale != "plain":
        raise ValueError(f"unknown softmax_scale control {softmax_scale!r}")
    return m * m / math.sqrt(dn + dr)


def attention(desc, x, w, rope="yarn", scale="yarn", query_scaling=True):
    """One layer's ``x + Attn(N1(x))`` over ``x [S, H]`` -> (that, the rows
    ``[c | k_rope] [S, R + dr]`` the layer would cache)."""
    nh, r = desc["num_attention_heads"], desc["kv_lora_rank"]
    dn, dr, dv = (desc["qk_nope_head_dim"], desc["qk_rope_head_dim"],
                  desc["v_head_dim"])
    s = x.shape[0]
    a, eps = w["attn"], desc["norm_eps"]
    t = jnp.arange(s)
    freqs = rope_frequencies(desc, rope)
    h = _rms(x, w["norm1"]["scale"], eps)
    q = (_rms(h @ a["w_dq"], a["q_norm"], eps) @ a["w_uq"]).reshape(
        s, nh, dn + dr)
    ckv = h @ a["w_dkv"]
    c = _rms(ckv[:, :r], a["kv_norm"], eps)
    k_rope = _rotate(ckv[:, r:], freqs, t)                       # [S, dr]
    kv = (c @ a["w_ukv"]).reshape(s, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope[:, None], (s, nh, dr))], axis=-1)                 # [S, NH, .]
    v = kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], freqs, t)], -1)
    a_t = jnp.ones((s,), F32)
    if query_scaling and desc["llama_4_scaling_beta"]:
        a_t = 1.0 + desc["llama_4_scaling_beta"] * jnp.log1p(
            jnp.floor(t / desc["rope_original_max"]).astype(F32))
    q = q * (a_t * softmax_scale(desc, scale))[:, None, None]

    pad = -s % _Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, _Q_BLOCK, nh,
                                                        dn + dr)
    tb = jnp.pad(t, (0, pad)).reshape(-1, _Q_BLOCK)

    def block(args):
        qs, ts = args
        sc = jnp.einsum("qnd,und->nqu", qs, k)
        sc = jnp.where(t[None, None, :] <= ts[None, :, None], sc, -jnp.inf)
        return jnp.einsum("nqu,und->qnd", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(block, (qb, tb)).reshape(-1, nh * dv)[:s]
    return x + o @ a["wo"], jnp.concatenate([c, k_rope], axis=-1)


def route(desc, h, router, bias, router_kind="sigmoid"):
    """``[S, held]`` weights of the held experts (0 where not picked)."""
    z = h @ router
    if router_kind == "sigmoid":
        g = jax.nn.sigmoid(z)
        select = g + bias
    elif router_kind == "softmax":
        g = select = jax.nn.softmax(z, axis=-1)
    else:
        raise ValueError(f"unknown router control {router_kind!r}")
    _, idx = jax.lax.top_k(select, desc["num_experts_per_tok"])
    top = jnp.take_along_axis(g, idx, axis=-1)
    if desc["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * desc["routed_scaling_factor"]
    full = jnp.zeros_like(g).at[jnp.arange(g.shape[0])[:, None], idx].set(top)
    first = desc["experts_first"]
    return full[:, first:first + desc["experts_held"]]


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _hashable(desc: Dict[str, Any]):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in desc.items()))


@functools.lru_cache(maxsize=None)
def _programs(desc_items, rope, scale, router_kind):
    desc = dict(desc_items)

    def hi(f):
        def g(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return jax.jit(g)

    return {
        "attn": hi(lambda x, w: attention(desc, x, w, rope, scale)),
        "pre": hi(lambda x, s: _rms(x, s, desc["norm_eps"])),
        "route": hi(lambda h, r, b: route(desc, h, r, b, router_kind)),
        "expert": hi(lambda y, h, g, a, b, c:
                     y + g[:, None] * _swiglu(h, a, b, c)),
        "shared": hi(_swiglu),
        "head": hi(lambda x, s, w: _rms(x, s, desc["norm_eps"]) @ w),
    }


@functools.lru_cache(maxsize=None)
def _as_f32(weights_dtype):
    """A weight as float32; ``weights_dtype`` first rounds it to that type's
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


def forward(desc: Dict[str, Any], params, ids, logits_from: int = 0,
            weights_dtype=None, rope: str = "yarn",
            softmax_scale: str = "yarn", router: str = "sigmoid"):
    """Full causal forward of ONE sequence.  ids ``[S]`` ints -> (float32
    logits ``[S - logits_from, V]`` at positions ``logits_from ..`` (V the
    held slice of the vocabulary), each layer's ``[c | k_rope]`` rows ``[S, R
    + dr]``, in layer order)."""
    f32 = _as_f32(weights_dtype)
    prog = _programs(_hashable(desc), rope, softmax_scale, router)
    ids = jnp.asarray(ids, jnp.int32)
    tok = params["embed"]["tok"]
    x = f32(tok[ids]) if weights_dtype is None else f32(tok)[ids]
    latents = []
    # the tree holds one period of one layer, its leaves stacked [layers]
    (stack,) = params["layers"]
    for i in range(stack["norm2"]["scale"].shape[0]):
        mlp = jax.tree_util.tree_map(lambda a: a[i], stack["mlp"])
        w = jax.tree_util.tree_map(
            lambda a: f32(a[i]), {k: v for k, v in stack.items()
                                  if k != "mlp"})
        x, rows = prog["attn"](x, w)
        latents.append(rows)
        h = prog["pre"](x, w["norm2"]["scale"])
        gates = prog["route"](h, f32(mlp["router"]), f32(mlp["router_bias"]))
        y = prog["shared"](h, *(f32(mlp[n]) for n in (
            "shared_w_gate", "shared_w_up", "shared_w_down")))
        for e in range(desc["experts_held"]):
            y = prog["expert"](y, h, gates[:, e], *(
                f32(mlp[n][e]) for n in ("w_gate", "w_up", "w_down")))
        x = x + y
    return prog["head"](x[logits_from:], f32(params["final_norm"]["scale"]),
                        f32(params["lm_head"]["w"])), latents
