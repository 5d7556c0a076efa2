"""Plain float32 reference of the Xing4.0 block: a residual of ``n`` streams
mixed by manifold-constrained hyper-connections (mHC, arXiv 2512.24880) round
every mixer and every feed-forward part; the mixer DeepSeek-V3's latent
attention (MLA), the feed-forward part a dense SwiGLU in the leading layers and
afterwards sigmoid-routed experts with a selection bias beside one ungated
shared expert.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
the residual as ``X [S, n, C]``, the mixing by its equations on ``[S, n, n]``
matrices, attention in the **expanded form only** — keys and values of every
head made from the latent at every position — no kernels, no cache, no chunks,
no absorption, no batching; the masked softmax a block of queries at a time,
the feed-forward parts a block of tokens at a time and the experts one at a
time, the head a block of the vocabulary at a time, so that a prompt of 8,448
fits beside a serving engine that nearly fills the chip.  It reads the
program's parameter tree — the same weights — a layer (and an expert) at a
time, and shares no code with the program.  Its own copy: nothing here is
imported from ``mla_moe_lm.py``.

Equations (``n = hc_mult``, ``C = hidden_size``; RMSNorm eps ``rms_norm_eps``;
a token's residual ``X`` in ``R^{n x C}``; every layer two sublayers ``F`` —
its mixer, then its feed-forward part — each with ``phi [nC, 2n + n^2]``,
``alpha = (a_pre, a_post, a_res)``, ``b = [b_pre (n) | b_post (n) | B_res (n x
n, row-major)]``):

  1  x~ = vec(X) (stream after stream), u = x~ rsqrt(mean(x~^2) + eps),
     m = u phi
  2  H_pre = sigmoid(a_pre m[0:n] + b_pre);  H_post = 2 sigmoid(a_post m[n:2n]
     + b_post);  H~ = clip(a_res mat_{n x n}(m[2n:]) + B_res, -30, 30)
  3  H_res = SK(H~):  M <- exp(H~);  hc_sinkhorn_iters times:  M <- M /
     (rowsum(M) + hc_eps), then M <- M / (colsum(M) + hc_eps)
  4  h = H_pre X (in R^C);  y = F(RMSNorm_F(h)) — the sublayer as in a
     one-stream block, its own input norm on h
  5  X' = H_res X + H_post^T y  (stream i gets sum_j H_res[i, j] X_j +
     H_post[i] y)

  entry  the embedding copied to the n streams;  exit  the streams summed,
         then the final RMSNorm and the untied head.
  MLA    h -> c_q = RMSNorm(h W_dq), q = c_q W_uq -> NH heads of [q_nope (dn)
         | q_rope (dr)];  [c_kv | k_r] = h W_dkv, c = RMSNorm(c_kv), k_rope =
         R_t(k_r) (one rotary key for all heads);  [k_nope (dn) | v (dv)] = c
         W_ukv per head;  q_rope <- R_t(q_rope);  s = sigma (q_nope . k_nope +
         q_rope . k_rope), causal softmax over u <= t, o = sum p v, y = o W_o.
         sigma = (dn + dr)^(-1/2) m^2, m = 0.1 mscale_all_dim ln(factor) + 1;
         no position-dependent query scaling.  No bias anywhere.
  R_t    rotates pairs (2i, 2i + 1) of dr dimensions by t f_i under YaRN (the
         closed form in ``rope_frequencies``).
  dense  SwiGLU of width ``intermediate_size`` (the leading layers).
  expert g = sigmoid(h W_r);  the top_k largest of g + b over all routed
         experts;  w = g[picked] / (sum g[picked] + 1e-20) x
         routed_scaling_factor;  y = SwiGLU_shared(h) + sum over picked of w_e
         SwiGLU_e(h).

``forward`` returns, beside the logits, what a token leaves in a cache: each
layer's ``[c | k_rope]`` rows ``[S, R + dr]``.  A rotated vector is written
with the pairs' even members first and the odd ones second, as the published
``apply_rotary_pos_emb_interleave`` leaves it (every query and key alike, so
no product changes).

Departures from the published model, each shared with the program and listed
in the configuration file under ``assumed`` or ``reduced``: no copy of the
model's own modeling code is in the sandbox, so the attention and the router
follow DeepSeek-V3's (whose keys the config has) and the residual follows the
mHC paper's equations; the order of a Sinkhorn round (rows first) and where
``hc_eps`` enters; the mixing's RMSNorm without a learned scale; entry by copy
and exit by sum; the multi-token-prediction layer absent.

Controls, for the benchmark's negative runs (each must read ``correct:
false``): ``weights_dtype`` (every weight rounded to that type's mantissa),
``rope="plain"`` (YaRN's blend left out), ``softmax_scale="plain"`` (``m^2``
left out), ``router="softmax"`` (softmax scores, no bias), and of the mixing
``mhc="static"`` (``alpha = 0``: the coefficients are their biases'),
``mhc="one_round"`` (one Sinkhorn round for ``hc_sinkhorn_iters``) and
``mhc="post_unscaled"`` (``H_post`` without its factor 2).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: queries a block of the masked softmax holds, and heads a group of it:
#: [8, 256, S] float32 scores
_Q_BLOCK = 256
_HEAD_GROUP = 8
#: tokens a call of a feed-forward part takes, and columns a call of the head
_T_BLOCK = 2048
_V_BLOCK = 16384
MHC_CONTROLS = ("full", "static", "one_round", "post_unscaled")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


# ------------------------------------------------------------------ the mixing
def sinkhorn(h_tilde, rounds: int, eps: float):
    """``H~ [..., n, n]`` -> ``exp(H~)`` after ``rounds`` of: rows divided by
    their sums + ``eps``, then columns by theirs."""
    m = jnp.exp(h_tilde)
    for _ in range(rounds):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def mixing(desc, x, w, mhc: str = "full"):
    """Equations 1 - 3 over ``x [S, n, C]`` with one sublayer's ``w = {phi,
    alpha, b}`` -> ``H_pre [S, n]``, ``H_post [S, n]``, ``H_res [S, n, n]``."""
    if mhc not in MHC_CONTROLS:
        raise ValueError(f"unknown mhc control {mhc!r}")
    n, clamp = desc["hc_mult"], desc["hc_clamp"]
    s = x.shape[0]
    flat = x.reshape(s, -1)
    u = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                             + desc["norm_eps"])
    m = u @ w["phi"]
    a_pre, a_post, a_res = (0.0, 0.0, 0.0) if mhc == "static" else w["alpha"]
    b = w["b"]
    h_pre = jax.nn.sigmoid(a_pre * m[:, :n] + b[:n])
    h_post = (1.0 if mhc == "post_unscaled" else 2.0) * jax.nn.sigmoid(
        a_post * m[:, n:2 * n] + b[n:2 * n])
    h_tilde = jnp.clip(a_res * m[:, 2 * n:].reshape(s, n, n)
                       + b[2 * n:].reshape(n, n), -clamp, clamp)
    rounds = 1 if mhc == "one_round" else desc["hc_sinkhorn_iters"]
    return h_pre, h_post, sinkhorn(h_tilde, rounds, desc["hc_eps"])


def read_in(h_pre, x):
    """Equation 4's ``h = H_pre X``: ``[S, n]``, ``[S, n, C]`` -> ``[S, C]``."""
    return jnp.einsum("sn,snc->sc", h_pre, x)


def write_back(h_post, h_res, x, y):
    """Equation 5: ``H_res X + H_post^T y``."""
    return jnp.einsum("sij,sjc->sic", h_res, x) \
        + h_post[:, :, None] * y[:, None, :]


# --------------------------------------------------------------- the sublayers
def rope_frequencies(desc, rope: str = "yarn"):
    """``[dr / 2]`` angles a position: ``theta_i = theta^(-2i / dr)``; under
    YaRN ``f_i = theta_i (1 - r_i) + theta_i / factor r_i``, ``r_i = clip((i -
    lo) / (hi - lo), 0, 1)``, ``lo = floor(dr ln(original_max / (beta_fast 2
    pi)) / (2 ln theta))``, ``hi = ceil(the same with beta_slow)``."""
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


def attention(desc, h, w, rope="yarn", scale="yarn"):
    """One layer's ``Attn(N1(h))`` over the read-in ``h [S, C]`` -> (that, the
    rows ``[c | k_rope] [S, R + dr]`` the layer would cache).  The heads are
    taken ``_HEAD_GROUP`` at a time, one group after another: a head's keys,
    values and scores depend on no other head's."""
    nh, r = desc["num_attention_heads"], desc["kv_lora_rank"]
    dn, dr, dv = (desc["qk_nope_head_dim"], desc["qk_rope_head_dim"],
                  desc["v_head_dim"])
    s = h.shape[0]
    a, eps = w["attn"], desc["norm_eps"]
    t = jnp.arange(s)
    freqs = rope_frequencies(desc, rope)
    h = _rms(h, w["norm1"]["scale"], eps)
    cq = _rms(h @ a["w_dq"], a["q_norm"], eps)
    ckv = h @ a["w_dkv"]
    c = _rms(ckv[:, :r], a["kv_norm"], eps)
    k_rope = _rotate(ckv[:, r:], freqs, t)                       # [S, dr]
    hg = _HEAD_GROUP if nh % _HEAD_GROUP == 0 else nh
    pad = -s % _Q_BLOCK
    tb = jnp.pad(t, (0, pad)).reshape(-1, _Q_BLOCK)

    def by_group(m, width):  # [in, NH * width] -> [groups, in, hg * width]
        return m.reshape(m.shape[0], nh // hg, hg * width).transpose(1, 0, 2)

    def group(weights):
        w_uq, w_ukv = weights
        q = (cq @ w_uq).reshape(s, hg, dn + dr)
        kv = (c @ w_ukv).reshape(s, hg, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_rope[:, None], (s, hg, dr))], axis=-1)             # [S, hg, .]
        v = kv[..., dn:]
        q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], freqs, t)],
                            -1) * softmax_scale(desc, scale)
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, _Q_BLOCK, hg, dn + dr)

        def block(args):
            qs, ts = args
            sc = jnp.einsum("qnd,und->nqu", qs, k)
            sc = jnp.where(t[None, None, :] <= ts[None, :, None], sc,
                           -jnp.inf)
            return jnp.einsum("nqu,und->qnd", jax.nn.softmax(sc, axis=-1), v)

        return jax.lax.map(block, (qb, tb)).reshape(-1, hg * dv)[:s]

    o = jax.lax.map(group, (by_group(a["w_uq"], dn + dr),
                            by_group(a["w_ukv"], dn + dv)))      # [G, S, .]
    o = o.transpose(1, 0, 2).reshape(s, nh * dv)
    return o @ a["wo"], jnp.concatenate([c, k_rope], axis=-1)


def route(desc, h, router, bias, router_kind="sigmoid"):
    """``[S, experts]`` weights of the routed experts (0 where not picked)."""
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
def _programs(desc_items, rope, scale, router_kind, mhc):
    desc = dict(desc_items)

    def hi(f):
        def g(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return jax.jit(g)

    def read(x, w):
        h_pre, h_post, h_res = mixing(desc, x, w, mhc)
        return read_in(h_pre, x), h_post, h_res

    return {
        "read": hi(read),
        "write": hi(write_back),
        "attn": hi(lambda h, w: attention(desc, h, w, rope, scale)),
        "pre": hi(lambda x, s: _rms(x, s, desc["norm_eps"])),
        "route": hi(lambda h, r, b: route(desc, h, r, b, router_kind)),
        "expert": hi(lambda y, h, g, a, b, c:
                     y + g[:, None] * _swiglu(h, a, b, c)),
        "swiglu": hi(_swiglu),
        "exit": hi(lambda x, s: _rms(jnp.sum(x, axis=1), s,
                                     desc["norm_eps"])),
        "head": hi(lambda h, w: h @ w),
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


def _feed_forward(desc, prog, f32, mlp, i, h):
    """Layer ``i`` of a run's feed-forward parts ``mlp`` (leaves stacked
    ``[layers, ...]``) over ``h [S, C]`` (normed): a tree with a router is the
    expert layer, one without the dense prologue's.  A weight is cut out of
    the stack where it is used — an expert at a time — never a layer's
    experts at once (1.4 GB at the published widths)."""
    if "router" not in mlp:
        return prog["swiglu"](h, *(f32(mlp[n][i]) for n in (
            "w_gate", "w_up", "w_down")))
    gates = prog["route"](h, f32(mlp["router"][i]),
                          f32(mlp["router_bias"][i]))
    y = prog["swiglu"](h, *(f32(mlp[n][i]) for n in (
        "shared_w_gate", "shared_w_up", "shared_w_down")))
    for e in range(desc["experts_held"]):
        # (waited for: calls are dispatched ahead of the device, and every
        # one queued holds its own float32 copies of its weights)
        y = prog["expert"](y, h, gates[:, e], *(
            f32(mlp[n][i, e]) for n in ("w_gate", "w_up", "w_down"))
        ).block_until_ready()
    return y


def forward(desc: Dict[str, Any], params, ids, logits_from: int = 0,
            weights_dtype=None, rope: str = "yarn",
            softmax_scale: str = "yarn", router: str = "sigmoid",
            mhc: str = "full"):
    """Full causal forward of ONE sequence.  ids ``[S]`` ints -> (float32
    logits ``[S - logits_from, V]`` at positions ``logits_from ..``, each
    layer's ``[c | k_rope]`` rows ``[S, R + dr]``, in layer order)."""
    f32 = _as_f32(weights_dtype)
    prog = _programs(_hashable(desc), rope, softmax_scale, router, mhc)
    ids = jnp.asarray(ids, jnp.int32)
    n = desc["hc_mult"]
    emb = f32(params["embed"]["tok"][ids])      # (rounding a row is the same)
    # the residual [S, n, C] is kept as blocks of _T_BLOCK tokens and never
    # whole: what is per token (the mixing, the feed-forward parts) runs a
    # block at a time, and a block written back takes the old block's place
    xs = [jnp.broadcast_to(e[:, None, :], (e.shape[0], n, e.shape[1]))
          for e in (emb[t:t + _T_BLOCK]
                    for t in range(0, emb.shape[0], _T_BLOCK))]
    latents = []

    def sublayer(w_hc, fn):
        """Equations 1 - 5 round ``fn``: h [S, C] -> y [S, C]; ``xs`` block
        by block, each new block in its old one's place."""
        reads = [prog["read"](xb, w_hc) for xb in xs]
        y = fn(jnp.concatenate([r[0] for r in reads]))
        for j, (_, h_post, h_res) in enumerate(reads):
            xs[j] = prog["write"](h_post, h_res, xs[j],
                                  y[j * _T_BLOCK:(j + 1) * _T_BLOCK])

    # the tree holds the stack's runs (the dense prologue, then the expert
    # layers), each one period of one layer, its leaves stacked [layers]
    for (stack,) in params["layers"]:
        for i in range(stack["norm2"]["scale"].shape[0]):
            w = jax.tree_util.tree_map(
                lambda a: f32(a[i]), {k: v for k, v in stack.items()
                                      if k != "mlp"})

            def mixer(h):
                y, rows = prog["attn"](h, w)
                latents.append(rows)
                return y

            def feed_forward(h):
                return jnp.concatenate([
                    _feed_forward(desc, prog, f32, stack["mlp"], i,
                                  prog["pre"](h[t:t + _T_BLOCK],
                                              w["norm2"]["scale"]))
                    for t in range(0, h.shape[0], _T_BLOCK)])

            sublayer(w["hc"]["mixer"], mixer)
            sublayer(w["hc"]["ffn"], feed_forward)
    # the blocks' rows from logits_from on
    starts = range(0, emb.shape[0], _T_BLOCK)
    x = jnp.concatenate([xb[max(logits_from - t, 0):]
                         for t, xb in zip(starts, xs)
                         if logits_from < t + xb.shape[0]])
    hidden = prog["exit"](x, f32(params["final_norm"]["scale"]))
    head = params["lm_head"]["w"]
    logits = jnp.concatenate([
        prog["head"](hidden, f32(head[:, v:v + _V_BLOCK])).block_until_ready()
        for v in range(0, head.shape[1], _V_BLOCK)], axis=-1)
    return logits, latents
