"""Plain float32 reference of the Solar-Open2 block: a period of one softmax
GQA layer and three delta-rule linear-attention (KDA) layers, every layer
followed by an expert layer with one shared expert.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no kernels, no cache, no chunks, no batching.  The linear-attention recurrence
is evaluated token by token (``lax.scan``), the experts one at a time over
every token.  It reads the program's parameter tree — the same weights — and
shares no code with the program; one layer (and one expert) is cast to float32
at a time so it fits beside a serving engine that nearly fills the chip.

Equations (h = RMSNorm(x), every mixer and every feed-forward part pre-norm
with a residual add):

  GQA    q = W_q h (NH x D); k, v = W_k h, W_v h (KVH x D); no rotary;
         a = softmax(q k^T / sqrt(D) + causal) v; y = W_o (a * sigmoid(W_g h))
  KDA    q~, k~, v~ = SiLU(conv4(W_q h)), SiLU(conv4(W_k h)), SiLU(conv4(W_v h))
         (causal depthwise convolution over time, kernel 4, no bias);
         q = L2norm(q~) / sqrt(D), k = L2norm(k~), v = v~ per head;
         g_t = -exp(A_log[head]) * softplus(W_f_up W_f_down h_t + dt_bias),
         alpha_t = exp(g_t) per key channel; beta_t = 2 sigmoid(W_beta h_t);
         S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,
         o_t = S_t^T q_t;  y_t = W_o [RMSNorm_head(o_t) * sigmoid(W_g_up
         W_g_down h_t)]
  expert p = softmax(W_r h) over every routed expert; the top_k largest,
         renormalised to sum 1; y = SwiGLU_shared(h) + sum_e g_e SwiGLU_e(h)

Departures from the published model, each shared with the program and listed
in the configuration file under ``assumed`` or ``reduced``:
- the config gives no key for the router's scoring (softmax), the rank of the
  decay and gate projections, the gate's width and place in the GQA layer, the
  q/k normalisation and SiLU after the convolution, the expert activation or
  the shared expert's width; the L2 norm adds 1e-6 under its root;
- ``routed_scaling_factor`` is 1 and is not multiplied in;
- the share of an expert-parallel deployment: of the routed experts only those
  the parameter tree holds (``experts_first`` ..) are evaluated — a pick on an
  absent expert adds nothing, here as in the program — and the vocabulary is
  the slice the tree holds.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _gqa(desc, x, w):
    nh, kvh, d = (desc["num_attention_heads"], desc["num_key_value_heads"],
                  desc["head_dim"])
    s = x.shape[0]
    a = w["attn"]
    h = _rms(x, w["norm1"]["scale"], desc["norm_eps"])
    q = (h @ a["wq"]).reshape(s, kvh, nh // kvh, d)
    k = (h @ a["wk"]).reshape(s, kvh, d)
    v = (h @ a["wv"]).reshape(s, kvh, d)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_kv_head(args):
        qg, kh, vh = args  # [S, G, D], [S, D], [S, D]
        scores = jnp.einsum("sgd,td->gst", qg, kh) / math.sqrt(d)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("gst,td->sgd", jax.nn.softmax(scores, axis=-1), vh)

    out = jax.lax.map(one_kv_head, (q.transpose(1, 0, 2, 3),
                                    k.transpose(1, 0, 2),
                                    v.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2, 3).reshape(s, nh * d)
    return x + (out * jax.nn.sigmoid(h @ a["wg"])) @ a["wo"]


def _kda(desc, x, w, state_dtype=F32):
    """-> (the layer's output, the state after the last token ``[NH, K, V]``).
    ``state_dtype``: float32; the benchmark's negative control rounds the
    state to bfloat16 after every token to show the check catches it."""
    nh, d, kc = desc["kda_heads"], desc["kda_head_dim"], desc["kda_conv"]
    s = x.shape[0]
    m = w["kda"]
    h = _rms(x, w["norm1"]["scale"], desc["norm_eps"])
    pre = jnp.concatenate([h @ m["wq"], h @ m["wk"], h @ m["wv"]], axis=-1)
    padded = jnp.concatenate([jnp.zeros((kc - 1, pre.shape[1]), F32), pre])
    conv = sum(padded[j:j + s] * m["conv"][j] for j in range(kc))
    q, k, v = jnp.split(jax.nn.silu(conv), 3, axis=-1)
    q, k, v = (t.reshape(s, nh, d) for t in (q, k, v))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(d)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g = -jnp.exp(m["a_log"])[:, None] * jax.nn.softplus(
        ((h @ m["f_down"]) @ m["f_up"] + m["dt_bias"]).reshape(s, nh, d))
    beta = 2.0 * jax.nn.sigmoid(h @ m["w_beta"])          # [S, NH]

    def token(S, xs):                                     # S: [NH, K, V]
        q_t, k_t, v_t, g_t, b_t = xs
        S = S.astype(F32) * jnp.exp(g_t)[:, :, None]      # diag(alpha) S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = (S + k_t[:, :, None] * u[:, None, :]).astype(state_dtype)
        return S, jnp.einsum("hkv,hk->hv", S.astype(F32), q_t)

    last, o = jax.lax.scan(token, jnp.zeros((nh, d, d), state_dtype),
                           (q, k, v, g, beta))
    o = _rms(o, m["o_norm"], desc["norm_eps"]).reshape(s, nh * d)
    gate = jax.nn.sigmoid((h @ m["g_down"]) @ m["g_up"])
    return x + (o * gate) @ m["wo"], last.astype(F32)


def _route(desc, h, router):
    """[S, held] gate weights of the held experts (0 where not picked)."""
    p = jax.nn.softmax(h @ router, axis=-1)
    top, idx = jax.lax.top_k(p, desc["num_experts_per_tok"])
    if desc["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    full = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], idx].set(top)
    first = desc["experts_first"]
    return full[:, first:first + desc["experts_held"]]


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _hashable(desc: Dict[str, Any]):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in desc.items()))


@functools.lru_cache(maxsize=None)
def _programs(desc_items, state_dtype):
    desc = dict(desc_items)

    def hi(f):
        def g(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return jax.jit(g)

    return {
        "gqa": hi(lambda x, w: _gqa(desc, x, w)),
        "kda": hi(lambda x, w: _kda(desc, x, w, state_dtype)),
        "pre": hi(lambda x, scale: _rms(x, scale, desc["norm_eps"])),
        "route": hi(lambda h, router: _route(desc, h, router)),
        "expert": hi(lambda y, h, g, a, b, c:
                     y + g[:, None] * _swiglu(h, a, b, c)),
        "shared": hi(_swiglu),
        "head": hi(lambda x, scale, w: _rms(x, scale, desc["norm_eps"]) @ w),
    }


@functools.lru_cache(maxsize=None)
def _as_f32(weights_dtype):
    """A weight as float32.  ``weights_dtype`` (the benchmark's negative
    control: the reference computed with its weights in the nearest precision
    below the served one) first rounds it to that type's mantissa, the
    exponent's range kept — what a tensor-scaled float8 holds.  (By
    ``reduce_precision``: the compiler may take a cast there and back for
    excess precision and drop it.)"""
    def cast(a):
        a = a.astype(F32)
        if weights_dtype is None:
            return a
        return jax.lax.reduce_precision(
            a, exponent_bits=8, mantissa_bits=jnp.finfo(weights_dtype).nmant)
    return jax.jit(cast)


def logits(desc: Dict[str, Any], params, ids, state_dtype=F32):
    """Full causal forward of ONE sequence.  ids: [S] ints -> [S, V] float32
    logits at every position (V the held slice of the vocabulary)."""
    return forward(desc, params, ids, state_dtype)[0]


def forward(desc: Dict[str, Any], params, ids, state_dtype=F32,
            weights_dtype=None):
    """``logits`` and, beside them, what every linear-attention layer keeps
    after the last token: a list, in layer order, of states ``[NH, K, V]``
    float32 (S of the equations above; a program may keep its transpose)."""
    f32 = _as_f32(weights_dtype)
    prog = _programs(_hashable(desc), state_dtype)
    ids = jnp.asarray(ids, jnp.int32)
    tok = params["embed"]["tok"]
    x = f32(tok[ids]) if weights_dtype is None else f32(tok)[ids]
    period = desc["period"]
    states = []
    n_periods = jax.tree_util.tree_leaves(params["layers"][0])[0].shape[0]
    for p in range(n_periods):
        for kind, stack in zip(period, params["layers"]):
            mlp = stack["mlp"]
            w = jax.tree_util.tree_map(
                lambda a: f32(a[p]), {k: v for k, v in stack.items()
                                      if k != "mlp"})
            x = prog[kind](x, w)
            if kind == "kda":
                x, last = x
                states.append(last)
            h = prog["pre"](x, w["norm2"]["scale"])
            gates = prog["route"](h, f32(mlp["router"][p]))
            y = prog["shared"](h, *(f32(mlp[n][p]) for n in (
                "shared_w_gate", "shared_w_up", "shared_w_down")))
            for e in range(desc["experts_held"]):
                y = prog["expert"](y, h, gates[:, e], *(
                    f32(mlp[n][p, e])
                    for n in ("w_gate", "w_up", "w_down")))
            x = x + y
    return prog["head"](x, f32(params["final_norm"]["scale"]),
                        f32(params["lm_head"]["w"])), states
