"""Plain float32 reference of the Laguna-S-2.1 block: window and full
attention layers whose QUERY head counts differ by type over the same K/V
heads, every head's output through a learned gate, half a head under YaRN on
the full layers beside a whole head under the plain table on the window
layers, behind a dense first layer; the other layers' feed-forward part is a
softmax-routed expert layer with a routed scale plus a shared expert.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
every layer at every position, attention as a masked softmax computed a K/V
head and a block of queries at a time (and the dense layer a slice of its
width at a time) so that a prompt of 12,288 fits beside a serving engine that
nearly fills the chip — no kernels, no cache, no ring, no chunks, no batching;
the experts one at a time over every token.  It reads the program's parameter
tree — the same weights — a layer (and an expert) at a time, and shares no
code with the program.

Equations (RMSNorm eps 1e-6, pre-norm, no bias anywhere; ``x <- x +
Attn(N1(x))``, then ``x <- x + F(N2(x))``; a final RMSNorm; logits by an
untied head over the held slice of the vocabulary).  A layer's type tau is
full or sliding; NH = heads_full or heads_window query heads over G K/V heads
of d = head_dim:

  Attn   h = N1(x);  q = h W_q -> NH heads of d;  k = h W_k, v = h W_v -> G
         heads of d.  No norm on q or k.
         full:     the FIRST rot_full lanes of every q and k head turned,
                   half-split pairs (i, i + rot_full / 2), by angle t f_i,
                   f = YaRN(rot_full, rope_theta, factor, original positions,
                   beta_fast, beta_slow): pair i at theta^(-2i / rot_full)
                   where it makes more than beta_fast turns over the original
                   context, at that over ``factor`` where fewer than
                   beta_slow, a linear ramp between; cos and sin TIMES the
                   attention factor (0.1 ln(factor) + 1), on queries and keys
                   alike — factor^2 on the rotated lanes' part of a score, 1
                   on the plain lanes' part, and the factor in the key rows a
                   cache keeps.  The other lanes pass.
         sliding:  the first rot_window lanes (the whole head), the plain
                   table of swa_rope_theta, no factor.
         s[t, u, n] = d^(-1/2) q[t, n] . k[u, n // (NH / G)];  causal; a
         sliding layer's query sees itself and the window - 1 positions before
         it;  softmax in float32;  a_n = sum p v.
         g = sigmoid(h W_g) -> NH scalars;  out = concat_n(g_n a_n) W_o.
  F      layer 0: SwiGLU of width dense_width.  Others: s = softmax(h W_r)
         over all routed experts (float32);  the top_k largest;  w =
         s[picked] / sum s[picked] x routed_scale;  y = sum over picked and
         held of w_e SwiGLU_e(h), width expert_width, PLUS SwiGLU_shared(h),
         width shared_width, on every token, ungated.

``forward`` returns, beside the logits, what a token leaves in a cache: each
layer's rotated keys ``[S, G, d]`` and values ``[S, G, d]``.

Departures from the published model, each shared with the program and listed
in the configuration file under ``assumed`` or ``reduced``:
- no copy of Laguna's own modeling code is in the sandbox (``grep -rli
  laguna`` over site-packages finds none).  The YaRN table and the attention
  factor are ``transformers.modeling_rope_utils._compute_yarn_parameters``'
  for ``partial_rotary_factor`` 0.5 (``tests/unit/test_laguna.py`` holds this
  file to it); the gate is the head-wise sigmoid gate of arXiv:2505.06708 on
  the layer's normed input, before ``W_o``; the router is Qwen2-MoE's (the
  config carries its key names);
- the share of an expert-parallel deployment: of the routed experts only those
  the parameter tree holds (``experts_first`` ..) are evaluated — a pick on an
  absent expert adds nothing, here as in the program — the shared expert is
  whole, and the vocabulary is the slice the tree holds.

Controls, for the benchmark's negative runs (each must read ``correct:
false``): ``weights_dtype`` (every weight rounded to that type's mantissa),
``gate="none"`` (no gate), ``yarn="plain"`` (the full layers' table plain, no
factor), ``yarn="scale_all"`` (the factor squared on the whole score and none
on cos and sin: the form latent attention folds into its softmax scale),
``rotary="whole_head"`` (a full layer rotates every lane of a head),
``rope="one_base"`` (window layers rotated with the full layers' base),
``window="full"`` (window layers see the whole context), ``router="sigmoid"``
(sigmoid scores), ``shared="none"`` (no shared expert).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: queries a block of the masked softmax holds: [NH / G, 64, S] float32 scores
_Q_BLOCK = 64

CONTROLS = {"gate": ("head", "none"),
            "yarn": ("yarn", "plain", "scale_all"),
            "rotary": ("by_type", "whole_head"),
            "rope": ("two_bases", "one_base"),
            "window": ("window", "full"),
            "router": ("softmax", "sigmoid"),
            "shared": ("expert", "none")}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def yarn_table(rot: int, theta: float, factor: float, original: int,
               beta_fast: float, beta_slow: float):
    """-> (``[rot / 2]`` float32 frequencies, the attention factor)."""
    i = jnp.arange(rot // 2, dtype=F32)
    plain = theta ** (-2.0 * i / rot)

    def pair_of(turns: float) -> float:
        return rot * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp),
            0.1 * math.log(factor) + 1.0)


def _rotate(x, freq, mult: float, t):
    """``x [S, heads, d]`` at positions ``t [S]``: the first ``2 len(freq)``
    lanes turned, pairs (i, i + len(freq)) by ``t freq_i``, cos and sin times
    ``mult``."""
    half = freq.shape[0]
    ang = t.astype(F32)[:, None, None] * freq
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., 2 * half:]], axis=-1)


def rotary_of(desc, windowed: bool, yarn="yarn", rotary="by_type",
              rope="two_bases"):
    """-> (frequencies, the factor on cos and sin, the factor on the whole
    score) of a layer of this type under the controls."""
    d = desc["head_dim"]
    if windowed:
        theta = desc["swa_rope_theta"] if rope == "two_bases" \
            else desc["rope_theta"]
        rot = desc["rot_window"]
        return (theta ** (-2.0 * jnp.arange(rot // 2, dtype=F32) / rot), 1.0,
                1.0)
    rot = d if rotary == "whole_head" else desc["rot_full"]
    y = desc["yarn"]
    if yarn == "plain":
        return (desc["rope_theta"] ** (-2.0 * jnp.arange(rot // 2, dtype=F32)
                                       / rot), 1.0, 1.0)
    freq, factor = yarn_table(rot, desc["rope_theta"], y["factor"],
                              y["original_max_position_embeddings"],
                              y["beta_fast"], y["beta_slow"])
    assert abs(factor - y["attention_factor"]) < 1e-9
    return (freq, 1.0, factor * factor) if yarn == "scale_all" \
        else (freq, factor, 1.0)


def attention(desc, x, w, windowed: bool, gate="head", yarn="yarn",
              rotary="by_type", rope="two_bases", window="window"):
    """One layer's ``x + Attn(N1(x))`` over ``x [S, H]`` -> (that, the keys
    ``[S, G, d]`` rotated and the values ``[S, G, d]``: what the layer would
    cache)."""
    nh = desc["heads_window" if windowed else "heads_full"]
    g, d = desc["kv_heads"], desc["head_dim"]
    s = x.shape[0]
    a, t = w["attn"], jnp.arange(s)
    freq, mult, score_mult = rotary_of(desc, windowed, yarn, rotary, rope)
    h = _rms(x, w["norm1"]["scale"], desc["norm_eps"])
    reach = desc["sliding_window"] if windowed and window == "window" else s
    gates = jax.nn.sigmoid(h @ a["wg"]) if gate == "head" \
        else jnp.ones((s, nh), F32)
    n = nh // g  # query heads a K/V head: query head i reads K/V head i // n
    pad = -s % _Q_BLOCK
    tb = jnp.pad(t, (0, pad)).reshape(-1, _Q_BLOCK)

    def kv_head(out, j):
        """One K/V head and the query heads that read it, so that no array
        of every head's queries or scores exists at once."""
        cut = lambda m, width, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            m, j * width, width, axis)
        q = _rotate((h @ cut(a["wq"], n * d, 1)).reshape(s, n, d), freq, mult,
                    t)
        k = _rotate((h @ cut(a["wk"], d, 1))[:, None], freq, mult, t)[:, 0]
        v = h @ cut(a["wv"], d, 1)
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, _Q_BLOCK, n, d)

        def block(args):
            qs, ts = args
            sc = jnp.einsum("qnd,ud->nqu", qs, k) * (score_mult
                                                     / math.sqrt(d))
            dist = ts[:, None] - t[None, :]
            sc = jnp.where((dist >= 0) & (dist < reach), sc, -jnp.inf)
            return jnp.einsum("nqu,ud->qnd", jax.nn.softmax(sc, axis=-1), v)

        o = jax.lax.map(block, (qb, tb)).reshape(-1, n, d)[:s]
        o = (o * cut(gates, n, 1)[..., None]).reshape(s, n * d)
        return out + o @ cut(a["wo"], n * d, 0), (k, v)

    out, (k, v) = jax.lax.scan(kv_head, x, jnp.arange(g))
    return out, k.transpose(1, 0, 2), v.transpose(1, 0, 2)


def route(desc, h, router, router_kind="softmax"):
    """``[S, held]`` weights of the held experts (0 where not picked)."""
    z = h @ router
    if router_kind == "softmax":
        g = jax.nn.softmax(z, axis=-1)
    elif router_kind == "sigmoid":
        g = jax.nn.sigmoid(z)
    else:
        raise ValueError(f"unknown router control {router_kind!r}")
    top, idx = jax.lax.top_k(g, desc["num_experts_per_tok"])
    if desc["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * desc["routed_scale"]
    full = jnp.zeros_like(g).at[jnp.arange(g.shape[0])[:, None], idx].set(top)
    first = desc["experts_first"]
    return full[:, first:first + desc["experts_held"]]


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _hashable(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return tuple(_hashable(x) for x in v) if isinstance(v, list) else v


def _unhashed(items):
    return {k: (dict(v) if k == "yarn" else v) for k, v in items}


@functools.lru_cache(maxsize=None)
def _programs(desc_items, gate, yarn, rotary, rope, window, router_kind):
    desc = _unhashed(desc_items)

    def hi(f):
        def g(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return jax.jit(g)

    return {
        "attn": {windowed: hi(functools.partial(
            lambda windowed, x, w: attention(desc, x, w, windowed, gate, yarn,
                                             rotary, rope, window), windowed))
            for windowed in (False, True)},
        "pre": hi(lambda x, s: _rms(x, s, desc["norm_eps"])),
        "route": hi(lambda h, r: route(desc, h, r, router_kind)),
        "expert": hi(lambda y, h, g, a, b, c:
                     y + g[:, None] * _swiglu(h, a, b, c)),
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


def layers_of(params):
    """The parameter tree's layers in stack order: the tree holds runs, each
    a period of trees stacked ``[repeats, ...]``."""
    for run in params["layers"]:
        for rep in range(run[0]["norm1"]["scale"].shape[0]):
            for tree in run:
                yield jax.tree_util.tree_map(lambda a: a[rep], tree)


def forward(desc: Dict[str, Any], params, ids, logits_from: int = 0,
            weights_dtype=None, gate: str = "head", yarn: str = "yarn",
            rotary: str = "by_type", rope: str = "two_bases",
            window: str = "window", router: str = "softmax",
            shared: str = "expert"):
    """Full causal forward of ONE sequence.  ids ``[S]`` ints -> (float32
    logits ``[S - logits_from, V]`` at positions ``logits_from ..`` (V the
    held slice of the vocabulary), each layer's ``(keys [S, G, d] rotated,
    values [S, G, d])``, in layer order)."""
    given = {"gate": gate, "yarn": yarn, "rotary": rotary, "rope": rope,
             "window": window, "router": router, "shared": shared}
    for name, value in given.items():
        if value not in CONTROLS[name]:
            raise ValueError(f"unknown control {name}={value!r}; known: "
                             f"{CONTROLS[name]}")
    f32 = _as_f32(weights_dtype)
    prog = _programs(_hashable(desc), gate, yarn, rotary, rope, window,
                     router)
    ids = jnp.asarray(ids, jnp.int32)
    tok = params["embed"]["tok"]
    x = f32(tok[ids]) if weights_dtype is None else f32(tok)[ids]
    kv = []
    mats = ("w_gate", "w_up", "w_down")
    for layer, windowed in zip(layers_of(params), desc["window_layers"]):
        mlp = layer["mlp"]
        w = jax.tree_util.tree_map(
            f32, {k: v for k, v in layer.items() if k != "mlp"})
        x, k, v = prog["attn"][bool(windowed)](x, w)
        kv.append((k, v))
        h = prog["pre"](x, w["norm2"]["scale"])
        y = jnp.zeros_like(h)
        ones = jnp.ones((h.shape[0],), F32)
        if "router" not in mlp:
            # the dense first layer, a slice of its width at a time as one
            # more expert of gate 1: no [S, dense_width] array exists
            width = desc["expert_width"]
            for lo in range(0, desc["dense_width"], width):
                y = prog["expert"](
                    y, h, ones, f32(mlp["w_gate"][:, lo:lo + width]),
                    f32(mlp["w_up"][:, lo:lo + width]),
                    f32(mlp["w_down"][lo:lo + width]))
        else:
            gates = prog["route"](h, f32(mlp["router"]))
            for e in range(desc["experts_held"]):
                y = prog["expert"](y, h, gates[:, e],
                                   *(f32(mlp[n][e]) for n in mats))
            if shared == "expert":
                y = prog["expert"](y, h, ones,
                                   *(f32(mlp["shared_" + n]) for n in mats))
        x = x + y
    return prog["head"](x[logits_from:], f32(params["final_norm"]["scale"]),
                        f32(params["lm_head"]["w"])), kv
