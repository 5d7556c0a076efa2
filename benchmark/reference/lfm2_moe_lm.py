"""Plain float32 reference of the LFM2-MoE language model (LiquidAI
``lfm2_moe``), as one chip's share of an expert-parallel job computes it:
forward, loss, and — by ``jax.grad`` of that plain forward — gradients.

    layer i:   h = x + mixer_i(RMSNorm(x));  y = h + ffn_i(RMSNorm(h))
    conv:      B, C, u = split3(z W_in);  v = B * u
               c_t = sum_j k_j * v_{t-(taps-1)+j}   (zeros before the start)
               out = (C * c) W_out
    attention: q (NH heads), k, v (KVH heads) of D; RMSNorm over D on every q
               head and every k head; rotary (theta, whole head, halves);
               causal softmax at 1/sqrt(D); W_o
    dense ffn: W2 (silu(W1 z) * W3 z)          (layers < num_dense_layers)
    experts:   s = sigmoid(z W_g)  [float32];  picks = top_k(s + b)
               w = s[picks] / (sum + 1e-6) * scale
               out = sum over the picks ON A HELD EXPERT of w * expert(z)
    then RMSNorm and the head, tied to the embedding; mean next-token
    cross entropy over the held slice of the vocabulary.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no kernels, no sorting, no padded buffers.  The experts are a plain loop over
the held ids, each run on every token and weighted by the token's gate for it
(zero where it was not picked).  It reads the program's parameter tree — the
same weights, ``params["layers"]`` a tuple of runs of layers alike in mixer
and feed-forward part, each stacked — and shares no code with the program.

So that it fits beside a training state that fills half the chip: every layer
is under ``jax.checkpoint``; attention runs one query head at a time (the
[S, S] scores of an 8192-token sequence are 268 MB), each head checkpointed;
sequences are differentiated one at a time and their gradients added up on
the host.

Departures from the published code, all shared with the program and listed in
the configuration file under ``assumed``: the selection bias ``b`` is fixed
(no update rule), no dropout, no auxiliary loss, the head tied.  What the
absent experts would add is left out, as on the chip.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rotary(x, theta):
    """x: [S, heads, D]; the head split in halves (HF convention)."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention_mixer(desc, z, a):
    s = z.shape[0]
    nh, kvh, d = (desc["num_attention_heads"], desc["num_key_value_heads"],
                  desc["head_dim"])
    eps = desc["norm_eps"]
    q = _rms((z @ a["wq"]).reshape(s, nh, d), a["q_norm"], eps)
    k = _rms((z @ a["wk"]).reshape(s, kvh, d), a["k_norm"], eps)
    v = (z @ a["wv"]).reshape(s, kvh, d)
    q, k = _rotary(q, desc["rope_theta"]), _rotary(k, desc["rope_theta"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args  # [S, D] each
        scores = jnp.where(causal, qh @ kh.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vh

    rep = nh // kvh  # query head h reads KV head h // rep
    out = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 jnp.repeat(k.transpose(1, 0, 2), rep, 0),
                                 jnp.repeat(v.transpose(1, 0, 2), rep, 0)))
    return out.transpose(1, 0, 2).reshape(s, nh * d) @ a["wo"]


def _conv_mixer(desc, z, c):
    b, g, u = jnp.split(z @ c["w_in"], 3, axis=-1)
    v = b * u
    taps, s = desc["conv_taps"], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, v.shape[1]), F32), v])
    conv = jnp.zeros_like(v)
    for j in range(taps):
        conv = conv + c["kernel"][j] * padded[j:j + s]
    return (g * conv) @ c["w_out"]


def _swiglu(z, w1, w3, w2):
    return (jax.nn.silu(z @ w1) * (z @ w3)) @ w2


def _experts(desc, z, m):
    """-> (the held experts' part of the layer, picks on each held expert)."""
    scores = jax.nn.sigmoid(z @ m["router"])
    _, picks = jax.lax.top_k(scores + m["router_bias"],
                             desc["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, picks, axis=1)
    if desc["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * desc["routed_scaling_factor"]
    out = jnp.zeros_like(z)
    counts = []
    for e in range(desc["experts_held"]):
        hit = picks == desc["experts_first"] + e
        gate = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)
        out = out + gate[:, None] * _swiglu(z, m["w_gate"][e], m["w_up"][e],
                                            m["w_down"][e])
        counts.append(jnp.sum(hit))
    return out, jnp.stack(counts).astype(jnp.int32)


def _layer(desc, kind, experts, x, w):
    eps = desc["norm_eps"]
    z = _rms(x, w["norm1"]["scale"], eps)
    if kind == "conv":
        x = x + _conv_mixer(desc, z, w["conv"])
    else:
        x = x + _attention_mixer(desc, z, w["attn"])
    z = _rms(x, w["norm2"]["scale"], eps)
    if experts:
        y, counts = _experts(desc, z, w["mlp"])
        return x + y, counts
    m = w["mlp"]
    return x + _swiglu(z, m["w_gate"], m["w_up"], m["w_down"]), None


def layer_plan(desc) -> List[Tuple[str, bool, int, int]]:
    """Each layer as (mixer, has experts, run, index in the run): the
    parameter tree keeps consecutive layers alike in both in one stack."""
    plan: List[Tuple[str, bool, int, int]] = []
    for i, kind in enumerate(desc["layer_types"]):
        experts = i >= desc["num_dense_layers"]
        if plan and plan[-1][:2] == (kind, experts):
            plan.append((kind, experts, plan[-1][2], plan[-1][3] + 1))
        else:
            plan.append((kind, experts, plan[-1][2] + 1 if plan else 0, 0))
    return plan


def _forward(desc, w, ids):
    """One sequence.  w: the parameter tree in float32; ids: [S] -> (logits
    [S, V], picks [expert layers, held])."""
    x = w["embed"]["tok"][ids]
    picks = []
    for kind, experts, run, i in layer_plan(desc):
        layer = jax.tree_util.tree_map(lambda a: a[i], w["layers"][run])
        x, counts = jax.checkpoint(
            functools.partial(_layer, desc, kind, experts))(x, layer)
        if counts is not None:
            picks.append(counts)
    x = _rms(x, w["final_norm"]["scale"], desc["norm_eps"])
    return x @ w["embed"]["tok"].T, jnp.stack(picks)


def _rounded(params, round_to, mantissa_bits):
    """The weights the comparison starts from, as float32 with the gradient
    passed straight through: rounded to ``round_to`` when the program
    computes in a narrower type than it stores, and — the benchmark's
    negative control — to ``mantissa_bits`` of mantissa (the exponent's range
    kept: what a tensor-scaled float8 holds; by ``reduce_precision``, which
    the compiler may not drop as excess precision)."""
    def one(a):
        r = a.astype(F32)
        if round_to is not None:
            r = r.astype(round_to).astype(F32)
        if mantissa_bits is not None:
            r = jax.lax.reduce_precision(r, exponent_bits=8,
                                         mantissa_bits=mantissa_bits)
        a = a.astype(F32)
        return a + jax.lax.stop_gradient(r - a)

    return jax.tree_util.tree_map(one, params)


def _hashable(desc: Dict[str, Any]):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in desc.items()))


@functools.lru_cache(maxsize=None)
def _programs(desc_items, round_to, mantissa_bits):
    desc = dict(desc_items)

    def nll_sum(params, ids):
        with jax.default_matmul_precision("highest"):
            logits, picks = _forward(
                desc, _rounded(params, round_to, mantissa_bits), ids)
            logp = jax.nn.log_softmax(logits[:-1], axis=-1)
            nll = -jnp.take_along_axis(logp, ids[1:, None], axis=1)
        return jnp.sum(nll), (logits, picks)

    return (jax.jit(nll_sum),
            jax.jit(jax.value_and_grad(nll_sum, has_aux=True)))


def forward(desc: Dict[str, Any], params, ids, round_to=None,
            mantissa_bits: Optional[int] = None):
    """Full causal forward of ONE sequence: ids [S] -> (logits [S, V] over
    the held slice of the vocabulary, picks on each held expert per expert
    layer [expert layers, held])."""
    fwd, _ = _programs(_hashable(desc), round_to, mantissa_bits)
    _, (logits, picks) = fwd(params, jnp.asarray(ids, jnp.int32))
    return logits, picks


def loss(desc: Dict[str, Any], params, batch_ids, round_to=None) -> float:
    """Mean next-token cross entropy over a batch [B, S], one sequence at a
    time."""
    fwd, _ = _programs(_hashable(desc), round_to, None)
    total = sum(float(fwd(params, jnp.asarray(row, jnp.int32))[0])
                for row in batch_ids)
    return total / (len(batch_ids) * (len(batch_ids[0]) - 1))


def loss_and_grads(desc: Dict[str, Any], params, batch_ids, round_to=None,
                   mantissa_bits: Optional[int] = None):
    """-> (the mean loss over the batch [B, S], its gradient with respect to
    every parameter leaf as float32 numpy — added up on the host, one
    sequence's at a time on the device — and the picks on each held expert
    per expert layer, summed over the batch)."""
    _, vg = _programs(_hashable(desc), round_to, mantissa_bits)
    count = len(batch_ids) * (len(batch_ids[0]) - 1)
    total, grads, picks = 0.0, None, 0
    for row in batch_ids:
        (nll, (_, p)), g = vg(params, jnp.asarray(row, jnp.int32))
        g = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), g)
        grads = g if grads is None else jax.tree_util.tree_map(
            np.add, grads, g)
        total += float(nll)
        picks = picks + np.asarray(p, np.int64)
    grads = jax.tree_util.tree_map(lambda a: a / count, grads)
    return total / count, grads, picks


def grad_norm(grads) -> float:
    return math.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                         for g in jax.tree_util.tree_leaves(grads)))
