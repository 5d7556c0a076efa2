"""Plain float32 reference of the dense decoder block, both variants.

    RMSNorm / SwiGLU / RoPE / grouped-query attention / untied head (Mistral)
    LayerNorm / ReLU or GELU MLP with biases / learned positions / tied head (OPT)

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes): no kernels, no
cache, no batching tricks.  It reads the program's parameter tree — the same
weights — and shares no code with the program: norms, rotary embedding,
attention, MLP and loss are written out here from the published equations.

One layer's weights are cast to float32 at a time, so the reference fits
beside a serving engine or a training state that nearly fills the chip.
Attention is evaluated one KV head (with its group of query heads) at a time
so the [S, S] scores of a 4096-token sequence stay a few hundred MB.

Departures from the published models, all shared with the program and listed
in the configuration files under ``assumed``: no sliding window (contexts
<= 4096), no dropout, OPT's position table without its offset of 2.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _norm(desc, x, scale, bias):
    if desc["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + desc["norm_eps"]) * scale
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + desc["norm_eps"]) * scale
    return out + bias if bias is not None else out


def _rotary(desc, x, positions):
    """HF Mistral convention: the head dim is split in halves, not in
    interleaved pairs.  x: [S, heads, D]; positions: [S]."""
    d = x.shape[-1]
    inv = desc["rope_theta"] ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None, None] * inv  # [S, 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(desc, q, k, v):
    """Causal softmax attention of one sequence.  q: [S, NH, D]; k, v:
    [S, KVH, D]; query head h reads KV head h // (NH // KVH)."""
    s, nh, d = q.shape
    kvh = k.shape[1]
    g = nh // kvh
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_kv_head(args):
        qg, kh, vh = args  # [S, G, D], [S, D], [S, D]
        scores = jnp.einsum("sgd,td->gst", qg, kh) / math.sqrt(d)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("gst,td->sgd", probs, vh)

    qg = q.reshape(s, kvh, g, d).transpose(1, 0, 2, 3)  # [KVH, S, G, D]
    out = jax.lax.map(one_kv_head, (qg, k.transpose(1, 0, 2),
                                    v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(s, nh * d)


def _layer(desc, x, positions, w):
    """One block on one sequence.  x: [S, H]; w: this layer's weights."""
    nh, kvh, d = (desc["num_attention_heads"], desc["num_key_value_heads"],
                  desc["head_dim"])
    s = x.shape[0]
    a, m = w["attn"], w["mlp"]
    h = _norm(desc, x, w["norm1"]["scale"], w["norm1"].get("bias"))
    q, k, v = h @ a["wq"], h @ a["wk"], h @ a["wv"]
    if desc["bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k, v = (q.reshape(s, nh, d), k.reshape(s, kvh, d),
               v.reshape(s, kvh, d))
    if desc["position"] == "rope":
        q, k = _rotary(desc, q, positions), _rotary(desc, k, positions)
    o = _attention(desc, q, k, v) @ a["wo"]
    if desc["bias"]:
        o = o + a["bo"]
    x = x + o
    h = _norm(desc, x, w["norm2"]["scale"], w["norm2"].get("bias"))
    if desc["mlp"] == "swiglu":
        y = (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
    else:
        u = h @ m["w_up"]
        if desc["bias"]:
            u = u + m["b_up"]
        act = {"relu": jax.nn.relu,
               "gelu": functools.partial(jax.nn.gelu, approximate=False)}
        y = act[desc["mlp"]](u) @ m["w_down"]
        if desc["bias"]:
            y = y + m["b_down"]
    return x + y


def _hashable(desc: Dict[str, Any]):
    return tuple(sorted(desc.items()))


@functools.lru_cache(maxsize=None)
def _programs(desc_items):
    desc = dict(desc_items)

    def layer(x, positions, w):
        with jax.default_matmul_precision("highest"):
            return _layer(desc, x, positions, w)

    def embed(tok, pos_table, ids, positions):
        x = tok[ids]
        return x + pos_table[positions] if pos_table is not None else x

    def head(x, scale, bias, w_out):
        with jax.default_matmul_precision("highest"):
            return _norm(desc, x, scale, bias) @ w_out

    def nll_sum(logits, targets):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=1))

    return (jax.jit(layer), jax.jit(embed), jax.jit(head), jax.jit(nll_sum))


def _as_f32(tree, round_to):
    """Weights as float32, first rounded to ``round_to`` when the program
    computes in a narrower type than it stores (bf16 compute over an fp32
    master): the reference then starts from the same rounded inputs."""
    def cast(a):
        if round_to is not None and a.dtype != round_to:
            a = a.astype(round_to)
        return a.astype(F32)

    return jax.tree_util.tree_map(cast, tree)


def logits(desc: Dict[str, Any], params, ids, round_to=None):
    """Full causal forward of ONE sequence.  ids: [S] ints -> [S, V] float32
    logits at every position."""
    layer, embed, head, _ = _programs(_hashable(desc))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    emb = _as_f32(params["embed"], round_to)
    x = embed(emb["tok"], emb.get("pos"), ids, positions)
    n_layers = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    for i in range(n_layers):
        w = _as_f32(jax.tree_util.tree_map(lambda a: a[i], params["layers"]),
                    round_to)
        x = layer(x, positions, w)
    fin = _as_f32(params["final_norm"], round_to)
    w_out = (emb["tok"].T if desc["tie_word_embeddings"]
             else _as_f32(params["lm_head"]["w"], round_to))
    return head(x, fin["scale"], fin.get("bias"), w_out)


def loss(desc: Dict[str, Any], params, batch_ids, round_to=None) -> float:
    """Mean next-token cross entropy over a batch [B, S] (every position but
    the last predicts its successor), one sequence at a time."""
    nll_sum = _programs(_hashable(desc))[3]
    total, count = 0.0, 0
    for row in batch_ids:
        lg = logits(desc, params, row, round_to)
        total += float(nll_sum(lg[:-1], jnp.asarray(row[1:], jnp.int32)))
        count += len(row) - 1
    return total / count
