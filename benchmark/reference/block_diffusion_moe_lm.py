"""Plain float32 reference of the SDAR-MoE block and of its generation loop:
a Qwen3-MoE layer (grouped-query attention with an RMSNorm on every q and k
head before rotary; softmax-routed experts, the ``num_experts_per_tok`` largest
kept and renormalised, no shared expert) under a mask that is causal between
blocks of ``block_length`` positions and bidirectional inside one, and
generation by diffusion over those blocks.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
over ONE whole sequence that may hold mask tokens: every layer at every
position, attention as a masked softmax a K/V head and a block of queries at a
time, every expert over every token (a token's weight on an expert it did not
pick is 0) — no kernel, no cache, no batching, no pass that reuses another's
K/V.  It reads the program's parameter tree — the same weights — a layer (and
an expert) at a time, and shares no code with the program.

Equations (RMSNorm eps ``rms_norm_eps``, pre-norm; ``x <- x + Attn(N1(x))``,
then ``x <- x + MoE(N2(x))``; a final RMSNorm; logits by an untied head):

  Attn   h = N1(x);  q = h W_q -> NH heads of d;  k = h W_k, v = h W_v -> G
         heads of d;  no bias.  q[n] <- RMSNorm_d(q[n]) * g_q, k[j] <-
         RMSNorm_d(k[j]) * g_k (one learned scale of d for all q heads, one
         for all k heads);  R_t over the whole head, half-split pairs (i, i +
         d / 2), angle t x theta^(-2i / d), t the absolute position.
         s[t, u, n] = d^(-1/2) q[t, n] . k[u, n // (NH / G)];  softmax in
         float32 over the u with  u // B <= t // B  (B = block_length):
         every earlier block, and the whole of the query's own;  o = sum p v;
         out = o W_o.
  MoE    p = softmax(h W_r) over all experts in float32; the top_k largest;
         w = p[picked] / sum p[picked] (``norm_topk_prob``);  y = sum_e w_e
         (silu(h W_gate,e) * (h W_up,e)) W_down,e.

Generation (``generate``; greedy, static low-confidence reveal): the blocks of
the sequence lie at absolute multiples of B.  The prompt's whole blocks stand
as they are.  Each later block starts as the prompt's left-over ``L mod B``
tokens (first block only) followed by ``mask_token_id``, and is denoised in
passes: a pass is ONE forward over the sequence through this block, reads the
logits AT the block's masked positions (no shift), takes at each the arg-max
token and its softmax probability as confidence, and reveals the ``B /
denoising_steps`` masked positions of highest confidence (the lower position on
a tie); a revealed token is never masked again.  When none is masked the block
is final.  (The served loop then runs one more pass, the commit, to write the
K/V it keeps; with no cache there is nothing to write, and the K/V this
reference computes for a final block in a later forward are the commit's.)

Departures from the published model, each shared with the program and listed
in the configuration file under ``assumed`` or ``reduced``:
- no copy of the family's modeling code is in the sandbox; the block length,
  the schedule (static low-confidence reveal of ``B / denoising_steps`` a
  pass), the per-head q/k norm (the Qwen3-MoE block's), the unshifted logits
  and the mask token's id are the ``assumed`` of the configuration file;
- the sequence is computed padded to a multiple of ``_PAD`` positions: the pad
  lies in later blocks, which no real position sees under either mask;
- the positions of a last block beyond the requested length are denoised as
  the loop does and not returned.

Controls, for the benchmark's negative runs (each must read ``correct:
false``): ``weights_dtype`` (every weight rounded to that type's mantissa) and
``mask="causal"`` (a causal mask inside the block too).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: queries a block of the masked softmax holds: [NH / G, 64, S] float32 scores
_Q_BLOCK = 64
#: the sequence is padded to a multiple of this (one compile a bucket)
_PAD = 128


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rotate(x, theta: float, t):
    """``x [S, heads, d]`` at positions ``t [S]``: the whole head turned,
    pairs (i, i + d / 2) by ``t theta^(-2i / d)``."""
    d = x.shape[-1]
    i = jnp.arange(d // 2, dtype=F32)
    ang = t.astype(F32)[:, None, None] * theta ** (-2.0 * i / d)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def attention(desc, x, w, mask="block"):
    """One layer's ``x + Attn(N1(x))`` over ``x [S, H]`` -> (that, the keys
    ``[S, G, d]`` normed and rotated, the values ``[S, G, d]``: what the layer
    would cache)."""
    nh, g, d = (desc["num_attention_heads"], desc["num_key_value_heads"],
                desc["head_dim"])
    if mask not in ("block", "causal"):
        raise ValueError(f"unknown mask control {mask!r}")
    blk = desc["block_length"] if mask == "block" else 1
    s = x.shape[0]
    a, t, eps = w["attn"], jnp.arange(s), desc["norm_eps"]
    h = _rms(x, w["norm1"]["scale"], eps)
    n = nh // g  # query heads a K/V head: query head i reads K/V head i // n
    pad = -s % _Q_BLOCK
    tb = jnp.pad(t, (0, pad)).reshape(-1, _Q_BLOCK)

    def kv_head(out, j):
        """One K/V head and the query heads that read it, so that no array
        of every head's scores exists at once."""
        cut = lambda m, width, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            m, j * width, width, axis)
        q = (h @ cut(a["wq"], n * d, 1)).reshape(s, n, d)
        q = _rotate(_rms(q, a["q_norm"], eps), desc["rope_theta"], t)
        k = _rotate(_rms((h @ cut(a["wk"], d, 1))[:, None], a["k_norm"], eps),
                    desc["rope_theta"], t)[:, 0]
        v = h @ cut(a["wv"], d, 1)
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, _Q_BLOCK, n, d)

        def block(args):
            qs, ts = args
            sc = jnp.einsum("qnd,ud->nqu", qs, k) / math.sqrt(d)
            # causal between blocks, bidirectional inside one
            sc = jnp.where((t[None, :] // blk <= ts[:, None] // blk)[None],
                           sc, -jnp.inf)
            return jnp.einsum("nqu,ud->qnd", jax.nn.softmax(sc, axis=-1), v)

        o = jax.lax.map(block, (qb, tb)).reshape(-1, n * d)[:s]
        return out + o @ cut(a["wo"], n * d, 0), (k, v)

    out, (k, v) = jax.lax.scan(kv_head, x, jnp.arange(g))
    return out, k.transpose(1, 0, 2), v.transpose(1, 0, 2)


def experts(desc, x, w, mlp, l, cast):
    """``x + MoE(N2(x))``: every expert over every token, one at a time.
    ``mlp`` is the whole stack's and ``l`` the layer: an expert's matrices are
    cut out of it one at a time, so no copy of a layer's 1.2 GB of experts
    exists beside a serving engine that fills the chip."""
    h = _rms(x, w["norm2"]["scale"], desc["norm_eps"])
    p = jax.nn.softmax(h @ cast(mlp["router"][l]), axis=-1)
    top, idx = jax.lax.top_k(p, desc["num_experts_per_tok"])
    if desc["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], idx].set(top)

    def one(y, e):
        wg, wu, wd = (cast(mlp[nm][l, e])
                      for nm in ("w_gate", "w_up", "w_down"))
        return y + gates[:, e, None] * (
            (jax.nn.silu(h @ wg) * (h @ wu)) @ wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(p.shape[1]))
    return x + y


def head(h, w, cast):
    """``h W`` a slice of the vocabulary at a time, so that no float32 copy
    of the whole head exists beside a serving engine that fills the chip."""
    n = next(n for n in (8, 4, 2, 1) if w.shape[1] % n == 0)
    width = w.shape[1] // n
    parts = jax.lax.map(lambda i: h @ cast(jax.lax.dynamic_slice_in_dim(
        w, i * width, width, 1)), jnp.arange(n))       # [n, rows, width]
    return parts.transpose(1, 0, 2).reshape(h.shape[0], w.shape[1])


def _cast(weights_dtype):
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
    return cast


def _hashable(desc: Dict[str, Any]):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in desc.items()))


@functools.lru_cache(maxsize=None)
def _programs(desc_items, weights_dtype, mask):
    desc, cast = dict(desc_items), _cast(weights_dtype)

    def hi(f):
        def g(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return jax.jit(g)

    def layer(x, stack, l):
        w = jax.tree_util.tree_map(
            lambda a: cast(a[l]),
            {k: v for k, v in stack.items() if k != "mlp"})
        x, k, v = attention(desc, x, w, mask)
        return experts(desc, x, w, stack["mlp"], l, cast), k, v

    return {
        "embed": hi(lambda tok, ids: cast(tok[ids])),
        "layer": hi(layer),
        "head": hi(lambda x, s, w: head(
            _rms(x, cast(s), desc["norm_eps"]), w, cast)),
    }


def forward(desc: Dict[str, Any], params, ids, logits_at: Sequence[int] = (),
            weights_dtype=None, mask: str = "block"):
    """Full forward of ONE sequence under the block mask.  ids ``[S]`` ints
    (a masked position holds ``mask_token_id``) -> (float32 logits
    ``[len(logits_at), V]`` at those positions, each layer's ``(keys [S, G,
    d] normed and rotated, values [S, G, d])``, in layer order)."""
    prog = _programs(_hashable(desc), weights_dtype, mask)
    n = len(ids)
    ids = jnp.asarray(list(ids) + [0] * (-n % _PAD), jnp.int32)
    x = prog["embed"](params["embed"]["tok"], ids)
    kv = []
    stack = params["layers"]
    for l in range(stack["norm1"]["scale"].shape[0]):
        x, k, v = prog["layer"](x, stack, jnp.int32(l))
        kv.append((k[:n], v[:n]))
    at = jnp.asarray(list(logits_at), jnp.int32)
    return prog["head"](x[at], params["final_norm"]["scale"],
                        params["lm_head"]["w"]), kv


def confidence(logits) -> Tuple[np.ndarray, np.ndarray]:
    """``[B, V]`` logits -> (the arg-max token, the log of its softmax
    probability) a position, in float64."""
    z = np.asarray(logits, np.float64)
    top = z.argmax(axis=-1)
    zmax = z.max(axis=-1)
    return top, -np.log(np.exp(z - zmax[:, None]).sum(axis=-1))


def reveal(logits, ids, masked, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """The reveal rule over one block: ``logits [B, V]`` at its positions,
    ``ids`` / ``masked`` ``[B]`` -> the block's ``(ids, masked)`` after the
    pass: the ``n`` masked positions of highest confidence (the lower
    position on a tie) take their arg-max token."""
    ids, masked = np.array(ids), np.array(masked, bool)
    top, conf = confidence(logits)
    order = sorted(np.flatnonzero(masked), key=lambda i: (-conf[i], i))[:n]
    ids[order], masked[order] = top[order], False
    return ids, masked


def generate(desc: Dict[str, Any], params, prompt: Sequence[int],
             max_new_tokens: int, denoising_steps: int = 0, **controls
             ) -> Tuple[List[int], List[Dict[str, Any]]]:
    """The generation loop -> (the ``max_new_tokens`` tokens, every pass's
    ``{"start", "ids", "masked"}`` before it and ``"ids_after"``,
    ``"masked_after"``)."""
    B, mask_id = desc["block_length"], desc["mask_token_id"]
    n = B // (denoising_steps or B)
    seq, passes = list(prompt), []
    while len(seq) < len(prompt) + max_new_tokens:
        start = len(seq) // B * B  # the whole blocks stand as they are
        given = len(seq) - start
        ids = np.asarray(seq[start:] + [mask_id] * (B - given), np.int64)
        masked = np.arange(B) >= given
        while masked.any():
            logits, _ = forward(desc, params, seq[:start] + ids.tolist(),
                                range(start, start + B), **controls)
            after = reveal(logits, ids, masked, n)
            passes.append({"start": start, "ids": ids, "masked": masked,
                           "ids_after": after[0], "masked_after": after[1]})
            ids, masked = after
        seq = seq[:start] + ids.tolist()
    return seq[len(prompt):len(prompt) + max_new_tokens], passes
