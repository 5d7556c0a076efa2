"""Operations and bytes from shapes, and the table of peaks.

The benchmark keeps its own arithmetic so that no PR that claims a gain can
change the yardstick, and reads no environment: a device kind that is not in
``peaks.json`` is an error, never a default.

Counting rules (they differ from ``models/transformer.py::flops_per_token``,
which prices an embedding look-up as a matmul and attention as not causal):

* a matmul of [m, k] x [k, n] is 2*m*k*n operations; training is forward
  plus twice that for the backward pass; recomputation is never credited;
* the embedding look-up is a gather, not a matmul; the vocabulary head is
  one [hidden, vocab] matmul whether or not it is tied to the embedding;
* causal attention over a sequence of S tokens does half the work of full
  attention: per layer and token, forward, 2 (QK^T and PV) * 2 * (S / 2) *
  heads * head_dim operations.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


def peaks(device_kind: str) -> Dict[str, Any]:
    with open(_PEAKS_FILE, "r", encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS_FILE}; known: {sorted(table)}")
    return table[device_kind]


# ------------------------------------------------------------------ model
def layer_matmul_params(m: Dict[str, Any]) -> int:
    """Weights of one dense block that take part in a matmul."""
    h, nh, kvh, d = (m["hidden_size"], m["num_attention_heads"],
                     m["num_key_value_heads"], m["head_dim"])
    ffn = m["intermediate_size"]
    attn = h * d * (nh + 2 * kvh) + nh * d * h
    mlp = h * ffn * (3 if m["mlp"] == "swiglu" else 2)
    return attn + mlp


def param_count(m: Dict[str, Any], n_layers: int) -> int:
    """Stored parameters (embedding, positions, head if untied, blocks;
    biases and norm scales left out: under 0.1 %)."""
    h, v = m["hidden_size"], m["vocab_size"]
    emb = v * h * (1 if m["tie_word_embeddings"] else 2)
    if m["position"] == "learned":
        emb += m["max_position_embeddings"] * h
    return emb + n_layers * layer_matmul_params(m)


def train_flops_per_token(m: Dict[str, Any], n_layers: int, seq: int) -> float:
    """Forward + backward, head once, causal attention, no recomputation."""
    h, v = m["hidden_size"], m["vocab_size"]
    matmul = n_layers * layer_matmul_params(m) + h * v
    attn = n_layers * 2 * 2 * (seq / 2) * m["num_attention_heads"] \
        * m["head_dim"]
    return 3.0 * (2.0 * matmul + attn)


def prefill_flops_per_token(m: Dict[str, Any], n_layers: int,
                            mean_context: float) -> float:
    """Forward only; the head runs on one token a prompt and is left out;
    ``mean_context`` is the mean number of keys a prompt token attends."""
    attn = n_layers * 2 * 2 * mean_context * m["num_attention_heads"] \
        * m["head_dim"]
    return 2.0 * n_layers * layer_matmul_params(m) + attn


# ---------------------------------------------------------------- kernels
def flash_ops_bytes(kind: str, batch: int, heads: int, kv_heads: int,
                    seq: int, head_dim: int, itemsize: int = 2
                    ) -> Tuple[float, float]:
    """(operations, bytes) one causal flash call needs.  ``kind``: ``fwd``
    (QK^T, PV: 2 matmuls), ``bwd_dq`` (recompute S, dP, dQ: 3) or
    ``bwd_dkv`` (recompute S, dP, dV, dK: 4).  The two backward kernels each
    recompute S and dP because they are separate calls; each is priced for
    what its own algorithm needs.  Bytes: every operand read once, every
    result written once (K/V once per KV head)."""
    per_matmul = 2.0 * batch * heads * seq * seq * head_dim / 2.0
    q = batch * heads * seq * head_dim * itemsize
    kv = batch * kv_heads * seq * head_dim * itemsize
    lse = batch * heads * seq * 4
    if kind == "fwd":
        return 2 * per_matmul, q + 2 * kv + q + lse
    if kind == "bwd_dq":
        return 3 * per_matmul, q + 2 * kv + q + 2 * lse + q
    if kind == "bwd_dkv":
        return 4 * per_matmul, q + 2 * kv + q + 2 * lse + 2 * kv
    raise ValueError(f"unknown flash kernel kind {kind!r}")


def paged_decode_bytes(context_pages: int, page_size: int, kv_heads: int,
                       head_dim: int, itemsize: int = 2) -> float:
    """Bytes one layer's paged decode call must read: K and V of every page
    that holds a visible token (``context_pages`` summed over the batch)."""
    return 2.0 * context_pages * page_size * kv_heads * head_dim * itemsize


def roofline_seconds(ops: float, nbytes: float, peak: Dict[str, Any]
                     ) -> Tuple[float, str]:
    """Least time the chip could take and which bound sets it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
