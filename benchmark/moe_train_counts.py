"""Operations and bytes of what the LFM2-MoE training configuration brought:
the model's operations per trained token, with the expert layers priced by the
picks the program counted, and the three grouped expert matmul kernels of a
trained expert share (forward, dX, dW).  Beside ``roofline.py`` and
``kernel_counts.py``, and like them the benchmark's own arithmetic, by
``roofline.py``'s rules: a ``[m, k] x [k, n]`` matmul is ``2 m k n``
operations, training is forward plus twice that, recomputation is never
credited, the embedding look-up is a gather, the head one ``[hidden, vocab]``
matmul over the held slice, causal attention half of full attention.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple


def layer_matmul_params(d: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights a token meets in each kind of part."""
    h, dh = d["hidden_size"], d["head_dim"]
    return {
        # W_in 3h x h and W_out h x h; the taps are no matmul
        "conv": 4 * h * h,
        "full_attention": h * dh * (d["num_attention_heads"]
                                    + 2 * d["num_key_value_heads"])
        + d["num_attention_heads"] * dh * h,
        "dense": 3 * h * d["intermediate_size"],
        "router": h * d["experts_routed"],
        "expert": 3 * h * d["expert_width"],
    }


def train_flops_per_token(d: Dict[str, Any], seq: int,
                          held_picks_per_token: float) -> float:
    """Forward + backward of one token through this chip's share.
    ``held_picks_per_token``: (token, held expert) pairs the program computed,
    all expert layers together, over the tokens it trained — counted by the
    program (``engine.moe_stats()``), not expected."""
    p = layer_matmul_params(d)
    types = d["layer_types"]
    n_dense = d["num_dense_layers"]
    matmul = (sum(p[t] for t in types) + n_dense * p["dense"]
              + (len(types) - n_dense) * p["router"]
              + held_picks_per_token * p["expert"]
              + d["hidden_size"] * d["vocab_size"])
    attn = types.count("full_attention") * 2 * 2 * (seq / 2) \
        * d["num_attention_heads"] * d["head_dim"]
    return 3.0 * (2.0 * matmul + attn)


def param_count(d: Dict[str, Any]) -> int:
    """Stored parameters of the share (norm scales, taps and the bias left
    out: under 0.01 %)."""
    p = layer_matmul_params(d)
    types = d["layer_types"]
    n_dense = d["num_dense_layers"]
    return (sum(p[t] for t in types) + n_dense * p["dense"]
            + (len(types) - n_dense) * (p["router"]
                                        + d["experts_held"] * p["expert"])
            + d["hidden_size"] * d["vocab_size"])


def expert_train_ops_bytes(picks: Sequence[Sequence[int]], hidden: int,
                           width: int, itemsize: int = 2
                           ) -> Tuple[float, float]:
    """The least the three kernels can do for a window's picks.  ``picks``:
    per expert layer, the picks on each held expert summed over the window's
    calls.  Operations: the SwiGLU expert's three matmuls in each of three
    passes (forward, dX, dW), real picks only, never the padding: ``9 * 2 *
    picks * hidden * width``.  Bytes, a lower bound so that the share cannot
    pass 100 %: each expert that got a pick has its three matrices read in
    the forward and the dX pass and written as gradients once — counted for
    ONE call a layer, since the sums do not say how many calls touched it —
    and each pick reads a row of ``hidden`` and writes one in each pass."""
    total = float(sum(sum(layer) for layer in picks))
    touched = sum(sum(1 for c in layer if c > 0) for layer in picks)
    ops = 9 * 2.0 * total * hidden * width
    nbytes = (3 * 3.0 * touched * hidden * width
              + 3 * 2.0 * total * hidden) * itemsize
    return ops, nbytes
