"""Operations and bytes of the kernels the Solar-Open2 configuration brought:
the grouped expert matmul over an expert share, and the delta-rule
linear-attention (KDA) step and chunk kernels; and of the paged decode
kernel where not every layer of the model keeps pages.  Beside ``roofline.py``, and
like it the benchmark's own arithmetic.

Bytes are the least that must move, so a share of the roofline cannot pass
100 %: the weights of the experts a call *touched* (from the program's
counter), never of the experts held or of the padded blocks; a sequence's
state read once and written once; activations once each way.  Operations are
those of the real rows, never of the padding.
"""

from __future__ import annotations

from typing import Tuple


def expert_ffn_ops_bytes(picks: int, experts_touched: int, hidden: int,
                         width: int, itemsize: int = 2
                         ) -> Tuple[float, float]:
    """The three matmuls of a SwiGLU expert layer's routed part.  ``picks``:
    (token, expert) pairs computed; ``experts_touched``: experts with at
    least one pick, summed over the layer calls — each reads its gate, up and
    down matrices once.  Every pick reads its row once and writes one."""
    ops = 3 * 2.0 * picks * hidden * width
    nbytes = (3.0 * experts_touched * hidden * width
              + 2.0 * picks * hidden) * itemsize
    return ops, nbytes


def kda_step_ops_bytes(rows: int, heads: int, k_dim: int, v_dim: int
                       ) -> Tuple[float, float]:
    """One token of ``rows`` sequences in one layer: the float32 state read
    once and written once; decay, S^T k, the rank-one update and S^T q are
    about 7 operations a state element.  q, k, g, v, o are float32."""
    state = rows * heads * k_dim * v_dim
    return 7.0 * state, 4.0 * (2 * state
                               + rows * heads * (3 * k_dim + 2 * v_dim))


def kda_chunk_ops_bytes(tokens: int, calls: int, heads: int, k_dim: int,
                        v_dim: int) -> Tuple[float, float]:
    """``tokens`` real tokens in ``calls`` chunk calls of one layer.  The
    recurrence itself, token by token, is about 7 operations a state element
    a token (what a chunkwise form spends beyond that is its own cost); each
    call reads and writes one state; q, k, g, v, o are float32."""
    state = heads * k_dim * v_dim
    return (7.0 * tokens * state,
            4.0 * (2 * calls * state
                   + tokens * heads * (3 * k_dim + 2 * v_dim)))


def paged_decode_ops_bytes(context_pages: int, page_size: int, heads: int,
                           kv_heads: int, head_dim: int, itemsize: int = 2
                           ) -> Tuple[float, float]:
    """One attention layer's paged decode calls: K and V of every page that
    holds a visible token (``context_pages`` summed over the decoded rows),
    read once; q k^T and p v over those tokens for every query head."""
    tokens = context_pages * page_size
    return (4.0 * tokens * heads * head_dim,
            2.0 * tokens * kv_heads * head_dim * itemsize)
