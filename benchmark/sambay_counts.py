"""Operations and bytes of the kernels the Phi-4-mini-flash configuration
brought: the selective-scan step and chunk kernels, the window decode over a
ring of the last ``sliding_window`` positions, and the paged decode over the
one pool layer that several layers' queries read.  Beside ``kernel_counts.py``
and like it the benchmark's own arithmetic.

Bytes are the least that must move, so a share of the roofline cannot pass
100 %: the state of the rows that decoded, read once and written once; the
keys and values of the positions a row can see, once a layer that reads them;
activations once each way; the decay matrix once a call.  Operations are those
of the real rows and tokens, never of the padding.
"""

from __future__ import annotations

from typing import Tuple


def ssm_step_ops_bytes(rows: int, calls: int, inner: int, state: int
                       ) -> Tuple[float, float]:
    """One token of ``rows`` sequences, summed over the layer calls
    (``calls``): each row's float32 state read once and written once; dt A,
    its exponential, the decay, the input's outer product, the sum, s C and
    its reduction are about 7 operations a state element.  dt, u and y are
    float32 rows of ``inner``, B and C of ``state``; A ``[state, inner]`` is
    read once a call."""
    elems = rows * state * inner
    return 7.0 * elems, 4.0 * (2 * elems + rows * (3 * inner + 2 * state)
                               + calls * state * inner)


def ssm_chunk_ops_bytes(tokens: int, calls: int, inner: int, state: int
                        ) -> Tuple[float, float]:
    """``tokens`` real prompt tokens in ``calls`` chunk calls, summed over
    the layers: the recurrence's ~7 operations a state element a token; each
    call reads a state and A and writes a state; dt, u and y are float32 rows
    of ``inner`` a token, B and C of ``state``."""
    return (7.0 * tokens * state * inner,
            4.0 * (3 * calls * state * inner
                   + tokens * (3 * inner + 2 * state)))


def attend_ops_bytes(tokens: int, heads: int, kv_heads: int, head_dim: int,
                     itemsize: int = 2) -> Tuple[float, float]:
    """One query a row in the differential form over ``tokens`` cached
    positions (summed over rows and over the layers that read them): K and V
    of ``kv_heads`` heads of ``head_dim`` read once; every query head scores
    against ``head_dim`` and reads a value pair of ``2 * head_dim``."""
    return (2.0 * tokens * heads * 3 * head_dim,
            2.0 * tokens * kv_heads * head_dim * itemsize)
