"""Bytes of the mixing the Xing4.0 configuration brought: a residual of ``n``
streams read and written round every sublayer by manifold-constrained
hyper-connections.  Beside ``kernel_counts.py`` and like it the benchmark's
own arithmetic.

Bytes are the least the mixing must move for a token and a sublayer, whatever
implements it, at the dtype the configuration states: the residual (``n x
hidden`` values) read ONCE for the coefficients and the read-in together (one
pass over it can serve the norm, the projection and ``H_pre X``), read once
more and written once for the write-back ``H_res X + H_post^T y``, the
sublayer's output ``y`` read once and its input ``h`` written once — ``3 n
hidden + 2 hidden`` values.  The projection's ``n hidden x (2n + n^2)``
weights, the biases and the Sinkhorn rounds' ``n^2`` values a token are left
out: a call reads the weights once whatever its tokens, and the rounds need
never leave the chip's registers.  So a share of the roofline computed from
these cannot pass 100 %, and a later kernel in the mixing's place is read
against the same bytes.  The operations (some ``2 (2n + n^2) n hidden + 2 n^2
hidden`` a token and sublayer: 67 a byte moved, against the chip's 240) never
bound it.
"""

from __future__ import annotations

#: a layer's sublayers, each mixed: its mixer and its feed-forward part
SUBLAYERS = 2


def mhc_stream_bytes(tokens: int, layers: int, streams: int, hidden: int,
                     itemsize: int = 2) -> float:
    """``tokens``: every token a program ran the layers over — a chunk's real
    tokens and a decode step's rows alike — summed over the window."""
    return float(tokens) * layers * SUBLAYERS * itemsize * (
        3 * streams * hidden + 2 * hidden)
