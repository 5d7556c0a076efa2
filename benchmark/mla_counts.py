"""Operations and bytes of the kernel the Mistral-Small-4 configuration
brought: the latent-attention decode over pages that hold one row a token,
``[latent | rotary key]``.  Beside ``kernel_counts.py`` and like it the
benchmark's own arithmetic.

Bytes are the least that must move, so a share of the roofline cannot pass
100 %: a visible token's row read ONCE a layer — it is key and value both —
at the width the configuration states (``kv_lora_rank + qk_rope_head_dim``
values: 640 B in bfloat16), never at what the device's tiling pads it to, so
the share reads what padding costs; each decoded row's absorbed queries in and
its heads' latents out.  Operations are those of the visible tokens, never of
a block's masked tail.
"""

from __future__ import annotations

from typing import Tuple


def mla_decode_ops_bytes(kv_tokens: int, rows: int, layers: int, heads: int,
                         rank: int, rope_dim: int, itemsize: int = 2
                         ) -> Tuple[float, float]:
    """``kv_tokens``: visible cached positions summed over the decoded rows
    (once, not a layer); ``rows``: decoded rows summed over the steps.  A
    head scores ``rank + rope_dim`` wide and sums latents ``rank`` wide: ``2
    (rank + rope_dim) + 2 rank`` operations a (token, head)."""
    ops = float(kv_tokens) * layers * heads * (2 * (rank + rope_dim)
                                               + 2 * rank)
    nbytes = float(layers) * itemsize * (
        kv_tokens * (rank + rope_dim)
        + rows * heads * ((rank + rope_dim) + rank))
    return ops, nbytes
