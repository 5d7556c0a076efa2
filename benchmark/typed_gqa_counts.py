"""Operations and bytes of the attention kernels of a stack whose grouped-query
layers take their shape from their TYPE: a full layer ``heads_full`` query
heads and pages, a window layer ``heads_window`` query heads and a ring of
``sliding_window`` rows, both over ``kv_heads`` K/V heads of ``head_dim``
(Laguna-S-2.1: 48 and 72 over 8 x 128).  Beside ``hybrid_attn_counts.py``,
which takes one query head count for both types and K/V head counts by type;
like it the benchmark's own arithmetic, from the same per-step counters.

Bytes are the least that must move, so a share of a roofline cannot pass
100 %: a visible token's keys and values read ONCE a layer at the widths the
configuration states (8 x (128 + 128) values = 4,096 B in bfloat16, on either
type), a ring's LIVE rows and never the rows a young sequence has not written
or a page's padding; each row's queries in and outputs out at the type's head
count.  Operations are those of the visible (query, key) pairs, never of a
block's masked tail nor of the rows a group of 6 or 9 queries is padded to:
``4 head_dim`` a pair a query head.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Sequence, Tuple

from benchmark.hybrid_attn_counts import chunk_pairs


def layers(desc: Dict[str, Any]) -> Tuple[int, int]:
    """(full layers, window layers) of the stack as run."""
    windowed = sum(1 for w in desc["window_layers"] if w)
    return len(desc["window_layers"]) - windowed, windowed


def heads(desc: Dict[str, Any], windowed: bool) -> int:
    return desc["heads_window" if windowed else "heads_full"]


def pair_ops(desc: Dict[str, Any], windowed: bool) -> float:
    """q . k and p v of one (query, key) pair over the type's query heads."""
    return 4.0 * heads(desc, windowed) * desc["head_dim"]


def token_values(desc: Dict[str, Any]) -> int:
    """Values a cached token keeps a layer: keys and values of every K/V
    head, the same on either type."""
    return 2 * desc["kv_heads"] * desc["head_dim"]


def decode_ops_bytes(desc: Dict[str, Any], windowed: bool, kv_tokens: int,
                     rows: int, itemsize: int = 2) -> Tuple[float, float]:
    """One decode kernel over the window's steps.  ``kv_tokens``: cached
    positions the kernel reads in ONE of its layers summed over the decoded
    rows (``full_kv_tokens``, or ``window_kv_tokens`` for the rings' live
    rows); ``rows``: decoded rows summed over the steps."""
    n = layers(desc)[1 if windowed else 0]
    ops = float(kv_tokens) * n * pair_ops(desc, windowed)
    nbytes = float(n) * itemsize * (
        kv_tokens * token_values(desc)
        + rows * 2 * heads(desc, windowed) * desc["head_dim"])
    return ops, nbytes


def flash_ops_bytes(desc: Dict[str, Any], chunks: Iterable[Sequence[int]],
                    itemsize: int = 2) -> Tuple[float, float]:
    """The chunk program's flash calls over ``chunks`` of ``(tokens, cached
    positions before them)``: every layer's visible pairs at its type's head
    count, queries in, outputs out, and the keys and values a layer's chunk
    can see read once."""
    full, windowed = layers(desc)
    W, d = desc["sliding_window"], desc["head_dim"]
    ops = nbytes = 0.0
    for tokens, ctx in chunks:
        ops += (full * pair_ops(desc, False) * chunk_pairs(tokens, ctx)
                + windowed * pair_ops(desc, True)
                * chunk_pairs(tokens, ctx, W))
        nbytes += itemsize * (
            tokens * 2 * d * (full * heads(desc, False)
                              + windowed * heads(desc, True))
            + token_values(desc) * (full * (ctx + tokens)
                                    + windowed * (min(ctx, W) + tokens)))
    return ops, nbytes
