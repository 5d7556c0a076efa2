"""Operations and bytes of the attention kernels as a model that generates by
diffusion over blocks runs them (SDAR-MoE): the paged decode kernel with a
block of ``B`` queries a row folded into its head axis, and the flash kernel
under the block mask.  Beside ``kernel_counts.py``, ``mla_counts.py`` and
``hybrid_attn_counts.py`` and like them the benchmark's own arithmetic.

Bytes are the least that must move, so a share of a roofline cannot pass
100 %: a visible position's keys and values read ONCE a layer (the ``B``
queries of a block see the same pages at the same length: ``B x G`` query
rows of one K/V head share one fetch), each row's queries in and outputs out.
Operations are those of the visible (query, key) pairs, never of a block's or
a tile's masked tail: ``4 head_dim`` a pair a query head.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Sequence, Tuple


def _pair_ops(desc: Dict[str, Any]) -> float:
    return 4.0 * desc["num_attention_heads"] * desc["head_dim"]


def _token_values(desc: Dict[str, Any]) -> int:
    return 2 * desc["num_key_value_heads"] * desc["head_dim"]


def block_pass_ops_bytes(desc: Dict[str, Any], layers: int, kv_tokens: int,
                         rows: int, itemsize: int = 2) -> Tuple[float, float]:
    """The paged kernel over the window's passes.  ``kv_tokens``: positions
    through each row's block, summed over the rows of the passes — what ONE
    layer's call reads (``block_kv_tokens``); ``rows``: row-passes.  Every one
    of a row's ``B`` queries sees all of its positions."""
    B = desc["block_length"]
    ops = float(kv_tokens) * B * layers * _pair_ops(desc)
    nbytes = float(layers) * itemsize * (
        kv_tokens * _token_values(desc)
        + rows * B * 2 * desc["num_attention_heads"] * desc["head_dim"])
    return ops, nbytes


def chunk_pairs(tokens: int, ctx: int, block: int) -> float:
    """(query, key) pairs a chunk of ``tokens`` queries (whole blocks) sees
    behind ``ctx`` cached positions under the block mask: a query of the
    chunk's ``b``-th block sees ``ctx + (b + 1) x block`` keys."""
    n = tokens // block
    return float(tokens) * ctx + block * block * n * (n + 1) / 2.0


def flash_ops_bytes(desc: Dict[str, Any], layers: int,
                    chunks: Iterable[Sequence[int]], itemsize: int = 2
                    ) -> Tuple[float, float]:
    """The chunk program's flash calls over ``chunks`` of ``(tokens, cached
    positions before them)``: every layer's visible pairs, queries in,
    outputs out, and the keys and values the chunk can see read once."""
    qo = 2 * desc["num_attention_heads"] * desc["head_dim"]
    ops = nbytes = 0.0
    for tokens, ctx in chunks:
        ops += layers * _pair_ops(desc) * chunk_pairs(
            tokens, ctx, desc["block_length"])
        nbytes += layers * itemsize * (
            tokens * qo + (ctx + tokens) * _token_values(desc))
    return ops, nbytes
