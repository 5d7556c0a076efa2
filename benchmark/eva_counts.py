"""Operations and bytes of the work the EvaByte configuration brought: EVA
attention over a cache that holds, for a query, one summary row a chunk of
every closed window and the open window's rows.  Beside ``kernel_counts.py``
and like it the benchmark's own arithmetic: from the program's counters and
the configuration's shapes, **whatever kernel or kernels implement it**.

Bytes are the least that must move, so a share of the roofline cannot pass
100 %: each row a query attends — a summary or an exact row alike, a key row
and a value row of ``heads x head_dim`` values — read ONCE a layer, each
query in and each output out; nothing for a page's unread rows, for the
composed table or for the row a step writes.  Operations are those of the
rows attended (a score and a weighted sum: ``2 d + 2 d`` a row and head),
never of a block's masked tail.
"""

from __future__ import annotations

from typing import Tuple


def eva_decode_ops_bytes(rows_attended: int, decode_rows: int, layers: int,
                         heads: int, head_dim: int, itemsize: int = 2
                         ) -> Tuple[float, float]:
    """``rows_attended``: the rows one layer reads, summed over the decoded
    rows of the window's steps (the program's ``eva_rows_attended``: visible
    summaries + open rows, the query's own included); ``decode_rows``: the
    decoded rows summed over the steps."""
    width = heads * head_dim
    ops = float(rows_attended) * layers * heads * 4 * head_dim
    nbytes = float(layers) * itemsize * width * (2 * rows_attended
                                                 + 2 * decode_rows)
    return ops, nbytes


def eva_chunk_ops_bytes(tokens: int, tokens_x_ctx: int, causal_pairs: int,
                        ctx_rows: int, layers: int, heads: int,
                        head_dim: int, itemsize: int = 2
                        ) -> Tuple[float, float]:
    """A window's chunk calls: ``tokens`` real tokens in all; ``tokens_x_ctx``
    the sum over calls of (the call's tokens) x (the rows it attends before
    itself: visible summaries, all of them seen by every token, and the open
    window's earlier rows); ``causal_pairs`` the sum over calls of ``n (n +
    1) / 2`` (a token sees itself and the call's earlier tokens); ``ctx_rows``
    the rows before the calls, summed.  Keys and values of the context and of
    the call read once a call, queries in, outputs out."""
    width = heads * head_dim
    ops = float(tokens_x_ctx + causal_pairs) * layers * heads * 4 * head_dim
    nbytes = float(layers) * itemsize * width * (2 * (ctx_rows + tokens)
                                                 + 2 * tokens)
    return ops, nbytes
