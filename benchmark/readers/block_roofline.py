"""Share of its roofline one attention kernel of a model that generates by
blocks reaches, %: the least time the chip could take for the window's calls
(the larger of operations / peak and bytes / peak, counted by
``block_counts.py`` from the program's per-step counters and the
configuration's widths) over the kernel's measured device time.  ``what``:
``paged`` (the block program's calls, ``B x G`` query rows a K/V head over
each visible page once, from ``block_kv_tokens`` and ``row_passes``) or
``flash`` (the chunk program's calls under the block mask, from each chunk's
``tokens`` and ``start``).  No such kernel in the trace, or no such counter in
the step records (a parent commit), is no reading."""

from benchmark import block_counts as counts
from benchmark import roofline

_COUNTER = {"paged": "block_kv_tokens", "flash": "chunk_spans"}


def bound(what, steps, desc, layers, peaks):
    """-> (the roofline's seconds, "memory" | "compute") for the step
    records' counters, or None where they hold none."""
    if "block_length" not in desc or not any(_COUNTER[what] in s
                                             for s in steps):
        return None
    if what == "flash":
        ops, nbytes = counts.flash_ops_bytes(
            desc, layers, [c for s in steps for c in s.get("chunk_spans", [])])
    else:
        ops, nbytes = counts.block_pass_ops_bytes(
            desc, layers, sum(s.get("block_kv_tokens", 0) for s in steps),
            sum(s.get("row_passes", 0) for s in steps))
    return roofline.roofline_seconds(ops, nbytes, peaks)


def read(ctx, what, kernel, span):
    tr, res = ctx["trace"], ctx["result"]
    spans = tr.span_list(span)
    steps = res.get("steps", [])[:len(spans)]
    got = tr.op_seconds(lambda name: kernel in name)
    if not spans or got == 0.0:
        return None
    least = bound(what, steps, res["desc"], res["n_layers"],
                  roofline.peaks(ctx["device"]["kind"]))
    return None if least is None or least[0] == 0.0 \
        else 100.0 * least[0] / got
